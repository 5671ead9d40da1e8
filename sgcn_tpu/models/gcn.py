"""Partitioned GCN model: per-chip layer stack over the pspmm op.

Reference model being matched (capability, not quirk-for-quirk):

  * ``PGCN(nn.Module)``: per layer, partitioned SpMM aggregation → bias-free
    Linear → ReLU (``GPU/PGCN.py:136-148``), log-softmax + NLL loss
    (``:204-205``), Glorot/averaged init (``:156-160``).
  * MPI flavor uses sigmoid activations and BCE (``Parallel-GCN/main.c:79-90,
    301-335``) — selectable here via ``activation='sigmoid'``.

Per-chip code: every function below runs inside ``shard_map``; weights are
replicated on every chip (the reference replicates W on every rank and
all-reduces dW — ``Parallel-GCN/main.c:422-430``, ``GPU/PGCN.py:150-154``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pspmm import (pass_store_forms, pspmm_ell_sym, pspmm_ell_sym_coo,
                         pspmm_overlap, pspmm_ragged_sym,
                         pspmm_replica, pspmm_replica_partial,
                         pspmm_replica_ragged, pspmm_replica_stale,
                         pspmm_replica_stale_ragged, pspmm_stale,
                         pspmm_stale_ragged)
from ..obs.tracing import scope
from ..parallel.mesh import AXIS
from .activations import get_activation
from .setup import (Hoist, ModelSetup, Request, plan_true_edges, refuse_args,
                    select_pallas, slot_pass, slot_work)

# plan arrays the GCN forward consumes (fullbatch ships exactly these).
# Symmetric Â takes the ELL + symmetric-backward fast path; general Â the
# split-COO overlap path whose backward is JAX's mechanical transpose.
# Under comm_schedule='ragged' the symmetric path swaps the dense a2a
# arrays for the per-round ppermute-ring layout (CommPlan.ensure_ragged).
GCN_PLAN_FIELDS_SYM = ("send_idx", "halo_src", "ell_idx", "ell_w",
                       "ltail_dst", "ltail_src", "ltail_w",
                       "hedge_dst", "hedge_src", "hedge_w")
# the exact full-batch step on symmetric Â: the same exchange and ELL, the
# hub tail and the halo-source edges in slot form instead of the COO lists
# (CommPlan.ensure_fold_slots; chosen by model_setup)
GCN_PLAN_FIELDS_SLOTS = ("send_idx", "halo_src", "ell_idx", "ell_w",
                         "ft_idx", "ft_w", "ft_row",
                         "fh_idx", "fh_w", "fh_row")
GCN_PLAN_FIELDS_GEN = ("send_idx", "halo_src", "ledge_dst", "ledge_src",
                       "ledge_w", "hedge_dst", "hedge_src", "hedge_w")
GCN_PLAN_FIELDS_RAGGED = ("rsend_idx", "ell_idx", "ell_w",
                          "ltail_dst", "ltail_src", "ltail_w",
                          "redge_dst", "redge_src", "redge_w")


# minimum input width (f32 elements) for the project-before-aggregate layer
# order to win: below this, random row gathers are HBM-access-bound, so
# shrinking the row does not shrink the SpMM time (measured on v5e)
PROJECT_FIRST_MIN_FIN = 256


def exchange_widths(fin: int, widths) -> list[int]:
    """Per-layer exchanged/aggregated row width (lanes) under the
    project-first rule of ``gcn_forward_local`` — THE shared encoding of
    that rule for every cost model (bench roofline, shard epoch model);
    change the forward's condition and this together.

    Where layer 0 is aggregate-first (``out[0] == fin``), the exact
    full-batch GCN trainer pays layer 0's entry ONCE PER DATA SET, not per
    step: ``agg(h0)`` is loop-invariant there and ``model_setup``'s
    ``Hoist`` makes it outside the step.  The entry
    keeps its meaning — the width that exchange has whenever it runs (the
    one-off build, the stale/replica families, serving, mini-batch)."""
    out, f = [], fin
    for w in widths:
        out.append(w if (w < f and f >= PROJECT_FIRST_MIN_FIN) else f)
        f = w
    return out


def gcn_slot_passes(plan, fin: int, widths, fold_classes, *,
                    hoisted: bool, remat: bool = False) -> list:
    """The aggregation passes of the exact slot-form step
    (``pspmm_ell_sym`` with ``fold_classes``), for the counter
    ``slots.work`` (``models/setup.py::slot_pass``): per layer one forward
    pass at ``exchange_widths``' lanes — none in layer 0 where ``Â·h0`` is
    ``hoisted`` — and one backward pass of the same stores — none in an
    aggregate-first layer 0, whose input is data.  Under ``remat`` the
    backward runs the forward passes again, under the backward's names."""
    true = plan_true_edges(plan)
    passes, f_in = [], fin
    for layer, lanes in enumerate(exchange_widths(fin, widths)):
        fwd = not (layer == 0 and hoisted)
        bwd = (layer > 0 or lanes != f_in) + (fwd and remat)
        stores = pass_store_forms(plan.ell_buckets, *fold_classes, lanes)
        for way, times in (("fwd", int(fwd)), ("bwd", int(bwd))):
            if times:
                passes.append(slot_pass(layer, way, lanes, stores,
                                        times_per_epoch=times,
                                        true_edges=true))
        f_in = widths[layer]
    return passes


def init_gcn_params(rng: jax.Array, dims: list[tuple[int, int]]):
    """Glorot-uniform weight list, one (fin, fout) matrix per layer.

    Reference init: Glorot uniform (``Parallel-GCN/main.c:584-594``); the
    torch flavor synchronizes via an allreduce average (``GPU/PGCN.py:156-160``)
    — here a shared seed makes every chip's copy identical by construction.
    """
    keys = jax.random.split(rng, len(dims))
    return [
        jax.nn.initializers.glorot_uniform()(k, (fin, fout), jnp.float32)
        for k, (fin, fout) in zip(keys, dims)
    ]


def _gcn_aggregator(
    pa,
    symmetric: bool = False,
    ell_buckets: tuple | None = None,
    pallas_tb: int | None = None,
    pallas_emulate: bool = False,
    pallas_lclasses: tuple | None = None,
    pallas_hclasses: tuple | None = None,
    halo_dtype: str | None = None,
    comm_schedule: str = "a2a",
    rr_sizes: tuple | None = None,
    rr_edge_sizes: tuple | None = None,
    fold_classes: tuple | None = None,
    axis_name: str = AXIS,
):
    """``agg(x) = Â·x`` (halo exchange + local fold) for one plan and one
    set of statics (``gcn_forward_local`` documents them) — the ONE
    kernel/transport selection of the exact GCN path, shared by the layer
    loop and by the one-off ``gcn_aggregate_local``."""
    if comm_schedule not in ("a2a", "ragged"):
        raise ValueError(f"unknown comm_schedule {comm_schedule!r} "
                         "(the trainer resolves 'auto' before the forward)")
    if symmetric and pallas_tb is not None and comm_schedule == "ragged":
        # schedule-agnostic Pallas aggregation: the ragged ring's receive
        # buffers feed the VMEM kernel directly (tile sources re-based to
        # ring positions at plan time — no HBM halo table; f32
        # bit-identical to the a2a-pallas flavor)
        from ..ops.pallas_spmm import pspmm_pallas_ragged

        if rr_sizes is None:
            raise ValueError(
                "ragged Pallas GCN forward needs the plan's static "
                "rr_sizes (CommPlan.ensure_ragged)")

        def agg(x):
            return pspmm_pallas_ragged(
                x, pa["rsend_idx"],
                pa["ptile_lsrc"], pa["ptile_lld"], pa["ptile_lw"],
                pa["ptile_hrsrc"], pa["ptile_hld"], pa["ptile_hw"],
                pallas_tb, pallas_lclasses, pallas_hclasses, rr_sizes,
                pallas_emulate, axis_name, halo_dtype)
    elif comm_schedule == "ragged":
        # ragged ppermute ring (docs/comm_schedule.md): per-round-sized
        # buffers replace the globally-padded a2a; same math, f32
        # bit-identical by construction (plan-time round-order edge sort)
        if not symmetric:
            raise ValueError(
                "comm_schedule='ragged' uses the symmetric custom backward "
                "(the gradient rides the same ring); asymmetric plans run "
                "the a2a schedule")
        if ell_buckets is None or rr_sizes is None or rr_edge_sizes is None:
            raise ValueError(
                "ragged GCN forward needs the plan's static ell_buckets + "
                "rr_sizes + rr_edge_sizes (CommPlan.ensure_ragged)")

        def agg(x):
            return pspmm_ragged_sym(
                x, pa["rsend_idx"], pa["ell_idx"], pa["ell_w"],
                pa["ltail_dst"], pa["ltail_src"], pa["ltail_w"],
                pa["redge_dst"], pa["redge_src"], pa["redge_w"],
                ell_buckets, rr_sizes, rr_edge_sizes, axis_name, halo_dtype)
    elif symmetric and pallas_tb is not None:
        # plan-driven kernel choice: per-chip tables fit the VMEM-resident
        # Pallas kernel (ops/pallas_spmm.py::use_pallas_spmm) — the regime
        # k-way sharding produces as k grows
        from ..ops.pallas_spmm import pspmm_pallas_sym

        def agg(x):
            return pspmm_pallas_sym(
                x, pa["send_idx"], pa["halo_src"],
                pa["ptile_lsrc"], pa["ptile_lld"], pa["ptile_lw"],
                pa["ptile_hsrc"], pa["ptile_hld"], pa["ptile_hw"],
                pallas_tb, pallas_lclasses, pallas_hclasses,
                pallas_emulate, axis_name, halo_dtype)
    elif symmetric:
        if ell_buckets is None:
            raise ValueError(
                "symmetric GCN forward needs the plan's static ell_buckets")

        if fold_classes is not None:
            # the exact full-batch setup: both COO stores in slot form
            def agg(x):
                return pspmm_ell_sym(
                    x, *(pa[f] for f in GCN_PLAN_FIELDS_SLOTS),
                    ell_buckets, *fold_classes, axis_name, halo_dtype)
        else:
            def agg(x):
                return pspmm_ell_sym_coo(
                    x, *(pa[f] for f in GCN_PLAN_FIELDS_SYM),
                    ell_buckets, axis_name, halo_dtype)
    else:
        def agg(x):
            return pspmm_overlap(
                x, pa["send_idx"], pa["halo_src"],
                pa["ledge_dst"], pa["ledge_src"], pa["ledge_w"],
                pa["hedge_dst"], pa["hedge_src"], pa["hedge_w"],
                axis_name=axis_name, halo_dtype=halo_dtype)

    return agg


def gcn_forward_local(
    params,
    h,                      # (B, f_in) local feature rows
    pa,                     # plan arrays dict (model_setup's fields)
    activation: str = "relu",
    final_activation: str = "none",
    symmetric: bool = False,
    ell_buckets: tuple | None = None,   # static plan.ell_buckets (sym path)
    pallas_tb: int | None = None,       # static: VMEM-kernel tile height —
                                        # selects the Pallas aggregator
    pallas_emulate: bool = False,       # static: jnp emulation (off-TPU shard_map CI)
    pallas_lclasses: tuple | None = None,  # static: degree-binned local
                                        # tile classes ((T,Emax,kern), ...)
    pallas_hclasses: tuple | None = None,  # static: halo tile classes
    halo_dtype: str | None = None,      # static: wire-only exchange dtype
                                        # ('bfloat16' halves ICI bytes;
                                        # tables/activations stay f32 —
                                        # ops/pspmm.py::halo_exchange)
    comm_schedule: str = "a2a",         # static: 'a2a' (dense all_to_all)
                                        # or 'ragged' (per-round ppermute
                                        # ring, docs/comm_schedule.md)
    rr_sizes: tuple | None = None,      # static plan.rr_sizes (ragged)
    rr_edge_sizes: tuple | None = None,  # static plan.rr_edge_sizes (ragged)
    fold_classes: tuple | None = None,  # static (tail, halo) width classes
                                        # of the slot-form COO stores
                                        # (GCN_PLAN_FIELDS_SLOTS); None: the
                                        # COO lists of GCN_PLAN_FIELDS_SYM
    axis_name: str = AXIS,
    hoisted: tuple | None = None,       # (Â·h0,), made by the Hoist of
                                        # model_setup — layer 0 goes
                                        # straight to its dense product
):
    """Per-chip forward: L × (pspmm ⊗ dense matmul → activation) → (B, nout).

    Aggregation uses ``pspmm_overlap`` — the split-edge-list formulation in
    which the local SpMM has no data dependence on the halo ``all_to_all``,
    so XLA overlaps communication with compute the way the MPI trainer's
    Irecv/compute/Waitany loop does (``Parallel-GCN/main.c:238-299``).

    Op order per layer exploits associativity: ``(Â·H)·W = Â·(H·W)``.  When
    the input is wide and the output narrower, the dense projection runs
    FIRST, so the halo exchange ships ``fout``-wide rows and the gather-bound
    SpMM touches ``fout``-wide features — both comm volume and the hot gather
    shrink by ``fout/fin`` (measured 2.7× per layer for cora-like 1433-wide
    inputs on v5e).  Below ~256 floats/row the gather is access-bound, not
    byte-bound (rows are shorter than an HBM burst), so narrowing does not
    pay and aggregate-first (the reference's fixed order,
    ``GPU/PGCN.py:144-148``) is kept.  Identical math either way.

    ``hoisted=(Â·h0,)`` takes the place of ``h`` (then ``None``): made
    once by ``gcn_aggregate_local`` with these same statics, so layer 0
    skips its aggregation (in full-batch training ``h0`` and Â never
    change, so ``Â·h0`` is the same array every step — and with it go layer
    0's exchange and folds, forward and, under ``remat``, backward).  Legal
    only where layer 0 is aggregate-first (``exchange_widths(fin,
    widths)[0] == fin``): under project-first layer 0 aggregates
    ``h0·W⁰``, which moves with the weights, and the call raises.  Only the
    exact full-batch trainer passes it (``model_setup``'s ``Hoist``).
    """
    act = get_activation(activation)
    fact = get_activation(final_activation)
    nl = len(params)

    agg = _gcn_aggregator(
        pa, symmetric=symmetric, ell_buckets=ell_buckets,
        pallas_tb=pallas_tb, pallas_emulate=pallas_emulate,
        pallas_lclasses=pallas_lclasses, pallas_hclasses=pallas_hclasses,
        halo_dtype=halo_dtype, comm_schedule=comm_schedule,
        rr_sizes=rr_sizes, rr_edge_sizes=rr_edge_sizes,
        fold_classes=fold_classes, axis_name=axis_name)

    if hoisted is not None:
        (h,) = hoisted
    for i, w in enumerate(params):
        with scope("layer", i):
            if w.shape[1] < h.shape[1] and h.shape[1] >= PROJECT_FIRST_MIN_FIN:
                if i == 0 and hoisted is not None:
                    raise ValueError(
                        "a hoisted Â·h0 needs an aggregate-first layer 0; "
                        f"widths {h.shape[1]} -> {w.shape[1]} project first, "
                        "and agg(h0 @ W) moves with the weights")
                with scope("dense"):
                    x = h @ w
                z = agg(x)
            else:
                z = h if (i == 0 and hoisted is not None) else agg(h)
                with scope("dense"):
                    z = z @ w
            h = fact(z) if i == nl - 1 else act(z)
    return h


def gcn_aggregate_local(h, pa, **statics):
    """``agg(h0)`` alone: layer 0's aggregation of ``gcn_forward_local`` as
    a per-chip program of its own, for its ``hoisted=``.
    ``statics`` are the forward's aggregator statics (``symmetric``,
    ``ell_buckets``, the Pallas and ragged ones, ``halo_dtype``), so the
    same kernel and the same wire make the array, under the scopes layer 0
    has in the step (``sgcn.layer0/agg_slots``, ...)."""
    with scope("layer", 0):
        return _gcn_aggregator(pa, **statics)(h)


def gcn_forward_local_stale(
    params,
    h,                      # (B, f_in) local feature rows
    pa,                     # plan arrays dict (GCN_PLAN_FIELDS_SYM, or
    #                         STALE_PLAN_FIELDS_RAGGED under 'ragged')
    halos,                  # per-layer halo carries (step t−1): (R, f_ℓ)
    #                         dense, (ΣS_d, f_ℓ) round-major under 'ragged'
    ghalos,                 # per-layer gradient-halo carries (same shapes)
    bases,                  # per-layer delta baselines (or dummies):
    #                         (k, S, f_ℓ) dense, (ΣS_d, f_ℓ) under 'ragged'
    activation: str = "relu",
    final_activation: str = "none",
    ell_buckets: tuple | None = None,
    delta: bool = False,            # static: halo-delta caching on the wire
    wire_dtype: str | None = None,  # static: feature-wire dtype
    gwire_dtype: str | None = None,  # static: gradient-wire dtype
    fresh: bool = False,            # static: full-sync step (exact math)
    gauges: bool = False,           # static: emit per-layer drift gauges
    comm_schedule: str = "a2a",     # static: 'a2a' (pspmm_stale) or
    #                                 'ragged' (pspmm_stale_ragged — the
    #                                 composed mode, docs/comm_schedule.md)
    rr_sizes: tuple | None = None,  # static plan.rr_sizes (ragged)
    rr_edge_sizes: tuple | None = None,  # static plan.rr_edge_sizes (ragged)
    replica: bool = False,          # static: hot-halo replication composed
    #                                 in (--replica-budget + staleness —
    #                                 stale steps ship the SHRUNKEN nrep_*
    #                                 exchange; the carry subsumes the
    #                                 replica tables)
    nrep_rr_sizes: tuple | None = None,  # static plan.nrep_rr_sizes
    #                                      (ragged composed)
    axis_name: str = AXIS,
):
    """Per-chip forward under the pipelined stale-halo exchange.

    Same layer math and project-first scheduling as ``gcn_forward_local``,
    but every aggregation goes through a stale op: layer ℓ consumes
    ``halos[ℓ]`` (exchanged during step t−1) and issues step t's exchange
    with no in-step consumer.  ``comm_schedule`` selects the transport the
    carry rides: the dense a2a (``pspmm_stale``, ``(R, f)`` carries) or the
    per-round ppermute ring (``pspmm_stale_ragged``, round-major
    ``(Σ_d S_d, f)`` carries — the composed mode, in which the k−1 ring
    rounds leave the critical path too).  Returns
    ``(out, new_halos, new_bases)``; the gradient-halo carries come back as
    the ``ghalos`` cotangents of ``jax.value_and_grad`` (see
    ``pspmm_stale``).  Symmetric-Â plans only — the trainer gates on
    ``plan.symmetric``.

    ``gauges=True`` (the telemetry program the trainer compiles when a
    ``RunRecorder`` is attached) additionally returns a per-layer list of
    halo-delta quantization residuals: ``Σ (full − base_next)²`` over the
    send buffer (dense ``(k, S, f)``, ragged ``(Σ_d S_d, f)``), which is
    EXACTLY this step's wire rounding error ``(full − base) −
    quantize(full − base)`` since ``base_next = base + quantized_wire`` —
    zero when ``delta`` is off (the f32 wire is exact) and zero on sync
    steps (the re-base wire is full f32).  The extra send-buffer gather per
    layer exists only in the gauged program; the default hot path is
    untouched.
    """
    if ell_buckets is None:
        raise ValueError(
            "stale GCN forward needs the plan's static ell_buckets")
    if comm_schedule not in ("a2a", "ragged"):
        raise ValueError(f"unknown comm_schedule {comm_schedule!r} "
                         "(the trainer resolves 'auto' before the forward)")
    if comm_schedule == "ragged" and (rr_sizes is None
                                      or rr_edge_sizes is None):
        raise ValueError(
            "composed stale-ragged forward needs the plan's static "
            "rr_sizes + rr_edge_sizes (CommPlan.ensure_ragged)")
    if replica and delta:
        raise ValueError(
            "replica × stale × delta is deferred: the delta baseline and "
            "the replica carry would disagree on what a stale step ships "
            "(docs/replication.md)")
    if replica and comm_schedule == "ragged" and nrep_rr_sizes is None:
        raise ValueError(
            "composed replica-stale-ragged forward needs the plan's "
            "static nrep_rr_sizes (CommPlan.ensure_replicas after "
            "ensure_ragged)")
    act = get_activation(activation)
    fact = get_activation(final_activation)
    nl = len(params)
    new_halos, new_bases, qerrs = [], [], []
    for i, w in enumerate(params):
        # identical scheduling rule to gcn_forward_local: the carry widths
        # (plan.stale_carry_shapes → exchange_widths) encode the same rule
        project_first = (w.shape[1] < h.shape[1]
                         and h.shape[1] >= PROJECT_FIRST_MIN_FIN)
        x = (h @ w) if project_first else h
        if replica and comm_schedule == "ragged":
            z, hn, bn = pspmm_replica_stale_ragged(
                x, halos[i], ghalos[i], bases[i], pa["rsend_idx"],
                pa["nrep_rsend_idx"], pa["nrep_ring_dst"],
                pa["ell_idx"], pa["ell_w"],
                pa["ltail_dst"], pa["ltail_src"], pa["ltail_w"],
                pa["redge_dst"], pa["redge_src"], pa["redge_w"],
                ell_buckets, rr_sizes, rr_edge_sizes, nrep_rr_sizes,
                axis_name, wire_dtype, gwire_dtype, fresh)
        elif replica:
            z, hn, bn = pspmm_replica_stale(
                x, halos[i], ghalos[i], bases[i],
                pa["send_idx"], pa["halo_src"],
                pa["nrep_send_idx"], pa["nrep_halo_src"], pa["rep_slots"],
                pa["ell_idx"], pa["ell_w"],
                pa["ltail_dst"], pa["ltail_src"], pa["ltail_w"],
                pa["hedge_dst"], pa["hedge_src"], pa["hedge_w"],
                ell_buckets, axis_name, wire_dtype, gwire_dtype, fresh)
        elif comm_schedule == "ragged":
            z, hn, bn = pspmm_stale_ragged(
                x, halos[i], ghalos[i], bases[i], pa["rsend_idx"],
                pa["ell_idx"], pa["ell_w"],
                pa["ltail_dst"], pa["ltail_src"], pa["ltail_w"],
                pa["redge_dst"], pa["redge_src"], pa["redge_w"],
                ell_buckets, rr_sizes, rr_edge_sizes, axis_name, delta,
                wire_dtype, gwire_dtype, fresh)
        else:
            z, hn, bn = pspmm_stale(
                x, halos[i], ghalos[i], bases[i],
                pa["send_idx"], pa["halo_src"], pa["ell_idx"], pa["ell_w"],
                pa["ltail_dst"], pa["ltail_src"], pa["ltail_w"],
                pa["hedge_dst"], pa["hedge_src"], pa["hedge_w"],
                ell_buckets, axis_name, delta, wire_dtype, gwire_dtype,
                fresh)
        if gauges:
            if delta:
                sidx = (pa["rsend_idx"] if comm_schedule == "ragged"
                        else pa["send_idx"])
                full = jnp.take(x, sidx, axis=0)
                qerrs.append(jnp.sum(jnp.square(full - bn)))
            else:
                qerrs.append(jnp.zeros((), x.dtype))
        if not project_first:
            z = z @ w
        new_halos.append(hn)
        new_bases.append(bn)
        h = fact(z) if i == nl - 1 else act(z)
    if gauges:
        return h, new_halos, new_bases, qerrs
    return h, new_halos, new_bases


def gcn_forward_local_replica(
    params,
    h,                      # (B, f_in) local feature rows
    pa,                     # plan arrays dict (REPLICA_PLAN_FIELDS /
    #                         REPLICA_PLAN_FIELDS_RAGGED /
    #                         REPLICA_PARTIAL_PLAN_FIELDS)
    reps,                   # per-layer replica carries: (RP, f_ℓ)
    greps,                  # per-layer gradient-replica carries (same shapes)
    activation: str = "relu",
    final_activation: str = "none",
    ell_buckets: tuple | None = None,
    halo_dtype: str | None = None,  # static: wire-only exchange dtype
    fresh: bool = False,            # static: refresh (sync) step — the full
    #                                 exact exchange, replicas re-read fresh
    comm_schedule: str = "a2a",     # static: 'a2a' (pspmm_replica) or
    #                                 'ragged' (pspmm_replica_ragged)
    rr_sizes: tuple | None = None,       # static plan.rr_sizes (ragged)
    rr_edge_sizes: tuple | None = None,  # static plan.rr_edge_sizes (ragged)
    nrep_rr_sizes: tuple | None = None,  # static plan.nrep_rr_sizes (ragged)
    halo_r: int | None = None,           # static plan.r (ragged halo table)
    rep_base=None,          # per-layer sender-side refresh baselines
    #                         (RS, f_ℓ) — --refresh-band trainers only
    track_base: bool = False,       # static: thread the baselines through
    #                                 (returns (logits, reps, bases, nships))
    partial_step: bool = False,     # static: THIS program is the partial
    #                                 refresh step (pspmm_replica_partial)
    band: float = 0.0,              # static: relative per-row drift band
    axis_name: str = AXIS,
):
    """Per-chip forward under hot-halo replication (``--replica-budget``).

    Same layer math and project-first scheduling as ``gcn_forward_local``,
    but every aggregation goes through a replica-aware op: the plan's top-B
    boundary rows never ride the per-layer wire — their halo slots fill
    from ``reps[ℓ]``/``greps[ℓ]``, refreshed only on ``fresh`` (sync)
    steps, where the program is EXACTLY the exact path plus the replica
    gathers (the f32 bit-identity contract of ``--sync-every 1``).
    Returns ``(out, new_reps)``; the gradient-replica carries come back as
    the ``greps`` cotangents of ``jax.value_and_grad`` (see
    ``pspmm_replica``).  Symmetric-Â plans only — the trainer gates on
    ``plan.symmetric``.
    """
    if ell_buckets is None:
        raise ValueError(
            "replica GCN forward needs the plan's static ell_buckets")
    if comm_schedule not in ("a2a", "ragged"):
        raise ValueError(f"unknown comm_schedule {comm_schedule!r} "
                         "(the trainer resolves 'auto' before the forward)")
    if comm_schedule == "ragged" and (rr_sizes is None
                                      or rr_edge_sizes is None
                                      or nrep_rr_sizes is None
                                      or halo_r is None):
        raise ValueError(
            "ragged replica forward needs the plan's static rr_sizes + "
            "rr_edge_sizes + nrep_rr_sizes + halo table height "
            "(CommPlan.ensure_ragged + ensure_replicas)")
    if partial_step and (not track_base or comm_schedule != "a2a"):
        raise ValueError(
            "the partial refresh step needs the threaded baselines "
            "(track_base=True) and rides the dense a2a transport only "
            "(docs/replication.md)")
    act = get_activation(activation)
    fact = get_activation(final_activation)
    nl = len(params)
    new_reps, new_bases, nships = [], [], []
    for i, w in enumerate(params):
        # identical scheduling rule to gcn_forward_local: the carry widths
        # (plan.replica_carry_shapes → exchange_widths) encode the same rule
        project_first = (w.shape[1] < h.shape[1]
                         and h.shape[1] >= PROJECT_FIRST_MIN_FIN)
        x = (h @ w) if project_first else h
        if partial_step:
            z, rn, bn, ns = pspmm_replica_partial(
                x, reps[i], greps[i], rep_base[i],
                pa["nrep_send_idx"], pa["nrep_halo_src"], pa["rep_slots"],
                pa["rep_rows"], pa["rep_row_counts"],
                pa["ronly_send_idx"], pa["ronly_send_counts"],
                pa["ronly_base_pos"], pa["rep_recv_src"],
                pa["ell_idx"], pa["ell_w"],
                pa["ltail_dst"], pa["ltail_src"], pa["ltail_w"],
                pa["hedge_dst"], pa["hedge_src"], pa["hedge_w"],
                ell_buckets, axis_name, halo_dtype, band)
        elif comm_schedule == "ragged":
            z, rn = pspmm_replica_ragged(
                x, reps[i], greps[i], pa["rsend_idx"],
                pa["nrep_rsend_idx"], pa["nrep_rhalo_dst"], pa["rep_slots"],
                pa["rep_ring_pos"],
                pa["ell_idx"], pa["ell_w"],
                pa["ltail_dst"], pa["ltail_src"], pa["ltail_w"],
                pa["hedge_dst"], pa["hedge_src"], pa["hedge_w"],
                pa["redge_dst"], pa["redge_src"], pa["redge_w"],
                ell_buckets, rr_sizes, rr_edge_sizes, nrep_rr_sizes,
                halo_r, axis_name, halo_dtype, fresh)
        else:
            z, rn = pspmm_replica(
                x, reps[i], greps[i], pa["send_idx"], pa["halo_src"],
                pa["nrep_send_idx"], pa["nrep_halo_src"], pa["rep_slots"],
                pa["ell_idx"], pa["ell_w"],
                pa["ltail_dst"], pa["ltail_src"], pa["ltail_w"],
                pa["hedge_dst"], pa["hedge_src"], pa["hedge_w"],
                ell_buckets, axis_name, halo_dtype, fresh)
        if track_base and not partial_step:
            if fresh:
                # full refresh re-anchors the sender-side baseline to what
                # the CONSUMERS actually received — the wire-quantized
                # value under --halo-dtype (halo_exchange casts the
                # refresh's send buffer to the wire dtype and upcasts on
                # arrival), so sender baseline and every consumer replica
                # start the next partial-refresh epoch in exact lockstep
                # (an exact-f32 anchor would carry the quantization error
                # as permanent sender/receiver disagreement).
                # lax.stop_gradient: the baselines are carry state, not a
                # loss path (no cotangent into x)
                valid = (jnp.arange(pa["rep_rows"].shape[0])
                         < pa["rep_row_counts"])[:, None].astype(x.dtype)
                bn = jnp.take(x, pa["rep_rows"], axis=0)
                if halo_dtype is not None:
                    bn = bn.astype(halo_dtype).astype(x.dtype)
                bn = lax.stop_gradient(bn * valid)
            else:
                bn = rep_base[i]        # replica steps pass them through
            ns = jnp.zeros((), jnp.int32)
        if not project_first:
            z = z @ w
        new_reps.append(rn)
        if track_base:
            new_bases.append(bn)
            nships.append(ns)
        h = fact(z) if i == nl - 1 else act(z)
    if track_base:
        return h, new_reps, new_bases, nships
    return h, new_reps


# -------------------------------------------------------------- the registry
def _agg0(pa, h0, statics: dict) -> tuple:
    """``gcn``'s ``Hoist`` build: ``(Â·h0,)`` in f32."""
    return (gcn_aggregate_local(h0, pa, **statics).astype("float32"),)


def model_setup(plan, fin: int, widths, model_args: dict | None,
                request: Request) -> ModelSetup:
    """The ``MODELS`` entry's setup hook (``models/setup.py``): the plan
    arrays and statics of the step the request asks for — the ELL exchange
    (symmetric Â) or the split COO lists, the ragged ring, the replica and
    stale × ragged contract tuples, the Pallas VMEM aggregator, or the exact
    step's slot-form fold — layer 0's ``Â·h0`` as a ``Hoist``, the wire's
    lanes and itemsizes, and the counter ``slots.work``."""
    refuse_args("gcn", model_args)
    sched, stale = request.comm_schedule, request.halo_staleness
    budget, ragged = request.replica_budget, sched == "ragged"
    fields, static = ((GCN_PLAN_FIELDS_SYM, {"ell_buckets": plan.ell_buckets})
                      if plan.symmetric else (GCN_PLAN_FIELDS_GEN, {}))
    if ragged:
        # the fold-as-you-arrive scatter over the per-owner edge split; the
        # composed (stale × ragged) step ships the same ring arrays under
        # its own contract tuple
        from ..parallel.plan import STALE_PLAN_FIELDS_RAGGED
        fields = STALE_PLAN_FIELDS_RAGGED if stale else GCN_PLAN_FIELDS_RAGGED
        static = {"ell_buckets": plan.ell_buckets, "comm_schedule": "ragged",
                  "rr_sizes": plan.rr_sizes,
                  "rr_edge_sizes": plan.rr_edge_sizes}
    pallas = None
    if budget:
        # hot-halo replication (docs/replication.md): the UNION of the full
        # exchange layout (the refresh program = the exact program + replica
        # gathers; evaluate() rides it) and the shrunken no-replica layout;
        # the statics stay the exact forward's (the replica-only ones live
        # on the trainer).  The composed (replica × stale) step ships its
        # own tuples: the stale carry subsumes the replica tables, and the
        # ragged flavour adds the carry scatter map ``nrep_ring_dst``;
        # ``refresh_band`` adds the partial refresh's side channel
        from ..parallel.plan import (REPLICA_PARTIAL_PLAN_FIELDS,
                                     REPLICA_PLAN_FIELDS,
                                     REPLICA_PLAN_FIELDS_RAGGED,
                                     REPLICA_STALE_PLAN_FIELDS,
                                     REPLICA_STALE_PLAN_FIELDS_RAGGED)
        plan.ensure_replicas(budget)
        if stale:
            fields = (REPLICA_STALE_PLAN_FIELDS_RAGGED if ragged
                      else REPLICA_STALE_PLAN_FIELDS)
        elif request.refresh_band is not None:
            fields = REPLICA_PARTIAL_PLAN_FIELDS
        else:
            fields = (REPLICA_PLAN_FIELDS_RAGGED if ragged
                      else REPLICA_PLAN_FIELDS)
    elif not stale:
        # the carried families keep the ELL aggregator: their carry
        # contracts are built around it
        pallas = select_pallas(plan, fin, widths, "gcn", request)
    if pallas is not None:
        from ..ops.pallas_spmm import (PALLAS_PLAN_FIELDS,
                                       PALLAS_PLAN_FIELDS_RAGGED)
        fields = PALLAS_PLAN_FIELDS_RAGGED if ragged else PALLAS_PLAN_FIELDS
        static = dict(pallas)
        if ragged:      # the ring concat needs only rr_sizes
            static.update(comm_schedule="ragged", rr_sizes=plan.rr_sizes)
    elif (plan.symmetric and sched == "a2a" and not stale and not budget
            and not request.serve_subgraph and not request.shared_envelope):
        # the exact step folds the hub tail and the halo-source edges as
        # slot passes over virtual rows, INSTEAD of the COO lists (a fold by
        # scatter-add costs two slots an edge: PERF.md §6).  The
        # carried families fold inside their own ops, sub-graph serving
        # mirrors the COO chains row by row, and a shared envelope has no
        # virtual-row count: they keep the lists
        plan.ensure_fold_slots()
        fields = GCN_PLAN_FIELDS_SLOTS
        static = dict(static, fold_classes=(plan.fold_tail_classes,
                                            plan.fold_halo_classes))
    lanes = tuple(exchange_widths(fin, widths))
    hoist = None
    if (not stale and not budget and not request.shared_envelope
            and lanes[0] == fin):
        # full-batch h0 and Â never change: layer 0's Â·h0 is made once per
        # h0 object, by the forward's own aggregator and statics (a shared
        # envelope brings new data every batch)
        statics = dict(symmetric=plan.symmetric, **static)
        if request.halo_dtype is not None:
            statics["halo_dtype"] = request.halo_dtype
        rows = int(plan.b * plan.k)
        hoist = Hoist(
            build=partial(_agg0, statics=statics), shapes=((plan.b, fin),),
            span="agg0.build", counter="agg0", replaces=True,
            report=lambda builds, served: {
                "engaged": True, "builds": builds, "steps_served": served,
                "rows": rows, "width": int(fin)})
    # the halo-delta cache narrows only the feature wire; the gradient wire
    # follows halo_dtype
    narrow = "bfloat16" in (request.halo_dtype, request.compute_dtype)
    dims = list(zip([fin] + list(widths[:-1]), widths))
    return ModelSetup(
        plan_fields=fields, fwd_static=static, init_static={},
        extra_arrays={}, mask_fields=(), lane_widths=lanes,
        lane_widths_bwd=(), param_count=sum(a * b for a, b in dims),
        counters={"slots.work": slot_work(gcn_slot_passes(
            plan, fin, widths, static["fold_classes"],
            hoisted=hoist is not None, remat=request.remat))
            if "fold_classes" in static else None},
        wire_itemsize=(2 if narrow or request.halo_delta else 4,
                       2 if narrow else 4),
        hoist=hoist)


def masked_softmax_xent_local(logits, labels, valid, axis_name: str = AXIS):
    """Global mean softmax cross-entropy over valid (non-padding) rows.

    Per-chip sums are ``psum``-reduced so every chip holds the same scalar —
    the analogue of the loss ``MPI_Reduce`` (``Parallel-GCN/main.c:318-323``)
    and ``dist.all_reduce`` of the loss (``GPU/PGCN.py:223-224``), but exact:
    a single global mean rather than a mean-of-per-rank-means.
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    local = -jnp.sum(picked * valid)
    total = lax.psum(local, axis_name)
    count = lax.psum(jnp.sum(valid), axis_name)
    # a (mini-)batch can contain zero valid train rows globally; 0/0 would
    # poison the replicated weights with NaN for every later step
    return total / jnp.maximum(count, 1.0)


def masked_sigmoid_bce_local(logits, labels, valid, axis_name: str = AXIS):
    """Global mean elementwise sigmoid+BCE against one-hot targets — the MPI
    trainer's loss flavor (``Parallel-GCN/main.c:70-90``).

    The C stack's backward chain ``H=(H−Y)/[H(1−H)]; G=H⊙σ'(Z)`` collapses
    to exactly ``σ(z)−y`` (the BCE-with-logits gradient), so training under
    this loss reproduces grbgcn's update rule; the stable softplus form
    avoids materializing σ(z) in the loss itself.
    """
    y = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    bce = (jnp.maximum(logits, 0) - logits * y
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    local = jnp.sum(bce * valid[:, None])
    total = lax.psum(local, axis_name)
    count = lax.psum(jnp.sum(valid), axis_name)
    return total / jnp.maximum(count, 1.0)


def masked_err_local(logits, labels, valid, axis_name: str = AXIS):
    """The MPI stack's printed ``err``: Σ −y·log σ(z) over valid rows, summed
    (not averaged) across ranks — ``T = −Y⊙log H; err = reduce(T)``
    (``Parallel-GCN/main.c:318-323``)."""
    logp = jax.nn.log_sigmoid(logits)
    picked = jnp.take_along_axis(
        logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return lax.psum(-jnp.sum(picked * valid), axis_name)


def masked_accuracy_local(logits, labels, valid, axis_name: str = AXIS):
    """Global accuracy over valid rows (every chip gets the same scalar)."""
    pred = jnp.argmax(logits, axis=-1)
    hits = jnp.sum((pred == labels) * valid)
    count = lax.psum(jnp.sum(valid), axis_name)
    return lax.psum(hits, axis_name) / jnp.maximum(count, 1.0)
