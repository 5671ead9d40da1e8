"""Full-batch distributed GCN trainer over a 1D vertex-parallel mesh.

Reference equivalents: the epoch loop of ``GPU/PGCN.py:162-238`` (NCCL/Gloo)
and ``Parallel-GCN/main.c:166-453`` (MPI+GraphBLAS).  Structure preserved:

  * one graph part per chip; weights replicated; per-step gradient allreduce
    (here ``lax.psum`` over the mesh) — ``GPU/PGCN.py:150-154``;
  * synchronized initialization (shared PRNG seed instead of the reference's
    init-allreduce, ``GPU/PGCN.py:156-160``);
  * a warm-up step excluded from timing, per-epoch wall-clock aggregated MAX
    over ranks (``GPU/PGCN.py:202-228``) — under jit all chips run the same
    program, so host wall-clock of the blocking step IS the max;
  * end-of-run comm statistics in the reference's vocabulary
    (``GPU/PGCN.py:230-238``, ``Parallel-GCN/main.c:506-524``).

The whole train step — L forward exchanges+SpMMs, loss, L backward
exchanges+SpMMs, grad psum, Adam update — is ONE jitted ``shard_map`` program:
XLA schedules the collectives asynchronously against local compute, which is
the compiler-native form of the reference's Irecv/compute/Waitany overlap
(``Parallel-GCN/main.c:238-299``).
"""

from __future__ import annotations

import contextlib
import functools
import weakref
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from ..models import deepergcn, gat, gcn, mhgat, rgat, rgcn, unimp
from ..models import setup as model_setup
from ..models.gcn import (
    masked_accuracy_local,
    masked_err_local,
    masked_sigmoid_bce_local,
    masked_softmax_xent_local,
)
from ..obs.tracing import scope, set_counter, span, subscope
from ..parallel.mesh import (AXIS, make_mesh_1d, replicate, shard_stacked,
                             vary)
from ..parallel.plan import CommPlan
from ..utils.stats import CommStats
from ..utils.timers import PhaseTimer

# model registry: name -> models/setup.py::Model.  GAT is the reference's
# PGAT capability (GPU/PGAT.py) on the same trainer scaffold — like the
# reference, only the nn.Module differs between PGCN.py and PGAT.py; the
# models after it are published configurations of their own, each with its
# ``model_args``.  Each hook picks the plan arrays its forward reads.
Model = model_setup.Model
MODELS = {
    "gcn": Model(gcn.init_gcn_params, gcn.gcn_forward_local, gcn.model_setup,
                 ragged=True),
    # PGAT stacks bare modules: no inter-layer nonlinearity unless asked
    "gat": Model(gat.init_gat_params, gat.gat_forward_local, gat.model_setup,
                 ragged=True, combined_edges=True, activation="none"),
    # multi-head attention as published (models/mhgat.py): the GCN's own
    # plan arrays
    "mhgat": Model(mhgat.init_mhgat_params, mhgat.mhgat_forward_local,
                   mhgat.model_setup, full_batch_only=True, activation="elu"),
    # the deep residual stack (models/deepergcn.py): the exact GCN step's
    # slot-form plan arrays with unit weights; one scanned, per-layer-
    # checkpointed body whatever the depth
    "deepergcn": Model(deepergcn.init_deepergcn_params,
                       deepergcn.deepergcn_forward_local,
                       deepergcn.model_setup, full_batch_only=True),
    # typed rows and relations (models/rgcn.py): one slot layout per
    # relation, arrays its hook derives from the plan's edges; per-node
    # embeddings owned with the rows (``ModelSetup.row_owned``)
    "rgcn": Model(rgcn.init_rgcn_params, rgcn.rgcn_forward_local,
                  rgcn.model_setup, full_batch_only=True),
    # attention inside the typed layouts (models/rgat.py): rgcn's layout per
    # relation, mhgat's slot bodies over it
    "rgat": Model(rgat.init_rgat_params, rgat.rgat_forward_local,
                  rgat.model_setup, full_batch_only=True),
    # dot-product attention with a gated residual and a label input
    # (models/unimp.py): mhgat's plan arrays and store machinery
    "unimp": Model(unimp.init_unimp_params, unimp.unimp_forward_local,
                   unimp.model_setup, full_batch_only=True),
}


# loss registry: 'xent' is the torch stack's log-softmax+NLL
# (GPU/PGCN.py:204-205), 'bce' the MPI stack's sigmoid+BCE
# (Parallel-GCN/main.c:70-90) whose reported metric is `err`.
LOSSES = {
    "xent": masked_softmax_xent_local,
    "bce": masked_sigmoid_bce_local,
}


@dataclass
class ForwardSetup:
    """Resolved forward configuration — the ONE model/schedule/aggregator
    selection shared by the trainer and the serve engine
    (``sgcn_tpu/serve/engine.py``).  Keeping a single resolver is what makes
    the serve engine's forward program the SAME program the trainer's
    ``evaluate()`` compiles (bit-identical f32 logits, tier-1-pinned by
    ``tests/test_serve.py``) — a second copy of the selection rules would
    drift on exactly the branch parity depends on (Pallas auto-select,
    ragged field tuples, GAT table forms)."""

    comm_schedule: str            # resolved: 'a2a' or 'ragged', never 'auto'
    plan_fields: tuple            # CommPlan array fields the forward ships
    fwd_static: dict              # static kwargs of the forward fn
    forward_fn: object            # per-chip forward (MODELS registry)
    init_fn: object               # param init (MODELS registry)
    decision: dict                # resolve_comm_schedule's selection log
    custom: "model_setup.ModelSetup"   # what the model's setup hook
    #                                    resolved (models/setup.py)
    replica_budget: int = 0       # resolved: 'auto' -> the λ·degree knee B

    def ship_arrays(self, plan) -> dict:
        """The plan arrays the forward consumes, ready to shard: the fields
        the hook names narrowed to int8 masks, and the arrays it derived
        from the plan."""
        arrays = _plan_arrays(plan, self.plan_fields)
        for f in self.custom.mask_fields:
            arrays[f] = (arrays[f] != 0).astype(np.int8)
        arrays.update(self.custom.extra_arrays)
        return arrays


def resolve_forward_setup(plan: "CommPlan", fin: int, widths,
                          model: str = "gcn",
                          comm_schedule: str | None = None,
                          compute_dtype: str | None = None,
                          halo_staleness: int = 0,
                          replica_budget: int | str = 0,
                          refresh_band: float | None = None,
                          serve_subgraph: bool = False,
                          allow_pallas: bool = True,
                          model_args: dict | None = None,
                          shared_envelope: bool = False,
                          halo_dtype: str | None = None,
                          halo_delta: bool = False,
                          remat: bool = False) -> ForwardSetup:
    """Resolve (schedule, shipped plan fields, static forward kwargs) for one
    plan: the transport (``parallel/plan.py::resolve_comm_schedule``), then
    the model's setup hook (``MODELS[model].hook``, ``models/setup.py``),
    which validates ``model_args``, refuses what the model has no form for
    and picks the arrays and statics of its forward.  Shared by both
    trainers and the serve engine.  Builds the lazy plan layouts the
    selection needs (``ensure_ragged``, ``ensure_cell``,
    ``ensure_pallas_tiles``, ``ensure_replicas``, ``ensure_fold_slots``) as
    side effects.  ``replica_budget`` is a TRAINING-only lever (serving
    never passes it).  ``allow_pallas=False`` keeps the selection on the
    slot-pass/ELL aggregators whatever the VMEM-fit rule says, and
    ``shared_envelope=True`` says the program is compiled once for many
    plans padded to one envelope, so nothing derived from one plan's edges
    may shape it: the mini-batch trainer's ONE compiled step passes both."""
    from ..parallel.plan import choose_replica_budget, resolve_comm_schedule

    entry = MODELS[model]
    decision: dict = {}
    if replica_budget == "auto":
        # --replica-budget auto: the λ·degree-knee rule, resolved BEFORE
        # the schedule selection so the auto transport scores the wire at
        # the chosen shrink; the knee log lands in the manifest's
        # comm_schedule block (docs/replication.md)
        knee: dict = {}
        replica_budget = choose_replica_budget(plan, decision=knee)
        decision["replica_auto"] = knee
    replica_budget = int(replica_budget or 0)
    comm_schedule = resolve_comm_schedule(
        comm_schedule, [plan], model, halo_staleness,
        fin=fin, widths=list(widths), compute_dtype=compute_dtype,
        replica_budget=replica_budget, ragged=entry.ragged,
        decision=decision)
    if comm_schedule == "ragged":
        if not plan.symmetric:
            raise ValueError(
                "comm_schedule='ragged' uses the symmetric custom "
                "backward (the gradient rides the same ppermute ring); "
                "this plan is asymmetric — run the a2a schedule")
        plan.ensure_ragged()
    custom = entry.hook(plan, fin, widths, model_args, model_setup.Request(
        comm_schedule=comm_schedule, compute_dtype=compute_dtype,
        halo_dtype=halo_dtype, halo_staleness=halo_staleness,
        halo_delta=halo_delta, replica_budget=replica_budget,
        refresh_band=refresh_band, remat=remat,
        serve_subgraph=serve_subgraph, allow_pallas=allow_pallas,
        shared_envelope=shared_envelope, decision=decision))
    return ForwardSetup(
        comm_schedule=comm_schedule,
        plan_fields=custom.plan_fields, fwd_static=custom.fwd_static,
        forward_fn=entry.forward,
        init_fn=functools.partial(entry.init, **custom.init_static),
        decision=decision, replica_budget=replica_budget, custom=custom)


@dataclass
class TrainData:
    """Stacked per-chip training data (leading axis k, sharded over the mesh)."""

    h0: Any        # (k, B, f) input features
    labels: Any    # (k, B) int32
    train_valid: Any  # (k, B) float32 — 1 on real rows in the train split
    eval_valid: Any   # (k, B) float32 — 1 on real rows in the eval split


def make_train_data(
    plan: CommPlan,
    features: np.ndarray,
    labels: np.ndarray,
    train_mask: np.ndarray | None = None,
    eval_mask: np.ndarray | None = None,
) -> TrainData:
    """Scatter global (n, f) features and (n,) int labels into per-chip blocks."""
    n = plan.n
    h0 = plan.scatter_rows(features.astype(np.float32))
    lab = plan.scatter_rows(labels.reshape(n, 1).astype(np.int32))[..., 0]
    if train_mask is None:
        train_mask = np.ones(n, dtype=np.float32)
    if eval_mask is None:
        eval_mask = train_mask
    tv = plan.scatter_rows(train_mask.reshape(n, 1).astype(np.float32))[..., 0]
    ev = plan.scatter_rows(eval_mask.reshape(n, 1).astype(np.float32))[..., 0]
    tv = tv * plan.row_valid
    ev = ev * plan.row_valid
    return TrainData(h0=h0, labels=lab, train_valid=tv, eval_valid=ev)


def make_train_data_multihost(
    plan: CommPlan,
    mesh,
    features: np.ndarray,
    labels: np.ndarray,
    train_mask: np.ndarray | None = None,
    eval_mask: np.ndarray | None = None,
) -> TrainData:
    """Multi-process data placement: each process materializes blocks ONLY
    for its own chips and assembles the global sharded arrays with
    ``jax.make_array_from_process_local_data`` — the supported multi-host
    path (a ``device_put`` of host-local data to a global sharding is not).

    ``features``/``labels``/masks are indexed globally, but only rows owned
    by this process's chips are READ — each host may leave remote rows as
    zeros / memory-mapped, exactly like each MPI rank reading only its own
    ``H.r`` shard (``Parallel-GCN/main.c:456-504``; SLURM deployment
    ``GPU/pytorch.3node.slurm:46-56`` + ``GPU/PGCN.py:241-260``).

    Returns a ``TrainData`` of global jax.Arrays, drop-in for ``step`` /
    ``run_epochs`` / ``evaluate``.
    """
    import jax

    from ..parallel.mesh import local_chip_slice
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = plan.n
    sl = local_chip_slice(mesh)
    chips = range(plan.k)[sl]
    if train_mask is None:
        train_mask = np.ones(n, dtype=np.float32)
    if eval_mask is None:
        eval_mask = train_mask

    sh = NamedSharding(mesh, P(AXIS))

    def put(local, gshape):
        if jax.process_count() == 1:
            return jax.device_put(local, sh)
        return jax.make_array_from_process_local_data(sh, local, gshape)

    scatter = lambda x, dt: plan.scatter_rows(  # noqa: E731 — local shorthand
        np.asarray(x, dtype=dt).reshape(n, -1), chips=chips)
    f = features.shape[1]
    rv = plan.row_valid[sl]
    h0 = put(scatter(features, np.float32), (plan.k, plan.b, f))
    lab = put(scatter(labels, np.int32)[..., 0], (plan.k, plan.b))
    tv = put(scatter(train_mask, np.float32)[..., 0] * rv, (plan.k, plan.b))
    ev = put(scatter(eval_mask, np.float32)[..., 0] * rv, (plan.k, plan.b))
    return TrainData(h0=h0, labels=lab, train_valid=tv, eval_valid=ev)


def _plan_arrays(plan: CommPlan, fields) -> dict:
    return {f: getattr(plan, f) for f in fields}


def _unblock(tree):
    """Strip the leading per-chip block axis shard_map hands us (size 1)."""
    return jax.tree.map(lambda x: x[0], tree)


def _reblock(tree):
    """Re-add the leading per-chip block axis for ``out_specs=P(AXIS)``
    outputs (the stacked-carry convention, like ``logits[None]`` in eval)."""
    return jax.tree.map(lambda x: x[None], tree)


def _global_grad_norm(grads):
    """L2 norm over every leaf of an (already psum'd, replicated) grad tree."""
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return jnp.sqrt(sq)


class FullBatchTrainer:
    """Distributed full-batch trainer (PGCN-equivalent, ``-b jax`` backend).

    **Loop-invariant work over data is paid once per data set, not per
    step.**  A model whose setup hook brings a ``Hoist`` (``models/setup.py``;
    ``self.hoist``) — ``gcn``'s ``Â·h0`` on the exact path with an
    aggregate-first layer 0, ``rgcn``'s layer-0 means over the features —
    has it made by ``_agg0_for`` the first time a ``step`` / ``run_epochs`` /
    ``evaluate`` / ``predict`` sees a given ``data.h0`` (by identity, not
    value — do not mutate it in place): the hoist's build under the step's
    ``shard_map``, compiled, run once and dropped, in the hoist's span
    (``agg0.build`` for ``gcn``).  The result stays on the device and goes
    to every exact program beside ``data.h0`` or, for ``gcn``, in its
    place — one exchange, one slot pass and one fold fewer per step, same
    arithmetic in the same order.  The caller's ``h0`` is never touched.
    Counter ``agg0`` (``obs.tracing.counters``) reports engaged / builds /
    steps_served for ``gcn``'s and says ``engaged: false`` otherwise; a
    model's own hoist reports in its own counter.  A caller that feeds the
    programs data of its own sets ``hoist`` to ``None`` before any of them
    is traced (``MiniBatchTrainer.evaluate_fullgraph``)."""

    def __init__(
        self,
        plan: CommPlan,
        fin: int,
        widths: list[int],
        mesh=None,
        lr: float = 0.01,
        activation: str = "relu",
        final_activation: str = "none",
        optimizer: optax.GradientTransformation | None = None,
        seed: int = 0,
        model: str = "gcn",
        loss: str = "xent",
        compute_dtype: str | None = None,
        remat: bool = False,
        halo_dtype: str | None = None,
        halo_staleness: int = 0,
        halo_delta: bool = False,
        sync_every: int = 0,
        comm_schedule: str | None = None,
        replica_budget: int | str = 0,
        refresh_band: float | None = None,
        auto_tune_sync: bool = False,
        allow_pallas: bool = True,
        memory_budget: int | None = None,
        model_args: dict | None = None,
        shared_envelope: bool = False,
    ):
        """``shared_envelope=True``: this trainer's step is compiled once for
        many plans padded to one envelope (``MiniBatchTrainer``'s inner
        trainer), so nothing derived from one plan's edges may shape it
        (``resolve_forward_setup``).

        ``compute_dtype='bfloat16'`` runs forward/backward (including the
        halo exchange — half the ICI bytes) in bf16 with f32 master params
        and f32 loss/grad reduction; the reference stacks are f32-only, this
        is the TPU-native mixed-precision option (MXU eats bf16).

        ``halo_dtype='bfloat16'`` narrows ONLY the wire: the a2a send buffer
        is cast after the send-side gather and upcast after the halo gather
        (both directions — the symmetric backward's gradient exchange too),
        so ICI bytes halve while every table, activation and accumulation
        stays f32.  The single-chip bf16 lesson of round 5 (casts of the
        master arrays cost more than the halved HBM bytes buy) does not
        apply: only the (k, S, f) boundary buffer is cast.  GCN only — the
        GAT exchange ships its attention tables, which narrow via
        ``compute_dtype='bfloat16'`` (the packed one-gather path).

        ``remat=True`` wraps the WHOLE forward in ONE ``jax.checkpoint``:
        the forward pass keeps nothing but its inputs, and the backward
        first re-runs all of it — so while the backward runs, every layer's
        rows are live again, exactly as without it.  It frees the
        activations only for as long as the loss is computed; it does not
        bound what a deep stack holds (no reference analogue; the MPI code
        stores every layer's H and Z, ``Parallel-GCN/main.c:553-607``).  The
        form that does is a checkpoint PER LAYER around a scanned body,
        which keeps one layer's rows live at a time: a model that brings it
        (``models/deepergcn.py``, ``ModelSetup.checkpointed``) refuses
        ``remat=True``.

        ``halo_staleness=1`` selects the PIPELINED exchange (the
        PipeGCN-style bounded-staleness mode, ``ops/pspmm.py::pspmm_stale``):
        each chip carries per-layer halo buffers across steps, layer ℓ of
        step t aggregates with the halo exchanged during step t−1, and step
        t's exchange (features forward, gradients backward) has no same-step
        consumer — XLA schedules the a2a entirely behind local compute, so
        the only collective on the critical path disappears from it.  Step 0
        and, with ``sync_every=N``, every N-th step run the FULL-SYNC
        program (fresh halos consumed — exact math) to initialize/bound the
        carries' drift.  ``halo_delta=True`` adds the halo-delta cache on
        the feature wire: boundary rows ship as ``h_t − h_{t−1}`` in bf16
        and both ends accumulate the identical quantized increment, halving
        wire bytes (the gradient wire stays at ``halo_dtype``).  ``0``
        (default) is EXACTLY the pre-existing trainer — same code path, same
        program.  GCN + symmetric Â only; evaluation always runs the exact
        forward.

        ``comm_schedule`` selects the halo transport
        (``docs/comm_schedule.md``): ``'a2a'`` (default) is the dense
        globally-padded ``all_to_all``; ``'ragged'`` the per-round-sized
        ppermute ring (``ops/pspmm.py::pspmm_ragged_sym``) — same math, f32
        bit-identical losses, strictly fewer wire bytes whenever
        ``send_counts`` is skewed; ``'auto'`` picks ragged when the plan's
        dense padding efficiency falls below ``RAGGED_AUTO_EFFICIENCY``
        (``parallel/plan.py`` — the wire-byte ratio, which reduces to the
        row ratio for every table form; under ``halo_staleness=1`` the
        hidden exchange switches ``auto`` to the wire-byte-only rule).
        ``None`` reads ``$SGCN_COMM_SCHEDULE`` (default ``'a2a'``).
        Model-agnostic: GCN rides the ring with feature rows, GAT with its
        per-layer attention tables (fused, packed-bf16 and split forms —
        the split pair's two dense dispatches collapse into one two-lane
        ring).  Symmetric edge patterns only.  ``'ragged'`` +
        ``halo_staleness=1`` is the COMPOSED mode
        (``ops/pspmm.py::pspmm_stale_ragged``): round-structured carries
        ride the ring across steps, so both the Σ(λ−1) wire win and the
        hidden-exchange critical-path win apply at once.

        ``replica_budget=B`` (B > 0) enables HOT-HALO REPLICATION
        (CaPGNN-style, ``docs/replication.md``): the plan's top-B boundary
        rows by λ·degree live as persistent per-layer replicas on their
        consumer chips (``CommPlan.ensure_replicas``), leaving the
        per-layer wire entirely — both directions ship the shrunken
        ``nrep_*`` buckets/ring and fill the replica halo slots from
        carried tables.  Step 0 and every ``sync_every``-th step run the
        REFRESH program: the full exact exchange (f32-bit-identical math —
        ``--sync-every 1`` reproduces the no-replica trajectory exactly)
        with the replica tables re-read fresh as a byproduct.  Unlike
        ``halo_staleness``, every exchange stays synchronous: replication
        shrinks wire bytes (``halo_bytes_true`` is the gauge), not
        exposure.  GCN + symmetric Â + f32 non-remat only; composition
        with ``halo_staleness=1`` is deferred with a clean error;
        evaluation always runs the exact forward."""
        if halo_staleness not in (0, 1):
            raise ValueError(
                f"halo_staleness must be 0 (exact) or 1 (pipelined), got "
                f"{halo_staleness}")
        if halo_delta and not halo_staleness:
            raise ValueError(
                "halo_delta accumulates into the stale halo carry; it "
                "requires halo_staleness=1")
        if sync_every < 0:
            raise ValueError(f"sync_every must be >= 0, got {sync_every}")
        if sync_every and not (halo_staleness or replica_budget):
            raise ValueError(
                "sync_every schedules the stale mode's full-sync steps / "
                "the replica mode's refresh steps; it requires "
                "halo_staleness=1 or replica_budget>0 (exact mode is "
                "always in sync)")
        if replica_budget != "auto" and replica_budget < 0:
            raise ValueError(
                f"replica_budget must be >= 0 or 'auto', got "
                f"{replica_budget}")
        if replica_budget:
            if halo_delta:
                raise ValueError(
                    "replica_budget composed with halo_delta is deferred: "
                    "the delta baseline and the replica carry would "
                    "disagree on what a stale step ships — compose "
                    "replication with plain --halo-staleness 1 instead "
                    "(docs/replication.md)")
            if not plan.symmetric:
                raise ValueError(
                    "replica_budget uses the symmetric-Â custom backward "
                    "(gradient replicas mirror the feature replicas); this "
                    "plan is asymmetric — run without replication")
            if compute_dtype is not None or remat:
                raise ValueError(
                    "replica_budget is defined for the f32 non-remat "
                    "trainer (replica carries are f32 state threaded "
                    "through the step); drop compute_dtype/remat or run "
                    "without replication")
        if refresh_band is not None:
            if refresh_band < 0:
                raise ValueError(
                    f"refresh_band must be >= 0, got {refresh_band}")
            if not replica_budget:
                raise ValueError(
                    "refresh_band schedules the drift-driven PARTIAL "
                    "replica refresh; it requires replica_budget > 0 "
                    "(docs/replication.md)")
            if halo_staleness:
                raise ValueError(
                    "refresh_band with halo_staleness=1 is deferred: the "
                    "composed mode's replica state lives inside the stale "
                    "halo carry, which partial refresh cannot address per "
                    "row — run full refreshes there (docs/replication.md)")
        if halo_staleness:
            if not plan.symmetric:
                raise ValueError(
                    "halo_staleness=1 uses the symmetric-Â custom backward "
                    "(stale gradient exchange == stale forward exchange "
                    "pattern); this plan is asymmetric — run exact mode")
            if compute_dtype is not None or remat:
                raise ValueError(
                    "halo_staleness=1 is defined for the f32 non-remat "
                    "trainer (carries are f32 state threaded through the "
                    "step); drop compute_dtype/remat or run exact mode")
        # ONE selection rule for both trainers AND the serve engine
        # (resolve_forward_setup → parallel/plan.py::resolve_comm_schedule):
        # 'auto' silently prefers ragged on skewed plans (the kernel family
        # is schedule-agnostic since ISSUE 15, so the transport choice no
        # longer forfeits the Pallas VMEM aggregator); an explicit 'ragged'
        # is a contract, validated loudly inside the resolver.  Composition
        # with halo_staleness=1 is SUPPORTED (the round-structured carry of
        # pspmm_stale_ragged); the gates above (symmetric, f32 non-remat)
        # and the model's hook (what it has no form for) cover the rest.
        setup = resolve_forward_setup(
            plan, fin, widths, model=model, comm_schedule=comm_schedule,
            compute_dtype=compute_dtype, halo_staleness=halo_staleness,
            replica_budget=replica_budget, refresh_band=refresh_band,
            allow_pallas=allow_pallas, model_args=model_args,
            shared_envelope=shared_envelope, halo_dtype=halo_dtype,
            halo_delta=halo_delta, remat=remat)
        self.comm_decision = setup.decision   # selection → run manifest
        comm_schedule = setup.comm_schedule
        replica_budget = setup.replica_budget   # 'auto' -> the knee B
        self.comm_schedule = comm_schedule
        self.halo_staleness = halo_staleness
        self.halo_delta = halo_delta
        self.sync_every = sync_every
        self.halo_dtype = halo_dtype
        self.replica_budget = replica_budget
        self.refresh_band = refresh_band
        if refresh_band is not None and comm_schedule != "a2a":
            raise ValueError(
                "refresh_band rides the dense-a2a replica path; the "
                "ragged partial-refresh side channel is deferred — run "
                "--comm-schedule a2a (docs/replication.md)")
        # mid-run --sync-every retune (docs/comm_schedule.md, controller):
        # enabled when the schedule was asked as 'auto' (the controller
        # contract) or explicitly via auto_tune_sync, on any mode with a
        # sync schedule to tune
        self.controller = None
        if ((auto_tune_sync
             or str(self.comm_decision.get("asked")) == "auto")
                and sync_every and (halo_staleness or replica_budget)):
            from .controller import CommController
            self.controller = CommController(sync_every=sync_every)
            # the controller block is manifest-visible even before any
            # retune — "the controller ran and held" is itself a decision
            self.comm_decision["controller"] = self.controller.log()
        self.plan = plan
        self.fin = fin
        self.widths = list(widths)
        # analytic per-chip HBM footprint (obs/memory.py) + the
        # --memory-budget plan-time gate: an over-budget (plan, mode) fails
        # HERE — before any params init or array shipping — with the
        # itemized per-family table (docs/observability.md, memory block)
        from ..obs.memory import check_memory_budget, memory_model
        self.memory = memory_model(
            plan, fin, self.widths, workload="train", model=model,
            compute_dtype=compute_dtype, halo_dtype=halo_dtype,
            halo_staleness=halo_staleness, halo_delta=halo_delta,
            refresh_band=refresh_band, setup=setup)
        check_memory_budget(self.memory, memory_budget,
                            what=f"{model} trainer")
        # run telemetry (sgcn_tpu.obs): attach_recorder() compiles the
        # telemetry step variants; until then the recorder is off and every
        # code path below is the pre-existing trainer
        self.recorder = None
        self.timer = PhaseTimer()   # CAGNET-vocabulary phase breakdown —
        # the ONE code path for phase boundaries (fit()'s wall-clock and the
        # JSONL phase records both read it; sync= callables sit at each
        # block_until_ready boundary)
        from ..obs.tracing import SpanTimer
        self.spans = SpanTimer(timer=self.timer)   # measured-span layer
        # over the same timer: without a recorder a span IS a phase (two
        # perf_counter reads); with one, every span exit appends a
        # schema-v2 span event (docs/observability.md, measured vs analytic)
        self._step_count = 0
        self._cost_cache = {}       # lazy obs.attribution.step_cost models,
        # keyed by step kind (sync vs stale) — under --halo-delta the
        # feature wire's itemsize differs between the two (obs glossary)
        self.mesh = mesh if mesh is not None else make_mesh_1d(plan.k)
        self.activation = activation
        self.final_activation = final_activation
        self.compute_dtype = compute_dtype
        self.remat = remat
        init_fn, self._forward_fn = setup.init_fn, setup.forward_fn
        self.plan_fields = setup.plan_fields
        self._fwd_static = setup.fwd_static  # e.g. the ELL bucket structure
        custom = setup.custom
        if custom.preflight is not None:
            custom.preflight(self.mesh.local_devices[0])
        if remat and custom.checkpointed:
            raise ValueError(
                f"model {model!r} checkpoints each of its layers itself; "
                "remat=True (one checkpoint around the whole forward) has "
                "nothing left to free — drop it")
        # the model's own estimate, from its per-row and per-table arrays,
        # fenced against the device's capacity
        self.model_memory = None
        if custom.estimate_memory is not None:
            self.model_memory = custom.estimate_memory(train=True)
            model_setup.check_memory(self.mesh.local_devices[0],
                                     self.model_memory)
        for name, value in custom.counters.items():
            set_counter(name, value)
        self.model = model
        # what this trainer's programs fold the hub tail and the halo-source
        # edges as — beside ``agg0``, for a reader of counters()
        fold_slots = "fold_classes" in setup.fwd_static
        if fold_slots or "ltail_src" in setup.plan_fields:
            set_counter("fold", plan.fold_counts(slots=fold_slots))
        # loop-invariant work over data the model makes outside its step
        # (models/setup.py::Hoist; _agg0_for builds it)
        self.hoist = custom.hoist
        self._agg0 = None           # what the hoist built, on the device
        self._agg0_src = None       # weakref to the data.h0 it was made from
        self._agg0_builds = self._agg0_served = 0
        self.loss_name = loss
        self._loss_fn = LOSSES[loss]
        dims = list(zip([fin] + widths[:-1], widths))
        # what the model's hook says of its parameters, its output rows and
        # its label input (models/setup.py): leaves owned with the rows,
        # rows the forward returns, rows whose label it reads
        self._row_owned = custom.row_owned
        self._out_rows = custom.out_rows
        self._label_input = custom.label_input
        self.params = init_fn(jax.random.PRNGKey(seed), dims)
        self.opt = optimizer if optimizer is not None else optax.adam(lr)
        self.opt_state = self._init_opt_state(self.params)
        self.params = self._place(self.params)
        self.opt_state = self._place(self.opt_state)
        self.last_err = None
        self.pa = shard_stacked(self.mesh, setup.ship_arrays(plan))
        # per-exchange wire lane widths (f32-lane equivalents) and itemsizes
        # per direction — the real tables each model ships (GCN feature rows
        # at the project-first widths, GAT its attention tables, mhgat [Z ‖
        # t] forward and [g ‖ s, m, 1/D, c] backward), so the CommStats byte
        # gauges (halo_bytes_true/halo_bytes_wire) reconcile EXACTLY with
        # the obs roofline's attribution (docs/observability.md); count_step
        # takes a per-step override for the halo-delta cache's f32 re-base
        # syncs
        self.stats = CommStats.from_plan(
            plan, schedule=comm_schedule, lane_widths=custom.lane_widths,
            lane_widths_bwd=custom.lane_widths_bwd,
            wire_itemsize=custom.wire_itemsize[0],
            wire_itemsize_bwd=custom.wire_itemsize[1])
        if replica_budget:
            # the shrunken no-replica exchange's per-rank/wire figures —
            # count_step(replica=True) books replica steps at these, so
            # the cumulative gauges reconcile with the per-step roofline
            self.stats.set_replica(plan)
        self._step = self._build_step()
        self._eval = self._build_eval()
        self._multi = {}        # epochs -> compiled on-device epoch loop
        # composed replica × stale statics (docs/comm_schedule.md): the
        # stale forward dispatches to the pspmm_replica_stale ops, whose
        # stale steps ship the SHRUNKEN nrep_* exchange; kept off
        # _fwd_static so evaluate()'s exact forward never sees them
        self._rep_stale_static = {}
        if replica_budget and halo_staleness:
            self._rep_stale_static = {"replica": True}
            if comm_schedule == "ragged":
                self._rep_stale_static["nrep_rr_sizes"] = plan.nrep_rr_sizes
        if halo_staleness:
            # per-layer carry state, stacked per chip and sharded like the
            # plan arrays; zeros are never consumed — the first step (and
            # every sync step) runs the full-sync program, which reads the
            # FRESH exchange and refreshes every carry as a byproduct.
            # Under the composed mode the carries are ROUND-STRUCTURED ring
            # receive buffers (plan.stale_carry_shapes, schedule-aware);
            # under replica × stale the SAME carries subsume the replica
            # tables (replica slots/positions just stop being overwritten
            # between syncs), so no extra state appears.
            shapes = plan.stale_carry_shapes(fin, widths, delta=halo_delta,
                                             comm_schedule=comm_schedule)
            carry = {
                name: [np.zeros((plan.k,) + s, np.float32) for s in shps]
                for name, shps in shapes.items()
            }
            self.halo_carry = shard_stacked(self.mesh, carry)
            self._stale_step_idx = 0
            self._last_sync_idx = 0     # staleness-age gauge anchor
            self._step_stale = self._build_step_stale(fresh=False)
            self._step_sync = self._build_step_stale(fresh=True)
            self._multi_stale = {}   # epochs -> compiled stale epoch loop
        if replica_budget and not halo_staleness:
            # per-layer feature/gradient replica tables, stacked per chip
            # and sharded like the plan arrays; zeros are never consumed —
            # step 0 (and every sync_every-th step) runs the refresh
            # program, which reads the FULL exchange and refreshes every
            # carry as a byproduct (plan.replica_carry_shapes).  (The
            # composed replica × stale mode carries NO replica state of
            # its own — the stale halo carry above subsumes it.)
            self._rep_static = (
                {"comm_schedule": "ragged",
                 "rr_sizes": plan.rr_sizes,
                 "rr_edge_sizes": plan.rr_edge_sizes,
                 "nrep_rr_sizes": plan.nrep_rr_sizes,
                 "halo_r": plan.r}
                if comm_schedule == "ragged" else {"comm_schedule": "a2a"})
            partial = refresh_band is not None
            if partial:
                self._rep_static = dict(self._rep_static, track_base=True)
            shapes = plan.replica_carry_shapes(fin, widths, partial=partial)
            carry = {
                name: [np.zeros((plan.k,) + s, np.float32) for s in shps]
                for name, shps in shapes.items()
            }
            self.replica_carry = shard_stacked(self.mesh, carry)
            self._rep_step_idx = 0
            self._last_refresh_idx = 0    # refresh-age gauge anchor
            self._step_rep = self._build_step_replica(fresh=False)
            self._step_rep_sync = self._build_step_replica(fresh=True)
            if partial:
                # the drift-banded partial refresh program (the
                # --refresh-band refresh step; step 0 stays FULL — it
                # initializes the carries and baselines)
                self._step_rep_partial = self._build_step_replica(
                    fresh=False, partial=True)
            self._multi_rep = {}     # epochs -> compiled replica epoch loop

    # ------------------------------------------------- row-owned parameters
    def _owned_rows(self, path):
        """Where the leaf at ``path`` (of the parameters or the optimiser
        state) keeps its rows — the ``(k, height)`` map of
        ``ModelSetup.row_owned`` — or ``None`` for a replicated leaf.  A
        leaf is row-owned if its path passes a dict key the hook names;
        Adam's moments mirror the parameter tree, so they follow."""
        for i, key in enumerate(path):
            node = self._row_owned.get(getattr(key, "key", None))
            if node is not None:
                for sub in path[i + 1:]:
                    node = node[sub.key]
                return node
        return None

    def _by_owner(self, tree, owned, shared=lambda x: x):
        """``tree`` with ``owned(leaf)`` on the row-owned leaves and
        ``shared(leaf)`` on the others."""
        if not self._row_owned:
            return jax.tree.map(shared, tree)
        return jax.tree_util.tree_map_with_path(
            lambda p, x: (owned(x) if self._owned_rows(p) is not None
                          else shared(x)), tree)

    def _place(self, tree):
        """Row-owned leaves stacked per chip and sharded like ``h0``, the
        rest replicated."""
        return self._by_owner(tree,
                              lambda x: shard_stacked(self.mesh, x),
                              lambda x: replicate(self.mesh, x))

    def _specs(self, tree):
        """The ``shard_map`` specs of a parameter / optimiser-state tree."""
        if not self._row_owned:
            return P()
        return self._by_owner(tree, lambda x: P(AXIS), lambda x: P())

    def _split(self, tree: dict) -> tuple:
        """``(shared, owned)`` halves of a parameter-shaped dict."""
        return ({k: v for k, v in tree.items() if k not in self._row_owned},
                {k: v for k, v in tree.items() if k in self._row_owned})

    def _init_opt_state(self, params):
        """One optimiser state — or, with row-owned leaves, one for the
        replicated half and one for the owned half, so that the owned
        update is an op group of its own (``sgcn.row_update``)."""
        if not self._row_owned:
            return self.opt.init(params)
        shared, owned = self._split(params)
        return {"shared": self.opt.init(shared),
                "owned": self.opt.init(owned)}

    def _update(self, grads, opt_state, params):
        """Complete the replicated leaves' gradients with one ``psum`` (a
        row-owned leaf's gradient is whole where its rows are) and apply the
        optimiser.  ``params`` and ``grads`` hold the row-owned leaves as a
        chip sees them, ``opt_state`` — and the parameters returned — with
        the leading block axis ``shard_map`` hands over and takes back: the
        owned half strips and restores it INSIDE its sub-scope, so that the
        update's fusion is rooted there and not in a reshape outside every
        scope."""
        with scope("grad_psum"):
            grads = self._by_owner(grads, lambda g: g,
                                   lambda g: lax.psum(g, AXIS))
        with scope("optimizer"):
            if not self._row_owned:
                updates, opt_state = self.opt.update(grads, opt_state,
                                                     params)
                return optax.apply_updates(params, updates), opt_state, grads
            new, state = {}, {}
            for half, g, p in zip(("shared", "owned"), self._split(grads),
                                  self._split(params)):
                with (subscope("row_update") if half == "owned"
                      else contextlib.nullcontext()):
                    updates, st = self.opt.update(
                        g, self._by_owner(opt_state[half], lambda x: x[0]),
                        p)
                    state[half] = self._by_owner(st, lambda x: x[None])
                    new.update(self._by_owner(
                        optax.apply_updates(p, updates), lambda x: x[None]))
            return new, state, grads

    def _select_out(self, pa, labels, valid):
        """Labels and mask of the rows the forward returns."""
        if self._out_rows is None:
            return labels, valid
        rows, real = (pa[name] for name in self._out_rows)
        with scope("loss"):
            return labels[rows], valid[rows] * real

    def _label_feed(self, pa, labels, valid) -> tuple:
        """``(extra forward arguments, loss rows)``: with a model's label
        input the label ids of its rows (-1 elsewhere), and those rows out of
        the loss; ``((), valid)`` for every other model."""
        if self._label_input is None:
            return (), valid
        with scope("loss"):
            on = pa[self._label_input] != 0
            return (jnp.where(on, labels, -1),), jnp.where(on, 0.0, valid)

    def host_state(self) -> tuple:
        """``(params, opt_state)`` on the host, every row-owned leaf
        gathered into its global row order — what a checkpoint holds, and
        what a reader outside the mesh (a reference) can follow."""
        def gather(path, x):
            rows = self._owned_rows(path)
            x = np.asarray(x)
            if rows is None:
                return x
            ok = rows >= 0
            out = np.zeros((int(ok.sum()),) + x.shape[2:], x.dtype)
            out[rows[ok]] = x[ok]
            return out

        return jax.tree_util.tree_map_with_path(
            gather, (self.params, self.opt_state))

    def load_host_state(self, params, opt_state) -> None:
        """Place ``host_state()``'s form back on the mesh."""
        def scatter(path, x):
            rows = self._owned_rows(path)
            if rows is None:
                return x
            x = np.asarray(x)
            return np.where((rows >= 0).reshape(rows.shape + (1,) * (
                x.ndim - 1)), x[np.maximum(rows, 0)], 0).astype(x.dtype)

        self.params, self.opt_state = self._place(
            jax.tree_util.tree_map_with_path(scatter, (params, opt_state)))

    # ------------------------------------------------------------------ build
    def _cast(self, params, pa, h0):
        """``compute_dtype``'s narrowing of everything the forward reads."""
        if self.compute_dtype is None:
            return params, pa, h0
        dt = jnp.dtype(self.compute_dtype)
        params = jax.tree.map(lambda w: w.astype(dt), params)
        pa = {k: v.astype(dt) if v.dtype == jnp.float32 else v
              for k, v in pa.items()}
        return params, pa, jax.tree.map(lambda x: x.astype(dt), h0)

    def _forward(self, params, pa, h0, label_ids=None):
        """Per-chip logits.  With a ``Hoist`` ``h0`` is what ``_agg0_for``
        gave: ``(h0, what the hoist built)``, or the latter alone where it
        replaces ``h0``; with a label input ``label_ids`` are
        ``_label_feed``'s."""
        extra = {} if label_ids is None else {"label_ids": label_ids}
        if self.halo_dtype is not None:     # the wire's (gcn)
            extra["halo_dtype"] = self.halo_dtype
        params, pa, h0 = self._cast(params, pa, h0)
        if self.hoist is not None:
            h0, extra["hoisted"] = ((None, (h0,)) if self.hoist.replaces
                                    else h0)
        out = self._forward_fn(
            params, h0, pa,
            activation=self.activation,
            final_activation=self.final_activation,
            symmetric=self.plan.symmetric,
            **self._fwd_static,
            **extra,
        )
        return out.astype("float32")

    # ------------------------------------------- hoisted loop-invariant work
    def _agg0_for(self, h0, served: int = 1):
        """What the exact programs take where ``data.h0`` went: ``h0``
        itself, or with a ``Hoist`` ``(h0, what its build gave)`` — the
        latter alone where it replaces ``h0`` — built the first time this
        ``h0`` object is seen.  ``served`` counts the steps / forwards the
        caller is about to run on it (the hoist's counter).

        The build runs under the forward's ``shard_map``, compiled ahead of
        time, called once and dropped: no ``jit`` cache keeps the
        executable, so its temporaries do not stay reserved beside the step
        program's (PERF.md §2), and building before the step is first
        dispatched means the two are never loaded together."""
        hoist = self.hoist
        set_counter("agg0", {
            "engaged": False, "builds": 0, "steps_served": 0,
            "rows": int(self.plan.b * self.plan.k), "width": int(self.fin)})
        if hoist is None:
            return h0
        if self._agg0_src is None or self._agg0_src() is not h0:
            # the old arrays go before the new ones come
            self._agg0 = self._agg0_src = None
            with self.spans.span(hoist.span, sync=lambda: self._agg0):
                def per_chip(pa, h0):
                    _, pa, h0 = self._cast((), *_unblock((pa, h0)))
                    return _reblock(hoist.build(pa, h0))

                build = jax.jit(jax.shard_map(
                    per_chip, mesh=self.mesh, in_specs=(P(AXIS), P(AXIS)),
                    out_specs=P(AXIS))).lower(self.pa, h0).compile()
                self._agg0 = build(self.pa, h0)
                del build
            self._agg0_src = weakref.ref(h0)
            self._agg0_builds += 1
        self._agg0_served += served
        set_counter(hoist.counter, hoist.report(self._agg0_builds,
                                                self._agg0_served))
        return self._agg0[0] if hoist.replaces else (h0, self._agg0)

    def _one_step(self, params, opt_state, pa, h0, labels, valid,
                  telemetry: bool = False):
        """One per-chip training step (shared by _build_step/_build_multi).
        ``h0`` is what ``_agg0_for(data.h0)`` gave (``_forward``).

        ``telemetry=True`` (the program compiled by ``attach_recorder``)
        additionally returns the global L2 norm of the psum'd weight grads
        — already replicated, so it costs one reduce of each grad leaf."""
        fwd = (jax.checkpoint(self._forward, static_argnums=())
               if self.remat else self._forward)

        feed, valid = self._label_feed(pa, labels, valid)
        labels, valid = self._select_out(pa, labels, valid)
        # (row-owned leaves arrive with shard_map's block axis: _update)
        params = self._by_owner(params, lambda x: x[0])

        def loss_fn(ps):
            logits = fwd(ps, pa, h0, *feed)
            with scope("loss"):
                loss = self._loss_fn(logits, labels, valid)
                err = (masked_err_local(logits, labels, valid)
                       if self.loss_name == "bce" else loss)
            return loss, err

        # with respect to per-chip copies of the replicated weights
        # (parallel/mesh.py::vary): the gradients come back as per-chip
        # partials and are summed once, below.  With respect to ``params``
        # itself the transposition has summed them already and the psum
        # below multiplied them by k — what this step did until PR 27 (Adam
        # hides a constant factor; SGD and the gradient norm do not)
        (loss, err), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            vary(params))
        # dense weight-grad allreduce — GPU/PGCN.py:150-154 /
        # Parallel-GCN/main.c:422-425 (psum of local partials = full grad)
        params, opt_state, grads = self._update(grads, opt_state, params)
        if telemetry:
            # (a row-owned leaf's squares are summed over its owners)
            gnorm = _global_grad_norm(self._by_owner(
                grads, lambda g: jnp.sqrt(lax.psum(jnp.sum(jnp.square(g)),
                                                   AXIS))))
            return params, opt_state, loss, err, gnorm
        return params, opt_state, loss, err

    # ------------------------------------------------------- stale pipelining
    def _forward_stale(self, params, pa, h0, halos, ghalos, bases,
                       fresh: bool, gauges: bool = False):
        from ..models.gcn import gcn_forward_local_stale

        # composed mode: the stale forward rides the ring — pass the static
        # ring spec through (absent under the dense a2a carry)
        ragged = {k: self._fwd_static[k]
                  for k in ("comm_schedule", "rr_sizes", "rr_edge_sizes")
                  if k in self._fwd_static}
        out = gcn_forward_local_stale(
            params, h0, pa, halos, ghalos, bases,
            activation=self.activation,
            final_activation=self.final_activation,
            ell_buckets=self._fwd_static["ell_buckets"],
            delta=self.halo_delta,
            # the delta cache IS the bf16 wire; otherwise the stale feature
            # wire keeps the exact mode's halo_dtype semantics
            wire_dtype="bfloat16" if self.halo_delta else self.halo_dtype,
            gwire_dtype=self.halo_dtype,
            fresh=fresh,
            gauges=gauges,
            **ragged,
            **self._rep_stale_static,
        )
        if gauges:
            logits, nh, nb, qe = out
            return logits.astype("float32"), nh, nb, qe
        logits, nh, nb = out
        return logits.astype("float32"), nh, nb

    def _one_step_stale(self, params, opt_state, carry, pa, h0, labels,
                        valid, fresh: bool, telemetry: bool = False):
        """One per-chip training step under the pipelined stale exchange.

        The gradient-halo carries ride jax's cotangent machinery: the loss
        is differentiated w.r.t. ``(params, ghalos)`` and ``pspmm_stale``'s
        custom VJP returns, as the "gradient" of each ``ghalos[ℓ]``, the
        FRESH gradient exchange that becomes next step's carry.

        ``telemetry=True`` additionally returns ``(gnorm, gauges)`` — the
        drift gauges of the stale mode (``docs/observability.md``), all
        psum'd to global scalars so they come back replicated:

          * ``drift_sq[ℓ]``  — ``Σ (halo_next − halo_in)²``: the fresh
            exchange against the stale carry the step actually consumed —
            the per-layer ‖stale − fresh‖² proxy, available EVERY step
            (on a full-sync step it measures the drift the sync erased);
          * ``ref_sq[ℓ]``    — ``Σ halo_next²``, the normalizer for a
            relative drift figure;
          * ``qerr_sq[ℓ]``   — this step's halo-delta wire quantization
            residual ``Σ (full − base_next)²`` (zero without ``--halo-delta``).
        """
        halos, ghalos, bases = carry["halos"], carry["ghalos"], carry["bases"]

        def loss_fn(ps, gh):
            if telemetry:
                logits, nh, nb, qe = self._forward_stale(
                    ps, pa, h0, halos, gh, bases, fresh, gauges=True)
            else:
                logits, nh, nb = self._forward_stale(
                    ps, pa, h0, halos, gh, bases, fresh)
                qe = None
            loss = self._loss_fn(logits, labels, valid)
            err = (masked_err_local(logits, labels, valid)
                   if self.loss_name == "bce" else loss)
            return loss, (err, nh, nb, qe)

        (loss, (err, nh, nb, qe)), (grads, ngh) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(vary(params), ghalos)
        # weight grads are global partial sums (exact mode's psum); the halo
        # carries are PER-CHIP state — never reduced
        grads = jax.tree.map(lambda g: lax.psum(g, AXIS), grads)
        updates, opt_state = self.opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        new_carry = {"halos": nh, "ghalos": list(ngh), "bases": nb}
        if not telemetry:
            return params, opt_state, new_carry, loss, err
        gauges = {
            "drift_sq": jnp.stack([
                lax.psum(jnp.sum(jnp.square(n - o)), AXIS)
                for n, o in zip(nh, halos)]),
            "ref_sq": jnp.stack([
                lax.psum(jnp.sum(jnp.square(n)), AXIS) for n in nh]),
            "qerr_sq": jnp.stack([lax.psum(q, AXIS) for q in qe]),
        }
        return (params, opt_state, new_carry, loss, err,
                _global_grad_norm(grads), gauges)

    def _build_step_stale(self, fresh: bool, telemetry: bool = False):
        def per_chip(params, opt_state, carry, pa, h0, labels, valid):
            carry, pa, h0, labels, valid = _unblock(
                (carry, pa, h0, labels, valid))
            out = self._one_step_stale(
                params, opt_state, carry, pa, h0, labels, valid, fresh,
                telemetry=telemetry)
            params, opt_state, carry = out[:3]
            return (params, opt_state, _reblock(carry)) + out[3:]

        smapped = jax.shard_map(
            per_chip,
            mesh=self.mesh,
            in_specs=(P(), P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(), P(), P(AXIS), P(), P()) + ((P(), P())
                                                       if telemetry else ()),
        )
        return jax.jit(smapped, donate_argnums=(0, 1, 2))

    def _build_multi_stale(self, epochs: int):
        """``epochs`` STALE steps as one on-device fori_loop (the carry
        threads through the loop body; sync steps are scheduled around the
        loop by ``run_epochs``)."""
        def per_chip(params, opt_state, carry, pa, h0, labels, valid):
            carry, pa, h0, labels, valid = _unblock(
                (carry, pa, h0, labels, valid))
            z = jnp.zeros((epochs,), jnp.float32)

            def body(i, st):
                params, opt_state, carry, losses, errs = st
                params, opt_state, carry, loss, err = self._one_step_stale(
                    params, opt_state, carry, pa, h0, labels, valid, False)
                return (params, opt_state, carry, losses.at[i].set(loss),
                        errs.at[i].set(err))

            params, opt_state, carry, losses, errs = lax.fori_loop(
                0, epochs, body, (params, opt_state, carry, z, z))
            return params, opt_state, _reblock(carry), losses, errs

        smapped = jax.shard_map(
            per_chip,
            mesh=self.mesh,
            in_specs=(P(), P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(), P(), P(AXIS), P(), P()),
        )
        return jax.jit(smapped, donate_argnums=(0, 1, 2))

    def _stale_sync_due(self) -> bool:
        """Carry init (step 0) + the periodic full-sync schedule."""
        if self._stale_step_idx == 0:
            return True
        return bool(self.sync_every) and \
            self._stale_step_idx % self.sync_every == 0

    def _stale_run_one(self, data: TrainData):
        """One stale-mode optimizer step (sync or pipelined per schedule).

        With a recorder attached the telemetry programs run instead and the
        drift gauges ride along: returns ``(loss, err, extra)`` where
        ``extra`` is ``(gnorm, gauges, staleness_age, sync_step)`` under
        telemetry, else ``None``."""
        sync_step = self._stale_sync_due()
        age = self._stale_step_idx - self._last_sync_idx
        first = sync_step and self._stale_step_idx == 0
        telemetry = self.recorder is not None or (
            self.controller is not None and sync_step)
        if telemetry:
            self._ensure_tel_programs()
            prog = self._step_sync_tel if sync_step else self._step_stale_tel
            (self.params, self.opt_state, self.halo_carry, loss, err, gnorm,
             gauges) = prog(
                self.params, self.opt_state, self.halo_carry, self.pa,
                data.h0, data.labels, data.train_valid,
            )
            extra = (gnorm, gauges, age, sync_step)
            if sync_step:
                self._controller_observe(gauges, kind="stale", first=first)
        else:
            prog = self._step_sync if sync_step else self._step_stale
            (self.params, self.opt_state, self.halo_carry, loss, err) = prog(
                self.params, self.opt_state, self.halo_carry, self.pa,
                data.h0, data.labels, data.train_valid,
            )
            extra = None
        if sync_step:
            self._last_sync_idx = self._stale_step_idx
        self._stale_step_idx += 1
        # per-step feature-wire itemsize: a delta-mode SYNC step re-bases
        # with the full f32 row (ops/pspmm.py::_stale_exchange), so its
        # wire bytes are booked at 4, not the stale steps' bf16 2.
        # Composed replica × stale: a stale step's hidden exchange ships
        # the SHRUNKEN wire (replica=True booking); sync steps the full one
        self.stats.count_step(
            nlayers=self.nlayers, hidden=not sync_step,
            wire_itemsize=4 if (self.halo_delta and sync_step) else None,
            replica=bool(self.replica_budget) and not sync_step)
        return loss, err, extra

    # ---------------------------------------------------- hot-halo replicas
    def _forward_replica(self, params, pa, h0, reps, greps, fresh: bool,
                         bases=None, partial: bool = False):
        from ..models.gcn import gcn_forward_local_replica

        extra = {}
        if self.refresh_band is not None:
            extra["rep_base"] = bases
            if partial:
                extra["partial_step"] = True
                extra["band"] = float(self.refresh_band)
        out = gcn_forward_local_replica(
            params, h0, pa, reps, greps,
            activation=self.activation,
            final_activation=self.final_activation,
            ell_buckets=self._fwd_static["ell_buckets"],
            halo_dtype=self.halo_dtype,
            fresh=fresh,
            **self._rep_static,
            **extra,
        )
        if self.refresh_band is not None:
            logits, new_reps, new_bases, nships = out
            return logits.astype("float32"), new_reps, new_bases, nships
        logits, new_reps = out
        return logits.astype("float32"), new_reps, None, None

    def _one_step_replica(self, params, opt_state, carry, pa, h0, labels,
                          valid, fresh: bool, partial: bool = False,
                          telemetry: bool = False):
        """One per-chip training step under hot-halo replication.

        The gradient-replica carries ride jax's cotangent machinery exactly
        like the stale mode's ``ghalos``: the loss is differentiated w.r.t.
        ``(params, greps)`` and ``pspmm_replica``'s custom VJP returns, as
        the "gradient" of each ``greps[ℓ]``, the refreshed gradient-replica
        table on sync steps (the carry itself on replica steps).

        ``partial=True`` compiles the drift-banded PARTIAL refresh step
        (``--refresh-band``, ``pspmm_replica_partial``): the shrunken
        exchange plus the replica-only side channel of masked deltas; the
        program additionally returns the per-layer psum'd count of
        side-channel slots that actually carried a row — the booking
        figure ``CommStats.count_partial_refresh_step`` consumes.

        ``telemetry=True`` additionally returns ``(gnorm, gauges)`` — the
        replica drift gauges (``docs/replication.md``), psum'd to global
        scalars: ``drift_sq[ℓ]`` = ``Σ (rep_next − rep_in)²`` (the drift a
        refresh erased; identically zero on replica steps, whose carries
        pass through) and ``ref_sq[ℓ]`` = ``Σ rep_next²``, its normalizer.
        """
        reps, greps = carry["reps"], carry["greps"]
        bases = carry.get("rep_base")

        def loss_fn(ps, gr):
            logits, nr, nb, ns = self._forward_replica(
                ps, pa, h0, reps, gr, fresh, bases=bases, partial=partial)
            loss = self._loss_fn(logits, labels, valid)
            err = (masked_err_local(logits, labels, valid)
                   if self.loss_name == "bce" else loss)
            return loss, (err, nr, nb, ns)

        (loss, (err, nr, nb, ns)), (grads, ngr) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(vary(params), greps)
        grads = jax.tree.map(lambda g: lax.psum(g, AXIS), grads)
        updates, opt_state = self.opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        new_carry = {"reps": nr, "greps": list(ngr)}
        if nb is not None:
            new_carry["rep_base"] = nb
        extra_out = ()
        if partial:
            # ACTUAL shipped side-channel rows per layer (global): the
            # booking figure — forward count; the gradient side channel
            # ships the same masked rows (count_partial books ×2)
            extra_out = (jnp.stack([lax.psum(s, AXIS) for s in ns]),)
        if not telemetry:
            return (params, opt_state, new_carry, loss, err) + extra_out
        gauges = {
            "drift_sq": jnp.stack([
                lax.psum(jnp.sum(jnp.square(n - o)), AXIS)
                for n, o in zip(nr, reps)]),
            "ref_sq": jnp.stack([
                lax.psum(jnp.sum(jnp.square(n)), AXIS) for n in nr]),
        }
        return (params, opt_state, new_carry, loss, err) + extra_out + (
            _global_grad_norm(grads), gauges)

    def _build_step_replica(self, fresh: bool, partial: bool = False,
                            telemetry: bool = False):
        def per_chip(params, opt_state, carry, pa, h0, labels, valid):
            carry, pa, h0, labels, valid = _unblock(
                (carry, pa, h0, labels, valid))
            out = self._one_step_replica(
                params, opt_state, carry, pa, h0, labels, valid, fresh,
                partial=partial, telemetry=telemetry)
            params, opt_state, carry = out[:3]
            return (params, opt_state, _reblock(carry)) + out[3:]

        n_extra = (1 if partial else 0) + (2 if telemetry else 0)
        smapped = jax.shard_map(
            per_chip,
            mesh=self.mesh,
            in_specs=(P(), P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(), P(), P(AXIS), P(), P()) + (P(),) * n_extra,
        )
        return jax.jit(smapped, donate_argnums=(0, 1, 2))

    def _build_multi_replica(self, epochs: int):
        """``epochs`` REPLICA (non-refresh) steps as one on-device
        fori_loop; refresh steps are scheduled around the loop by
        ``run_epochs`` (cf. ``_build_multi_stale``)."""
        def per_chip(params, opt_state, carry, pa, h0, labels, valid):
            carry, pa, h0, labels, valid = _unblock(
                (carry, pa, h0, labels, valid))
            z = jnp.zeros((epochs,), jnp.float32)

            def body(i, st):
                params, opt_state, carry, losses, errs = st
                params, opt_state, carry, loss, err = \
                    self._one_step_replica(
                        params, opt_state, carry, pa, h0, labels, valid,
                        False)
                return (params, opt_state, carry, losses.at[i].set(loss),
                        errs.at[i].set(err))

            params, opt_state, carry, losses, errs = lax.fori_loop(
                0, epochs, body, (params, opt_state, carry, z, z))
            return params, opt_state, _reblock(carry), losses, errs

        smapped = jax.shard_map(
            per_chip,
            mesh=self.mesh,
            in_specs=(P(), P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(), P(), P(AXIS), P(), P()),
        )
        return jax.jit(smapped, donate_argnums=(0, 1, 2))

    def _replica_sync_due(self) -> bool:
        """Carry init (step 0) + the periodic refresh schedule.  With
        ``sync_every=0`` only step 0 refreshes — replicas then age for the
        whole run (the drift gauges are the signal that that was too
        lax)."""
        if self._rep_step_idx == 0:
            return True
        return bool(self.sync_every) and \
            self._rep_step_idx % self.sync_every == 0

    def _replica_run_one(self, data: TrainData):
        """One replica-mode optimizer step (refresh or shrunken-wire per
        schedule).  Returns ``(loss, err, extra)`` with ``extra`` =
        ``(gnorm, gauges, refresh_age, sync_step, first, refresh_rows)``
        under telemetry.

        With ``--refresh-band`` set, the scheduled refresh steps (every
        refresh EXCEPT step 0, which must initialize the carries and
        baselines in full) run the PARTIAL program instead: the per-layer
        counts of actually-shipped side-channel rows come back as a step
        output and are booked at their true value
        (``CommStats.count_partial_refresh_step``)."""
        sync_step = self._replica_sync_due()
        age = self._rep_step_idx - self._last_refresh_idx
        first = sync_step and self._rep_step_idx == 0
        partial = (sync_step and not first
                   and self.refresh_band is not None)
        telemetry = self.recorder is not None or (
            self.controller is not None and sync_step)
        refresh_rows = None
        args = (self.params, self.opt_state, self.replica_carry, self.pa,
                data.h0, data.labels, data.train_valid)
        if telemetry:
            self._ensure_tel_programs()
            prog = (self._step_rep_partial_tel if partial
                    else self._step_rep_sync_tel if sync_step
                    else self._step_rep_tel)
            out = prog(*args)
            (self.params, self.opt_state, self.replica_carry, loss,
             err) = out[:5]
            if partial:
                refresh_rows = np.asarray(out[5]).astype(np.int64)
            gnorm, gauges = out[-2], out[-1]
            extra = (gnorm, gauges, age, sync_step, first, refresh_rows)
            if sync_step:
                self._controller_observe(gauges, kind="replica",
                                         first=first)
        else:
            prog = (self._step_rep_partial if partial
                    else self._step_rep_sync if sync_step
                    else self._step_rep)
            out = prog(*args)
            (self.params, self.opt_state, self.replica_carry, loss,
             err) = out[:5]
            if partial:
                refresh_rows = np.asarray(out[5]).astype(np.int64)
            extra = None
        if sync_step:
            self._last_refresh_idx = self._rep_step_idx
        self._rep_step_idx += 1
        # replica steps ship the shrunken wire (and the shrunken TRUE
        # volume — replicated rows genuinely leave the exchange); full
        # refresh steps ship the full exact exchange; PARTIAL refresh
        # steps ship the shrunken wire plus the side channel, booked at
        # the ACTUAL per-layer shipped rows read back above
        if partial:
            self.stats.count_partial_refresh_step(
                nlayers=self.nlayers,
                refresh_rows=[int(x) for x in refresh_rows],
                wire_rows=int(self.plan.partial_refresh_wire_rows))
        else:
            self.stats.count_step(nlayers=self.nlayers,
                                  replica=not sync_step)
        return loss, err, extra

    def _run_epochs_replica(self, data: TrainData, epochs: int, sync: bool):
        return self._run_epochs_carried(
            data, epochs, sync,
            sync_due=self._replica_sync_due, run_one=self._replica_run_one,
            multi=self._multi_rep, build_multi=self._build_multi_replica,
            carry_attr="replica_carry", idx_attr="_rep_step_idx",
            count_kwargs={"replica": True})

    def _ensure_tel_programs(self) -> None:
        """Compile the telemetry step variants on first need — attached
        recorder (``attach_recorder``) or an active controller (which
        reads the drift gauges at sync/refresh steps even without a run
        directory).  ``jax.jit`` wrappers are lazy, so building them
        eagerly costs nothing until dispatch."""
        if getattr(self, "_step_tel", None) is None:
            self._step_tel = self._build_step(telemetry=True)
        if self.halo_staleness and \
                getattr(self, "_step_stale_tel", None) is None:
            self._step_stale_tel = self._build_step_stale(
                fresh=False, telemetry=True)
            self._step_sync_tel = self._build_step_stale(
                fresh=True, telemetry=True)
        if self.replica_budget and not self.halo_staleness and \
                getattr(self, "_step_rep_tel", None) is None:
            self._step_rep_tel = self._build_step_replica(
                fresh=False, telemetry=True)
            self._step_rep_sync_tel = self._build_step_replica(
                fresh=True, telemetry=True)
            if self.refresh_band is not None:
                self._step_rep_partial_tel = self._build_step_replica(
                    fresh=False, partial=True, telemetry=True)

    def _controller_observe(self, gauges, kind: str,
                            first: bool = False) -> None:
        """Feed a sync/refresh step's measured drift to the controller and
        apply its (possibly unchanged) ``sync_every`` target.  The
        INITIALIZING refresh is skipped — its in-graph gauge compares
        against the zero-init carry, so it measures initialization
        magnitude, not drift (the PR-10 lesson).  Every retune decision is
        appended to the manifest ``comm_schedule.controller`` log."""
        if self.controller is None or first:
            return
        d = np.sqrt(np.maximum(
            np.asarray(gauges["drift_sq"], np.float64), 0))
        r = np.sqrt(np.maximum(np.asarray(gauges["ref_sq"], np.float64), 0))
        rel = float(np.max(d / np.maximum(r, 1e-30))) if d.size else 0.0
        step_idx = (self._rep_step_idx if kind == "replica"
                    else self._stale_step_idx)
        self.sync_every = self.controller.observe(step_idx, rel)
        self.comm_decision["controller"] = self.controller.log()
        if self.recorder is not None:
            self.recorder.set_comm_schedule(self.comm_decision)

    @staticmethod
    def _replica_fields(gauges: dict, age: int, sync_step: bool,
                        replica_rows: int,
                        first_refresh: bool = False,
                        refresh_rows=None,
                        refresh_wire_rows: int | None = None) -> dict:
        """Host-side rendering of the in-graph replica gauges into the
        schema's ``replica`` block (``obs.schema.REPLICA_KEYS``): per-layer
        ‖replica − fresh‖ at each refresh (zero between refreshes — fresh
        values only exist on the wire when a refresh ships them) plus the
        refresh age of the consumed tables.  ``first_refresh`` (step 0)
        reports ZERO drift: the in-graph gauge there compares against the
        zero-initialized carry, so it measures initialization magnitude,
        not drift any refresh erased — feeding it to the operator would
        dominate every max/mean in the rendered report."""
        import numpy as np

        d = np.sqrt(np.maximum(np.asarray(gauges["drift_sq"], np.float64),
                               0))
        r = np.sqrt(np.maximum(np.asarray(gauges["ref_sq"], np.float64), 0))
        if first_refresh:
            d = np.zeros_like(d)
        out = {
            "refresh_age": int(age),
            "sync_step": bool(sync_step),
            "replica_rows": int(replica_rows),
            "replica_drift_rms": [float(x) for x in d],
            "replica_drift_rel": [float(x / max(y, 1e-30))
                                  for x, y in zip(d, r)],
        }
        if refresh_rows is not None:
            # drift-banded PARTIAL refresh (--refresh-band): the ACTUAL
            # per-layer side-channel rows this step shipped (each consumer
            # copy counts, like every send-volume gauge) — the per-step
            # face of CommStats' partial_refresh_* totals, which must
            # reconcile exactly (docs/replication.md)
            out["refresh_kind"] = "partial"
            out["refresh_rows"] = [int(x) for x in refresh_rows]
            out["refresh_wire_rows"] = int(refresh_wire_rows or 0)
        elif sync_step:
            out["refresh_kind"] = "full"
        return out

    def _build_step(self, mesh=None, telemetry: bool = False):
        def per_chip(params, opt_state, pa, h0, labels, valid):
            pa, h0, labels, valid = _unblock((pa, h0, labels, valid))
            return self._one_step(params, opt_state, pa, h0, labels, valid,
                                  telemetry=telemetry)

        ps, os = self._specs(self.params), self._specs(self.opt_state)
        smapped = jax.shard_map(
            per_chip,
            mesh=mesh if mesh is not None else self.mesh,
            in_specs=(ps, os, P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(ps, os, P(), P()) + ((P(),) if telemetry else ()),
        )
        return jax.jit(smapped, donate_argnums=(0, 1))

    def lower_step(self, mesh=None, fin: int | None = None,
                   kind: str = "step"):
        """AOT-lower ONE train step — no compilation, no execution.

        ``mesh`` may be an arbitrary mesh, including a device-less
        ``jax.experimental.topologies`` mesh (e.g. an 8-chip v5e slice this
        host does not have); ``None`` uses the trainer's own mesh.  Inputs
        are ShapeDtypeStructs shaped like this trainer's live arrays, so
        the lowered module is exactly the program ``step()`` runs, just
        targeted at the given topology — with a ``Hoist``, the step that
        takes what it builds beside ``h0`` or in its place (``gcn``: ``Â·h0``,
        and no layer-0 aggregation).

        ``kind`` selects which of the trainer's step programs to lower:
        ``'step'`` the exact-mode step; ``'stale'`` / ``'sync'`` the
        pipelined stale-mode step and its periodic full-sync flavor
        (``halo_staleness=1`` trainers only); ``'rep'`` / ``'rep_sync'``
        the hot-halo-replication step (shrunken wire) and its refresh
        flavor (``replica_budget>0`` trainers only).  The carry-threading
        kinds include the carry inputs and lower on the trainer's own mesh
        — those builders are mesh-bound.

        Two consumers: the overlap evidence test
        (``tests/test_overlap_hlo.py``) compiles the real multi-chip TPU
        program and asserts the async all-to-all start/done schedule
        brackets the local slot passes — the compiled-schedule form of the
        reference's Irecv/compute/Waitany overlap
        (``Parallel-GCN/main.c:238-299``); and the static-analysis HLO
        audit (``sgcn_tpu/analysis``) lowers every supported mode on the
        virtual 8-dev mesh and checks the collective census / wire dtype /
        donation contracts of the lowered module."""
        from jax.sharding import NamedSharding

        if kind not in ("step", "stale", "sync", "rep", "rep_sync",
                        "rep_partial"):
            raise ValueError(f"unknown step kind {kind!r}")
        if kind in ("stale", "sync") and not self.halo_staleness:
            raise ValueError(
                f"kind={kind!r} lowers the stale-mode programs; this "
                "trainer runs exact mode (halo_staleness=0)")
        if kind in ("rep", "rep_sync") and not (self.replica_budget
                                               and not self.halo_staleness):
            raise ValueError(
                f"kind={kind!r} lowers the replica-mode programs; this "
                "trainer runs without standalone replication (the composed "
                "replica × stale programs lower via kind='stale'/'sync')")
        if kind == "rep_partial" and self.refresh_band is None:
            raise ValueError(
                "kind='rep_partial' lowers the --refresh-band partial "
                "refresh program; this trainer runs full refreshes")
        if kind != "step" and mesh not in (None, self.mesh):
            raise ValueError(
                "carry-threading step programs are built against the "
                "trainer's own mesh; pass mesh=None for "
                "kind='stale'/'sync'/'rep'/'rep_sync'")
        mesh = self.mesh if mesh is None else mesh
        fin = self.fin if fin is None else fin
        rep = NamedSharding(mesh, P())
        shd = NamedSharding(mesh, P(AXIS))
        k, b = self.plan.k, self.plan.b

        def sds(x, sharding):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        params, opt_state = self._by_owner(
            (self.params, self.opt_state), lambda x: sds(x, shd),
            lambda x: sds(x, rep))
        pa = jax.tree.map(lambda x: sds(x, shd), self.pa)
        h0 = jax.ShapeDtypeStruct((k, b, fin), np.float32, sharding=shd)
        if self.hoist is not None:
            built = tuple(jax.ShapeDtypeStruct((k, *shape), np.float32,
                                               sharding=shd)
                          for shape in self.hoist.shapes)
            h0 = built[0] if self.hoist.replaces else (h0, built)
        labels = jax.ShapeDtypeStruct((k, b), np.int32, sharding=shd)
        valid = jax.ShapeDtypeStruct((k, b), np.float32, sharding=shd)
        if kind != "step":
            live = (self.halo_carry if kind in ("stale", "sync")
                    else self.replica_carry)
            carry = jax.tree.map(lambda x: sds(x, shd), live)
            prog = {"stale": getattr(self, "_step_stale", None),
                    "sync": getattr(self, "_step_sync", None),
                    "rep": getattr(self, "_step_rep", None),
                    "rep_sync": getattr(self, "_step_rep_sync", None),
                    "rep_partial": getattr(self, "_step_rep_partial",
                                           None)}[kind]
            return prog.lower(params, opt_state, carry, pa, h0, labels,
                              valid)
        return self._build_step(mesh=mesh).lower(
            params, opt_state, pa, h0, labels, valid)

    def _build_multi(self, epochs: int):
        """Compile `epochs` training steps as ONE on-device fori_loop.

        One host dispatch and one loss readback per call instead of one
        per epoch (PERF.md bring-up has the measured step()-vs-fused-epoch
        gap on the chip).  Semantics are identical to `epochs` sequential
        ``step()`` calls; per-epoch losses come back as an array (the
        reference's per-epoch loss print, ``GPU/PGCN.py:223-224``, reads
        them after the run).
        """
        def per_chip(params, opt_state, pa, h0, labels, valid):
            pa, h0, labels, valid = _unblock((pa, h0, labels, valid))
            z = jnp.zeros((epochs,), jnp.float32)

            def body(i, carry):
                params, opt_state, losses, errs = carry
                params, opt_state, loss, err = self._one_step(
                    params, opt_state, pa, h0, labels, valid)
                return (params, opt_state, losses.at[i].set(loss),
                        errs.at[i].set(err))

            params, opt_state, losses, errs = lax.fori_loop(
                0, epochs, body, (params, opt_state, z, z))
            return params, opt_state, losses, errs

        ps, os = self._specs(self.params), self._specs(self.opt_state)
        smapped = jax.shard_map(
            per_chip,
            mesh=self.mesh,
            in_specs=(ps, os, P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(ps, os, P(), P()),
        )
        return jax.jit(smapped, donate_argnums=(0, 1))

    def run_epochs(self, data: TrainData, epochs: int, sync: bool = True):
        """Run ``epochs`` steps in one device program; return per-epoch losses.

        ``sync=False`` returns the on-device loss array without blocking.

        Stale mode runs the same on-device loop over PIPELINED steps, with
        the full-sync steps (carry init + every ``sync_every``-th step)
        dispatched individually around the loop segments.

        With a recorder attached, epochs dispatch as individual ``step()``
        calls so each emits its JSONL event — per-step observability is
        exactly what the fused loop cannot provide (documented trade;
        ``attach_recorder``)."""
        if self.recorder is not None:
            losses = np.asarray([self.step(data) for _ in range(epochs)],
                                np.float32)
            return losses
        if self.halo_staleness:
            return self._run_epochs_stale(data, epochs, sync)
        if self.replica_budget:
            return self._run_epochs_replica(data, epochs, sync)
        if epochs not in self._multi:
            self._multi[epochs] = self._build_multi(epochs)
        self.params, self.opt_state, losses, errs = self._multi[epochs](
            self.params, self.opt_state, self.pa,
            self._agg0_for(data.h0, epochs), data.labels, data.train_valid,
        )
        self.last_err = errs[-1]        # keep step()'s scalar contract
        for _ in range(epochs):
            self.stats.count_step(nlayers=self.nlayers)
        return np.asarray(losses) if sync else losses

    def _run_epochs_stale(self, data: TrainData, epochs: int, sync: bool):
        return self._run_epochs_carried(
            data, epochs, sync,
            sync_due=self._stale_sync_due, run_one=self._stale_run_one,
            multi=self._multi_stale, build_multi=self._build_multi_stale,
            carry_attr="halo_carry", idx_attr="_stale_step_idx",
            # composed replica × stale: the fused stale steps ship the
            # shrunken wire AND hide it — book both
            count_kwargs={"hidden": True,
                          "replica": bool(self.replica_budget)})

    def _run_epochs_carried(self, data: TrainData, epochs: int, sync: bool,
                            *, sync_due, run_one, multi, build_multi,
                            carry_attr: str, idx_attr: str,
                            count_kwargs: dict):
        """The shared carried-epoch loop of the stale and replica modes:
        sync/refresh steps (per ``sync_due``) dispatch individually through
        ``run_one`` (which also advances the step index and books stats);
        the stretches between them run as ONE on-device fori_loop over the
        ``build_multi`` program, with the carry threading through
        ``carry_attr``.  One implementation — the two modes differ only in
        which carry, which sync predicate, and how ``count_step`` books
        the fused steps (hidden vs replica)."""
        parts, err_parts = [], []
        left = epochs
        while left > 0:
            if sync_due():
                loss, err, _ = run_one(data)
                parts.append(jnp.reshape(loss, (1,)))
                err_parts.append(jnp.reshape(err, (1,)))
                left -= 1
                continue
            run = left
            if self.sync_every:
                until_sync = (self.sync_every
                              - getattr(self, idx_attr) % self.sync_every)
                run = min(left, until_sync)
            if run not in multi:
                multi[run] = build_multi(run)
            (self.params, self.opt_state, carry, losses,
             errs) = multi[run](
                self.params, self.opt_state, getattr(self, carry_attr),
                self.pa, data.h0, data.labels, data.train_valid,
            )
            setattr(self, carry_attr, carry)
            setattr(self, idx_attr, getattr(self, idx_attr) + run)
            for _ in range(run):
                self.stats.count_step(nlayers=self.nlayers, **count_kwargs)
            parts.append(losses)
            err_parts.append(errs)
            left -= run
        losses = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        errs = (err_parts[0] if len(err_parts) == 1
                else jnp.concatenate(err_parts))
        self.last_err = errs[-1]
        return np.asarray(losses) if sync else losses

    def _build_eval(self):
        def per_chip(params, pa, h0, labels, valid):
            pa, h0, labels, valid = _unblock((pa, h0, labels, valid))
            params = self._by_owner(params, lambda x: x[0])
            feed, valid = self._label_feed(pa, labels, valid)
            labels, valid = self._select_out(pa, labels, valid)
            logits = self._forward(params, pa, h0, *feed)
            # eval loss uses the SAME objective as training, so train/eval
            # losses are comparable under --loss bce too (the MPI stack
            # reports the one flavor it trains with,
            # Parallel-GCN/main.c:318-335)
            loss = self._loss_fn(logits, labels, valid)
            acc = masked_accuracy_local(logits, labels, valid)
            return loss, acc, logits[None]

        smapped = jax.shard_map(
            per_chip,
            mesh=self.mesh,
            in_specs=(self._specs(self.params), P(AXIS), P(AXIS), P(AXIS),
                      P(AXIS)),
            out_specs=(P(), P(), P(AXIS)),
        )
        return jax.jit(smapped)

    # ------------------------------------------------------ run telemetry
    def attach_recorder(self, recorder) -> None:
        """Attach a ``sgcn_tpu.obs.RunRecorder``: compiles telemetry step
        variants (grad-norm out; drift gauges in stale mode) and switches
        ``step``/``run_epochs`` to per-step event emission.  ``run_epochs``
        then dispatches one program per step instead of the fused on-device
        epoch loop — per-step wall times and loss readbacks are exactly what
        the fused loop cannot surface; detach (``recorder=None``) to get the
        one-dispatch path back."""
        self.recorder = recorder
        self.spans.recorder = recorder   # span exits now emit span events
        if getattr(self, "comm_decision", None):
            # the schedule-selection inputs (resolve_comm_schedule) land in
            # the run manifest, so an 'auto' pick is reconstructible from
            # the run directory alone (docs/observability.md)
            recorder.set_comm_schedule(self.comm_decision)
        if getattr(self, "memory", None) is not None:
            # the analytic footprint (model-only here — the measured join
            # needs a compiled program; the audit and the serve engine add
            # it) lands in the manifest's schema-v6 memory block
            recorder.set_memory(self.memory.block())
        self._ensure_tel_programs()

    def _step_cost_model(self, sync_step: bool = True):
        """Per-step-kind analytic cost: under ``--halo-delta`` the FEATURE
        wire is bf16 on stale steps but full f32 on (re-base) sync steps,
        while the gradient wire keeps ``--halo-dtype`` — so the cost model
        takes a per-direction wire-itemsize split and is cached per step
        kind (the obs glossary documents the split).  Under
        ``--replica-budget`` a non-sync step prices the SHRUNKEN exchange
        (``step_cost(replica=True)``): replicated rows leave both the true
        and the wire volume, which is exactly what ``count_step``'s
        replica booking accumulates — the gauges reconcile per step."""
        key = bool(sync_step)
        if key not in self._cost_cache:
            from ..obs.attribution import step_cost
            # (halo_dtype and halo_delta are gcn's: every other hook
            # refuses them)
            wire = None
            if self.halo_delta and sync_step:
                # the re-base wire ships the FULL f32 row regardless of
                # --halo-dtype (ops/pspmm.py fresh-delta path) — must
                # match count_step's wire_itemsize=4 override exactly
                fwd = 4
            elif self.halo_dtype == "bfloat16" or self.halo_delta:
                fwd = 2
            else:
                fwd = None
            bwd = 2 if self.halo_dtype == "bfloat16" else None
            if fwd is not None or bwd is not None:
                wire = (fwd, bwd)
            self._cost_cache[key] = step_cost(
                self.plan, self.fin, self.widths,
                compute_dtype=self.compute_dtype,
                wire_itemsize=wire,
                comm_schedule=self.comm_schedule,
                model=self.model,
                replica=bool(self.replica_budget) and not sync_step)
        return self._cost_cache[key]

    def _record_step_event(self, loss: float, err, gnorm, wall_s: float,
                           drift: dict | None,
                           replica: dict | None = None) -> None:
        from ..obs.attribution import roofline_fields
        from ..obs.tracing import measured_vs_model_block

        roofline = mvm = None
        # honesty gate: the gather model describes the
        # bucketed slot-pass aggregators (GCN ELL, GAT combined-edge) — for
        # the Pallas VMEM kernel it would describe a program that didn't
        # run, so omit it rather than mislead.  GAT attributes against its
        # own table-form-aware model (attribution.step_cost(model='gat')),
        # which is what makes the wire gauges reconcile with CommStats'.
        if "pallas_tb" not in self._fwd_static:
            sync_like = drift is None or bool(drift.get("sync_step"))
            if replica is not None:
                # replica steps price the shrunken exchange; FULL refresh
                # steps the full one; PARTIAL refresh steps the shrunken
                # exchange plus the side channel at the step's ACTUAL
                # shipped rows (add_partial_refresh — CommStats books the
                # identical figures, so the two reconcile per step).
                # Exposure is NOT affected — every replica-mode exchange
                # has a same-step consumer (unlike staleness)
                partial = replica.get("refresh_kind") == "partial"
                sync_like = bool(replica.get("sync_step")) and not partial
            cost = self._step_cost_model(sync_like)
            if replica is not None and partial:
                from ..obs.attribution import add_partial_refresh
                bwd_item = (self.stats.wire_itemsize_bwd
                            if self.stats.wire_itemsize_bwd is not None
                            else self.stats.wire_itemsize)
                cost = add_partial_refresh(
                    cost, replica["refresh_rows"],
                    replica["refresh_wire_rows"],
                    self.stats.wire_itemsize, bwd_item)
            ex_step = 2 * self.nlayers      # this step's exchanges
            exposed_step = 0 if (drift is not None
                                 and not drift.get("sync_step")) else ex_step
            roofline = roofline_fields(
                cost, wall_s, exchanges=ex_step,
                exposed_exchanges=exposed_step,
                device_kind=self.mesh.devices.flat[0].device_kind)
            # measured-vs-analytic reconciliation: the span-measured step
            # time joined against the same cost model, per component —
            # wall_s here IS the step span's duration, so the block's
            # phase_total_s reconciles with PhaseTimer.report() exactly
            mvm = measured_vs_model_block(cost, wall_s)
        self.recorder.record_step(
            step=self._step_count, loss=loss, wall_s=wall_s,
            err=float(err) if self.loss_name == "bce" else None,
            grad_norm=float(gnorm) if gnorm is not None else None,
            comm=self.stats.report(),
            phases=self.timer.report() or None,
            drift=drift,
            replica=replica,
            roofline=roofline,
            measured_vs_model=mvm,
        )

    @staticmethod
    def _drift_fields(gauges: dict, age: int, sync_step: bool,
                      rr_sizes: tuple | None = None) -> dict:
        """Host-side rendering of the in-graph gauge scalars (see
        ``_one_step_stale``) into the schema's drift block.

        ``rr_sizes`` (composed stale × ragged mode only): adds the
        per-round staleness-age vector ``round_age`` — for each ring round,
        the age of the buffer the step CONSUMED (0 on a sync step: received
        this step; the staleness age on a stale step: carried from t−1;
        null for rounds with S_d = 0, which ship nothing).  Uniform today
        (all rounds share one sync schedule) but per-round by construction,
        so ``--sync-every`` tuning stays observable if round scheduling
        ever diverges (``scripts/obs_report.py`` renders it)."""
        import numpy as np

        d = np.sqrt(np.maximum(np.asarray(gauges["drift_sq"], np.float64), 0))
        r = np.sqrt(np.maximum(np.asarray(gauges["ref_sq"], np.float64), 0))
        q = np.sqrt(np.maximum(np.asarray(gauges["qerr_sq"], np.float64), 0))
        out = {
            "staleness_age": int(age),
            "sync_step": bool(sync_step),
            "halo_drift_rms": [float(x) for x in d],
            "halo_drift_rel": [float(x / max(y, 1e-30))
                               for x, y in zip(d, r)],
            "halo_quant_err_rms": [float(x) for x in q],
        }
        if rr_sizes is not None:
            out["round_age"] = [None if sd == 0
                                else (0 if sync_step else int(age))
                                for sd in rr_sizes]
        return out

    # ------------------------------------------------- checkpoint/resume state
    # The carry attribute (at most one exists) whose leaves a full-state
    # checkpoint must persist: the stale-halo carry subsumes the replica
    # tables under the composed mode, so the two are mutually exclusive.
    def _carry_attr(self) -> str | None:
        if self.halo_staleness:
            return "halo_carry"
        if self.replica_budget:
            return "replica_carry"
        return None

    def resume_state(self) -> tuple[dict, list]:
        """``(state, carry_leaves)`` — everything beyond (params, opt_state)
        a bit-identical resume needs (``docs/resilience.md``):

          * the step counters that drive the sync/refresh SCHEDULE
            (``_stale_step_idx``/``_rep_step_idx`` and their last-sync
            anchors) — without them a resumed stale run re-runs the
            initializing full-sync and diverges from the uninterrupted
            trajectory on the very first step;
          * the EFFECTIVE ``sync_every`` plus the controller's retune log
            (a mid-run retune is algorithmic state, not configuration);
          * the cumulative CommStats gauges, so the end-of-run comm report
            reconciles across the seam;
          * the stale/replica carry leaves (host copies, f32) — the
            PipeGCN/CaPGNN algorithmic state itself.

        ``state`` is JSON-able; ``carry_leaves`` is a flat list of numpy
        arrays in ``jax.tree`` order for the live carry structure."""
        state: dict = {
            "step_count": int(self._step_count),
            "sync_every": int(self.sync_every),
            "comm_stats": self.stats.state(),
        }
        if self.halo_staleness:
            state["stale_step_idx"] = int(self._stale_step_idx)
            state["last_sync_idx"] = int(self._last_sync_idx)
        if self.replica_budget and not self.halo_staleness:
            state["rep_step_idx"] = int(self._rep_step_idx)
            state["last_refresh_idx"] = int(self._last_refresh_idx)
        if self.controller is not None:
            state["controller"] = self.controller.state()
        carry_leaves: list = []
        attr = self._carry_attr()
        if attr is not None:
            live = jax.tree.leaves(getattr(self, attr))
            if any(not getattr(x, "is_fully_addressable", True)
                   for x in live):
                # multi-process mesh: the carry is P(AXIS)-sharded across
                # hosts, so the coordinator cannot fetch it — fail with
                # the repo's standard clean deferral instead of the
                # cryptic non-addressable-devices RuntimeError np.asarray
                # would raise mid-save (params/opt_state are replicated
                # and stay checkpointable; exact mode is unaffected)
                raise ValueError(
                    "full-state checkpointing of the stale/replica carry "
                    "is single-process for now: the carry is sharded "
                    "across hosts and the coordinator cannot fetch it — "
                    "run exact mode for multi-host durable checkpoints, "
                    "or checkpoint carried modes from a single-process "
                    "run (docs/resilience.md)")
            state["carry"] = attr
            carry_leaves = [np.asarray(x) for x in live]
            state["n_carry"] = len(carry_leaves)
        return state, carry_leaves

    def restore_resume_state(self, state: dict, carry_leaves=None) -> None:
        """Restore ``resume_state()`` output onto a trainer built with the
        SAME flags (plan, mode levers, widths) — the checkpoint loader
        validates shape/mode agreement and raises clear errors before
        calling this; here the carry is re-sharded exactly like its
        zero-init was."""
        self._step_count = int(state.get("step_count", 0))
        if "sync_every" in state:
            self.sync_every = int(state["sync_every"])
        if self.halo_staleness:
            self._stale_step_idx = int(state.get("stale_step_idx", 0))
            self._last_sync_idx = int(state.get("last_sync_idx", 0))
        if self.replica_budget and not self.halo_staleness:
            self._rep_step_idx = int(state.get("rep_step_idx", 0))
            self._last_refresh_idx = int(state.get("last_refresh_idx", 0))
        if self.controller is not None and state.get("controller"):
            self.controller.load_state(state["controller"])
            self.comm_decision["controller"] = self.controller.log()
        if state.get("comm_stats"):
            self.stats.load_state(state["comm_stats"])
        attr = self._carry_attr()
        if attr is not None and carry_leaves:
            live = getattr(self, attr)
            treedef = jax.tree.structure(live)
            carry = jax.tree.unflatten(treedef, list(carry_leaves))
            setattr(self, attr, shard_stacked(self.mesh, carry))

    # ------------------------------------------------------------------- api
    def step(self, data: TrainData, sync: bool = True):
        """One training step.  ``sync=True`` (default) blocks on the loss
        scalar and returns a float — the per-epoch readback the reference's
        loss print implies (``GPU/PGCN.py:223-224``).  ``sync=False`` returns
        the on-device loss array so callers can pipeline many steps and pay
        one host round-trip at the end.

        With a recorder attached, every step additionally appends one JSONL
        event (loss, grad-norm, wall time, cumulative comm split, roofline
        attribution, stale-mode drift gauges) — the readback this implies
        makes ``sync=False`` behave like ``sync=True`` for timing purposes."""
        if self.halo_staleness:
            # under a recorder, the step span brackets dispatch AND the loss
            # readback (the sync point), so its duration is the measured
            # step time the event's wall_s and measured_vs_model block both
            # carry; nullcontext keeps ONE copy of the step bookkeeping for
            # the plain path (which stays readback-free under sync=False)
            cm = (self.spans.span("step", step=self._step_count + 1)
                  if self.recorder is not None else contextlib.nullcontext())
            with cm as sp:
                loss, err, extra = self._stale_run_one(data)
                if self.recorder is not None:
                    loss = float(loss)
            self.last_err = err
            self._step_count += 1
            if self.recorder is not None:
                gnorm, gauges, age, sync_step = extra
                self._record_step_event(
                    loss, err, gnorm, sp.dur_s,
                    drift=self._drift_fields(
                        gauges, age, sync_step,
                        rr_sizes=(self.plan.rr_sizes
                                  if self.comm_schedule == "ragged"
                                  else None)))
                return loss
            return float(loss) if sync else loss
        if self.replica_budget:
            cm = (self.spans.span("step", step=self._step_count + 1)
                  if self.recorder is not None else contextlib.nullcontext())
            with cm as sp:
                loss, err, extra = self._replica_run_one(data)
                if self.recorder is not None:
                    loss = float(loss)
            self.last_err = err
            self._step_count += 1
            if self.recorder is not None:
                gnorm, gauges, age, sync_step, first, rrows = extra
                self._record_step_event(
                    loss, err, gnorm, sp.dur_s, drift=None,
                    replica=self._replica_fields(
                        gauges, age, sync_step, self.plan.replica_rows,
                        first_refresh=first, refresh_rows=rrows,
                        refresh_wire_rows=(
                            int(self.plan.partial_refresh_wire_rows)
                            if rrows is not None else None)))
                return loss
            return float(loss) if sync else loss
        h_in = self._agg0_for(data.h0)      # before the step's spans: the
        # one-off build (span agg0.build) is set-up, not part of a step
        if self.recorder is not None:
            with self.spans.span("step", step=self._step_count + 1) as sp:
                self.params, self.opt_state, loss, err, gnorm = \
                    self._step_tel(
                        self.params, self.opt_state, self.pa, h_in,
                        data.labels, data.train_valid,
                    )
                loss = float(loss)      # readback = the span's sync point
            self.last_err = err
            self.stats.count_step(nlayers=self.nlayers)
            self._step_count += 1
            self._record_step_event(loss, err, gnorm, sp.dur_s, drift=None)
            return loss
        with span("step.dispatch"):
            self.params, self.opt_state, loss, err = self._step(
                self.params, self.opt_state, self.pa, h_in, data.labels,
                data.train_valid,
            )
        self.last_err = err   # the MPI stack's `err` metric under loss='bce'
        self.stats.count_step(nlayers=self.nlayers)
        self._step_count += 1
        if not sync:
            return loss
        with span("step.readback"):
            return float(loss)

    def evaluate(self, data: TrainData) -> tuple[float, float]:
        h_in = self._agg0_for(data.h0)
        with self.spans.span("eval") as sp:
            loss, acc, _ = self._eval(
                self.params, self.pa, h_in, data.labels, data.eval_valid
            )
            loss, acc = float(loss), float(acc)
        self.stats.count_forward(nlayers=self.nlayers)
        if self.recorder is not None:
            self.recorder.record_eval(step=self._step_count, loss=loss,
                                      acc=acc, wall_s=sp.dur_s)
        return loss, acc

    def predict(self, data: TrainData) -> np.ndarray:
        """Global (n, nout) logits in original vertex order."""
        _, _, logits = self._eval(
            self.params, self.pa, self._agg0_for(data.h0), data.labels,
            data.eval_valid
        )
        self.stats.count_forward(nlayers=self.nlayers)
        logits = np.asarray(logits)
        if self._out_rows is not None:
            # the forward returned some rows only: the others read 0
            rows, real = (np.asarray(self.pa[name])
                          for name in self._out_rows)
            ids = self.plan.global_row_ids()
            out = np.zeros((self.plan.n, logits.shape[-1]), logits.dtype)
            for c in range(self.plan.k):
                ok = real[c] > 0
                out[ids[c][rows[c][ok]]] = logits[c][ok]
            return out
        return self.plan.gather_rows(logits)

    @property
    def nlayers(self) -> int:
        """Aggregating layers = exchanges of one sweep (``CommStats``)."""
        return len(self.stats.lane_widths)

    def fit(
        self,
        data: TrainData,
        epochs: int = 5,
        warmup: int = 1,
        verbose: bool = True,
    ) -> dict:
        """Epoch loop with reference-style timing: ``warmup`` untimed epochs,
        then wall-clock over the timed ones (``GPU/PGCN.py:202-228``).

        Phase boundaries route through ``self.spans`` (the measured-span
        layer over the CAGNET-vocabulary ``PhaseTimer``) with a ``sync=``
        callable at each block_until_ready boundary — the SAME accounting
        the per-step JSONL events snapshot, so ``report()['phases']`` and
        the event stream cannot disagree.  Under a recorder, ``step()``
        opens its own nested ``step`` span inside each epoch's
        ``train_step`` span, so the epoch totals read from the timer's
        INCLUSIVE side (the nested span claims the self time)."""
        data = TrainData(**shard_stacked(self.mesh, vars(data)))
        history: list[float] = []
        # fit() may be re-entered — measure the delta, inclusive of any
        # nested step spans the telemetry path opens
        t_prior = self.timer.inclusive_total("train_step")
        with self.spans.span("warmup", sync=lambda: self.params):
            for _ in range(warmup):
                self.step(data)
        for ep in range(epochs):
            with self.spans.span("train_step", sync=lambda: self.params):
                loss = self.step(data)
            history.append(loss)
            if verbose:
                print(f"epoch {ep}: loss {loss:.6f}", flush=True)
        elapsed = self.timer.inclusive_total("train_step") - t_prior
        report = self.stats.report()
        report.update(
            epochs=epochs,
            elapsed_s=elapsed,
            epoch_s=elapsed / max(epochs, 1),
            loss_history=history,
            phases=self.timer.report(),
        )
        if self.loss_name == "bce":
            # rank-0 err line of the MPI stack (Parallel-GCN/main.c:322-323)
            report["err"] = float(self.last_err)
        if self.recorder is not None:
            self.recorder.record_summary(
                {k: v for k, v in report.items() if k != "loss_history"})
        return report
