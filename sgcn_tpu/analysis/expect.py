"""Plan-derived expectations for the compiled-program audit.

Everything the HLO auditor asserts is computed HERE, from the same plan
fields and shared helpers the real programs are built from — never from a
golden dump of a previous lowering:

  * exchange collectives ride ``CommPlan.wire_buffer_shapes`` (the
    ``(peers, S)`` dense pad / per-live-round ``(S_d,)`` ring buffers,
    empty rounds elided per ``ops.pspmm.ragged_live_rounds``) crossed with
    the model's lane widths (``models.gcn.exchange_widths`` /
    ``models.gat.gat_table_form``);
  * the gradient allreduce census is the trainer's own parameter pytree —
    one full-mesh ``psum`` per leaf;
  * donation expectations are the trainer's argument pytrees classified
    donate/keep exactly as ``donate_argnums`` classifies them.

One constant is pinned empirically rather than derived:
``XENT_SCALAR_PSUMS`` — the scalar f32 allreduces the masked-xent loss
machinery lowers to: the two ``lax.psum`` calls in
``models.gcn.masked_softmax_xent_local`` (jax 0.9.0's partial evaluation
re-emits neither on the linearized path; an earlier JAX re-emitted one,
and the constant read 3).  It is a property of the loss code + JAX
version, not of the plan; the full-matrix audit at HEAD validates it for
every mode, and a loss-code change that shifts it fails the audit loudly
(the point of a lint).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

# scalar f32 add-allreduces of one masked-xent train step (see module
# docstring); every audited train program uses the xent loss
XENT_SCALAR_PSUMS = 2

_DTYPE_SHORT = {
    "float32": "f32", "bfloat16": "bf16", "float64": "f64", "float16":
    "f16", "int8": "i8", "int16": "i16", "int32": "i32", "int64": "i64",
    "uint8": "ui8", "uint32": "ui32", "bool": "i1",
}


def dtype_short(dt) -> str:
    return _DTYPE_SHORT.get(np.dtype(dt).name if not isinstance(dt, str)
                            else dt, str(dt))


@dataclass
class Expectation:
    """What one lowered program must contain."""

    # exchange collectives: multiset of (kind, wire shape, wire dtype)
    exchanges: list = field(default_factory=list)
    # grad-sync allreduces: multiset of operand shapes (add-reduce, f32)
    grad_shapes: list = field(default_factory=list)
    # scalar f32 add-allreduces (loss machinery)
    scalar_psums: int = 0
    # max-allreduces (the GAT per-layer softmax stabilizer pmax; the deep
    # stack's per-body one): count
    max_psums: int = 0
    # statistics allreduces of a normalisation layer (add-reduce, f32):
    # multiset of operand shapes, counted beside ``grad_shapes``
    stat_shapes: list = field(default_factory=list)
    # serve logit gather: list of (shape,) add-allreduce operands
    gather_shapes: list = field(default_factory=list)
    # argument classification for the donation check, in flatten order:
    # list of (shape, dtype, klass) with klass in {'donate', 'keep'}
    args: list = field(default_factory=list)
    # result SHAPES no scatter op in the program may produce — the
    # halo-materialization rule of the ragged-Pallas modes: assembling the
    # (R, f_ℓ) halo table before the kernel (instead of feeding the ring's
    # receive concat to the VMEM tile accumulator directly) betrays itself
    # as a scatter with exactly that signature.  Shapes that collide with
    # the program's LEGITIMATE scatters (the emulate-mode segment-sums'
    # per-class (T_c·tb, f) blocks, the (B, f) folds) are dropped at
    # expectation-build time, never silently matched.
    forbidden_scatters: list = field(default_factory=list)


def _gcn_layer_plan(fin: int, widths) -> tuple[list, list]:
    """(per-layer exchanged lane widths, per-layer project-first flags) —
    the lane widths are ``models.gcn.exchange_widths`` verbatim; the flags
    re-state its condition so the backward-exchange census below can apply
    the layer-0 dead-code rule."""
    from ..models.gcn import PROJECT_FIRST_MIN_FIN, exchange_widths

    fs = exchange_widths(fin, list(widths))
    pf, f = [], fin
    for w in widths:
        pf.append(bool(w < f and f >= PROJECT_FIRST_MIN_FIN))
        f = w
    return fs, pf


def _exchange_ops(plan, schedule: str, lane: int | None, dtype: str,
                  replica: bool = False) -> list:
    """The collective dispatches of ONE halo exchange shipping ``lane``
    trailing lanes (``None`` = no lane axis, e.g. the GAT split scalar).
    ``replica=True``: the SHRUNKEN no-replica exchange of a
    ``--replica-budget`` step (``CommPlan.wire_buffer_shapes(replica=True)``
    — the ``nrep_s`` pad / live rounds of ``nrep_rr_sizes``)."""
    kind = "all_to_all" if schedule == "a2a" else "collective_permute"
    out = []
    for shape in plan.wire_buffer_shapes(schedule, replica=replica):
        full = shape if lane is None else shape + (lane,)
        out.append((kind, full, dtype))
    return out


def _wire_dtypes_gcn(mode, fresh: bool) -> tuple[str, str]:
    """(feature wire, gradient wire) dtypes of one GCN step — the
    ``halo_dtype`` / ``--halo-delta`` / f32-rebase rules of
    ``ops.pspmm._stale_exchange`` and ``halo_exchange``."""
    base = "bf16" if mode.halo_dtype == "bfloat16" else "f32"
    if not mode.staleness:
        return base, base
    if mode.delta:
        # stale steps ship the bf16 increment; a fresh step RE-BASES on the
        # full f32 row (both ends reset exactly — docs/stale_halo.md)
        return ("f32" if fresh else "bf16"), base
    return base, base


def pallas_ragged_forbidden_scatters(trainer, mode) -> list:
    """The ragged-Pallas halo-materialization rule's forbidden scatter
    result shapes: ``(R, f_ℓ)`` at every lane width the mode's exchanges
    ship (GCN: ``exchange_widths``; GAT: the fused ``fout+1`` and split
    ``fout`` table heights).  Shapes colliding with the program's
    legitimate scatter outputs — the per-class ``(T_c·tb, f)`` blocks of
    the emulate-mode segment-sums and the ``(B, f)`` folds — are dropped
    (a collision would turn the lint vacuous OR false-positive; dropping
    is the conservative side and the audit fixture does not collide)."""
    if not getattr(mode, "pallas", False) or mode.schedule != "ragged":
        return []
    plan = trainer.plan
    legit = {int(plan.b)}
    for cls, tb in ((plan.pallas_lclasses, plan.pallas_tb),
                    (plan.pallas_hclasses, plan.pallas_tb),
                    (plan.pallas_cclasses, plan.pallas_ctb)):
        if cls and tb:
            legit |= {int(t) * int(tb) for t, _e in cls}
    if int(plan.r) in legit:
        return []
    if mode.model == "gcn":
        fs, _ = _gcn_layer_plan(trainer.fin, trainer.widths)
        lanes = set(int(f) for f in fs)
    else:
        lanes = set()
        for fout in trainer.widths:
            lanes |= {int(fout), int(fout) + 1}
    return [(int(plan.r), lane) for lane in sorted(lanes)]


def train_expectation(trainer, mode, fresh: bool = False) -> Expectation:
    """Expected contents of one lowered train step for ``mode``.

    ``fresh`` selects the stale mode's full-sync program (both programs of
    a stale mode are audited — the f32 delta re-base is a sync-step-only
    contract)."""
    import jax

    plan = trainer.plan
    exp = Expectation()
    L = trainer.nlayers

    if mode.model == "gcn":
        fs, pf = _gcn_layer_plan(trainer.fin, trainer.widths)
        fdt, gdt = _wire_dtypes_gcn(mode, fresh)
        # replica REPLICA step (fresh=False): both directions ship the
        # SHRUNKEN nrep layout; the refresh (fresh=True) step ships the
        # full exact exchange
        rep_wire = bool(mode.replica) and not fresh
        # forward: every layer — but the exact full-batch trainer hoists an
        # aggregate-first layer 0 out of the step (agg0_hoisted: Â·h0 is
        # made once per data set), so its step starts at layer 1, the way
        # bwd_layers below already drops layer 0's dead backward
        fwd_layers = range(1 if trainer.agg0_hoisted else 0, L)
        for i in fwd_layers:
            exp.exchanges += _exchange_ops(plan, mode.schedule, fs[i], fdt,
                                           replica=rep_wire)
        if mode.staleness or (mode.replica and fresh):
            # backward: the fresh gradient exchange is EMITTED for every
            # layer — it is next step's carry (stale mode) / the refreshed
            # gradient-replica table (replica refresh step), so layer 0's
            # survives even though dL/dh0 is dead
            bwd_layers = range(L)
        else:
            # exact mode (and the replica step, whose grep cotangent is a
            # pass-through): layer 0's backward exchange exists only under
            # project-first (dL/d(h·W) feeds dW); aggregate-first layer 0
            # only needs dL/dagg-out, and its dL/dh0 path is dead code
            bwd_layers = [i for i in range(L) if i > 0 or pf[0]]
        for i in bwd_layers:
            exp.exchanges += _exchange_ops(plan, mode.schedule, fs[i], gdt,
                                           replica=rep_wire)
    elif mode.model == "deepergcn":
        # one scanned, per-layer-checkpointed body: the lowered program
        # holds layer 0's block and ONE body for the L - 1 layers after it,
        # each forward and backward — its collectives do not scale with L
        st = trainer._fwd_static
        hidden, bodies = st["hidden"], 1 + (st["layers"] > 1)
        for _body in range(bodies):
            # the 2·hidden-lane table forward (again in the backward where
            # the checkpoint keeps the layer's input alone), hidden lanes
            # of gradient backward
            for lane in ((2 * hidden, hidden, 2 * hidden)
                         if st["keep"] == "input" else (2 * hidden, hidden)):
                exp.exchanges += _exchange_ops(plan, mode.schedule, lane,
                                               "f32")
        # BatchNorm's mean and variance forward, their two column sums
        # backward: the scanned body's norm and the head's; the kept
        # statistics mean a recomputed forward runs none
        exp.stat_shapes = [(hidden,)] * (4 * bodies)
        exp.max_psums = bodies                   # the stabiliser's pmax
    elif mode.model == "rgcn":
        # per layer a row's input forward, and backward the cotangent
        # blocks its gradient types want, side by side — layer 0's too:
        # the embeddings are trainable, so a halo copy's gradient goes home
        for lanes in (trainer.stats.lane_widths,
                      trainer.stats.lane_widths_bwd):
            for lane in lanes:
                if lane:
                    exp.exchanges += _exchange_ops(plan, mode.schedule,
                                                   lane, "f32")
    else:
        from ..models.gat import gat_table_form
        for i in range(L):
            fout = trainer.widths[i]
            form = gat_table_form(fout, mode.compute_dtype)
            for _direction in ("fwd", "bwd"):    # both ride the same form
                if form == "packed":
                    exp.exchanges += _exchange_ops(
                        plan, mode.schedule, fout // 2 + 1, "f32")
                elif form == "fused":
                    exp.exchanges += _exchange_ops(
                        plan, mode.schedule, fout + 1, "f32")
                elif mode.schedule == "a2a":
                    # split pair: feature table + its own scalar buffer —
                    # TWO dense dispatches per exchange
                    exp.exchanges += _exchange_ops(plan, "a2a", fout, "f32")
                    exp.exchanges += _exchange_ops(plan, "a2a", None, "f32")
                else:
                    # on the ring the pair collapses into ONE two-lane
                    # dispatch per live round (halo_exchange_ragged_multi)
                    exp.exchanges += _exchange_ops(
                        plan, "ragged", fout + 1, "f32")
        exp.max_psums = L                        # per-layer softmax pmax

    # one all-reduce a REPLICATED leaf: a leaf owned with the rows
    # (``ModelSetup.row_owned``) keeps its gradient where its rows are
    exp.grad_shapes = [
        tuple(np.shape(x)) for path, x in
        jax.tree_util.tree_flatten_with_path(trainer.params)[0]
        if trainer._owned_rows(path) is None]
    exp.scalar_psums = XENT_SCALAR_PSUMS
    exp.forbidden_scatters = pallas_ragged_forbidden_scatters(trainer, mode)

    # argument classification (donation): the jit args in flatten order
    groups = [("donate", trainer.params), ("donate", trainer.opt_state)]
    if mode.staleness:
        # the composed replica × stale mode carries NO replica state of
        # its own — the stale halo carry subsumes it, so the carry pytree
        # is exactly the stale mode's
        groups.append(("donate", trainer.halo_carry))
    elif mode.replica:
        groups.append(("donate", trainer.replica_carry))
    groups += [("keep", trainer.pa)]
    exp.args = _classify_args(groups)
    k, b = plan.k, plan.b
    exp.args += [((k, b, trainer.fin), "f32", "keep"),   # h0
                 ((k, b), "i32", "keep"),                # labels
                 ((k, b), "f32", "keep")]                # valid
    return exp


def serve_expectation(engine, mode, bucket: int) -> Expectation:
    """Expected contents of one lowered serve bucket program: L forward
    exchanges, ONE full-mesh logit-gather psum, and NO donated inputs
    (engine params/plan arrays are reused across micro-batches)."""
    import jax

    plan = engine.plan
    exp = Expectation()
    L = engine.nlayers
    if mode.model == "gcn":
        fs, _ = _gcn_layer_plan(engine.fin, engine.widths)
        dt = "bf16" if mode.halo_dtype == "bfloat16" else "f32"
        for i in range(L):
            exp.exchanges += _exchange_ops(plan, mode.schedule, fs[i], dt)
    else:
        from ..models.gat import gat_table_form
        for i in range(L):
            fout = engine.widths[i]
            form = gat_table_form(fout, None)
            if form == "fused":
                exp.exchanges += _exchange_ops(
                    plan, mode.schedule, fout + 1, "f32")
            elif mode.schedule == "a2a":
                exp.exchanges += _exchange_ops(plan, "a2a", fout, "f32")
                exp.exchanges += _exchange_ops(plan, "a2a", None, "f32")
            else:
                exp.exchanges += _exchange_ops(
                    plan, "ragged", fout + 1, "f32")
        exp.max_psums = L
    exp.gather_shapes = [(bucket, engine.widths[-1])]
    groups = [("keep", engine.params), ("keep", engine.pa)]
    exp.args = _classify_args(groups)
    k, b = plan.k, plan.b
    exp.args += [((k, b, engine.fin), "f32", "keep"),    # h0
                 ((bucket,), "i32", "keep"),             # q_owner
                 ((bucket,), "i32", "keep")]             # q_local
    return exp


def serve_subgraph_expectation(engine, mode, key: tuple) -> Expectation:
    """Expected contents of one lowered SUB-GRAPH serve program
    (``ServeEngine.lower_subgraph``) — the tentpole contract: NO exchange
    collectives at all (every source row is computed locally from
    host-gathered receptive-set features), no pmax (the GAT stabilizers
    arrive as an input), no scalar psums (no loss machinery), exactly ONE
    full-mesh logit-gather psum, and nothing donated (params and batch
    arrays are reused / engine-owned)."""
    from ..serve.subgraph import batch_struct

    exp = Expectation()
    qb = key[1]
    exp.gather_shapes = [(qb, engine.widths[-1])]
    groups = [("keep", engine.params),
              ("keep", np.zeros((engine.nlayers,), np.float32)),  # cgs
              ("keep", batch_struct(engine.sgindex, key, engine.fin))]
    exp.args = _classify_args(groups)
    exp.args += [((qb,), "i32", "keep"),                 # q_owner
                 ((qb,), "i32", "keep")]                 # q_pos
    return exp


def _classify_args(groups) -> list:
    import jax

    out = []
    for klass, tree in groups:
        for leaf in jax.tree.leaves(tree):
            out.append((tuple(np.shape(leaf)),
                        dtype_short(np.asarray(leaf).dtype
                                    if not hasattr(leaf, "dtype")
                                    else leaf.dtype), klass))
    return out
