"""DeeperGCN on the partitioned full-batch path: a deep residual stack whose
layers run as ONE scanned, per-layer-checkpointed body.

Li, Xiong, Thabet, Ghanem, "DeeperGCN: All You Need to Train Deeper GCNs"
(arXiv:2006.07739), in the configuration its authors publish for the OGB
``ogbn-products`` leaderboard (``lightaime/deep_gcns_torch``,
``examples/ogb/ogbn_products``: 14 layers of 128, ``softmax_sg``
aggregation at t = 0.1, ``res+`` blocks, BatchNorm, one-layer MLPs).  With
``N(i)`` the neighbours of i in A + I (symmetric; Â's VALUES are ignored,
every edge weighs 1)::

    GENConv(x)_i = W (x_i + a_i) + b          m_j = ReLU(x_j) + eps
    w_ij[c] = softmax_{j in N(i)} (t m_j[c])  per channel, DETACHED (softmax_sg)
    a_i[c]  = sum_j w_ij[c] m_j[c]

    h0 = GENConv_0(X W_enc + b_enc)
    hl = h(l-1) + GENConv_l(ReLU(BN_(l-1)(h(l-1))))      l = 1 .. L-1   (res+)
    logits = ReLU(BN_(L-1)(h(L-1))) W_out + b_out

    BN(h)[c] = gamma[c] (h[c] - mu[c]) / sqrt(var[c] + 1e-5) + beta[c]

``mu`` / ``var`` (biased) are over the rows of the WHOLE graph — training and
evaluation alike: full-batch, the evaluation batch is the training batch, so
``predict()`` / ``evaluate()`` normalise with the statistics of the graph at
the weights given, and no running statistics are carried (ROADMAP B).
Dropout is 0.

**The aggregation** (``softmax_aggregate``).  A message depends on its
SOURCE only, so the per-destination softmax factorises per source with any
per-channel constant ``M[c]``: ``u_j = exp(t (m_j - M))``, ``S_i = sum_j
u_j``, ``a_i = (sum_j u_j m_j) / S_i`` — ONE unit-weight aggregation of the
256-lane table ``[u m ‖ u]`` forward, through the symmetric GCN aggregator's
slot passes (``ops.pspmm.pspmm_ell_sym_detached``: ELL slots, hub tail and
halo-source edges in slot form, the plan's weights narrowed to 0/1 masks).
The weights are detached, so ``dL/dm_j = u_j sum_i g_i / S_i``: ONE
unit-weight aggregation of a 128-lane table backward — the op's backward
gathers only the lanes that carry a cotangent.  ``M`` is the column max of
``m`` over every chip's owned rows (one ``pmax``), which keeps ``u`` in
(0, 1].

**The stack.**  Layers 1 .. L-1 are identical in shape: they run as one
``lax.scan`` body over stacked weights, each iteration under
``jax.checkpoint``, so the compiled step holds one aggregating body forward
and one backward whatever the depth.  What the backward keeps is chosen by a
checkpoint policy over named residuals (``keep``):

* ``"aggregate"`` (default): per layer its input ``h`` and the aggregated
  ``[sum u m ‖ S]`` (three ``rows x hidden`` arrays); the backward
  recomputes the row-wise work (norm, ReLU, exp, table, divide, dense) and
  NOT the aggregation;
* ``"input"``: per layer its input alone; the backward re-runs the 256-lane
  aggregation too.

The statistics (``mu``, ``var``, ``M``: ``hidden`` floats each) are kept
under both, so a recomputed forward runs no collective.  BatchNorm's sums run
over OWNED rows (``row_valid`` masks padding: ``plan.b · k >= n``) and one
``psum`` each; their backward column sums are the transposition's.

Per-chip code, meant to run inside ``shard_map`` over the 1D vertex mesh.
Refused, loudly: an asymmetric plan, ``comm_schedule='ragged'``, stale /
replica modes, the Pallas aggregator, ``compute_dtype``, mini-batch, serving,
the trainer's ``remat=True`` (the layers are always checkpointed).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..obs.tracing import scope, subscope
from ..ops.pspmm import (_FOLD_SCAN_LIVE, _SCAN_LIVE_LIMIT, pass_store_forms,
                         pspmm_ell_sym_detached)
from ..parallel.mesh import AXIS, vary
from .setup import ModelSetup, plan_true_edges, slot_pass, slot_work

# the exact GCN step's slot-form arrays (``GCN_PLAN_FIELDS_SLOTS``), every
# weight array narrowed to a 0/1 mask (``ModelSetup.mask_fields``), and the
# mask of owned rows the statistics run over
DEEPERGCN_PLAN_FIELDS = ("send_idx", "halo_src", "ell_idx", "ell_w",
                         "ft_idx", "ft_w", "ft_row",
                         "fh_idx", "fh_w", "fh_row", "row_valid")
_AGG_FIELDS = DEEPERGCN_PLAN_FIELDS[:-1]

BN_EPS = 1e-5       # torch.nn.BatchNorm1d's default
_TINY = 1e-30       # guard of S for rows without edges (padding rows)
# names of the residuals a checkpoint policy may keep
KEPT_AGG, KEPT_STAT = "deep_agg", "deep_stat"
KEEP = {"aggregate": (KEPT_AGG, KEPT_STAT), "input": (KEPT_STAT,)}


class Env(NamedTuple):
    """What every layer reads beside its input and weights: the plan arrays
    of the aggregation, the owned-row mask, and the statics."""
    edges: tuple            # the ``_AGG_FIELDS`` arrays
    valid: jax.Array        # (B,) 1.0 on owned rows
    n_rows: int             # rows of the whole graph (Σ valid over chips)
    buckets: tuple
    fold_classes: tuple     # (tail classes, halo classes)
    t: float
    eps: float
    axis_name: str


# ----------------------------------------------------------- configuration
def resolve_args(widths, model_args: dict | None) -> dict:
    """The configuration as the statics of ``deepergcn_forward_local`` /
    ``init_deepergcn_params`` — constructor data
    (``FullBatchTrainer(model_args=...)``).  The trainer's ``widths`` are
    the ``layers`` convolutions' outputs and the head's: ``[hidden] · layers
    + [classes]``; ``layers`` / ``hidden`` default to what they say and
    must agree with them.  Defaults as published."""
    args = dict(model_args or {})
    widths = [int(w) for w in widths]
    out = {"layers": int(args.pop("layers", len(widths) - 1)),
           "hidden": int(args.pop("hidden", widths[0])),
           "t": float(args.pop("t", 0.1)),
           "eps": float(args.pop("eps", 1e-7)),
           "keep": args.pop("keep", "aggregate")}
    fixed = {"aggr": "softmax_sg", "norm": "batch", "block": "res+",
             "mlp_layers": 1}
    for name, only in fixed.items():
        got = args.pop(name, only)
        if got != only:
            raise ValueError(f"deepergcn: {name}={got!r} has no form here "
                             f"(only {only!r})")
    if args:
        raise ValueError(f"deepergcn: unknown model_args {sorted(args)}")
    if out["layers"] < 1 or widths[:-1] != [out["hidden"]] * out["layers"]:
        raise ValueError(
            f"deepergcn: widths {widths} are not {out['layers']} layers of "
            f"{out['hidden']} and a head ([hidden] * layers + [classes])")
    if out["keep"] not in KEEP:
        raise ValueError(f"deepergcn: keep={out['keep']!r} is not one of "
                         f"{sorted(KEEP)}")
    if not (out["t"] > 0 and out["eps"] > 0):
        raise ValueError("deepergcn: t and eps must be positive (the "
                         "stabiliser's identity is 0 < eps)")
    return out


def param_count(fin: int, hidden: int, layers: int, classes: int) -> int:
    """Encoder, ``layers`` convolutions, ``layers`` norms, head."""
    return (fin * hidden + hidden + layers * (hidden * hidden + hidden)
            + layers * 2 * hidden + hidden * classes + classes)


def _linear(key, fan_in: int, fan_out: int, stack: int | None = None):
    """torch's ``Linear.reset_parameters``: weight and bias both
    U(-1/sqrt(fan_in), 1/sqrt(fan_in)); ``stack`` leading copies."""
    kw, kb = jax.random.split(key)
    bound = 1.0 / np.sqrt(fan_in)
    lead = () if stack is None else (stack,)
    draw = functools.partial(jax.random.uniform, dtype=jnp.float32,
                             minval=-bound, maxval=bound)
    return {"w": draw(kw, lead + (fan_in, fan_out)),
            "b": draw(kb, lead + (fan_out,))}


def init_deepergcn_params(rng: jax.Array, dims, layers: int = 0,
                          hidden: int = 0, **_static):
    """``enc`` (fin -> hidden), ``conv0``, ``layers`` (BN_(l-1) and
    GENConv_l for l = 1 .. L-1, STACKED on a leading axis of L - 1: the
    scan's weights), ``head`` (BN_(L-1) and hidden -> classes).  Linear
    layers as torch draws them, gamma = 1, beta = 0."""
    fin, classes = int(dims[0][0]), int(dims[-1][1])
    if len(dims) != layers + 1:
        raise ValueError(f"deepergcn: {len(dims)} widths for {layers} "
                         "layers and a head")
    ke, k0, kl, kh = jax.random.split(rng, 4)
    one, zero = jnp.ones, jnp.zeros
    return {
        "enc": _linear(ke, fin, hidden),
        "conv0": _linear(k0, hidden, hidden),
        "layers": {"gamma": one((layers - 1, hidden), jnp.float32),
                   "beta": zero((layers - 1, hidden), jnp.float32),
                   **_linear(kl, hidden, hidden, stack=layers - 1)},
        "head": {"gamma": one((hidden,), jnp.float32),
                 "beta": zero((hidden,), jnp.float32),
                 **_linear(kh, hidden, classes)},
    }


# ------------------------------------------------------------- the layer
def batch_norm(h, gamma, beta, env: Env):
    """BatchNorm over the owned rows of every chip: mean, then the biased
    variance about it — two column sums and one ``psum`` each."""
    v = env.valid[:, None]
    # each statistic is cast to per-chip copies ONCE, so that the
    # transposition sums its cotangents with one ``psum`` (a cast per use
    # would bring a ``psum`` per use)
    mean = vary(checkpoint_name(
        lax.psum(jnp.sum(h * v, axis=0), env.axis_name) / env.n_rows,
        KEPT_STAT), env.axis_name)
    d = (h - mean) * v
    var = vary(checkpoint_name(
        lax.psum(jnp.sum(d * d, axis=0), env.axis_name) / env.n_rows,
        KEPT_STAT), env.axis_name)
    return (h - mean) * (lax.rsqrt(var + BN_EPS) * gamma) + beta


def softmax_aggregate(x, env: Env):
    """``a_i = sum_j softmax_j(t m_j) m_j`` per channel over ``N(i)``, the
    weights detached, ``m = ReLU(x) + eps`` (module docstring): one
    256-lane aggregation forward, one 128-lane backward."""
    f = x.shape[1]
    with scope("dense"), subscope("softmax_table"):
        m = jax.nn.relu(x) + env.eps
        # any per-channel constant is exact; the max over owned rows keeps
        # u in (0, 1] (m > 0, so 0 is the identity on padding rows)
        # (detached before the max: pmax has no differentiation rule)
        top = lax.pmax(jnp.max(lax.stop_gradient(m) * env.valid[:, None],
                               axis=0), env.axis_name)
        top = checkpoint_name(top, KEPT_STAT)
        u = lax.stop_gradient(jnp.exp(env.t * (m - top)))
        table = jnp.concatenate([u * m, u], axis=1)
    agg = pspmm_ell_sym_detached(table, *env.edges, env.buckets,
                                 *env.fold_classes, f, env.axis_name)
    agg = checkpoint_name(agg, KEPT_AGG)
    with scope("dense"), subscope("softmax_table"):
        den = lax.stop_gradient(agg[:, f:])
        # a row without an edge (padding) sums nothing: 0, not 0 / 0
        return agg[:, :f] * jnp.where(den > 0, 1.0 / jnp.maximum(den, _TINY),
                                      0.0)


def _linear_apply(x, p):
    """``x W + b`` in float32 proper (``Precision.HIGHEST``): at the TPU's
    default precision (bf16 multiplicands) fourteen normalised layers put
    the second loss 2.5e-4 and the logits 3e-3 rms from exact float32 — as
    far as holding the aggregated table in bfloat16 does (PERF.md §6,
    PR 31) — and the products are under 3 % of this model's epoch."""
    return jnp.dot(x, p["w"], precision=lax.Precision.HIGHEST) + p["b"]


def gen_conv(x, p, env: Env, skip=None):
    """``W (x + a) + b`` with the softmax aggregation ``a`` of ``x``, plus
    the block's residual ``skip`` where there is one."""
    a = softmax_aggregate(x, env)
    with scope("dense"):
        out = _linear_apply(x + a, p)
        return out if skip is None else skip + out


def res_layer(h, p, env: Env):
    """One ``res+`` block: norm -> ReLU -> GENConv -> add."""
    with scope("dense"), subscope("norm"):
        x = jax.nn.relu(batch_norm(h, p["gamma"], p["beta"], env))
    return gen_conv(x, p, env, skip=h)


def first_layer(params, x, env: Env):
    """``GENConv_0`` of the encoded features (no norm, no residual)."""
    with scope("dense"):
        x = _linear_apply(x, params["enc"])
    return gen_conv(x, params["conv0"], env)


def head(h, p, env: Env):
    with scope("dense"):
        with subscope("norm"):
            x = jax.nn.relu(batch_norm(h, p["gamma"], p["beta"], env))
        return _linear_apply(x, p)


def make_env(pa, ell_buckets, fold_classes, n_rows, t, eps,
             axis_name=AXIS) -> Env:
    return Env(edges=tuple(pa[f] for f in _AGG_FIELDS),
               valid=pa["row_valid"].astype(jnp.float32), n_rows=int(n_rows),
               buckets=ell_buckets, fold_classes=tuple(fold_classes),
               t=float(t), eps=float(eps), axis_name=axis_name)


# ------------------------------------------------------------------ forward
def deepergcn_forward_local(
    params,
    h,                            # (B, fin) local rows
    pa,                           # plan arrays dict (DEEPERGCN_PLAN_FIELDS)
    activation: str = "relu",
    final_activation: str = "none",
    symmetric: bool = False,
    ell_buckets: tuple | None = None,   # static plan.ell_buckets
    fold_classes: tuple | None = None,  # static (tail, halo) width classes
    layers: int = 0,              # static: GENConv layers L
    hidden: int = 0,              # static: their width
    t: float = 0.1,               # static: softmax temperature
    eps: float = 1e-7,            # static: the message's offset
    keep: str = "aggregate",      # static: what a layer's checkpoint keeps
    n_rows: int = 0,              # static: rows of the whole graph
    comm_schedule: str = "a2a",
    axis_name: str = AXIS,
    halo_carry=None,
):
    """Per-chip forward (module docstring): encoder and ``GENConv_0`` as one
    checkpointed block under ``sgcn.layer0``, the L - 1 ``res+`` blocks as
    one scanned, checkpointed body under ``sgcn.layer1`` (a scan cannot name
    its iterations), the head outside any layer.  The configuration arrives
    as statics through ``resolve_forward_setup`` from the trainer's
    ``model_args``."""
    if halo_carry is not None:
        raise NotImplementedError(
            "stale-halo pipelining is implemented for the GCN hot path "
            "only; run deepergcn with halo_staleness=0")
    if not symmetric:
        raise ValueError(
            "deepergcn's backward aggregates the gradient over the same "
            "slots, which holds for a symmetric edge pattern only; this "
            "plan is asymmetric")
    if comm_schedule != "a2a":
        raise ValueError("deepergcn ships its tables over the dense "
                         f"all_to_all only, not comm_schedule={comm_schedule!r}")
    if ell_buckets is None or fold_classes is None:
        raise ValueError("deepergcn forward needs the plan's static "
                         "ell_buckets and fold_classes (resolve_forward_setup)")
    if (activation, final_activation) != ("relu", "none"):
        raise ValueError(
            "deepergcn's activations are its equations' (ReLU inside the "
            f"blocks, none after the head), not activation={activation!r} / "
            f"final_activation={final_activation!r}")
    if params["layers"]["w"].shape[0] != layers - 1:
        raise ValueError(f"deepergcn: {params['layers']['w'].shape[0]} "
                         f"stacked layers of parameters for layers={layers}")
    env = make_env(pa, ell_buckets, fold_classes, n_rows, t, eps, axis_name)
    policy = jax.checkpoint_policies.save_only_these_names(*KEEP[keep])
    with scope("layer", 0):
        h = jax.checkpoint(lambda ps, x: first_layer(ps, x, env),
                           policy=policy)(
            {k: params[k] for k in ("enc", "conv0")}, h)
    if layers > 1:
        body = jax.checkpoint(lambda hh, p: (res_layer(hh, p, env), None),
                              policy=policy)
        with scope("layer", 1):
            h, _ = lax.scan(body, h, params["layers"])
    return head(h, params["head"], env)


# ------------------------------------------------------------------- memory
def estimate_deepergcn_hbm_bytes(plan, fin: int, hidden: int, layers: int,
                                 classes: int, keep: str, slots: int,
                                 train: bool = True) -> dict:
    """Per-chip HBM of one fwd+bwd step, itemised (f32; ``plan.b`` rows,
    ``plan.r`` halo rows, ``slots`` executed slots of one pass over the
    three edge stores):

    * ``rows_kept``: what the layers' checkpoints hold from forward to
      backward — per layer its input (``hidden`` lanes; layer 0's is the
      features, already resident) and, under ``keep="aggregate"``, the
      aggregated ``[sum u m ‖ S]`` (2 · ``hidden``); the head's input and
      normalised rows;
    * ``rows_transient``: ONE layer's recomputed forward and its backward at
      their peak — the normalised input, message, table (2), aggregate (2,
      unless kept), the sum, the cotangent, its quotient, the gathered
      gradient and the carry's cotangent: about eleven ``hidden``-lane
      arrays — and the virtual rows' sums;
    * ``halo``: the received tables, both directions;
    * ``slot_temps``: the slot passes' gathered rows at 2 · ``hidden``
      lanes, bounded by the scan-unroll budgets of the main passes and the
      folds;
    * ``plan``: index (int32) and mask (int8) per executed slot, a
      destination per virtual row.

    An estimate of what the arrays need; PERF.md §6 (PR 31) sets it beside
    the chip's ``memory_stats()``."""
    b, r = int(plan.b), int(plan.r)
    row = b * 4 * hidden
    vrows = sum(nv for cls in (plan.fold_tail_classes, plan.fold_halo_classes)
                for nv, _ in cls)
    per_layer = 3 if keep == "aggregate" else 1
    kept = (layers * per_layer + 2) * row if train else 0
    transient = (11 if train else 5) * row + vrows * 4 * 2 * hidden
    halo = r * 4 * (2 * hidden + (hidden if train else 0))
    widest = max((nb for nb, _ in plan.ell_buckets), default=0)
    slot_temps = min(_SCAN_LIVE_LIMIT + _FOLD_SCAN_LIVE,
                     4 * widest * 4 * 2 * hidden)
    parts = {"rows_kept": kept, "rows_transient": transient, "halo": halo,
             "slot_temps": slot_temps, "plan": 5 * slots + 4 * vrows,
             "features": b * 4 * (fin + 3),
             "params": 16 * param_count(fin, hidden, layers, classes)}
    parts["total"] = sum(parts.values())
    return parts


def slot_passes(plan, layers: int, hidden: int, keep: str,
                fold_classes) -> list:
    """The step's pass list for the counter ``slots.work``
    (``models/setup.py::slot_pass``): the token ``sgcn.layer0`` covers the
    first layer and ``sgcn.layer1`` the scanned body's ``layers − 1``
    (``times_per_epoch``); each aggregates ``[u m ‖ u]`` forward (2 ·
    ``hidden`` lanes) and the cotangent backward (``hidden``), and under
    ``keep="input"`` the backward runs the forward's pass again first."""
    true = plan_true_edges(plan)
    wide = pass_store_forms(plan.ell_buckets, *fold_classes, 2 * hidden)
    narrow = pass_store_forms(plan.ell_buckets, *fold_classes, hidden)
    passes = []
    for layer, times in ((0, 1), (1, layers - 1)):
        if not times:
            continue
        kw = {"times_per_epoch": times, "true_edges": true}
        passes.append(slot_pass(layer, "fwd", 2 * hidden, wide, **kw))
        if keep == "input":
            passes.append(slot_pass(layer, "bwd", 2 * hidden, wide, **kw))
        passes.append(slot_pass(layer, "bwd", hidden, narrow, **kw))
    return passes


# -------------------------------------------------------------- the registry
def model_setup(plan, fin: int, widths, model_args: dict | None, *,
                comm_schedule: str, compute_dtype, serve_subgraph: bool
                ) -> ModelSetup:
    """The ``MODELS`` entry's setup hook (``models/setup.py``): validates
    ``model_args``, refuses what the stack has no form for, builds the
    plan's slot-form fold stores, and hands the shared code the statics,
    the exchange's lane widths per direction, the parameter count, the
    memory estimate and the ``deep.work`` counter."""
    if not plan.symmetric:
        raise ValueError(
            "deepergcn's gather-only backward needs a symmetric edge "
            "pattern; this plan is asymmetric (models/deepergcn.py)")
    if comm_schedule != "a2a" or serve_subgraph:
        raise ValueError(
            "deepergcn runs the dense a2a schedule and the full forward "
            f"only (comm_schedule={comm_schedule!r}, "
            f"serve_subgraph={serve_subgraph})")
    if compute_dtype is not None:
        raise ValueError(
            f"deepergcn is float32 only (compute_dtype={compute_dtype!r})")
    args = resolve_args(widths, model_args)
    layers, hidden, keep = args["layers"], args["hidden"], args["keep"]
    classes = int(list(widths)[-1])
    plan.ensure_fold_slots()
    fold_classes = (plan.fold_tail_classes, plan.fold_halo_classes)
    exchanged = bool(plan.fold_halo_classes)
    recomputed = layers if keep == "input" else 0
    work = plan.work_counts()["executed"]      # one mask pass, not three
    estimate = functools.partial(
        estimate_deepergcn_hbm_bytes, plan, fin, hidden, layers, classes,
        keep, sum(work[e] for e in ("slot_edges", "tail_edges",
                                    "halo_edges")))
    counter = {
        "layers": layers, "hidden": hidden, "keep": keep,
        "lanes": {"forward": 2 * hidden, "backward": hidden},
        # per epoch: every layer aggregates once forward and once backward;
        # under keep="input" the backward re-runs the forward's first
        "agg_passes_per_step": {"forward": layers, "backward": layers,
                                "recomputed": recomputed},
        "rows_kept_bytes": estimate(train=True)["rows_kept"],
        # none where no chip has a halo edge (k = 1)
        "exchanges_per_step": (2 * layers + recomputed) if exchanged else 0,
        # BatchNorm: two sums forward and two backward a norm; the
        # stabiliser's max once a layer; recomputation runs none
        "stat_collectives_per_step": {"psum": 4 * layers, "pmax": layers},
    }
    statics = {**args, "n_rows": int(plan.n), "fold_classes": fold_classes}
    return ModelSetup(
        fwd_static=statics,
        init_static={"layers": layers, "hidden": hidden},
        extra_arrays={},
        # the aggregation weighs every edge 1: Â's values narrow to masks
        mask_fields=("ell_w", "ft_w", "fh_w"),
        lane_widths=(2 * hidden,) * layers,
        lane_widths_bwd=(hidden,) * layers,
        param_count=param_count(fin, hidden, layers, classes),
        estimate_memory=estimate,
        counters={"deep.work": counter,
                  "slots.work": slot_work(slot_passes(
                      plan, layers, hidden, keep, fold_classes))},
        allow_pallas=False,         # no VMEM form of the two-width rule
        checkpointed=True)          # per layer, always: remat=True refused
