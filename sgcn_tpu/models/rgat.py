"""R-GAT on the partitioned full-batch path: attention inside the typed
layouts.

Busbridge et al.'s relational GAT in the form the OGB-LSC repository
publishes for MAG240M (``examples/lsc/mag240m/rgnn.py --model rgat``):
Schlichtkrull et al.'s per-relation sum (arXiv:1703.06103) with one
multi-head ``GATConv`` (Veličković et al., arXiv:1710.10903) per relation.
For a row i of the layer's target set ``T`` and input x::

    h_i = W_skip x_i + b_skip + Σ_{r ∈ R} b_r
          + Σ_{r = (s -> type(i))} ‖_k Σ_{j ∈ N_r(i)} α^r_ijk (W_r x_j)_k
    α^r_ijk = softmax_{j ∈ N_r(i)} LeakyReLU(a^r_src,k·(W_r x_j)_k
                                             + a^r_dst,k·(W_r x_i)_k)
    x'_i = ELU(BatchNorm_T(h)_i)

one softmax PER RELATION, an empty ``N_r(i)`` giving 0, ``W_r`` bias-free
(K heads of C channels, concatenated), ``R`` the relations with an edge into
``T`` — each ``b_r`` is added to EVERY row of ``T``, whatever its type, as
PyG's ``out += conv((x, x_target), subadj)`` adds a ``GATConv``'s bias to all
targets — and BatchNorm's statistics over the rows of ``T`` (biased
variance, ``deepergcn.batch_norm``).  The target sets are the sampler's hops:
the last layer's ``T`` is the labelled type, a layer before it adds the
sources of the relations into the next (``rgcn.reachable``: papers ∪ authors,
then papers, on MAG240M).  The head over the labelled rows is ``Linear →
BatchNorm → ReLU → Linear``; the loss the trainer's, over its training rows.

**Layouts and slot bodies are borrowed, not rewritten.**  The typed order,
the reachability and the per-relation slot layouts are ``models/rgcn.py``'s
(``build_typed_layout``: one layout per ordered pair of types, ELL buckets
over the destination type's rows and virtual rows past them, halo-source
edges as virtual rows).  The slot bodies are ``models/mhgat.py``'s
(``attend`` / ``attend_bwd``: the max pass over ``t``, the score, exponent
and signed-spread aggregation, the normalisation, the gather-only
backward), driven by a relation's layout as a store set (``mhgat.Store``).
The forward of ``s -> d`` walks the pair's layout with the slots' forward
mask; its backward walks the REVERSE pair ``(d -> s)`` — the rows of s,
their slots the same edges read from the other side — with the transposed
mask, gathering ``[g ‖ s, m, 1/D, c]`` of d's rows.  At k > 1 one
``all_to_all`` a relation and direction ships the source's ``[Z ‖ t]``
forward and the destination's ``[g ‖ s, m, 1/D, c]`` backward, each in the
stacked typed order rgcn's exchange uses.

**Destination scores** are ``x_i · (W_r a^r_dst)``, a K-column product, in
place of ``a^r_dst · (W_r x_i)`` over the destination's projected row: an
exact reassociation, so no relation projects its destination type's rows
(the plain reference, ``benchmark/reference/rgat_ref.py``, does not take
it).  Every dense product runs at ``Precision.HIGHEST``.

Per-chip code, inside ``shard_map`` over the 1D vertex mesh.  Refused,
loudly: an asymmetric plan, ``comm_schedule='ragged'``, stale / replica
modes, ``halo_dtype``, ``compute_dtype``, the Pallas aggregator, mini-batch,
serving, and an embedded type (every type brings features).
"""

from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.tracing import pair_scope, scope, subscope
from ..ops.pspmm import halo_exchange_multi
from ..parallel.mesh import AXIS
from . import deepergcn, mhgat, rgcn
from .activations import get_activation
from .setup import ModelSetup, slot_pass, slot_work

STORES = rgcn.STORES            # ELL slots, the runs past them, halo edges
HEAD = {"norm": "batch", "activation": "relu"}   # the published head MLP


class RattSpec(NamedTuple):
    """Statics of the whole forward."""
    heights: tuple      # rows of each type's table, in type order
    layouts: tuple      # ((s, d), (ell buckets, tail classes, halo classes))
    exchange: bool      # some chip has a halo-source edge
    read: tuple         # the types whose features layer 0 reads
    dst: tuple          # per layer: the target types T
    live: tuple         # per layer: the relations with an edge into T
    rows: tuple         # per layer: the rows of T over all chips; then the
    #                     labelled type's (the head's BatchNorm)


class RelStatic(NamedTuple):
    """Statics of one relation's ``relation_attention``."""
    pair: tuple         # (source type, destination type)
    fwd: tuple          # the pair's layout shapes
    bwd: tuple          # the reverse pair's
    heights: tuple
    exchange: bool
    heads: int
    slope: float
    axis_name: str


# ----------------------------------------------------------- configuration
def resolve_args(fin: int, widths, model_args: dict | None) -> dict:
    """The configuration as statics: rgcn's type table, relation list,
    labelled type, ``hidden`` and ``layers`` (over the trainer's widths less
    the head's output), and ``heads``, the LeakyReLU ``slope`` and the
    ``head`` MLP (``{"hidden": hidden, "norm": "batch", "activation":
    "relu"}``, the published one, is the only form)."""
    args = dict(model_args or {})
    widths = [int(w) for w in widths]
    heads = int(args.pop("heads", 1))
    slope = float(args.pop("slope", 0.2))
    head = dict(args.pop("head", {}))
    if len(widths) < 2:
        raise ValueError(f"rgat: widths {widths} need the layers' and the "
                         "head's output")
    out = rgcn.resolve_args(fin, widths[:-1], args)
    hidden = out["hidden"]
    if heads < 1 or hidden % heads:
        raise ValueError(f"rgat: {heads} heads do not divide hidden {hidden}")
    if not 0.0 <= slope <= 1.0:
        raise ValueError(f"rgat: LeakyReLU slope {slope} outside [0, 1] (the "
                         "max pass relies on a monotone score)")
    if head and head != {"hidden": hidden, **HEAD}:
        raise ValueError(f"rgat: head {head} is not the published "
                         f"{ {'hidden': hidden, **HEAD} }")
    embedded = [n for n, _, kind in out["types"] if kind != "features"]
    if embedded:
        raise ValueError(f"rgat: types {embedded} are embedded; every type "
                         "brings features here (no row-owned parameters)")
    return {**out, "heads": heads, "slope": slope}


def param_count(fin: int, hidden: int, classes: int, nrel: int,
                layers: int = 2) -> int:
    """Per layer a bias-free ``W_r``, ``a_src``, ``a_dst`` and ``b_r`` a
    relation, the biased skip and BatchNorm's two vectors; the head."""
    total = 0
    for a in [fin] + [hidden] * (layers - 1):
        total += nrel * (a * hidden + 3 * hidden) + a * hidden + hidden \
            + 2 * hidden
    return total + hidden * hidden + hidden + 2 * hidden \
        + hidden * classes + classes


def layer_plan(args: dict, layout: dict) -> RattSpec:
    """The forward's statics from the configuration and rgcn's layout."""
    types, rels = args["types"], args["relations"]
    need = rgcn.reachable(len(types), rels, args["label"], args["layers"])
    dst = tuple(tuple(need[layer + 1]) for layer in range(args["layers"]))
    live = tuple(tuple(r for r, (s, name, d) in enumerate(rels)
                       if d in into and layout["edges"][name] > 0)
                 for into in dst)
    rows = tuple(sum(types[t][1] for t in into) for into in dst) \
        + (types[args["label"]][1],)
    return RattSpec(heights=layout["heights"], layouts=layout["layouts"],
                    exchange=layout["exchange"], read=tuple(need[0]),
                    dst=dst, live=live, rows=rows)


def walked(spec: RattSpec, relations) -> dict:
    """``{pair: {"mf", "mb"}}``: the pairs some pass walks and the masks
    it reads — forward ``(s, d)`` with ``mf``, backward ``(d, s)`` with
    ``mb`` — for every relation live at some layer."""
    out: dict = {}
    for live in spec.live:
        for r in live:
            s, _, d = relations[r]
            out.setdefault((s, d), set()).add("mf")
            out.setdefault((d, s), set()).add("mb")
    return out


def shipped_arrays(layout: dict, spec: RattSpec, relations) -> dict:
    """The arrays a step reads: per walked pair its slots' sources, the
    virtual rows' destinations and the int8 masks its passes pick
    (``ratt_<s>_<d>_<store>_<idx|row|mf|mb>``)."""
    out = {}
    for (s, d), masks in sorted(walked(spec, relations).items()):
        arr = layout["arrays"]["rels"][s, d]
        for st in STORES:
            out[f"ratt_{s}_{d}_{st}_idx"] = arr[f"{st}_idx"]
            if st != "e":
                out[f"ratt_{s}_{d}_{st}_row"] = arr[f"{st}_row"]
            for m in sorted(masks):
                w = arr[f"{st}_w{m[1]}"]
                out[f"ratt_{s}_{d}_{st}_{m}"] = (w != 0).astype(np.int8)
    return out


# ------------------------------------------------------------------ params
def _uniform(key, shape, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def init_rgat_params(rng: jax.Array, dims, relations=(), heads: int = 1,
                     **_static):
    """``layers`` (per layer ``w`` (relations, d_in, K·C), ``att_src`` /
    ``att_dst`` (relations, K, C) Glorot-uniform and ``b`` (relations, K·C)
    zeros, as ``GATConv.reset_parameters``; ``skip_w`` / ``skip_b`` as
    torch's ``Linear`` draws them; BatchNorm's ``bn_g`` ones and ``bn_b``
    zeros) and ``head`` (``w1``, ``b1``, ``bn_g``, ``bn_b``, ``w2``,
    ``b2``).  ``dims`` are the trainer's: the layers', then the head's
    ``(hidden, classes)``."""
    glorot = jax.nn.initializers.glorot_uniform(batch_axis=(0,))
    nrel = len(relations)
    keys = jax.random.split(rng, len(dims))
    layers = []
    for key, (a, b) in zip(keys[:-1], dims[:-1]):
        kw, ks, kd, kk, kb = jax.random.split(key, 5)
        shape = (nrel, heads, b // heads)
        layers.append({
            "w": glorot(kw, (nrel, a, b), jnp.float32),
            "att_src": glorot(ks, shape, jnp.float32),
            "att_dst": glorot(kd, shape, jnp.float32),
            "b": jnp.zeros((nrel, b), jnp.float32),
            "skip_w": _uniform(kk, (a, b), 1.0 / np.sqrt(a)),
            "skip_b": _uniform(kb, (b,), 1.0 / np.sqrt(a)),
            "bn_g": jnp.ones((b,), jnp.float32),
            "bn_b": jnp.zeros((b,), jnp.float32)})
    hid, ncls = dims[-1]
    k1, k2, k3, k4 = jax.random.split(keys[-1], 4)
    bound = 1.0 / np.sqrt(hid)
    head = {"w1": _uniform(k1, (hid, hid), bound),
            "b1": _uniform(k2, (hid,), bound),
            "bn_g": jnp.ones((hid,), jnp.float32),
            "bn_b": jnp.zeros((hid,), jnp.float32),
            "w2": _uniform(k3, (hid, ncls), bound),
            "b2": _uniform(k4, (ncls,), bound)}
    return {"layers": layers, "head": head}


# ------------------------------------------------------------- aggregation
def _stores(arrays: dict, shapes: tuple, mask: str) -> tuple:
    """One layout walked as ``mhgat``'s store set: ELL slots, then the
    virtual rows past them and the halo-source edges, scanned as rgcn's
    typed passes scan them (``ops.pspmm.fold_policy``)."""
    buckets, tails, halos = shapes
    at = arrays.get
    return (mhgat.Store("agg_slots", buckets, at("e_idx"), at("e_" + mask)),
            mhgat.Store("agg_tail", tails, at("t_idx"), at("t_" + mask),
                        at("t_row"), scanned=True),
            mhgat.Store("agg_halo_fold", halos, at("h_idx"), at("h_" + mask),
                        at("h_row"), halo=True, scanned=True))


def _exchange(parts, u: int, arrays: dict, rs: RelStatic):
    """The halo copy of type ``u``'s rows of ``parts``, in the stacked
    typed order of rgcn's exchange (the other types' rows ship zeros)."""
    with scope("dense"):
        tables = tuple(jnp.concatenate(
            [p if t == u else jnp.zeros((h, p.shape[1]), p.dtype)
             for t, h in enumerate(rs.heights)], axis=0) for p in parts)
    return halo_exchange_multi(tables, arrays["send_rows"],
                               arrays["halo_src"], rs.axis_name)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def relation_attention(z, s, t, arrays, rs: RelStatic):
    """One relation's attention: ``z`` (rows of the source type, K·C) and
    ``t`` (its K source scores), ``s`` (rows of the destination type, K
    destination scores) in, ``O`` (destination rows, K·C) out — ``mhgat``'s
    slot bodies over the pair's layout, gathers only, forward and backward
    (module docstring)."""
    return _rel_fwd(z, s, t, arrays, rs)[0]


def _rel_fwd(z, s, t, arrays, rs):
    src, dst = rs.pair
    zh, th = (_exchange((z, t), src, arrays, rs) if rs.exchange
              else (None, None))
    with pair_scope(src, dst):
        out, (m, dinv, pos, ppos) = mhgat.attend(
            z, s, t, zh, th, _stores(arrays["fwd"], rs.fwd, "mf"), rs.heads,
            rs.slope, rows=rs.heights[dst])
    return out, (z, s, t, m, dinv, out, pos, ppos, arrays)


def _rel_bwd(rs, res, g):
    z, s, t, m, dinv, out, pos, ppos, arrays = res
    src, dst = rs.pair
    exchange = ((lambda parts: _exchange(parts, dst, arrays, rs))
                if rs.exchange else None)
    # named after the layout walked: the pair (d -> s), rows of s
    with pair_scope(dst, src):
        dz, ds, dt = mhgat.attend_bwd(
            g, z, s, t, m, dinv, out, pos, ppos,
            _stores(arrays["bwd"], rs.bwd, "mb"), exchange, rs.heads,
            rs.slope, rows=rs.heights[src])
    return dz, ds, dt, None


relation_attention.defvjp(_rel_fwd, _rel_bwd)


# ----------------------------------------------------------------- forward
def _dot(x, w):
    return jnp.dot(x, w, precision=lax.Precision.HIGHEST)


def _norm(h, gamma, beta, valid, n_rows: int, axis_name: str):
    """``deepergcn.batch_norm`` over the rows ``valid`` marks, all chips."""
    env = deepergcn.Env(edges=(), valid=valid, n_rows=n_rows, buckets=(),
                        fold_classes=((), ()), t=0.0, eps=0.0,
                        axis_name=axis_name)
    return deepergcn.batch_norm(h, gamma, beta, env)


def rgat_forward_local(
    params,
    h,                            # (B, fin) local rows, plan order
    pa,                           # shipped arrays (RGCN_PLAN_FIELDS + ratt_*)
    activation: str = "elu",
    final_activation: str = "none",
    symmetric: bool = False,
    relations: tuple = (),        # static: (source, name, destination)
    label: int = 0,               # static: the labelled type
    heads: int = 1,               # static: K
    slope: float = 0.2,           # static: LeakyReLU slope of the scores
    spec: RattSpec | None = None,  # static: layer_plan
    comm_schedule: str = "a2a",
    axis_name: str = AXIS,
    halo_carry=None,
    halo_dtype=None,
    **_static,
):
    """Per-chip forward (module docstring); returns the labelled type's
    logits, ``(height, classes)`` in typed order — the trainer reads labels
    and masks through ``ModelSetup.out_rows``."""
    if halo_carry is not None:
        raise NotImplementedError(
            "stale-halo pipelining is implemented for the GCN hot path "
            "only; run rgat with halo_staleness=0")
    if not symmetric:
        raise ValueError(
            "rgat's backward walks the reverse pair's slots, which hold the "
            "same edges for a symmetric pattern only; this plan is "
            "asymmetric")
    if comm_schedule != "a2a" or halo_dtype is not None:
        raise ValueError(
            "rgat ships float32 tables over the dense all_to_all only "
            f"(comm_schedule={comm_schedule!r}, halo_dtype={halo_dtype!r})")
    act, last = get_activation(activation), get_activation(final_activation)
    layouts = dict(spec.layouts)

    def arrays_of(s, d):
        return {st + "_" + n: pa[f"ratt_{s}_{d}_{st}_{n}"]
                for st in STORES for n in ("idx", "row", "mf", "mb")
                if f"ratt_{s}_{d}_{st}_{n}" in pa}

    xchg = {"send_rows": pa["ratt_send_rows"], "halo_src": pa["halo_src"]}
    with scope("dense"):
        x = [jnp.take(h, pa[f"ratt_{t}_rows"], axis=0) if t in spec.read
             else None for t in range(len(spec.heights))]
    for layer, (p, into, live) in enumerate(zip(params["layers"], spec.dst,
                                                spec.live)):
        with scope("layer", layer):
            got = {d: [] for d in into}
            for r in live:
                s, _, d = relations[r]
                with scope("dense"), subscope("ratt_project"):
                    w = p["w"][r]
                    z = _dot(x[s], w)                           # (n_s, K·C)
                    t = mhgat._dot_heads(z, p["att_src"][r].reshape(1, -1),
                                         heads)
                    # a_dst · (W x_i) = x_i · (W a_dst): K columns
                    fold = jnp.einsum(
                        "akc,kc->ak", w.reshape(w.shape[0], heads, -1),
                        p["att_dst"][r], precision=lax.Precision.HIGHEST)
                    sd = _dot(x[d], fold)                       # (n_d, K)
                rs = RelStatic(pair=(s, d), fwd=layouts[s, d],
                               bwd=layouts[d, s], heights=spec.heights,
                               exchange=spec.exchange, heads=heads,
                               slope=float(slope), axis_name=axis_name)
                got[d].append(relation_attention(
                    z, sd, t, {"fwd": arrays_of(s, d),
                               "bwd": arrays_of(d, s), **xchg}, rs))
            with scope("dense"), subscope("ratt_project"):
                bias = p["skip_b"] + sum(p["b"][r] for r in live)
                hs = [sum(got[d], _dot(x[d], p["skip_w"]) + bias)
                      for d in into]
            with scope("dense"), subscope("ratt_norm"):
                valid = jnp.concatenate([pa[f"ratt_{d}_valid"]
                                         for d in into])
                y = act(_norm(jnp.concatenate(hs), p["bn_g"], p["bn_b"],
                              valid, spec.rows[layer], axis_name))
            x = [None] * len(spec.heights)
            at = 0
            for d in into:
                x[d] = y[at:at + spec.heights[d]]
                at += spec.heights[d]
    q = params["head"]
    with scope("dense"), subscope("ratt_norm"):
        y = _dot(x[label], q["w1"]) + q["b1"]
        y = jax.nn.relu(_norm(y, q["bn_g"], q["bn_b"],
                              pa[f"ratt_{label}_valid"], spec.rows[-1],
                              axis_name))
        return last(_dot(y, q["w2"]) + q["b2"])


# ------------------------------------------------------------------- memory
def estimate_rgat_hbm_bytes(plan, fin: int, widths, args: dict,
                            spec: RattSpec, plan_bytes: int,
                            train: bool = True) -> dict:
    """Per-chip HBM of one fwd+bwd step, itemised (f32, typed heights):

    * ``rows_kept``: what a layer's forward holds for its backward — the
      gathered features, per live relation the source table ``Z`` and the
      destination's ``O`` and ``P`` (K·C each) with 8K scalars, the pre-
      and post-BatchNorm rows of ``T``; the head's three row arrays;
    * ``rows_transient``: the widest layer's backward at its peak — a
      relation's cotangent, its ``∂Z`` and the ``∂x`` being summed, three
      rows of ``T``'s width;
    * ``slot_temps``: the slot passes' gathered rows, bounded by the
      scan-unroll budget ``mhgat._SCAN_LIVE`` and the unrolled buckets;
    * ``plan``: per walked slot an int32 source and an int8 mask a
      direction, an int32 destination per virtual row;
    * ``features``, ``params`` (parameters, gradient, Adam's two moments).

    An estimate of what the arrays need, set beside the chip's reading in
    PERF.md §5 (PR 39)."""
    hts, hid = spec.heights, int(widths[0])
    k = args["heads"]
    rels = args["relations"]
    kept = sum(hts[t] for t in spec.read) * fin * 4
    widest = 0
    for into, live in zip(spec.dst, spec.live):
        rows = sum(hts[d] for d in into)
        widest = max(widest, rows)
        for r in live:
            s, _, d = rels[r]
            kept += (hts[s] * hid + hts[d] * (2 * hid + 8 * k)) * 4
        kept += rows * hid * 4 * 3 if train else 0
    kept += hts[args["label"]] * hid * 4 * 3
    transient = 3 * widest * hid * 4
    temps = min(mhgat._SCAN_LIVE + 3 * 4 * hid * max(hts),
                3 * 4 * hid * sum(hts))
    nparams = param_count(fin, hid, int(widths[-1]), len(rels),
                          args["layers"])
    parts = {"rows_kept": kept if train else 0,
             "rows_transient": transient, "slot_temps": temps,
             "plan": plan_bytes, "features": int(plan.b) * 4 * (fin + 3),
             "params": (16 if train else 4) * nparams}
    parts["total"] = sum(parts.values())
    return parts


# -------------------------------------------------------------- the registry
def _layout_slots(arrays: dict) -> tuple:
    """``(executed slots, virtual-row slots, virtual rows)`` of one layout
    on every chip."""
    vslots = sum(arrays[f"{st}_idx"].shape[1] for st in ("t", "h"))
    return (arrays["e_idx"].shape[1] + vslots, vslots,
            sum(arrays[f"{st}_row"].shape[1] for st in ("t", "h")))


def rel_passes(args: dict, spec: RattSpec, layout: dict) -> tuple:
    """The step's pass list for ``slots.work`` and the ``ratt.work``
    counter's rows: per layer and live relation ``s -> d`` the narrow max
    pass and the forward aggregation over the pair's layout (tags
    ``att_max`` and ``pair_<s>_<d>``), and the backward over the reverse
    pair's (``pair_<d>_<s>``), in the forms ``mhgat._store_reduce`` runs
    them."""
    k, hid = args["heads"], args["hidden"]
    rels = args["relations"]
    names = [n for n, _, _ in args["types"]]
    layouts = dict(spec.layouts)

    def forms(pair, slot_bytes):
        return mhgat.store_forms(_stores({}, layouts[pair], "mf"), slot_bytes)

    passes, rows = [], []
    for layer, live in enumerate(spec.live):
        for r in live:
            s, name, d = rels[r]
            fwd, bwd = layout["counts"][s, d], layout["counts"][d, s]
            tag = f"pair_{s}_{d}"
            passes += [
                slot_pass(layer, "fwd", k,
                          forms((s, d), mhgat._max_slot_bytes),
                          tags=("att_max", tag), true_edges=fwd["chip_edges"]),
                slot_pass(layer, "fwd", hid + k,
                          forms((s, d), mhgat._agg_slot_bytes(hid)),
                          tags=(tag,), true_edges=fwd["chip_edges"]),
                slot_pass(layer, "bwd", hid + 4 * k,
                          forms((d, s), mhgat._agg_slot_bytes(hid)),
                          tags=(f"pair_{d}_{s}",),
                          true_edges=bwd["chip_edges"])]
            ef, vf, rf = _layout_slots(layout["arrays"]["rels"][s, d])
            eb, vb, rb = _layout_slots(layout["arrays"]["rels"][d, s])
            rows.append({
                "layer": layer, "relation": name, "source": names[s],
                "destination": names[d], "heads": k, "channels": hid // k,
                # the fullest chip's edges; what every chip executes
                "true_edges": fwd["edges"],
                # max and forward pass over (s -> d), backward over (d -> s)
                "passes": 3,
                "executed_slots": 2 * ef + eb,
                "virtual_row_slots": 2 * vf + vb,
                "virtual_rows": 2 * rf + rb})
    return passes, rows


def model_setup(plan, fin: int, widths, model_args: dict | None, *,
                comm_schedule: str, compute_dtype, serve_subgraph: bool
                ) -> ModelSetup:
    """The ``MODELS`` entry's setup hook (``models/setup.py``): validates
    ``model_args``, refuses what the model has no form for, derives rgcn's
    typed layout from the plan, and hands the shared code the statics, the
    masks and rows it ships, the exchange's lanes per direction, the output
    rows, the parameter count, the memory estimate and the counters
    ``ratt.work`` and ``slots.work``.  Typed layouts are built here and
    nowhere else."""
    if not plan.symmetric:
        raise ValueError(
            "rgat walks a relation's reverse pair for its backward; this "
            "plan is asymmetric (models/rgat.py)")
    if comm_schedule != "a2a" or serve_subgraph:
        raise ValueError(
            "rgat runs the dense a2a schedule and the full forward only "
            f"(comm_schedule={comm_schedule!r}, "
            f"serve_subgraph={serve_subgraph})")
    if compute_dtype is not None:
        raise ValueError(
            f"rgat is float32 only (compute_dtype={compute_dtype!r})")
    args = resolve_args(fin, widths, model_args)
    layout = rgcn.build_typed_layout(plan, args)
    spec = layer_plan(args, layout)
    types, rels, label = args["types"], args["relations"], args["label"]
    k, hid = args["heads"], args["hidden"]
    extra = {"ratt_send_rows": layout["arrays"]["send_rows"],
             **shipped_arrays(layout, spec, rels)}
    for t in range(len(types)):
        if t in spec.read:
            extra[f"ratt_{t}_rows"] = layout["plan_rows"][t]
        extra[f"ratt_{t}_valid"] = (layout["table_rows"][t] >= 0
                                    ).astype(np.float32)
    extra["ratt_out_rows"] = layout["plan_rows"][label]
    extra["ratt_out_valid"] = extra[f"ratt_{label}_valid"]
    passes, rows = rel_passes(args, spec, layout)
    plan_bytes = sum(x[0].nbytes for name, x in extra.items()
                     if name.endswith(("_idx", "_row", "_mf", "_mb")))
    # [Z ‖ t] of each live relation forward, [g ‖ s, m, 1/D, c] backward
    lanes_f = tuple(len(live) * (hid + k) for live in spec.live)
    lanes_b = tuple(len(live) * (hid + 4 * k) for live in spec.live)
    names = [n for n, _, _ in types]
    counter = {
        "types": {n: {"rows": int(c), "height": int(h)}
                  for (n, c, _), h in zip(types, spec.heights)},
        "targets": [[names[t] for t in into] for into in spec.dst],
        "relations": rows,
        "per_step": {key: sum(r[key] for r in rows) for key in (
            "true_edges", "executed_slots", "virtual_row_slots",
            "virtual_rows")},
        "exchanges_per_step": 2 * len(rows) if spec.exchange else 0}
    statics = {"relations": rels, "label": label, "heads": k,
               "slope": args["slope"], "spec": spec}
    return ModelSetup(
        fwd_static=statics,
        init_static={"relations": rels, "heads": k},
        extra_arrays=extra,
        mask_fields=(),
        lane_widths=lanes_f, lane_widths_bwd=lanes_b,
        param_count=param_count(fin, hid, int(widths[-1]), len(rels),
                                args["layers"]),
        estimate_memory=functools.partial(
            estimate_rgat_hbm_bytes, plan, fin, list(widths), args, spec,
            plan_bytes),
        counters={"ratt.work": counter,
                  "slots.work": slot_work(passes, {
                      tag: _pair_name(tag, rels) for p in passes
                      for tag in p["tags"] if tag.startswith("pair_")})},
        allow_pallas=False,         # no VMEM form of the per-edge softmax
        out_rows=("ratt_out_rows", "ratt_out_valid"))


def _pair_name(tag: str, rels) -> str:
    """The relation whose forward walks the pair of ``tag`` (``name^T``
    where only its reverse has one), as rgcn names its pairs."""
    s, d = (int(x) for x in tag[5:].split("_"))
    name_of = {(a, b): n for a, n, b in rels}
    return name_of.get((s, d), f"{name_of.get((d, s))}^T")
