"""Multi-head graph attention as published, on the partitioned full-batch path.

Veličković et al., "Graph Attention Networks" (ICLR 2018), in the form
PyTorch Geometric's ``GATConv`` gives it and the OGB ``ogbn-products``
leaderboard trains (``examples/ogbn_products_gat.py``).  Per layer with ``K``
heads of ``C`` channels, input ``H`` (n × F), edges ``N(i) = {j : Â_ij ≠ 0}``
(A + I, symmetric; Â's values are ignored)::

    Z = H W  (n × K·C)     t_j[k] = Z_j[k,:]·a_src[k]     s_i[k] = Z_i[k,:]·a_dst[k]
    e_ij[k] = LeakyReLU(s_i[k] + t_j[k])      m_i = max_j e_ij     D_i = Σ_j exp(e_ij − m_i)
    O_i[k,:] = Σ_j exp(e_ij[k] − m_i[k]) / D_i[k] · Z_j[k,:]
    out_i = concat_k O_i[k,:] + b  (hidden)  |  mean_k O_i[k,:] + b  (last)
    H' = act(out + H W_skip + b_skip)

``models/gat.py`` stays the reference repository's single-head layer, whose
un-rectified scores let the softmax factorise per source; here the LeakyReLU
sits between the sum and the softmax, so a coefficient exists only per edge.

**Where the edges are.**  The layer reads the plan's three edge stores, the
GCN's own: the bucketed ELL slots (local sources, ``ell_idx`` as shipped to
the GCN), the hub tail (``ltail_*``) and the halo-source edges (``hedge_*``).
A score is computed on all three; the per-destination max and sums run across
the three.  The two COO stores are not folded by scatter-adds, as the GCN
folds them: a scatter of 512-lane rows into four accumulators cost 400 ns an
edge on the v5e and the tail alone was 54 % of the epoch (PERF.md §6, PR 27).
``CommPlan.virtual_rows`` (``parallel/plan.py``, beside the ELL builder)
re-lays each, once per plan on the host, as **virtual rows**: a
destination's edges cut into runs of ``VROW_WIDTH``, each run one row of a
single-bucket width-major slot layout, so tail and halo edges go through
``bucketed_slot_reduce`` like every other slot and one sorted scatter per
pass adds the virtual rows' sums to their destinations.  A store without a
real edge on any chip has no layout and no pass (k = 1 has no halo edges:
neither the halo fold nor the exchange is in its program).  One
``all_to_all`` a layer and pass ships ``[Z_j ‖ t_j]`` forward (K·C + K
lanes) and ``[g_i ‖ s_i, m_i, 1/D_i, c_i]`` backward (K·C + 4K).

**Table form** (v5e, 306,129 rows, PERF.md §6 PR 27): one gather of the whole
K·C-lane row costs 19.6 ns an edge, four gathers of 128-lane per-head rows
44.0 ns, so the table is ONE ``(rows, K·C)`` array and the per-head
coefficients are spread over the gathered row's lanes by a product with a
0/1 matrix (``_scale_heads``); the K scalars of a row ride a narrow table
of their own (5.7 ns an edge).  A slot's time is its fusions' HBM traffic,
not its MXU passes (PERF.md §6 PR 32): a forward slot runs ONE spread where
its two accumulators would take two — of the coefficient signed by
[s_i + t_j > 0], whose magnitude and positive part are the two factors —
as one bfloat16 pass over the coefficient's three exact pieces (``split3``;
the 0/1 side is exact in one piece, every output is one input times one, so
the result is the f32 broadcast to the bit).  The backward slot's spread and
its sum over a head's lanes (``_dot_heads``) stay ``HIGHEST`` products.

**Backward** (``attention_aggregate``'s custom rule; a symmetric pattern is
required, as for the GCN's): with ``g = ∂L/∂O`` and ``c_i = g_i·O_i`` per head,

* ``∂L/∂Z_j = Σ_i α_ij g_i`` and ``∂L/∂t_j = Σ_i φ'(s_i+t_j) α_ij (g_i·Z_j − c_i)``:
  row j's slots enumerate exactly the i that aggregate j, so both are ONE
  gather pass over the same layout, reading the exchanged ``[g ‖ s, m, 1/D, c]``
  table, with α recomputed per slot;
* ``∂L/∂s_i = Σ_j φ'(s_i+t_j) α_ij (g_i·Z_j − c_i)`` needs no pass at all:
  ``φ' = slope + (1 − slope)·[s_i+t_j > 0]`` and the slope part sums to
  ``c_i − c_i = 0``, so ``∂L/∂s_i = (1 − slope)(g_i·P_i − c_i p_i)`` where
  ``P_i = Σ_{j: s_i+t_j>0} α_ij Z_j`` and ``p_i = Σ_{j: s_i+t_j>0} α_ij`` are a
  second accumulator of the FORWARD pass (no extra gather).

Residuals are per row (``Z, s, t, m, D, O, P, p``), never per edge.  The max
pass gathers only ``t``: LeakyReLU with a slope ≥ 0 is monotone, so
``max_j e_ij = LeakyReLU(s_i + max_j t_j)`` exactly.

Per-chip code, meant to run inside ``shard_map`` over the 1D vertex mesh.
Refused, loudly: an asymmetric plan, ``comm_schedule='ragged'``, the Pallas
aggregator, stale / replica modes, ``compute_dtype``, mini-batch, serving.
"""

from __future__ import annotations

import contextlib
import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.tracing import scope, subscope
from ..ops.pspmm import (bucket_forms, bucketed_slot_reduce,
                         fold_rows_scope, halo_exchange_multi)
from ..parallel.mesh import AXIS
from .activations import get_activation
from .setup import (ModelSetup, Request, plan_true_edges,
                    refuse_beyond_full_f32, slot_pass, slot_work)

# plan arrays shipped as they are (``ell_w`` narrowed to a 0/1 mask by
# ``ForwardSetup.ship_arrays``), and the virtual-row layouts of the hub
# tail (``vt_*``) and the halo-source edges (``vh_*``) that
# ``CommPlan.virtual_rows`` derives from ``ltail_*`` / ``hedge_*`` (a store
# without edges ships none)
MHGAT_PLAN_FIELDS = ("send_idx", "halo_src", "ell_idx", "ell_w")
MHGAT_VROW_ARRAYS = ("vt_idx", "vt_mask", "vt_row",
                     "vh_idx", "vh_mask", "vh_row")

_NEG = -1e30        # identity of the max pass; finite, so 0·x stays 0
_TINY = 1e-30       # guard of D for rows without edges (pad rows); TPUs
#                     flush subnormals, so nothing smaller would guard
# several slot reduces share one program (max, forward, backward; three
# layers): each gets half the default scan-unroll liveness budget, as the
# factorised layer's passes do (models/gat.py::_GAT_SCAN_LIVE)
_SCAN_LIVE = 3 * 1024**3 // 2


# ----------------------------------------------------------- configuration
def layer_shapes(fin: int, widths, heads, concat) -> list:
    """``(input width, heads K, channels C, output width)`` per layer, from
    the trainer's ``widths`` (layer OUTPUT widths: K·C where the heads are
    concatenated, C where they are averaged)."""
    widths, heads, concat = list(widths), list(heads), list(concat)
    if not (len(widths) == len(heads) == len(concat)):
        raise ValueError(
            f"mhgat: {len(widths)} widths, {len(heads)} heads and "
            f"{len(concat)} concat flags — one of each per layer")
    out, f = [], int(fin)
    for w, k, cat in zip(widths, heads, concat):
        w, k = int(w), int(k)
        if k < 1 or (cat and w % k):
            raise ValueError(
                f"mhgat: a layer of width {w} cannot concatenate {k} heads")
        out.append((f, k, w // k if cat else w, w))
        f = w
    return out


def resolve_args(widths, model_args: dict | None) -> dict:
    """The layer's hyper-parameters as the static keyword arguments of
    ``mhgat_forward_local`` / ``init_mhgat_params`` — constructor data
    (``FullBatchTrainer(model_args=...)``), defaults as published: one
    head, concatenated but for the last layer, slope 0.2, skip and bias."""
    args = dict(model_args or {})
    nl = len(widths)
    heads = tuple(int(k) for k in args.pop("heads", (1,) * nl))
    concat = tuple(bool(c) for c in args.pop(
        "concat", (True,) * (nl - 1) + (False,)))
    out = {"heads": heads, "concat": concat,
           "slope": float(args.pop("slope", 0.2)),
           "skip": bool(args.pop("skip", True)),
           "bias": bool(args.pop("bias", True))}
    if args:
        raise ValueError(f"mhgat: unknown model_args {sorted(args)}")
    if not 0.0 <= out["slope"] <= 1.0:
        raise ValueError(
            f"mhgat: LeakyReLU slope {out['slope']} outside [0, 1] (the max "
            "pass relies on a monotone score)")
    layer_shapes(0, widths, heads, concat)      # validates the three lists
    return out


def init_mhgat_params(rng: jax.Array, dims, heads=(), concat=(),
                      skip: bool = True, bias: bool = True, **_static):
    """Per layer ``w`` (fin, K·C), ``a_src`` / ``a_dst`` (K, C) — Glorot
    uniform, as ``GATConv.reset_parameters`` — ``b`` (K·C or C, zeros), and
    the linear skip ``w_skip`` (fin, out) Glorot uniform, ``b_skip`` zeros."""
    glorot = jax.nn.initializers.glorot_uniform()
    shapes = layer_shapes(dims[0][0], [fo for _, fo in dims], heads, concat)
    params = []
    for key, (fin, k, c, out) in zip(jax.random.split(rng, len(dims)),
                                     shapes):
        kw, ks, kd, kk = jax.random.split(key, 4)
        p = {"w": glorot(kw, (fin, k * c), jnp.float32),
             "a_src": glorot(ks, (k, c), jnp.float32),
             "a_dst": glorot(kd, (k, c), jnp.float32)}
        if bias:
            p["b"] = jnp.zeros((out,), jnp.float32)
        if skip:
            p["w_skip"] = glorot(kk, (fin, out), jnp.float32)
            p["b_skip"] = jnp.zeros((out,), jnp.float32)
        params.append(p)
    return params


def param_count(fin: int, widths, heads, concat, skip=True, bias=True) -> int:
    total = 0
    for f, k, c, out in layer_shapes(fin, widths, heads, concat):
        total += f * k * c + 2 * k * c + (out if bias else 0)
        total += (f * out + out) if skip else 0
    return total


def mhgat_exchange_lane_widths(fin: int, widths, heads, concat) -> tuple:
    """``(forward, backward)`` f32 lanes of each layer's exchange: the
    table ``[Z_j ‖ t_j]`` is K·C + K wide, ``[g_i ‖ s_i, m_i, 1/D_i, c_i]``
    K·C + 4K — the lane model ``CommStats`` and the counter ``att.work``
    price the wire with."""
    shapes = layer_shapes(fin, widths, heads, concat)
    return (tuple(k * c + k for _, k, c, _ in shapes),
            tuple(k * c + 4 * k for _, k, c, _ in shapes))


# ----------------------------------------------------------- per-head algebra
def _leaky(x, slope):
    return jnp.where(x > 0, x, slope * x)


def _head_lanes(k: int, f: int):
    """(K, K·C) 0/1 matrix: row k is one on head k's lanes."""
    return jnp.repeat(jnp.eye(k, dtype=jnp.float32), f // k, axis=1)


def split3(a):
    """``a`` (f32) cut into three bfloat16 pieces ``hi, mid, lo`` with
    ``hi + mid + lo == a`` to the bit (8 + 8 + 8 significand bits) wherever
    the pieces stay normal, |a| ≥ 2⁻¹⁰² — the pieces ``Precision.HIGHEST``
    cuts an operand into; below that ``lo`` flushes to zero and the sum is
    ``a`` to 2⁻¹⁶ of a number under 2⁻¹⁰², as ``HIGHEST``'s on this chip.
    The cuts are ``reduce_precision``, not a cast there and back: a compiler
    may drop an f32 → bf16 → f32 round trip as excess precision, and ``mid``
    and ``lo`` would be zero."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    rest = a - hi
    mid = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
    return tuple(x.astype(jnp.bfloat16) for x in (hi, mid, rest - mid))


def _spread_heads(p, f: int):
    """``p`` (n, K) → (n, K·C), column k copied to head k's lanes, as ONE
    bfloat16 MXU pass: every output is one input times one, so the stacked
    pieces ``[hi ‖ mid ‖ lo]`` (n, 3K) against the 0/1 matrix stacked three
    times (3K, K·C), f32 out, give the f32 broadcast to the bit — the
    accumulator adds ``hi + mid + lo``, whose partial sums are all f32
    numbers.  ``Precision.HIGHEST`` runs six passes for the same bits, three
    of them against the zero pieces of the 0/1 side.  The forward slot's
    spread (``_aggregate_fwd``); never differentiated, it sits inside the
    aggregation's own rule (a transposed ``split3`` would round a cotangent
    to bfloat16)."""
    lanes = _head_lanes(p.shape[1], f).astype(jnp.bfloat16)
    return jnp.dot(jnp.concatenate(split3(p), axis=1),
                   jnp.concatenate([lanes] * 3, axis=0),
                   preferred_element_type=jnp.float32)


def _scale_heads(p, rows):
    """``rows`` (n, K·C) with head k's lanes multiplied by ``p[:, k]``.  The
    K coefficients of a row are spread over its lanes by a product with a
    0/1 matrix at ``HIGHEST`` precision (exact: each output is one input),
    which the v5e runs at 39.0 ns an edge where per-head lane slices ran
    45.3 and ``jnp.repeat`` 67.3 (forward pass, C = 128; PERF.md §6 PR 27).
    The backward slot's spread and the rows' normalisation; in the backward
    slot the one-pass form of ``_spread_heads`` costs the same alone and
    more in the step (PERF.md §6 PR 32)."""
    k = p.shape[1]
    if k == 1:
        return rows * p
    return rows * jnp.dot(p, _head_lanes(k, rows.shape[1]),
                          precision=jax.lax.Precision.HIGHEST)


def _dot_heads(a, b, k: int):
    """Per-head inner products of two (·, K·C) arrays → (n, K): the
    products summed by the transposed 0/1 matrix, likewise at ``HIGHEST``
    (34.8 ns an edge against 44.2 for sums of lane slices, backward pass;
    its exact three-piece split costs 45.7 against 31.4: the pieces are
    K·C-lane arrays, read back from HBM by a product each; PR 32)."""
    ab = a * b
    if k == 1:
        return ab.sum(axis=1, keepdims=True)
    return jnp.dot(ab, _head_lanes(k, ab.shape[1]).T,
                   precision=jax.lax.Precision.HIGHEST)


def head_products() -> dict:
    """What ``att.work`` says of the products by the 0/1 head matrix, in
    layers of K > 1 (one head multiplies and sums without a product): which
    products a slot of the forward and of the backward aggregation runs and
    a layer's row-wise work runs once, in what form and how many MXU passes
    each.  The row-wise ones: the two score projections' sums and the
    normalisation's two spreads forward; the sums of ``c`` and ``∂L/∂s`` and
    the projections' transposes (spreads, by autodiff) backward."""
    split, highest = ({"form": "split3", "passes": 1},
                      {"form": "highest", "passes": 6})
    return {"forward_slot": {"spread": 1, **split},
            "backward_slot": {"spread": 1, "sum": 1, **highest},
            "layer_rows": {"spread": 4, "sum": 4, **highest}}


def _concat_buckets(outs):
    if len(outs) == 1:
        return outs[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)


def plan_virtual_rows(plan) -> tuple:
    """``(arrays, statics)`` of one plan's tail and halo-edge virtual rows
    (``CommPlan.virtual_rows``): the ``MHGAT_VROW_ARRAYS`` of the stores
    that have edges, stacked per chip like the plan's, and ``{"tail_shape":
    (nv, W) | None, "halo_shape": (nv, W) | None}``."""
    arrays, statics = {}, {}
    layouts = plan.virtual_rows()       # one class, ``VROW_WIDTH`` wide
    for store, pre in (("tail", "vt"), ("halo", "vh")):
        lay = layouts[store]
        statics[store + "_shape"] = None if lay is None else lay["classes"][0]
        if lay is not None:
            # attention reads Â's pattern only: the weights narrow to a mask
            arrays.update({f"{pre}_idx": lay["idx"],
                           f"{pre}_mask": (lay["w"] != 0).astype(np.int8),
                           f"{pre}_row": lay["row"]})
    return arrays, statics


def _max_slot_bytes(nb: int) -> int:
    """A slot's temporaries in the max pass: one tile of lanes a row."""
    return nb * 128 * 4


def _agg_slot_bytes(f: int):
    """... and in an aggregation pass over ``f`` = K·C lanes: the gathered
    rows and two products of their size."""
    return lambda nb: 3 * nb * f * 4


def slot_passes(plan, fin: int, widths, heads, concat, tail_shape,
                halo_shape) -> list:
    """The step's pass list for the counter ``slots.work``
    (``models/setup.py::slot_pass``): per layer the narrow max pass (tag
    ``att_max``, the sub-scope its ops carry) and the forward and the
    backward aggregation, each over the ELL buckets and the one class of
    the tail's and of the halo edges' virtual rows, in the form
    ``_store_reduce`` runs them."""
    true = plan_true_edges(plan)
    stores = plan_stores((None,) * 8, plan.ell_buckets, tail_shape,
                         halo_shape)

    def forms(slot_bytes):
        return store_forms(stores, slot_bytes)

    passes = []
    for layer, (_, k, c, _) in enumerate(layer_shapes(fin, widths, heads,
                                                      concat)):
        f = k * c
        passes += [
            slot_pass(layer, "fwd", k, forms(_max_slot_bytes),
                      tags=("att_max",), true_edges=true),
            # [Z ‖ t] gathered forward, [g ‖ s, m, 1/D, c] backward
            slot_pass(layer, "fwd", f + k, forms(_agg_slot_bytes(f)),
                      true_edges=true),
            slot_pass(layer, "bwd", f + 4 * k, forms(_agg_slot_bytes(f)),
                      true_edges=true)]
    return passes


def _store_reduce(idx, mask, buckets, vrow, dst_side, contrib, init,
                  slot_bytes, combine=jnp.add, scanned=False):
    """One edge store through ``bucketed_slot_reduce``: ``contrib(idx, mask,
    dst_side rows of the slot)`` combined over the slots of every bucket.
    ``dst_side`` are per-destination arrays ``(B, ·)``; a virtual-row store
    (``vrow`` given) reads them at its rows' destinations, and its result is
    one array a class, per virtual row."""
    if vrow is not None:
        with fold_rows_scope():
            dst_side = tuple(jnp.take(x, vrow, axis=0) for x in dst_side)
    outs = bucketed_slot_reduce(
        idx, mask, buckets,
        contrib=lambda i, w, row: contrib(
            i, w, tuple(x[row:row + i.shape[0]] for x in dst_side)),
        init=lambda nb, row: init(nb), slot_bytes=slot_bytes,
        scan_live_limit=_SCAN_LIVE, combine=combine, with_rows=True,
        scanned=scanned)
    return outs if vrow is not None else _concat_buckets(outs)


class Store(NamedTuple):
    """One edge store of a pass: its leaf scope, its buckets or width
    classes, the slots' sources and 0/1 mask, the virtual rows' destinations
    (``None``: ELL slots over every destination row, in order), whether its
    sources are halo rows, and whether every class wider than two slots
    scans (``bucketed_slot_reduce``'s ``scanned``)."""
    scope: str
    shapes: tuple
    idx: object
    mask: object
    row: object = None
    halo: bool = False
    scanned: bool = False


def plan_stores(pa, buckets, tail_shape, halo_shape) -> tuple:
    """The homogeneous layer's store set: the plan's ELL slots, and the one
    class of the tail's and of the halo edges' virtual rows (none where a
    store has no edges)."""
    (ell_idx, ell_w, vt_idx, vt_mask, vt_row, vh_idx, vh_mask, vh_row) = pa
    return (Store("agg_slots", tuple(buckets), ell_idx, ell_w),
            Store("agg_tail", () if tail_shape is None else (tail_shape,),
                  vt_idx, vt_mask, vt_row),
            Store("agg_halo_fold", () if halo_shape is None else (halo_shape,),
                  vh_idx, vh_mask, vh_row, halo=True))


def store_forms(stores, slot_bytes) -> dict:
    """``{"ell" | "tail" | "halo": [((rows, width), unroll), ...]}`` of one
    pass over ``stores`` (``Store``, or anything with its ``shapes`` and
    ``scanned``, in the order ELL, tail, halo): the forms ``_store_reduce``
    runs them in — what ``slot_pass`` lists."""
    return {name: list(zip(st.shapes, bucket_forms(
        st.shapes, slot_bytes, _SCAN_LIVE, st.scanned)))
        for name, st in zip(("ell", "tail", "halo"), stores)}


def _all_stores(tables, halo_tables, dst_side, stores, contrib, init,
                slot_bytes, combine=jnp.add, sub=None, rows=None):
    """``contrib`` over a pass's store set (``Store``: the ELL slots first,
    then virtual rows) — local stores reading ``tables``, halo stores
    ``halo_tables`` — combined per destination; a store without buckets or
    classes has no pass.  ``rows`` is the destination count where the ELL
    store may have no buckets; ``sub`` names a sub-scope for the whole
    pass."""
    inner = (lambda: subscope(sub)) if sub else contextlib.nullcontext
    scatter = {jnp.add: lambda a, r, v: a.at[r].add(
                   v, indices_are_sorted=True),
               jnp.maximum: lambda a, r, v: a.at[r].max(
                   v, indices_are_sorted=True)}[combine]
    acc = None
    for st in stores:
        if not st.shapes:
            continue
        tabs = halo_tables if st.halo else tables
        with scope(st.scope), inner():
            part = _store_reduce(st.idx, st.mask, st.shapes, st.row,
                                 dst_side, partial(contrib, tabs), init,
                                 slot_bytes, combine, st.scanned)
            if st.row is None:
                acc = part
                continue
            if acc is None:
                acc = init(rows)
            # one sorted scatter a class: a class's destinations ascend,
            # the classes' concatenation need not (the sort flag is a
            # promise the TPU's scatter holds a program to)
            r0 = 0
            for (nv, _), cls in zip(st.shapes, part):
                at = st.row if len(st.shapes) == 1 else st.row[r0:r0 + nv]
                with fold_rows_scope():
                    acc = jax.tree.map(
                        lambda a, v, at=at: scatter(a, at, v), acc, cls)
                r0 += nv
    return init(rows) if acc is None else acc


# ------------------------------------------------------------- aggregation
def attend(z, s, t, zh, th, stores, heads, slope, rows=None):
    """The forward slot bodies over one store set: ``O_i = Σ_{j∈N(i)}
    softmax_j(LeakyReLU(s_i + t_j)) Z_j`` per head, with ``z`` (·, K·C) and
    ``t`` (·, K) the sources' table (``zh``, ``th``: its halo rows; ``None``
    where no store reads them) and ``s`` (B, K) the destinations' scores.
    Returns ``out`` and what the backward reads beside the inputs: ``(m,
    1/D, P, p)``."""
    f, k = z.shape[1], heads

    # ---- max pass: LeakyReLU is monotone, so only t is gathered
    tmax = _all_stores(
        (t,), (th,), (), stores,
        contrib=lambda tabs, idx, w, _dst: jnp.where(
            (w != 0)[:, None], jnp.take(tabs[0], idx, axis=0), _NEG),
        init=lambda nb: jnp.full((nb, k), _NEG, jnp.float32),
        slot_bytes=_max_slot_bytes, combine=jnp.maximum, sub="att_max",
        rows=rows)
    with scope("agg_slots"), subscope("att_max"):
        m = _leaky(s + tmax, slope)

    # ---- aggregation pass: un-normalised sums, all edges and the
    # positive-score part of them (the backward's ∂L/∂s reads the latter)
    def edge(tabs, src, mask, dst_side):
        (tab_z, tab_t), (s_i, m_i) = tabs, dst_side
        with subscope("att_score"):
            x = s_i + jnp.take(tab_t, src, axis=0)
            p = jnp.where((mask != 0)[:, None],
                          jnp.exp(_leaky(x, slope) - m_i), 0.0)
            q = jnp.where(x > 0, p, 0.0)
        rows_ = jnp.take(tab_z, src, axis=0)
        # ONE spread a slot for both accumulators: the coefficient signed
        # by [x > 0] — its magnitude scales every edge's row, its positive
        # part the positive-score edges' (±0 where p is 0: nothing added)
        signed = jnp.where(x > 0, p, -p)
        if k > 1:
            signed = _spread_heads(signed, f)
        return (rows_ * jnp.abs(signed), p,
                rows_ * jnp.maximum(signed, 0.0), q)

    num, den, pnum, pden = _all_stores(
        (z, t), (zh, th), (s, m), stores, contrib=edge,
        init=lambda nb: (jnp.zeros((nb, f), jnp.float32),
                         jnp.zeros((nb, k), jnp.float32),
                         jnp.zeros((nb, f), jnp.float32),
                         jnp.zeros((nb, k), jnp.float32)),
        slot_bytes=_agg_slot_bytes(f), rows=rows)
    with scope("agg_slots"), subscope("att_norm"):
        dinv = 1.0 / jnp.maximum(den, _TINY)
        out = _scale_heads(dinv, num)
        pos = _scale_heads(dinv, pnum)
        ppos = pden * dinv
    return out, (m, dinv, pos, ppos)


def attend_bwd(g, z, s, t, m, dinv, out, pos, ppos, stores, exchange,
               heads, slope, rows=None):
    """The gather-only backward of ``attend`` (module docstring): ``∂L/∂s``
    from the forward's second accumulator, then ONE pass over ``stores`` —
    the REVERSE walk of the forward's (rows: the sources, slots: the
    destinations that aggregate them) — reading ``[g ‖ s, m, 1/D, c]`` of
    the destinations, halo rows through ``exchange((g, scal)) -> (gh,
    scalh)`` (``None`` where no store reads them).  Returns ``(∂Z, ∂s,
    ∂t)``."""
    f, k = z.shape[1], heads
    with scope("agg_slots"), subscope("att_norm"):
        c = _dot_heads(g, out, k)
        ds = (1.0 - slope) * (_dot_heads(g, pos, k) - c * ppos)
        scal = jnp.concatenate([s, m, dinv, c], axis=1)         # (B, 4K)
    gh, scalh = exchange((g, scal)) if exchange else (None, None)

    # row j collects from every i that aggregates it
    def edge(tabs, src, mask, dst_side):
        (tab_g, tab_scal), (t_j, z_j) = tabs, dst_side
        si = jnp.take(tab_scal, src, axis=0)
        with subscope("att_score"):
            x = si[:, :k] + t_j
            alpha = jnp.where(
                (mask != 0)[:, None],
                jnp.exp(_leaky(x, slope) - si[:, k:2 * k]) * si[:, 2 * k:3 * k],
                0.0)
        gi = jnp.take(tab_g, src, axis=0)
        de = alpha * (_dot_heads(gi, z_j, k) - si[:, 3 * k:])
        return _scale_heads(alpha, gi), jnp.where(x > 0, de, slope * de)

    dz, dt = _all_stores(
        (g, scal), (gh, scalh), (t, z), stores, contrib=edge,
        init=lambda nb: (jnp.zeros((nb, f), jnp.float32),
                         jnp.zeros((nb, k), jnp.float32)),
        slot_bytes=_agg_slot_bytes(f), rows=rows)
    return dz, ds, dt


@partial(jax.custom_vjp, nondiff_argnums=(13, 14, 15, 16, 17, 18))
def attention_aggregate(z, s, t, send_idx, halo_src, ell_idx, ell_w,
                        vt_idx, vt_mask, vt_row, vh_idx, vh_mask, vh_row,
                        heads, buckets, tail_shape, halo_shape, slope,
                        axis_name=AXIS):
    """``O_i = Σ_{j∈N(i)} softmax_j(LeakyReLU(s_i + t_j)) Z_j`` per head:
    ``z`` (B, K·C) and ``s``, ``t`` (B, K) in, ``O`` (B, K·C) out; gather
    passes only, forward and backward (module docstring)."""
    return _aggregate_fwd(z, s, t, send_idx, halo_src, ell_idx, ell_w,
                          vt_idx, vt_mask, vt_row, vh_idx, vh_mask, vh_row,
                          heads, buckets, tail_shape, halo_shape, slope,
                          axis_name)[0]


def _aggregate_fwd(z, s, t, send_idx, halo_src, ell_idx, ell_w,
                   vt_idx, vt_mask, vt_row, vh_idx, vh_mask, vh_row,
                   heads, buckets, tail_shape, halo_shape, slope, axis_name):
    pa = (ell_idx, ell_w, vt_idx, vt_mask, vt_row, vh_idx, vh_mask, vh_row)
    # no halo edge on any chip: nothing reads a halo table, nothing is sent
    zh, th = (halo_exchange_multi((z, t), send_idx, halo_src, axis_name)
              if halo_shape is not None else (None, None))
    out, (m, dinv, pos, ppos) = attend(
        z, s, t, zh, th, plan_stores(pa, buckets, tail_shape, halo_shape),
        heads, slope)
    res = (z, s, t, m, dinv, out, pos, ppos, send_idx, halo_src) + pa
    return out, res


def _aggregate_bwd(heads, buckets, tail_shape, halo_shape, slope, axis_name,
                   res, g):
    z, s, t, m, dinv, out, pos, ppos, send_idx, halo_src, *pa = res
    # the same stores, read the other way round (symmetric pattern)
    dz, ds, dt = attend_bwd(
        g, z, s, t, m, dinv, out, pos, ppos,
        plan_stores(tuple(pa), buckets, tail_shape, halo_shape),
        (lambda parts: halo_exchange_multi(parts, send_idx, halo_src,
                                           axis_name))
        if halo_shape is not None else None, heads, slope)
    return (dz, ds, dt) + (None,) * 10


attention_aggregate.defvjp(_aggregate_fwd, _aggregate_bwd)


# ------------------------------------------------------------------ forward
def mhgat_forward_local(
    params,
    h,                            # (B, fin) local rows
    pa,                           # plan arrays dict (MHGAT_PLAN_FIELDS)
    activation: str = "elu",
    final_activation: str = "none",
    symmetric: bool = False,
    ell_buckets: tuple | None = None,   # static plan.ell_buckets
    tail_shape: tuple | None = None,    # static (nv, W) of the tail's and
    halo_shape: tuple | None = None,    # the halo edges' virtual rows; None
    #                                     where the store has no edges
    heads: tuple = (),            # static: K per layer
    concat: tuple = (),           # static: concatenate (True) or average
    slope: float = 0.2,           # static: LeakyReLU slope of the scores
    skip: bool = True,            # static: params carry the linear skip
    bias: bool = True,            # static: params carry the layer bias
    comm_schedule: str = "a2a",
    axis_name: str = AXIS,
    halo_carry=None,
):
    """Per-chip forward: stacked multi-head attention layers (module
    docstring).  ``heads`` / ``concat`` / ``slope`` / ``skip`` / ``bias``
    are the configuration, threaded as statics by ``resolve_forward_setup``
    from the trainer's ``model_args``."""
    if halo_carry is not None:
        raise NotImplementedError(
            "stale-halo pipelining is implemented for the GCN hot path "
            "only; run mhgat with halo_staleness=0")
    if not symmetric:
        raise ValueError(
            "mhgat's backward reads row j's slots as the rows that aggregate "
            "j, which holds for a symmetric edge pattern only; this plan is "
            "asymmetric and the layer refuses it (no autodiff fallback)")
    if comm_schedule != "a2a":
        raise ValueError(
            f"mhgat ships its tables over the dense all_to_all only, not "
            f"comm_schedule={comm_schedule!r}")
    if ell_buckets is None:
        raise ValueError(
            "mhgat forward needs the plan's static ell_buckets "
            "(resolve_forward_setup)")
    if not (len(params) == len(heads) == len(concat)):
        raise ValueError(
            f"mhgat: {len(params)} layers of parameters, {len(heads)} heads, "
            f"{len(concat)} concat flags")
    act = get_activation(activation)
    fact = get_activation(final_activation)
    nl = len(params)
    edges = (tuple(pa[f] for f in MHGAT_PLAN_FIELDS)
             + tuple(pa.get(f) for f in MHGAT_VROW_ARRAYS))
    for i, (p, k, cat) in enumerate(zip(params, heads, concat)):
        with scope("layer", i):
            with scope("dense"):
                z = h @ p["w"]                                  # (B, K·C)
                with subscope("att_project"):
                    t = _dot_heads(z, p["a_src"].reshape(1, -1), k)
                    s = _dot_heads(z, p["a_dst"].reshape(1, -1), k)
            out = attention_aggregate(z, s, t, *edges, k, ell_buckets,
                                      tail_shape, halo_shape, float(slope),
                                      axis_name)
            if not cat:
                with scope("agg_slots"), subscope("att_norm"):
                    c = out.shape[1] // k
                    out = sum(out[:, j * c:(j + 1) * c]
                              for j in range(k)) / k
            with scope("dense"):
                if bias:
                    out = out + p["b"]
                if skip:
                    out = out + h @ p["w_skip"] + p["b_skip"]
            h = fact(out) if i == nl - 1 else act(out)
    return h


# ------------------------------------------------------------------- memory
def executed_slots(plan, tail_shape, halo_shape) -> dict:
    """Slots every chip executes in one pass over the three edge stores: the
    plan's ELL slots, and the tail's and the halo edges' virtual rows."""
    return {"slot_edges": plan.work_counts()["executed"]["slot_edges"],
            "tail_edges": _prod(tail_shape), "halo_edges": _prod(halo_shape)}


def _prod(shape) -> int:
    return 0 if shape is None else shape[0] * shape[1]


def estimate_mhgat_hbm_bytes(plan, fin: int, widths, heads, concat,
                             tail_shape, halo_shape,
                             train: bool = True) -> dict:
    """Per-chip HBM of one fwd+bwd step, itemised from the arrays the layer
    keeps and makes (f32; ``plan.b`` rows, ``plan.r`` halo rows, the
    executed slots of a pass and the virtual rows of the tail and the halo
    edges, ``plan_virtual_rows``'s static shapes):

    * ``rows_kept``: what lives from a layer's forward to its backward — per
      layer the input ``H`` (dW, the skip), ``Z``, ``O``, ``P`` (K·C each),
      the pre-activation (the activation's derivative) and 4K + 4K scalars;
    * ``rows_transient``: the widest layer's backward at its peak — ``g``,
      ``∂Z`` and its accumulator's update, the ``∂H`` product — and the
      virtual rows' own accumulators and destination-side rows;
    * ``halo``: the received tables of the widest layer, both directions;
    * ``slot_temps``: the slot passes' gathered rows, bounded by the
      scan-unroll budget (``_SCAN_LIVE``) or by the widest bucket;
    * ``plan``: index and mask arrays (int32 + int8 per slot, int32 per
      virtual row).

    Compared once with the chip's ``memory_stats()`` in PERF.md §6 (PR 27);
    it is an estimate of what the arrays need, not a calibration."""
    b, r = int(plan.b), int(plan.r)
    slots = sum(executed_slots(plan, tail_shape, halo_shape).values())
    vrows = sum(sh[0] for sh in (tail_shape, halo_shape) if sh is not None)
    shapes = layer_shapes(fin, widths, heads, concat)
    kept = sum(b * 4 * ((f + 3 * k * c + out + 8 * k) if train
                        else 0) for f, k, c, out in shapes)
    fmax = max(k * c for _, k, c, _ in shapes)
    kmax = max(k for _, k, _, _ in shapes)
    transient = (b * 4 * (4 * fmax if train else 3 * fmax)
                 + vrows * 4 * 3 * fmax)
    halo = r * 4 * ((fmax + kmax) + ((fmax + 4 * kmax) if train else 0))
    slot_temps = min(_SCAN_LIVE + 3 * 4 * fmax * b // 4, 3 * 4 * fmax * b)
    plan = 5 * slots + 4 * vrows
    parts = {"rows_kept": kept, "rows_transient": transient, "halo": halo,
             "slot_temps": slot_temps, "plan": plan,
             "features": b * 4 * (fin + 3)}
    parts["total"] = sum(parts.values())
    return parts


# -------------------------------------------------------------- the registry
def model_setup(plan, fin: int, widths, model_args: dict | None,
                request: Request) -> ModelSetup:
    """The ``MODELS`` entry's setup hook (``models/setup.py``): validates
    ``model_args``, refuses what the layer has no form for, and hands the
    shared code the statics, the virtual-row arrays, the exchange's lane
    widths per direction, the parameter count, the memory estimate and the
    ``att.work`` counter."""
    if not plan.symmetric:
        raise ValueError(
            "mhgat's gather-only backward needs a symmetric edge pattern; "
            "this plan is asymmetric (models/mhgat.py)")
    refuse_beyond_full_f32("mhgat", request)
    args = resolve_args(widths, model_args)
    hc = {"heads": args["heads"], "concat": args["concat"]}
    arrays, vshapes = plan_virtual_rows(plan)
    lanes_f, lanes_b = mhgat_exchange_lane_widths(fin, widths, **hc)
    work = executed_slots(plan, **vshapes)
    true = plan.work_counts()["true"]
    counter = {
        "heads": list(hc["heads"]),
        "channels": [c for _, _, c, _ in layer_shapes(fin, widths, **hc)],
        # per chip; every chip executes the padded shapes
        "true_edges_per_pass": [sum(x) for x in zip(*(true[e] for e in (
            "slot_edges", "tail_edges", "halo_edges")))],
        "executed_slots_per_pass": sum(work.values()),
        "virtual_rows": {"tail": vshapes["tail_shape"],
                         "halo": vshapes["halo_shape"]},
        # per layer: the max pass (narrow), the forward and the backward
        # aggregation (whole rows)
        "passes_per_step": {"max": len(widths), "aggregate": 2 * len(widths)},
        "exchange_lanes": {"forward": list(lanes_f),
                           "backward": list(lanes_b)},
        # none where no chip has a halo edge (k = 1)
        "exchanges_per_step": (2 * len(widths)
                               if vshapes["halo_shape"] is not None else 0),
        "head_products": head_products()}
    return ModelSetup(
        plan_fields=MHGAT_PLAN_FIELDS,
        fwd_static={"ell_buckets": plan.ell_buckets, **args, **vshapes},
        init_static=args,
        extra_arrays=arrays,
        mask_fields=("ell_w",),     # attention reads Â's pattern only
        lane_widths=lanes_f, lane_widths_bwd=lanes_b,
        param_count=param_count(fin, widths, **hc, skip=args["skip"],
                                bias=args["bias"]),
        estimate_memory=functools.partial(
            estimate_mhgat_hbm_bytes, plan, fin, widths, **hc, **vshapes),
        counters={"att.work": counter,
                  "slots.work": slot_work(slot_passes(
                      plan, fin, widths, **hc, **vshapes))})
