"""UniMP's graph-transformer layer as published, on the partitioned full-batch
path.

Shi et al., "Masked Label Prediction: Unified Message Passing Model for
Semi-Supervised Classification" (IJCAI 2021, arXiv:2009.03509), in the form
PyTorch Geometric gives it (``TransformerConv(..., beta=True)`` and
``MaskLabel``, ``examples/unimp_arxiv.py``).  Per layer with input ``x``
(n × F), K heads of C channels and ``N(i)`` the pattern of Â WITHOUT i itself
(the layer adds no self loop; the plan's diagonal is masked out)::

    q_i = W_q x_i + b_q   k_j = W_k x_j + b_k   v_j = W_v x_j + b_v  (K·C each)
    e_ij[h] = q_i[h]·k_j[h] / √C      α_ij[h] = softmax_{j∈N(i)} e_ij[h]
    o_i[h] = Σ_j α_ij[h] v_j[h]       (an empty N(i) gives o_i = 0)
    o_i = ‖_h o_i[h]  (hidden)  |  mean_h o_i[h]  (last)
    r_i = W_r x_i + b_r      β_i = sigmoid(w_β·[o_i ‖ r_i ‖ o_i − r_i])
    y_i = β_i r_i + (1 − β_i) o_i
    hidden: x'_i = act(LayerNorm(y_i))      last: logits_i = y_i

The input is ``x⁰_i = h0_i + [i ∈ P] E[label_i]``, ``E`` a (classes × f)
embedding and ``P`` the label-input rows: a seeded Bernoulli draw over the
global rows (``label_rows``), shipped as the 0/1 array ``LABEL_INPUT``.  The
trainer hands the forward the label ids (−1 off P) and takes P out of the
loss rows (``ModelSetup.label_input``).

**The score is a product over channels of a destination row and a source
row**, not a sum of two per-row scalars as in ``models/mhgat.py``, so every
pass gathers whole rows.  Per layer, over mhgat's store set (the ELL slots,
the hub tail's and the halo-source edges' virtual rows: ``mhgat.Store``,
``plan_stores``, ``_all_stores``):

* the max pass gathers ``k_j`` (K·C lanes): ``m_i = max_j e_ij``;
* the forward gathers ``[k_j ‖ v_j]`` (2·K·C) and sums ``exp(e_ij − m_i)``
  and ``exp(e_ij − m_i) v_j``; ``o = num / D``.

**Backward** (``dot_attention``'s custom rule; a symmetric pattern is
required), with ``g = ∂L/∂o`` and ``c_i[h] = g_i[h]·o_i[h]``::

    ∂v_j = Σ_i α_ij g_i                 ∂e_ij = α_ij (g_i·v_j − c_i)
    ∂q_i = Σ_j ∂e_ij k_j / √C           ∂k_j = Σ_i ∂e_ij q_i / √C

On a symmetric pattern row r's slots enumerate exactly the s that aggregate
r, so ONE walk over the slots serves both sums: slot (r, s) adds
``∂e_rs k_s`` to ``∂q_r`` (r as the destination) and ``∂e_sr q_s/√C``,
``α_sr g_s`` to ``∂k_r``, ``∂v_r`` (r as the source).  Each slot gathers one
row of ONE table ``[k ‖ q/√C ‖ g ‖ v ‖ m, 1/D, c]`` (4·K·C + 3K lanes) and
reads row r of the same table; one accumulator ``[∂q ‖ ∂k ‖ ∂v]`` is
``[k_s ‖ q_s/√C ‖ g_s]`` times one spread of ``[∂e_rs ‖ ∂e_sr ‖ α_sr]``, and
each class of virtual rows folds it in one sorted scatter.  A layer whose
K·C is not a whole number of lane tiles keeps two walks over the same slots
(``fused_backward``): the destination's for ``∂q`` gathering ``[k ‖ v]``,
the source's for ``∂k``, ``∂v`` gathering ``[q/√C ‖ g ‖ m, 1/D, c]``.
Every pass gathers one table a slot.  α is recomputed per slot; residuals
are per row (``q/√C, [k ‖ v], m, 1/D, o``), never per edge.  At k > 1 one
``all_to_all`` a layer ships ``[k ‖ v]`` forward (read by the max pass and
the forward, and kept for the backward's halo table) and one ships ``[q/√C
‖ g ‖ m, 1/D, c]`` backward.  The max stays a pass of its own: the
forward's sums are exact once m is known, with no rescale.

Every dense product, and every product by the 0/1 head matrix outside the
forward slot's one-pass spread (``mhgat._spread_heads``: exact), runs at
``Precision.HIGHEST``.

Per-chip code, meant to run inside ``shard_map`` over the 1D vertex mesh.
Refused, loudly: an asymmetric plan, ``comm_schedule='ragged'``, the Pallas
aggregator, stale / replica modes, ``compute_dtype``, mini-batch, serving.
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.tracing import scope, subscope
from ..ops.pspmm import halo_exchange_multi
from ..parallel.mesh import AXIS
from . import mhgat
from .activations import get_activation
from .setup import (ModelSetup, Request, plan_true_edges,
                    refuse_beyond_full_f32, slot_pass, slot_work)

# the plan fields mhgat ships; ``ell_w`` and the tail's mask arrive as 0/1
# masks with the diagonal zeroed (``_masks``)
UNIMP_PLAN_FIELDS = mhgat.MHGAT_PLAN_FIELDS
LABEL_INPUT = "label_in"        # shipped (k, B) int8: 1 on the rows of P
LN_EPS = 1e-5                   # LayerNorm's, torch's default

_HIGHEST = jax.lax.Precision.HIGHEST
LANE_TILE = 128                 # the lanes of a TPU vector register


# ----------------------------------------------------------- configuration
def layer_shapes(fin: int, widths, heads: int) -> list:
    """``(input width, K, C, output width)`` per layer: the hidden layers
    concatenate K heads of ``width ÷ K`` channels, the last averages K heads
    of its full width (the number of classes)."""
    out, f = [], int(fin)
    for i, w in enumerate(widths):
        w, last = int(w), i == len(widths) - 1
        if not last and w % heads:
            raise ValueError(
                f"unimp: a hidden layer of width {w} cannot concatenate "
                f"{heads} heads")
        out.append((f, heads, w if last else w // heads, w))
        f = w
    return out


def resolve_args(widths, model_args: dict | None) -> dict:
    """The configuration's keys, checked against the trainer's ``widths``:
    ``heads`` (one), ``layers`` and ``hidden`` (where given, they must be
    ``len(widths)`` and every hidden width), and ``label_rate`` (0.625, the
    published rate) and ``label_seed`` (0) of the label-input draw."""
    args = dict(model_args or {})
    heads = int(args.pop("heads", 1))
    layers = int(args.pop("layers", len(widths)))
    hidden = args.pop("hidden", None)
    out = {"heads": heads,
           "label_rate": float(args.pop("label_rate", 0.625)),
           "label_seed": int(args.pop("label_seed", 0))}
    if args:
        raise ValueError(f"unimp: unknown model_args {sorted(args)}")
    if layers != len(widths) or len(widths) < 2:
        raise ValueError(
            f"unimp: {layers} layers against {len(widths)} widths (at least "
            "one hidden layer and the output)")
    if hidden is not None and any(int(w) != int(hidden) for w in widths[:-1]):
        raise ValueError(f"unimp: hidden {hidden} against widths {widths}")
    if heads < 1 or not 0.0 <= out["label_rate"] <= 1.0:
        raise ValueError(f"unimp: heads {heads}, label_rate "
                         f"{out['label_rate']}")
    layer_shapes(0, widths, heads)
    return out


def label_rows(n: int, rate: float, seed: int) -> np.ndarray:
    """P, the label-input rows: one seeded Bernoulli(``rate``) draw over the
    ``n`` global rows, fixed for the run — ``jax.random``'s counter-based
    generator, bit-exact on every backend (the plain reference draws it the
    same way)."""
    return np.asarray(jax.random.uniform(jax.random.PRNGKey(seed), (n,))
                      < rate)


def init_unimp_params(rng: jax.Array, dims, heads: int = 1, **_static):
    """As PyG draws them: ``emb`` (classes, f) N(0, 1); per layer the query,
    key, value and skip ``Linear``s, weight and bias U(±1/√fan_in); ``w_beta``
    (3·out, 1), bias-free, U(±1/√(3·out)); LayerNorm ``ln_scale`` 1 and
    ``ln_bias`` 0 on the hidden layers."""
    fin, classes = dims[0][0], dims[-1][1]
    shapes = layer_shapes(fin, [fo for _, fo in dims], heads)
    keys = jax.random.split(rng, len(dims) + 1)

    def lin(key, fi, fo, bias=True):
        bound = 1.0 / math.sqrt(fi)
        kw, kb = jax.random.split(key)
        w = jax.random.uniform(kw, (fi, fo), jnp.float32, -bound, bound)
        if not bias:
            return w
        return w, jax.random.uniform(kb, (fo,), jnp.float32, -bound, bound)

    layers = []
    for i, (key, (f, k, c, out)) in enumerate(zip(keys[1:], shapes)):
        kq, kk, kv, ks, kb = jax.random.split(key, 5)
        p = {}
        for name, kx, fo in (("query", kq, k * c), ("key", kk, k * c),
                             ("value", kv, k * c), ("skip", ks, out)):
            p["w_" + name], p["b_" + name] = lin(kx, f, fo)
        p["w_beta"] = lin(kb, 3 * out, 1, bias=False)
        if i < len(shapes) - 1:
            p["ln_scale"] = jnp.ones((out,), jnp.float32)
            p["ln_bias"] = jnp.zeros((out,), jnp.float32)
        layers.append(p)
    return {"emb": jax.random.normal(keys[0], (classes, fin), jnp.float32),
            "layers": layers}


def param_count(fin: int, widths, heads: int) -> int:
    shapes = layer_shapes(fin, widths, heads)
    total = int(widths[-1]) * int(fin)                  # the label embedding
    for i, (f, k, c, out) in enumerate(shapes):
        total += 3 * (f * k * c + k * c) + f * out + out + 3 * out
        total += 2 * out if i < len(shapes) - 1 else 0
    return total


def exchange_lane_widths(fin: int, widths, heads: int) -> tuple:
    """``(forward, backward)`` f32 lanes of each layer's exchange:
    ``[k ‖ v]`` 2·K·C, ``[q/√C ‖ g ‖ m, 1/D, c]`` 2·K·C + 3K (the backward
    walk's halo table joins the two)."""
    shapes = layer_shapes(fin, widths, heads)
    return (tuple(2 * k * c for _, k, c, _ in shapes),
            tuple(2 * k * c + 3 * k for _, k, c, _ in shapes))


def fused_backward(k: int, c: int) -> bool:
    """Whether a layer of K heads of C channels walks its backward ONCE:
    where every block of its row ``[k ‖ q/√C ‖ g ‖ v ‖ m, 1/D, c]`` starts
    on a lane tile (K·C a multiple of ``LANE_TILE``).  Otherwise the v5e's
    compiler lays the table out transposed and copies it back whole inside
    every scanned bucket, whatever the row's width (K·C = 8, 136 and 188
    alike; 128 and 256 not: compiled steps, ``PERF.md`` §6) — +0.55 GB of
    temporaries for the products cell's last layer (C = 47) — so such a
    layer keeps the two walks."""
    return (k * c) % LANE_TILE == 0


def pass_lanes(k: int, c: int) -> dict:
    """f32 lanes a slot of each pass of one layer gathers: the max pass
    ``k``, the forward ``[k ‖ v]``, and the backward's one walk ``bwd``
    ``[k ‖ q/√C ‖ g ‖ v ‖ m, 1/D, c]`` (``fused_backward``) or its two,
    ``dst`` ``[k ‖ v]`` and ``src`` ``[q/√C ‖ g ‖ m, 1/D, c]``."""
    f = k * c
    if fused_backward(k, c):
        return {"max": f, "fwd": 2 * f, "bwd": 4 * f + 3 * k}
    return {"max": f, "fwd": 2 * f, "dst": 2 * f, "src": 2 * f + 3 * k}


# ------------------------------------------------------- the diagonal masks
def _slot_rows(buckets) -> np.ndarray:
    """The destination row of every flat slot of a width-major ELL layout
    (bucket after bucket; slot t of a bucket's row r at ``off + t·nb + r``)."""
    parts, row0 = [], 0
    for nb, wb in buckets:
        parts.append(np.tile(np.arange(row0, row0 + nb, dtype=np.int32), wb))
        row0 += nb
    return (np.concatenate(parts) if parts else np.zeros(0, np.int32))


def _masks(plan, vrows: dict, tail_shape) -> tuple:
    """``(ell_mask, tail_mask, diagonal)``: the 0/1 int8 masks of the ELL
    slots and of the tail's virtual rows with every diagonal entry zeroed,
    and the real diagonal slots each chip had (halo-source edges never hold
    one)."""
    ell = plan.ell_idx != _slot_rows(plan.ell_buckets)[None]
    ell_mask = (plan.ell_w != 0) & ell
    diag = (plan.ell_w != 0).sum(axis=1) - ell_mask.sum(axis=1)
    tail_mask = None
    if tail_shape is not None:
        nv, wd = tail_shape
        dst = np.tile(vrows["vt_row"], (1, wd))        # slot t·nv + v -> row v
        tail_mask = (vrows["vt_mask"] != 0) & (vrows["vt_idx"] != dst)
        diag = diag + (vrows["vt_mask"] != 0).sum(axis=1) - tail_mask.sum(
            axis=1)
    return (ell_mask.astype(np.int8),
            None if tail_mask is None else tail_mask.astype(np.int8),
            [int(x) for x in diag])


# ------------------------------------------------------------- aggregation
def attend(qs, k_, kv, kvh, stores, heads, rows=None):
    """The forward over one store set: the max pass over ``k`` and the
    aggregation over ``[k ‖ v]`` (``kvh``: the halo rows, ``None`` where no
    store reads them).  ``qs`` is ``q / √C``.  Returns ``o`` (B, K·C) and
    ``(m, 1/D)``."""
    f, k = qs.shape[1], heads
    kh = None if kvh is None else kvh[:, :f]

    def score(q_i, k_j):
        return mhgat._dot_heads(q_i, k_j, k)

    m = mhgat._all_stores(
        (k_,), (kh,), (qs,), stores,
        contrib=lambda tabs, idx, w, dst: jnp.where(
            (w != 0)[:, None],
            score(dst[0], jnp.take(tabs[0], idx, axis=0)), mhgat._NEG),
        init=lambda nb: jnp.full((nb, k), mhgat._NEG, jnp.float32),
        slot_bytes=mhgat._agg_slot_bytes(f), combine=jnp.maximum,
        sub="xatt_max", rows=rows)

    def edge(tabs, idx, w, dst):
        (tab,), (q_i, m_i) = tabs, dst
        kv_j = jnp.take(tab, idx, axis=0)
        p = jnp.where((w != 0)[:, None],
                      jnp.exp(score(q_i, kv_j[:, :f]) - m_i), 0.0)
        spread = mhgat._spread_heads(p, f) if k > 1 else p
        return kv_j[:, f:] * spread, p

    num, den = mhgat._all_stores(
        (kv,), (kvh,), (qs, m), stores, contrib=edge,
        init=lambda nb: (jnp.zeros((nb, f), jnp.float32),
                         jnp.zeros((nb, k), jnp.float32)),
        slot_bytes=mhgat._agg_slot_bytes(2 * f), sub="xatt_fwd", rows=rows)
    with scope("agg_slots"), subscope("xatt_fwd"):
        dinv = 1.0 / jnp.maximum(den, mhgat._TINY)
        out = mhgat._scale_heads(dinv, num)
    return out, (m, dinv)


def attend_bwd(g, qs, kv, kvh, m, dinv, out, stores, exchange, heads,
               scale: float, rows=None):
    """The backward of ``attend`` (module docstring): ONE walk over the
    slots where the layer's shape fuses (``fused_backward``), else the
    destination pass for ``∂q`` and the source pass for ``∂k``, ``∂v``.  At
    k > 1 the halo rows of ``[q/√C ‖ g ‖ m, 1/D, c]`` come through
    ``exchange(parts) -> parts`` (``None`` where no store reads them).
    Returns ``(∂q, ∂k, ∂v)``."""
    f, k = qs.shape[1], heads
    with scope("agg_slots"), subscope("xatt_src"):
        c = mhgat._dot_heads(g, out, k)
    walk = _walk_once if fused_backward(k, f // k) else _walk_twice
    return walk(g, qs, kv, kvh, m, dinv, c, stores, exchange, k, scale, rows)


def _alpha(q_i, k_j, m_i, dinv_i, w, k: int):
    return jnp.where((w != 0)[:, None],
                     jnp.exp(mhgat._dot_heads(q_i, k_j, k) - m_i) * dinv_i,
                     0.0)


def _scalars(t, lo: int, k: int):
    """The 3K scalars ``[m, 1/D, c]`` at lanes ``lo:`` of a table's rows, by
    an exact 0/1 product, not a slice: a slice ties a whole gathered row to
    the narrow arrays' transposed layout."""
    s = jnp.dot(t[:, lo:], jnp.eye(3 * k, dtype=t.dtype), precision=_HIGHEST)
    return s[:, :k], s[:, k:2 * k], s[:, 2 * k:]


def _walk_once(g, qs, kv, kvh, m, dinv, c, stores, exchange, k: int,
               scale: float, rows):
    """One walk: slot (r, s) gathers row s of ``[k ‖ q/√C ‖ g ‖ v ‖ m, 1/D,
    c]`` and reads row r of the same table, its scalars from ``m``, ``1/D``
    and ``c`` themselves (no product a slot); ``[∂q ‖ ∂k ‖ ∂v]`` is ``[k_s
    ‖ q_s/√C ‖ g_s]`` times one spread of ``[∂e_rs ‖ ∂e_sr ‖ α_sr]``."""
    f = qs.shape[1]
    with scope("agg_slots"), subscope("xatt_src"):
        # (B, 4F + 3K): the last use of ``kv``
        tab = jnp.concatenate([kv[:, :f], qs, g, kv[:, f:], m, dinv, c],
                              axis=1)
        qgs = jnp.concatenate([qs, g, m, dinv, c], axis=1) if exchange \
            else None
    tabh = None
    if exchange:
        (qgsh,) = exchange((qgs,))
        with scope("agg_slots"), subscope("xatt_src"):
            tabh = jnp.concatenate([kvh[:, :f], qgsh[:, :2 * f], kvh[:, f:],
                                    qgsh[:, 2 * f:]], axis=1)

    def edge(tabs, idx, w, dst):
        (tab_,), (tab_r, m_r, dinv_r, c_r) = tabs, dst
        # the plan's indices are in bounds, padding included: "clip" gathers
        # without "fill"'s select over every gathered row
        row = jnp.take(tab_, idx, axis=0, mode="clip")
        k_s, q_s, g_s, v_s = (row[:, i * f:(i + 1) * f] for i in range(4))
        k_r, q_r, g_r, v_r = (tab_r[:, i * f:(i + 1) * f] for i in range(4))
        m_s, dinv_s, c_s = _scalars(row, 4 * f, k)
        # r as the destination: ∂q_r = Σ_s ∂e_rs k_s / √C
        a_rs = _alpha(q_r, k_s, m_r, dinv_r, w, k)
        de_rs = a_rs * (mhgat._dot_heads(g_r, v_s, k) - c_r)
        # r as the source, s aggregating it: ∂k_r = Σ_s ∂e_sr q_s / √C,
        # ∂v_r = Σ_s α_sr g_s
        a_sr = _alpha(q_s, k_r, m_s, dinv_s, w, k)
        de_sr = a_sr * (mhgat._dot_heads(g_s, v_r, k) - c_s)
        return row[:, :3 * f] * _spread_blocks((de_rs, de_sr, a_sr), f)

    acc = mhgat._all_stores(
        (tab,), (tabh,), (tab, m, dinv, c), stores, contrib=edge,
        init=lambda nb: jnp.zeros((nb, 3 * f), jnp.float32),
        slot_bytes=mhgat._agg_slot_bytes(4 * f + 3 * k), sub="xatt_src",
        rows=rows)
    with scope("agg_slots"), subscope("xatt_src"):
        return acc[:, :f] * scale, acc[:, f:2 * f], acc[:, 2 * f:]


def _walk_twice(g, qs, kv, kvh, m, dinv, c, stores, exchange, k: int,
                scale: float, rows):
    """Two walks: over the destination's slots for ``∂q``, gathering ``[k ‖
    v]``; over the source's own for ``∂k``, ``∂v``, gathering ONE table
    ``[q/√C ‖ g ‖ m, 1/D, c]``."""
    f = qs.shape[1]
    with scope("agg_slots"), subscope("xatt_src"):
        qgs = jnp.concatenate([qs, g, m, dinv, c], axis=1)   # (B, 2F + 3K)

    def edge_dst(tabs, idx, w, dst):
        (tab,), (q_i, m_i, dinv_i, g_i, c_i) = tabs, dst
        kv_j = jnp.take(tab, idx, axis=0)
        k_j, v_j = kv_j[:, :f], kv_j[:, f:]
        a = _alpha(q_i, k_j, m_i, dinv_i, w, k)
        de = a * (mhgat._dot_heads(g_i, v_j, k) - c_i)
        return mhgat._scale_heads(de, k_j)

    dq = mhgat._all_stores(
        (kv,), (kvh,), (qs, m, dinv, g, c), stores, contrib=edge_dst,
        init=lambda nb: jnp.zeros((nb, f), jnp.float32),
        slot_bytes=mhgat._agg_slot_bytes(2 * f), sub="xatt_dst", rows=rows)
    with scope("agg_slots"), subscope("xatt_dst"):
        dq = dq * scale
    (qgsh,) = exchange((qgs,)) if exchange else (None,)

    def edge_src(tabs, idx, w, dst):
        (tab,), (kv_j,) = tabs, dst
        row = jnp.take(tab, idx, axis=0)
        qg_i = row[:, :2 * f]
        m_i, dinv_i, c_i = _scalars(row, 2 * f, k)
        a = _alpha(qg_i[:, :f], kv_j[:, :f], m_i, dinv_i, w, k)
        de = a * (mhgat._dot_heads(qg_i[:, f:], kv_j[:, f:], k) - c_i)
        # [∂e ‖ α] spread over [q ‖ g]'s lanes in ONE product
        return qg_i * _spread_blocks((de, a), f)

    dkv = mhgat._all_stores(
        (qgs,), (qgsh,), (kv,), stores, contrib=edge_src,
        init=lambda nb: jnp.zeros((nb, 2 * f), jnp.float32),
        slot_bytes=mhgat._agg_slot_bytes(2 * f + 3 * k), sub="xatt_src",
        rows=rows)
    return dq, dkv[:, :f], dkv[:, f:]


def _spread_blocks(cols, f: int):
    """``[a ‖ b ‖ …]`` (n, J·K) → (n, J·K·C): column h of the j-th block
    over head h's lanes of the j-th K·C — one ``HIGHEST`` product by a
    block-diagonal 0/1 matrix (exact: each output is one input)."""
    lanes = mhgat._head_lanes(cols[0].shape[1], f)
    block = jnp.kron(jnp.eye(len(cols), dtype=lanes.dtype), lanes)
    return jnp.dot(jnp.concatenate(cols, axis=1), block, precision=_HIGHEST)


@partial(jax.custom_vjp, nondiff_argnums=(13, 14, 15, 16, 17))
def dot_attention(q, k_, v, send_idx, halo_src, ell_idx, ell_w,
                  vt_idx, vt_mask, vt_row, vh_idx, vh_mask, vh_row,
                  heads, buckets, tail_shape, halo_shape, axis_name=AXIS):
    """``o_i = Σ_{j∈N(i)} softmax_j(q_i·k_j / √C) v_j`` per head: ``q``,
    ``k``, ``v`` (B, K·C) in, ``o`` (B, K·C) out; gather passes only, forward
    and backward (module docstring)."""
    return _attention_fwd(q, k_, v, send_idx, halo_src, ell_idx, ell_w,
                          vt_idx, vt_mask, vt_row, vh_idx, vh_mask, vh_row,
                          heads, buckets, tail_shape, halo_shape,
                          axis_name)[0]


def _attention_fwd(q, k_, v, send_idx, halo_src, ell_idx, ell_w,
                   vt_idx, vt_mask, vt_row, vh_idx, vh_mask, vh_row,
                   heads, buckets, tail_shape, halo_shape, axis_name):
    pa = (ell_idx, ell_w, vt_idx, vt_mask, vt_row, vh_idx, vh_mask, vh_row)
    scale = 1.0 / math.sqrt(q.shape[1] // heads)
    with scope("agg_slots"), subscope("xatt_fwd"):
        qs = q * scale
        kv = jnp.concatenate([k_, v], axis=1)
    # no halo edge on any chip: nothing reads a halo table, nothing is sent
    (kvh,) = (halo_exchange_multi((kv,), send_idx, halo_src, axis_name)
              if halo_shape is not None else (None,))
    out, (m, dinv) = attend(
        qs, k_, kv, kvh, mhgat.plan_stores(pa, buckets, tail_shape,
                                           halo_shape), heads)
    res = (qs, kv, kvh, m, dinv, out, send_idx, halo_src) + pa
    return out, res


def _attention_bwd(heads, buckets, tail_shape, halo_shape, axis_name, res, g):
    qs, kv, kvh, m, dinv, out, send_idx, halo_src, *pa = res
    scale = 1.0 / math.sqrt(qs.shape[1] // heads)
    dq, dk, dv = attend_bwd(
        g, qs, kv, kvh, m, dinv, out,
        mhgat.plan_stores(tuple(pa), buckets, tail_shape, halo_shape),
        (lambda parts: halo_exchange_multi(parts, send_idx, halo_src,
                                           axis_name))
        if halo_shape is not None else None, heads, scale)
    return (dq, dk, dv) + (None,) * 10


dot_attention.defvjp(_attention_fwd, _attention_bwd)


# ------------------------------------------------------------------ forward
def _dense(x, w, b=None):
    y = jnp.dot(x, w, precision=_HIGHEST)
    return y if b is None else y + b


def unimp_forward_local(
    params,
    h,                            # (B, fin) local rows
    pa,                           # plan arrays dict (UNIMP_PLAN_FIELDS)
    activation: str = "relu",
    final_activation: str = "none",
    symmetric: bool = False,
    ell_buckets: tuple | None = None,   # static plan.ell_buckets
    tail_shape: tuple | None = None,    # static (nv, W) of the tail's and
    halo_shape: tuple | None = None,    # the halo edges' virtual rows
    heads: int = 1,               # static: K of every layer
    label_ids=None,               # (B,) int32: the label of a row of P, -1
    comm_schedule: str = "a2a",
    axis_name: str = AXIS,
    halo_carry=None,
):
    """Per-chip forward: the label input, then the stacked graph-transformer
    layers (module docstring)."""
    if halo_carry is not None:
        raise NotImplementedError(
            "stale-halo pipelining is implemented for the GCN hot path "
            "only; run unimp with halo_staleness=0")
    if not symmetric:
        raise ValueError(
            "unimp's backward reads row j's slots as the rows that aggregate "
            "j, which holds for a symmetric edge pattern only; this plan is "
            "asymmetric and the layer refuses it")
    if comm_schedule != "a2a":
        raise ValueError(
            f"unimp ships its tables over the dense all_to_all only, not "
            f"comm_schedule={comm_schedule!r}")
    if ell_buckets is None or label_ids is None:
        raise ValueError(
            "unimp forward needs the plan's static ell_buckets and the "
            "label ids of its input rows (FullBatchTrainer hands both)")
    act = get_activation(activation)
    fact = get_activation(final_activation)
    layers = params["layers"]
    edges = (tuple(pa[f] for f in UNIMP_PLAN_FIELDS)
             + tuple(pa.get(f) for f in mhgat.MHGAT_VROW_ARRAYS))
    x = h
    for i, p in enumerate(layers):
        last = i == len(layers) - 1
        with scope("layer", i):
            with scope("dense"), subscope("xatt_project"):
                if i == 0:
                    # x⁰ = h0 + [i ∈ P] E[label_i]: a one-hot product (the
                    # one-hot of -1 is zero), whose transpose sums the rows
                    # of each class without a scatter
                    onehot = jax.nn.one_hot(label_ids, params["emb"].shape[0],
                                            dtype=jnp.float32)
                    x = x + _dense(onehot, params["emb"])
                q = _dense(x, p["w_query"], p["b_query"])
                k_ = _dense(x, p["w_key"], p["b_key"])
                v = _dense(x, p["w_value"], p["b_value"])
                r = _dense(x, p["w_skip"], p["b_skip"])
            o = dot_attention(q, k_, v, *edges, heads, ell_buckets,
                              tail_shape, halo_shape, axis_name)
            with scope("dense"), subscope("xatt_project"):
                if last:
                    c = o.shape[1] // heads
                    o = sum(o[:, j * c:(j + 1) * c]
                            for j in range(heads)) / heads
                beta = jax.nn.sigmoid(_dense(
                    jnp.concatenate([o, r, o - r], axis=1), p["w_beta"]))
                y = beta * r + (1.0 - beta) * o
            if last:
                x = fact(y)
                continue
            with scope("dense"), subscope("xatt_norm"):
                mu = jnp.mean(y, axis=1, keepdims=True)
                var = jnp.mean(jnp.square(y - mu), axis=1, keepdims=True)
                y = ((y - mu) * jax.lax.rsqrt(var + LN_EPS) * p["ln_scale"]
                     + p["ln_bias"])
                x = act(y)
    return x


# ------------------------------------------------------------------- memory
def estimate_unimp_hbm_bytes(plan, fin: int, widths, heads: int,
                             tail_shape, halo_shape,
                             train: bool = True) -> dict:
    """Per-chip HBM of one fwd+bwd step, itemised from the arrays the layer
    keeps and makes (f32; ``plan.b`` rows, ``plan.r`` halo rows):

    * ``rows_kept``: per layer the input, ``q/√C``, ``[k ‖ v]``, ``o``,
      ``m``, ``1/D``, the skip, the gate, ``y``, LayerNorm's statistics and
      its output;
    * ``rows_transient``: the widest layer's backward — ``g``, the
      accumulators ``[∂q ‖ ∂k ‖ ∂v]`` and the table its walk gathers, ``[k
      ‖ q/√C ‖ g ‖ v ‖ m, 1/D, c]`` where it walks once, else ``[q/√C ‖ g
      ‖ m, 1/D, c]`` — and the virtual rows' accumulators and their
      destination-side rows;
    * ``halo``: the received tables of the widest layer, both directions,
      and a fused walk's halo table;
    * ``slot_temps``: the slot passes' gathered rows, bounded by the
      scan-unroll budget or by the widest bucket;
    * ``plan``: index and mask arrays.

    An estimate of what the arrays need, not a calibration."""
    b, r = int(plan.b), int(plan.r)
    slots = sum(mhgat.executed_slots(plan, tail_shape, halo_shape).values())
    vrows = sum(sh[0] for sh in (tail_shape, halo_shape) if sh is not None)
    shapes = layer_shapes(fin, widths, heads)
    kept = sum(b * 4 * (f + 4 * k * c + 2 * k + 3 * out + 3)
               for f, k, c, out in shapes) if train else 0
    fmax = max(k * c for _, k, c, _ in shapes)
    # per layer the widest row its backward gathers, and the one table of a
    # fused walk (its halo rows too)
    walks = [(max(pass_lanes(k, c).values()), k * c, k, fused_backward(k, c))
             for _, k, c, _ in shapes]
    lanes = max(row for row, _, _, _ in walks)
    if train:
        transient = max(b * 4 * (4 * f + row) + vrows * 4 * (3 * f + row)
                        for row, f, _, _ in walks)
        halo = max(r * 4 * (4 * f + 3 * k + (row if fused else 0))
                   for row, f, k, fused in walks)
    else:
        transient, halo = b * 4 * 3 * fmax, r * 4 * 2 * fmax
    slot_temps = min(mhgat._SCAN_LIVE + 3 * 4 * lanes * b // 4,
                     3 * 4 * lanes * b)
    parts = {"rows_kept": kept, "rows_transient": transient, "halo": halo,
             "slot_temps": slot_temps, "plan": 5 * slots + 4 * vrows,
             "features": b * 4 * (fin + 4)}
    parts["total"] = sum(parts.values())
    return parts


# ------------------------------------------------------------ the counters
# ``pass_lanes``' keys: the pass's direction and its sub-scope
_PASS_TAGS = {"max": ("fwd", "xatt_max"), "fwd": ("fwd", "xatt_fwd"),
              "bwd": ("bwd", "xatt_src"), "dst": ("bwd", "xatt_dst"),
              "src": ("bwd", "xatt_src")}


def slot_passes(plan, fin: int, widths, heads: int, tail_shape, halo_shape,
                true_edges) -> list:
    """The step's pass list for ``slots.work``: per layer the max pass (tag
    ``xatt_max``), the forward (``xatt_fwd``) and the backward's one walk
    (``xatt_src``, 4·K·C + 3K lanes) or, in a layer that does not fuse, its
    two (``xatt_dst``, ``xatt_src``), each over the ELL buckets and the one
    class of the tail's and of the halo edges' virtual rows, in the form
    ``mhgat._store_reduce`` runs them."""
    stores = mhgat.plan_stores((None,) * 8, plan.ell_buckets, tail_shape,
                               halo_shape)
    passes = []
    for layer, (_, k, c, _) in enumerate(layer_shapes(fin, widths, heads)):
        for key, lanes in pass_lanes(k, c).items():
            way, tag = _PASS_TAGS[key]
            passes.append(slot_pass(
                layer, way, lanes,
                mhgat.store_forms(stores, mhgat._agg_slot_bytes(lanes)),
                tags=(tag,), true_edges=true_edges))
    return passes


def _work(plan, fin, widths, heads, vshapes, true, diag) -> dict:
    """The program counter ``xatt.work``: per layer heads, channels, the
    lanes a slot of each pass gathers (the backward's one walk under
    ``bwd``) and whether the backward walks the slots once
    (``fused_backward``); the tables each pass gathers a slot; per chip the
    true edges of a pass (the diagonal masked out) and the diagonal slots
    masked; the executed slots of a pass, the passes a step, the exchange's
    lanes, and the lanes gathered an epoch over the true edges
    (``lanes_per_epoch`` ÷ ``true_edges_per_pass`` = Σ of every pass's
    lanes)."""
    shapes = layer_shapes(fin, widths, heads)
    lanes = [pass_lanes(k, c) for _, k, c, _ in shapes]
    per_edge = sum(sum(x.values()) for x in lanes)
    lanes_f, lanes_b = exchange_lane_widths(fin, widths, heads)
    return {
        "heads": [k for _, k, _, _ in shapes],
        "channels": [c for _, _, c, _ in shapes],
        "lanes_per_pass": lanes,
        "fused_backward": [fused_backward(k, c) for _, k, c, _ in shapes],
        "gathers_per_slot": {key: 1 for x in lanes for key in x},
        "true_edges_per_pass": list(true),
        "masked_diagonal_slots": list(diag),
        "executed_slots_per_pass": sum(mhgat.executed_slots(
            plan, **vshapes).values()),
        "passes_per_step": {"max": len(shapes), "aggregate": len(shapes),
                            "backward": sum(len(x) - 2 for x in lanes)},
        "lanes_per_edge": per_edge,
        "lanes_per_epoch": [per_edge * t for t in true],
        "exchange_lanes": {"forward": list(lanes_f),
                           "backward": list(lanes_b)},
        "exchanges_per_step": (2 * len(shapes)
                               if vshapes["halo_shape"] is not None else 0)}


# -------------------------------------------------------------- the registry
def model_setup(plan, fin: int, widths, model_args: dict | None,
                request: Request) -> ModelSetup:
    """The ``MODELS`` entry's setup hook (``models/setup.py``): validates
    ``model_args``, refuses what the layer has no form for, and hands the
    shared code the statics, the diagonal-free masks and the virtual rows,
    the label-input rows, the exchange's lanes, the parameter count, the
    memory estimate and the counters ``xatt.work`` and ``slots.work``."""
    if not plan.symmetric:
        raise ValueError(
            "unimp's gather-only backward needs a symmetric edge pattern; "
            "this plan is asymmetric (models/unimp.py)")
    refuse_beyond_full_f32("unimp", request)
    args = resolve_args(widths, model_args)
    heads = args["heads"]
    arrays, vshapes = mhgat.plan_virtual_rows(plan)
    ell_mask, tail_mask, diag = _masks(plan, arrays, vshapes["tail_shape"])
    arrays["ell_w"] = ell_mask
    if tail_mask is not None:
        arrays["vt_mask"] = tail_mask
    in_p = label_rows(plan.n, args["label_rate"], args["label_seed"])
    arrays[LABEL_INPUT] = plan.scatter_rows(in_p.astype(np.int8))[..., 0]
    true = [t - d for t, d in zip(plan_true_edges(plan), diag)]
    lanes_f, lanes_b = exchange_lane_widths(fin, widths, heads)
    return ModelSetup(
        plan_fields=UNIMP_PLAN_FIELDS,
        fwd_static={"ell_buckets": plan.ell_buckets, "heads": heads,
                    **vshapes},
        init_static={"heads": heads},
        extra_arrays=arrays,
        mask_fields=(),             # ell_w arrives as a mask above
        lane_widths=lanes_f, lane_widths_bwd=lanes_b,
        param_count=param_count(fin, widths, heads),
        estimate_memory=functools.partial(
            estimate_unimp_hbm_bytes, plan, fin, widths, heads, **vshapes),
        counters={"xatt.work": _work(plan, fin, widths, heads, vshapes,
                                     true, diag),
                  "slots.work": slot_work(slot_passes(
                      plan, fin, widths, heads, **vshapes,
                      true_edges=true))},
        label_input=LABEL_INPUT)
