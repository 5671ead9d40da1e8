"""Partitioned SpMM with halo exchange — the framework's core distributed op.

Reference semantics being reproduced (TPU-first, not translated):

  * ``PSpMM`` autograd op: forward = halo exchange then local sparse matmul,
    backward = transposed matmul then the reversed exchange
    (``GPU/PGCN.py:121-134``; MPI flavor ``Parallel-GCN/main.c:233-316`` fwd,
    ``:338-438`` bwd).
  * The exchange ships owned boundary feature rows to exactly the chips whose
    local nonzeros reference them (``GPU/PGCN.py:85-119``).

TPU design:

  * every function here is **per-chip code** meant to run inside
    ``jax.shard_map`` over a 1D mesh axis (default ``'v'``);
  * the ragged P2P protocol becomes one static ``lax.all_to_all`` of a
    ``(k, S, f)`` buffer (S = padded per-peer bucket, see
    ``sgcn_tpu.parallel.plan``) — riding ICI, no ordering protocol needed;
  * local SpMM is a padded-edge-list segment-sum over the concatenated
    ``[local; halo]`` row table: dense gathers + one ``segment_sum``, which XLA
    fuses; padding edges carry weight 0 so they contribute nothing;
  * no ``custom_vjp`` is required: JAX transposes ``all_to_all`` to the reverse
    all_to_all, gathers to scatter-adds, and the segment-sum to a gather —
    yielding exactly the reference's swapped send/recv backward plan
    (``GPU/PGCN.py:93-97``) with ``Âᵀ`` (= ``Â``, symmetric) aggregation.
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.tracing import (bucket_scope, in_leaf_scope, pair_scope, scope,
                           subscope)
from ..parallel.mesh import AXIS

# bound on the gather temps XLA's latency-hiding scheduler can keep live
# concurrently on the unrolled path (it overlaps up to ~16 slots); above it
# the bucketed slot reduce switches to a lax.scan over width slots, whose
# unroll factor is derived from _SCAN_LIVE_LIMIT so scan liveness stays
# bounded too
_CONCURRENT_TEMP_LIMIT = 3 * 1024**3 // 2
_SCHED_OVERLAP_SLOTS = 16
_SCAN_LIVE_LIMIT = 3 * 1024**3


def _scan_unroll(wb: int, slot_bytes: int, live_limit: int,
                 scanned: bool = False) -> int | None:
    """How ``bucketed_slot_reduce`` runs a bucket of ``wb`` slots of
    ``slot_bytes``: ``None`` for the unrolled branch, else the unroll factor
    of its scan — the LARGEST (≤ 4) whose live temporaries fit
    ``live_limit``."""
    if wb <= 2 or (not scanned and min(wb, _SCHED_OVERLAP_SLOTS) * slot_bytes
                   <= _CONCURRENT_TEMP_LIMIT):
        return None
    # cap 8 measured OOM at ogbn-products f32 (16.59/15.75 GB): the budget
    # models only slot temps, and the rest of the epoch program leaves
    # < _SCAN_LIVE_LIMIT of true headroom there
    return max(1, min(4, live_limit // max(slot_bytes, 1)))


def _in_leaf(named, *args):
    """``named(*args)`` inside a leaf scope, nothing outside one: a caller
    outside every leaf scope (the factorised GAT, the micro-benchmarks)
    names nothing — its ops read ``unscoped`` anyway."""
    return named(*args) if in_leaf_scope() else contextlib.nullcontext()


def bucket_forms(buckets, slot_bytes, scan_live_limit: int | None = None,
                 scanned: bool = False) -> list:
    """The form ``bucketed_slot_reduce`` runs each bucket of ``buckets`` in
    under the same arguments: ``None`` for the unrolled branch, else the
    unroll factor of its scan.  THE one decision — the reduce runs it, the
    bucket's scope is named by it (``obs.tracing.bucket_scope``) and the
    counter ``slots.work`` lists it (``models/setup.py``)."""
    live_limit = (_SCAN_LIVE_LIMIT if scan_live_limit is None
                  else scan_live_limit)
    return [_scan_unroll(wb, slot_bytes(nb), live_limit, scanned)
            for nb, wb in buckets]


def ell_policy(lanes: int, scan_live_limit: int | None = None) -> dict:
    """How an ELL store of ``lanes``-wide rows goes through
    ``bucketed_slot_reduce`` (and ``bucket_forms``): a slot's temporaries
    are its gathered rows at 4 B a lane, unrolled while they fit."""
    return {"slot_bytes": lambda nb: nb * lanes * 4,
            "scan_live_limit": scan_live_limit}


def bucketed_slot_reduce(flat_idx, flat_w, buckets, contrib, init,
                         slot_bytes, scan_live_limit: int | None = None,
                         combine=jnp.add, with_rows: bool = False,
                         scanned: bool = False):
    """Σ over width slots of ``contrib(idx_t, w_t)`` per bucket — THE shared
    memory policy for every bucketed width-major layout (GCN SpMM, GAT
    attention passes).

    Unrolled while the scheduler's concurrent gather temps
    (``min(wb, _SCHED_OVERLAP_SLOTS) · slot_bytes(nb)``) fit the budget —
    each slot's gather fuses into its add; above it (ogbn-products-scale
    buckets: tens of multi-hundred-MB temps measured as 17+ GB of HLO temps
    on a 16 GB chip) a ``lax.scan`` serializes the slots.  The scan body is
    software-pipelined with the LARGEST unroll whose live temps still fit
    ``_SCAN_LIVE_LIMIT`` (≤4), so liveness stays provably bounded for every
    bucket shape; what a slot costs in either form, by bucket width, is the
    benchmark's ``ell_slot_ns`` / ``fold_slot_ns`` and its ``slot_prices``
    table, per cell (PERF.md §5) — and a bucket's ROW COUNT decides more
    than its form: modulo 1,024, a count of 0 or past 896 doubles the price
    of every slot (``parallel.plan.snap_rows`` chooses the plan's shapes
    off those residues; forced shapes run as given).  The width-major flat
    layout makes each slot a contiguous ``(nb,)`` run, so the ``(wb, nb)``
    reshape moves nothing row-major; under the TPU's tiled layout the
    compiler still copies it, every step (~0.04 s an epoch at products
    scale, PERF.md §6, PR 25).

    Inside a leaf scope every bucket is named in the compiled step:
    ``sgcn.bkt_<nb>x<wb>_u`` around the unrolled slots and their
    accumulates, ``..._s<unroll>`` around the reshapes, the carry and the
    scan (``obs.tracing.bucket_scope``; metadata only) — with the unroll
    ``bucket_forms`` returned, so the name cannot disagree with the form
    that ran.

    ``contrib(idx (nb,), w (nb,)) -> pytree of (nb, ...) f32 arrays``;
    ``init(nb)`` builds the matching zero pytree; ``slot_bytes(nb)``
    estimates one slot's gather-temp bytes.  ``scan_live_limit`` lowers the
    scan-unroll liveness budget below the default — for callers that run
    SEVERAL slot reduces in one program (the GAT num/den passes): at
    products scale each pass unrolling to the full budget measured as the
    difference between fitting and a 264 MB OOM.  ``combine`` is the
    reduction (``jnp.maximum`` for the attention layer's max pass, with an
    ``init`` of its identity); ``with_rows=True`` hands ``contrib`` and
    ``init`` the bucket's first output row as a third / second argument
    (a static int: the attention passes slice their per-destination
    scalars by it).  ``scanned=True`` takes the scan form for every bucket
    wider than two slots, whatever its size (the fold passes of
    ``fold_slots``).  Returns the per-bucket reduced pytrees in bucket order.
    """
    forms = bucket_forms(buckets, slot_bytes, scan_live_limit, scanned)
    outs = []
    off = row = 0
    for (nb, wb), unroll in zip(buckets, forms):
        at = (row,) if with_rows else ()
        with _in_leaf(bucket_scope, nb, wb, unroll):
            if unroll is None:
                acc = None
                for t in range(wb):
                    seg = slice(off + t * nb, off + (t + 1) * nb)
                    c = contrib(flat_idx[seg], flat_w[seg], *at)
                    acc = c if acc is None else jax.tree.map(combine, acc, c)
            else:
                seg_i = flat_idx[off: off + nb * wb].reshape(wb, nb)
                seg_w = flat_w[off: off + nb * wb].reshape(wb, nb)
                # carry must match the body output's varying-axes type under
                # shard_map; adding 0·(an int32 element of the sharded index
                # array) marks the zeros varying — integer 0·x is exactly 0,
                # so (unlike 0·h[0,0]) an inf/NaN activation cannot poison it
                zero = seg_i[0, 0] * 0

                def body(carry, iw, at=at):
                    i_t, w_t = iw
                    return jax.tree.map(combine, carry,
                                        contrib(i_t, w_t, *at)), None

                acc0 = jax.tree.map(lambda x: x + zero.astype(x.dtype),
                                    init(nb, *at))
                acc, _ = jax.lax.scan(body, acc0, (seg_i, seg_w),
                                      unroll=unroll)
        outs.append(acc)
        off += nb * wb
        row += nb
    return outs


def halo_exchange(h, send_idx, halo_src, axis_name: str = AXIS,
                  halo_dtype=None):
    """Exchange boundary rows; return this chip's halo row block.

    Args:
      h: (B, f) local feature rows.
      send_idx: (k, S) local row indices to ship to each peer (receivers
        never gather a bucket's padded slots; they name distinct in-bounds
        rows, ``parallel.plan.padding_rows``, because a send-side gather of
        one row S times over costs twice what distinct rows cost).
      halo_src: (R,) flat indices into the received (k*S, f) buffer, in the
        plan's (owner, vertex-id) halo order; its padding likewise names
        distinct slots of that buffer.
      halo_dtype: optional narrower dtype for the WIRE only (the TPU-native
        lever the f32-only reference lacks): the send buffer is cast after
        the send-side gather and the halo rows are upcast back to ``h.dtype``
        after the halo gather, so exactly the ``all_to_all`` bytes halve
        (``'bfloat16'``) while every table, activation and accumulation
        stays f32.  Single-chip bf16 compute measured SLOWER in round 5
        (gathers are row-rate-bound and master-array casts are pure
        overhead; ROADMAP A1); the wire is the one place narrow pays, because ICI
        bytes are the multi-chip bottleneck the partitioner minimizes.

    Returns:
      (R, f) halo rows (padding rows hold whatever finite rows their slots
      received; they are only referenced by weight-0 edges).
    """
    with scope("xchg_pack"):
        buf = jnp.take(h, send_idx, axis=0)                 # (k, S, f)
        if halo_dtype is not None:
            buf = buf.astype(halo_dtype)
    with scope("xchg_a2a"):
        recv = a2a_or_identity(buf, axis_name)
    with scope("xchg_unpack"):
        flat = recv.reshape(-1, h.shape[-1])                # (k*S, f)
        return jnp.take(flat, halo_src, axis=0).astype(h.dtype)  # (R, f)


def halo_exchange_multi(parts, send_idx, halo_src, axis_name: str = AXIS):
    """``halo_exchange`` of several row tables that share their rows, as ONE
    ``all_to_all``: each part's send rows are gathered on their own and
    concatenated at buffer size ``(k, S, Σf)`` — the ``(B, Σf)`` table is
    never built (at 516 lanes it would be tile-padded to 640) — and the
    halo block is split back into ``len(parts)`` arrays ``(R, f_p)``.  The
    multi-head attention layer ships ``[Z ‖ t]`` forward and
    ``[g ‖ s, m, 1/D, c]`` backward this way (``models/mhgat.py``)."""
    widths = [p.shape[-1] for p in parts]
    with scope("xchg_pack"):
        buf = jnp.concatenate(
            [jnp.take(p, send_idx, axis=0) for p in parts], axis=-1)
    with scope("xchg_a2a"):
        recv = a2a_or_identity(buf, axis_name)
    with scope("xchg_unpack"):
        halo = jnp.take(recv.reshape(-1, sum(widths)), halo_src, axis=0)
        cuts = [sum(widths[:i]) for i in range(len(widths) + 1)]
        return tuple(halo[:, a:b] for a, b in zip(cuts, cuts[1:]))


def ragged_live_rounds(rr_sizes) -> tuple:
    """Ring distances d (1-based) of the rounds with ``S_d > 0`` — exactly
    the rounds that EXIST in a traced ragged-schedule program (every loop
    below skips ``S_d = 0`` rounds, so they vanish at trace time: no
    ppermute, no buffer, no fold step).  The single shared encoding of that
    elision rule: the ragged ops here iterate it, and the static-analysis
    collective census (``sgcn_tpu/analysis``) derives its expected
    ``collective_permute`` count per exchange from it — change one without
    the other and the HLO audit fails the commit."""
    return tuple(d for d, sd in enumerate(rr_sizes, start=1) if sd > 0)


def ppermute_or_identity(buf, axis_name: str, d: int):
    """Round-``d`` ring shift of the ragged schedule: chip ``p`` sends
    ``buf`` to chip ``(p+d) % k`` (so each chip receives from ``(p−d) % k``)
    via ``lax.ppermute``.  Degrades to an ``optimization_barrier``-pinned
    identity on a size-1 mesh axis under the SAME fidelity contract as
    ``a2a_or_identity``: the shard-proxy measurement needs the send-side
    gather to stay materialized exactly as on a real k-chip mesh."""
    k = lax.axis_size(axis_name)
    if k == 1:
        (recv,) = lax.optimization_barrier((buf,))
        return recv
    return lax.ppermute(buf, axis_name,
                        perm=[(p, (p + d) % k) for p in range(k)])


def halo_exchange_ragged_multi(parts, rsend_idx, rhalo_dst, rr_sizes, r: int,
                               axis_name: str = AXIS, halo_dtype=None):
    """Ragged ppermute-ring exchange of SEVERAL row tables in ONE ring.

    The table-width-agnostic core of the ragged schedule: ``parts`` is a
    tuple of per-vertex arrays — each ``(B, d_i)`` (or ``(B,)`` for a scalar
    lane) — and every live round ships ONE concatenated
    ``(S_d, Σ d_i)``-lane buffer, so a feature table and its companion
    scalar (the GAT split path's ``(p, u)`` pair, two dense dispatches per
    exchange on the a2a schedule) cost a single ppermute per round.  The
    per-vertex send/receive layout (``rsend_idx``/``rhalo_dst``,
    ``CommPlan.ensure_ragged``) is model-independent: round ``d`` carries
    chip p → (p+d)%k in a buffer statically sized to that round's own max
    send count (``rr_sizes[d-1]``), rounds with S_d = 0 vanish at trace
    time, and received rows scatter (``.set``, each slot written exactly
    once) into their contiguous per-owner halo slice — so every part's halo
    table holds bit-identical rows to the dense exchange's, whatever its
    lane count.  Padding receive slots target row ``r`` and are dropped;
    padding halo rows therefore hold zeros (the dense exchange leaves
    garbage there — both are only ever referenced by weight-0/masked
    slots).  ``halo_dtype`` narrows the whole concatenated wire buffer
    only; mixed part dtypes ride the promoted dtype and are cast back per
    part on arrival.

    Returns a tuple of per-part halo tables, shaped ``(r,) + part.shape[1:]``.
    """
    lanes = [p.shape[1] if p.ndim == 2 else 1 for p in parts]
    halos = [jnp.zeros((r,) + p.shape[1:], p.dtype) for p in parts]
    live = ragged_live_rounds(rr_sizes)
    off = 0
    for d, sd in enumerate(rr_sizes, start=1):
        if d not in live:
            off += sd      # keep slice bookkeeping right under ANY rule
            continue
        idx = rsend_idx[off: off + sd]
        bufs = [jnp.take(p, idx, axis=0) for p in parts]
        if len(parts) == 1:
            buf = bufs[0]
        else:
            buf = jnp.concatenate(
                [b.reshape(sd, ln) for b, ln in zip(bufs, lanes)], axis=-1)
        if halo_dtype is not None:
            buf = buf.astype(halo_dtype)
        recv = ppermute_or_identity(buf, axis_name, d)
        dst = rhalo_dst[off: off + sd]
        col = 0
        for i, (p, ln) in enumerate(zip(parts, lanes)):
            seg = recv if len(parts) == 1 else recv[:, col: col + ln]
            seg = seg.reshape((sd,) + p.shape[1:]).astype(p.dtype)
            halos[i] = halos[i].at[dst].set(seg, mode="drop")
            col += ln
        off += sd
    return tuple(halos)


def halo_exchange_ragged(h, rsend_idx, rhalo_dst, rr_sizes, r: int,
                         axis_name: str = AXIS, halo_dtype=None):
    """Ragged ppermute-ring halo exchange; returns the (R, f) halo block.

    The plan-driven replacement for ``halo_exchange``'s dense all_to_all:
    the single-table form of ``halo_exchange_ragged_multi`` — per-round pad,
    not global pad, so the wire carries Σ_d k·S_d rows instead of k²·S.
    ``halo_dtype`` narrows the wire only, exactly like the dense exchange's
    lever.

    Args:
      h: (B, f) local feature rows.
      rsend_idx: (ΣS_d,) per-round send gather rows (round-major flat).
      rhalo_dst: (ΣS_d,) halo rank of each receive slot (``r`` = padding).
      rr_sizes: static per-round sizes, length k−1.
      r: halo table height.
    """
    (halo,) = halo_exchange_ragged_multi(
        (h,), rsend_idx, rhalo_dst, rr_sizes, r, axis_name, halo_dtype)
    return halo


def a2a_or_identity(buf, axis_name: str):
    """``lax.all_to_all`` of a per-peer-bucketed buffer, degrading to an
    identity on a size-1 mesh axis (jax's all_to_all rejects
    split_dim != axis_size).  The identity is pinned with an
    ``optimization_barrier``: XLA would otherwise fuse the send-side gather
    into the halo gather — fine for a true k=1 plan (empty halo), but the
    shard-proxy measurement (``sgcn_tpu.parallel.proxy``) runs a k>1 chip's
    program on one device and needs the send-buffer materialization to
    stay, exactly as on a real k-chip mesh.  Shared by every exchange
    (feature rows here, the GAT scalar buffer in ``models/gat.py``) so
    proxy fidelity has one home."""
    if lax.axis_size(axis_name) == 1:
        (recv,) = lax.optimization_barrier((buf,))
        return recv
    return lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0)


def spmm_local(edge_dst, edge_src, edge_w, table, num_rows: int):
    """Masked segment-sum SpMM: ``out[i] = Σ_e w_e · table[src_e]`` for dst_e=i.

    ``table`` is the concatenated ``[local (B); halo (R)]`` row block. Edges are
    sorted by dst at plan time. Mirrors the reference's accumulate-as-you-go
    structure ``AH = Â_local·H + Σ_r Â·Ĥ_r`` (``Parallel-GCN/main.c:269-299``)
    collapsed into one fused gather/segment-sum.
    """
    gathered = jnp.take(table, edge_src, axis=0) * edge_w[:, None]
    return jax.ops.segment_sum(
        gathered, edge_dst, num_segments=num_rows, indices_are_sorted=True
    )


def pspmm(h, halo, edge_dst, edge_src, edge_w):
    """Aggregate with an already-exchanged halo: ``Â_local · [h; halo]``."""
    table = jnp.concatenate([h, halo], axis=0)
    return spmm_local(edge_dst, edge_src, edge_w, table, h.shape[0])


def pspmm_exchange(h, send_idx, halo_src, edge_dst, edge_src, edge_w,
                   axis_name: str = AXIS):
    """``PSpMM`` over the combined ``[h; halo]`` edge list.

    Every edge's gather depends on the exchanged halo, so XLA cannot start
    the SpMM until the ``all_to_all`` lands.  Kept for ops that genuinely
    need the combined table (the GAT edge-softmax normalizes over local and
    halo edges together); the GCN hot path uses ``pspmm_overlap``.
    """
    halo = halo_exchange(h, send_idx, halo_src, axis_name)
    return pspmm(h, halo, edge_dst, edge_src, edge_w)


def pspmm_overlap(h, send_idx, halo_src,
                  ledge_dst, ledge_src, ledge_w,
                  hedge_dst, hedge_src, hedge_w,
                  axis_name: str = AXIS, halo_dtype=None):
    """``PSpMM`` with the reference's comm/compute-overlap structure.

    The edge list is split at plan time by source locality
    (``sgcn_tpu.parallel.plan``): the local-src segment-sum reads only ``h``
    and therefore has no data dependence on the ``all_to_all`` — XLA is free
    to run it while boundary rows are in flight — after which the halo-src
    segment-sum folds in the remote contribution.  This is exactly
    ``AH = Â·H_local + Σ_r Â·Ĥ_r`` of the MPI trainer
    (``Parallel-GCN/main.c:238-299``: post Irecv, compute local SpMM, fold
    arrivals), expressed as a dependence structure instead of explicit waits.

    Under JAX transposition the backward keeps the same split: the gradient
    all_to_all overlaps with the local-src transpose-SpMM.
    """
    halo = halo_exchange(h, send_idx, halo_src, axis_name, halo_dtype)
    # no data dependence on `halo` — XLA overlaps this with the exchange
    local = spmm_local(ledge_dst, ledge_src, ledge_w, h, h.shape[0])
    remote = spmm_local(hedge_dst, hedge_src, hedge_w, halo, h.shape[0])
    return local + remote


def _ell_slots(ell_idx, ell_w, h, buckets):
    """The bucketed-ELL slot passes of ``spmm_ell`` / ``_pspmm_ell_once``:
    per slot one fused gather·weight + accumulate, under ``agg_slots``."""
    if sum(nb * wb for nb, wb in buckets) != ell_idx.shape[0]:
        raise ValueError(
            f"bucket structure {buckets} does not cover the flat ELL arrays "
            f"({ell_idx.shape[0]} slots) — pass the owning plan's ell_buckets")
    f = h.shape[-1]
    # slot temps are budgeted at 4 B/elem even when h is bf16 — deliberately
    # NOT promote(h, ell_w).itemsize: budgeting with the true bf16 itemsize
    # re-engages the unrolled branch for twice as many buckets, and the
    # resulting program measured 23.2 GB of HLO temps on a 15.75 GB chip at
    # ogbn-products scale (mixed precision already double-books HBM with the
    # bf16 casts of every master-f32 array, so the slot budget must stay
    # conservative; the f32-equivalent budget is that 2× safety factor)
    with scope("agg_slots"):
        outs = bucketed_slot_reduce(
            ell_idx, ell_w, buckets,
            contrib=lambda idx, w: jnp.take(h, idx, axis=0) * w[:, None],
            init=lambda nb: jnp.zeros((nb, f), h.dtype), **ell_policy(f))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def spmm_ell(ell_idx, ell_w, tail_dst, tail_src, tail_w, h, buckets):
    """Local SpMM in bucketed-ELL layout + COO overflow tail.

    ``buckets = ((nb, wb), ...)`` is the plan's static degree-bucket
    structure (``sgcn_tpu.parallel.plan``): the next ``nb`` output rows each
    own ``wb`` flat slots of ``ell_idx``/``ell_w``, stored WIDTH-MAJOR (slot
    t of the bucket's rows is one contiguous (nb,) run).  Per slot this is
    one fused gather·weight + accumulate — no (nb, wb, f) intermediate
    exists, which is the point: the row-major gather+reduce form makes XLA
    relayout that intermediate.  The v5e gather is row-rate-bound (5.1 ns
    an executed slot at 128 lanes f32 whatever the index pattern, and 11.0
    in a bucket whose row count modulo 1,024 is 0 or past 896 — shapes the
    plan's ``snap_rows`` keeps its buckets off: the benchmark's
    ``ell_slot_ns`` and its ``slot_prices`` line, PERF.md §5, §6 PR 36), so
    executed slots are the time and the bucketed layout's padding (4.5 % in
    ``products.fullbatch``) is what a single-width ELL would multiply.

    The tail is folded per edge by a sorted ``segment_sum``, which costs
    about two slots an edge (15.2 ns; same ledger line): the form of the
    callers that keep COO stores (the ragged, stale and replica ops, the
    mini-batch envelope, sub-graph serving).  The exact full-batch step
    folds the same edges as slot passes over virtual rows
    (``_pspmm_ell_once``).
    """
    out = _ell_slots(ell_idx, ell_w, h, buckets)
    with scope("agg_tail"):
        tg = jnp.take(h, tail_src, axis=0) * tail_w[:, None]
        # the tail is dst-sorted by construction (plan edges are dst-sorted
        # and padding appends dst=b-1), so the segment_sum is told so
        tsum = jax.ops.segment_sum(tg, tail_dst, num_segments=out.shape[0],
                                   indices_are_sorted=True)
        return out + tsum


# the fold passes run beside the main slot passes in one program.  Their
# scans unroll only as far as this many bytes of live slot temporaries —
# at the cells' shapes the large classes (0.5 GB a slot) do not unroll at
# all — so that the folds never set the step's workspace: compiled for the
# v5e, the step's temporaries read 3.97 GB (gp4) / 7.16 GB (one chip)
# against the parent's 4.05 / 7.29, and 5.12 / 8.78 GB at twice this limit,
# for 7–11 % of a fold pass (PERF.md §6, PR 30)
_FOLD_SCAN_LIVE = 3 * 1024**3 // 4


def fold_policy(lanes: int, scan_live_limit: int = _FOLD_SCAN_LIVE) -> dict:
    """``ell_policy`` of a store of virtual rows: every class wider than two
    slots scans (``fold_slots``)."""
    return dict(ell_policy(lanes, scan_live_limit), scanned=True)


def pass_store_forms(buckets, tail_classes, halo_classes, lanes: int,
                     ell_live_limit: int | None = None) -> dict:
    """``{"ell" | "tail" | "halo": [((rows, width), unroll), ...]}`` of one
    pass of ``_pspmm_ell_once`` / ``_typed_pass`` over ``lanes``-wide rows:
    per store its buckets or classes with the form each runs in, by the
    policies those passes hand ``bucketed_slot_reduce`` — what the counter
    ``slots.work`` lists (``models/setup.py::slot_pass``)."""
    return {name: list(zip(shapes, bucket_forms(shapes, **policy)))
            for name, shapes, policy in (
                ("ell", buckets, ell_policy(lanes, ell_live_limit)),
                ("tail", tail_classes, fold_policy(lanes)),
                ("halo", halo_classes, fold_policy(lanes)))}


def fold_rows_scope():
    """The sub-scope of a class's sorted row scatter (``sgcn.fold_rows``)."""
    return _in_leaf(subscope, "fold_rows")


def fold_slots(out, table, idx, w, row, classes,
               scan_live_limit: int = _FOLD_SCAN_LIVE, contrib=None):
    """``out`` plus one COO edge store in slot form
    (``parallel.plan._build_virtual_rows``): every class ``(nv, W)`` of
    ``classes`` is a bucket of ``bucketed_slot_reduce`` (``take(table, idx)
    · w`` summed over its W slots, per virtual row), and one sorted
    scatter-add a class folds the ``nv`` row sums into their destinations
    ``row``.  No classes, no pass.  Every class runs in the scan form: on
    the v5e a scanned class of virtual rows cost 5–7 ns a slot where the
    unrolled form of the same store cost 7–10 (PERF.md §6, PR 30, step 1:
    gp4's tail at one width of 16, scanned, 18.5 ms a pass; at classes {8,
    16}, fewer slots but unrolled, 29.9).  ``contrib(idx, w) -> rows shaped
    like out's`` replaces the default ``take(table, idx) · w`` (the typed
    aggregation decodes its slots itself; ``table`` is then unused), and
    ``out`` may then be a pytree of row arrays."""
    if not classes:
        return out
    if contrib is None:
        def contrib(i, wt):
            return jnp.take(table, i, axis=0) * wt[:, None]
    f = sum(x.shape[-1] for x in jax.tree.leaves(out))
    parts = bucketed_slot_reduce(
        idx, w, classes, contrib=contrib,
        init=lambda nv: jax.tree.map(
            lambda x: jnp.zeros((nv, x.shape[-1]), x.dtype), out),
        **fold_policy(f, scan_live_limit))
    r0 = 0
    for (nv, _), part in zip(classes, parts):
        rows = row[r0: r0 + nv]
        with fold_rows_scope():
            out = jax.tree.map(
                lambda x, y, rows=rows: x.at[rows].add(
                    y, indices_are_sorted=True), out, part)
        r0 += nv
    return out


def _pspmm_ell_once(h, send_idx, halo_src, ell_idx, ell_w,
                    ft_idx, ft_w, ft_row, fh_idx, fh_w, fh_row,
                    buckets, tail_classes, halo_classes, axis_name,
                    halo_dtype=None):
    # no halo edge on any chip: nothing reads a halo table, nothing is sent
    halo = (halo_exchange(h, send_idx, halo_src, axis_name, halo_dtype)
            if halo_classes else None)
    # local ELL aggregation has no data dependence on the exchange (overlap)
    out = _ell_slots(ell_idx, ell_w, h, buckets)
    with scope("agg_tail"):
        out = fold_slots(out, h, ft_idx, ft_w, ft_row, tail_classes)
    with scope("agg_halo_fold"):
        return fold_slots(out, halo, fh_idx, fh_w, fh_row, halo_classes)


@partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13, 14, 15))
def pspmm_ell_sym(h, send_idx, halo_src, ell_idx, ell_w,
                  ft_idx, ft_w, ft_row, fh_idx, fh_w, fh_row,
                  buckets, tail_classes, halo_classes,
                  axis_name=AXIS, halo_dtype=None):
    """``PSpMM`` for a SYMMETRIC Â — the exact full-batch aggregation: ELL
    slot passes over the local edges, the hub tail (``ft_*``) and the
    halo-source edges (``fh_*``) as slot passes over virtual rows in the
    width classes the plan chose for each store
    (``CommPlan.ensure_fold_slots``), and a custom backward that reuses the
    forward form.

    JAX's mechanical transpose of a gather is a scatter-add, which the v5e
    runs at about half the rate of the gather form per entry; for symmetric
    Â (the reference's standing assumption — its backward applies A, not
    Aᵀ, ``Parallel-GCN/main.c:374-404``) the gradient is just ``Â·g``,
    computed exactly like the forward, including the same halo exchange
    (the symmetric pattern makes the reversed comm identical to the forward
    comm).

    Only valid when ``plan.symmetric``; callers must fall back to
    ``pspmm_overlap`` otherwise.  ``pspmm_ell_sym_coo`` is the same
    aggregation with both stores folded per edge.
    """
    return _pspmm_ell_once(h, send_idx, halo_src, ell_idx, ell_w,
                           ft_idx, ft_w, ft_row, fh_idx, fh_w, fh_row,
                           buckets, tail_classes, halo_classes, axis_name,
                           halo_dtype)


def _pspmm_ell_sym_fwd(h, *args):
    return _pspmm_ell_once(h, *args), args[:10]


def _pspmm_ell_sym_bwd(buckets, tail_classes, halo_classes, axis_name,
                       halo_dtype, res, g):
    # the gradient exchange rides the same narrow wire as the forward's —
    # both directions of ICI traffic halve under halo_dtype='bfloat16'
    gh = _pspmm_ell_once(g, *res, buckets, tail_classes, halo_classes,
                         axis_name, halo_dtype)
    return (gh, *[None] * 10)


pspmm_ell_sym.defvjp(_pspmm_ell_sym_fwd, _pspmm_ell_sym_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13, 14, 15))
def pspmm_ell_sym_detached(h, send_idx, halo_src, ell_idx, ell_w,
                           ft_idx, ft_w, ft_row, fh_idx, fh_w, fh_row,
                           buckets, tail_classes, halo_classes,
                           grad_lanes: int, axis_name=AXIS):
    """``pspmm_ell_sym`` of a table whose lanes past ``grad_lanes`` the
    caller has DETACHED (they carry no cotangent): the forward aggregates
    every lane of ``h``, the backward only the first ``grad_lanes`` of the
    cotangent and returns zeros for the rest — different widths forward and
    backward through the same slot passes and the same exchange.  The deep
    stack's softmax aggregation ships ``[u m ‖ u]`` forward (2f lanes: the
    numerator's table and the detached denominator's) and ``g / S`` backward
    (f lanes) this way (``models/deepergcn.py``)."""
    return _pspmm_ell_once(h, send_idx, halo_src, ell_idx, ell_w,
                           ft_idx, ft_w, ft_row, fh_idx, fh_w, fh_row,
                           buckets, tail_classes, halo_classes, axis_name)


def _pspmm_ell_sym_detached_fwd(h, *args):
    *plan, _grad_lanes, axis_name = args    # ten arrays, three statics
    return _pspmm_ell_once(h, *plan, axis_name), args[:10]


def _pspmm_ell_sym_detached_bwd(buckets, tail_classes, halo_classes,
                                grad_lanes, axis_name, res, g):
    gh = _pspmm_ell_once(g[:, :grad_lanes], *res, buckets, tail_classes,
                         halo_classes, axis_name)
    pad = jnp.zeros((g.shape[0], g.shape[1] - grad_lanes), g.dtype)
    return (jnp.concatenate([gh, pad], axis=1), *[None] * 10)


pspmm_ell_sym_detached.defvjp(_pspmm_ell_sym_detached_fwd,
                              _pspmm_ell_sym_detached_bwd)


def _pspmm_ell_coo_once(h, send_idx, halo_src, ell_idx, ell_w,
                        ltail_dst, ltail_src, ltail_w,
                        hedge_dst, hedge_src, hedge_w, buckets, axis_name,
                        halo_dtype=None):
    halo = halo_exchange(h, send_idx, halo_src, axis_name, halo_dtype)
    # local ELL aggregation has no data dependence on the exchange (overlap)
    local = spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, h, buckets)
    with scope("agg_halo_fold"):
        remote = spmm_local(hedge_dst, hedge_src, hedge_w, halo, h.shape[0])
        return local + remote


@partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13))
def pspmm_ell_sym_coo(h, send_idx, halo_src, ell_idx, ell_w,
                      ltail_dst, ltail_src, ltail_w,
                      hedge_dst, hedge_src, hedge_w, buckets,
                      axis_name=AXIS, halo_dtype=None):
    """``pspmm_ell_sym`` with the hub tail and the halo-source edges as the
    plan's COO lists (``ltail_*``, ``hedge_*``), each folded per edge by a
    sorted ``segment_sum`` — the form of the programs that cannot take a
    plan's virtual rows: the mini-batch step (one compiled envelope serves
    every batch's plan, and a virtual-row count has no envelope), and the
    exact forward the stale and replica trainers evaluate with (they ship
    the COO lists their step ops fold).  Same edges, weights and f32
    accumulation; only the order of the additions differs."""
    return _pspmm_ell_coo_once(h, send_idx, halo_src, ell_idx, ell_w,
                               ltail_dst, ltail_src, ltail_w,
                               hedge_dst, hedge_src, hedge_w, buckets,
                               axis_name, halo_dtype)


def _pspmm_ell_sym_coo_fwd(h, *args):
    return _pspmm_ell_coo_once(h, *args), args[:10]


def _pspmm_ell_sym_coo_bwd(buckets, axis_name, halo_dtype, res, g):
    gh = _pspmm_ell_coo_once(g, *res, buckets, axis_name, halo_dtype)
    return (gh, *[None] * 10)


pspmm_ell_sym_coo.defvjp(_pspmm_ell_sym_coo_fwd, _pspmm_ell_sym_coo_bwd)


# -------------------------------------------------------------------- ragged
# Ragged ppermute-ring PSpMM: the per-round exchange of halo_exchange_ragged
# with FOLD-AS-YOU-ARRIVE remote aggregation — round d's halo-src edges
# (split per owner at plan time, src re-based to the round's receive buffer)
# scatter-add straight into the output accumulator, so each round's remote
# contribution folds while later rounds are still in flight: the TPU
# dependence-structure expression of the reference's post-Irecv
# compute-local / accumulate-arrivals loop (Parallel-GCN/main.c:238-299).
#
# f32 bit-parity with the dense schedule is STRUCTURAL, not approximate: the
# plan sorts the dense hedge family by (dst, round, recv-pos), and XLA's
# scatter-add applies updates in order, so the round-major chain of scatters
# below performs, per output slot, the exact addition sequence of the dense
# path's single halo-src segment-sum (verified by tests/test_ragged.py).


def _ragged_remote(x, rsend_idx, redge_dst, redge_src, redge_w,
                   rr_sizes, rr_edge_sizes, num_rows: int, axis_name,
                   halo_dtype):
    """Σ_d (round-d scatter-add of Â_halo·recv_d) over the ppermute ring."""
    remote = jnp.zeros((num_rows, x.shape[-1]), x.dtype)
    live = ragged_live_rounds(rr_sizes)
    off_s = off_e = 0
    for d, (sd, ed) in enumerate(zip(rr_sizes, rr_edge_sizes), start=1):
        if d not in live:                 # no pair at this ring distance
            off_s += sd   # keep slice bookkeeping right under ANY rule
            off_e += ed
            continue
        buf = jnp.take(x, rsend_idx[off_s: off_s + sd], axis=0)  # (S_d, f)
        if halo_dtype is not None:
            buf = buf.astype(halo_dtype)                         # wire only
        recv = ppermute_or_identity(buf, axis_name, d).astype(x.dtype)
        g = (jnp.take(recv, redge_src[off_e: off_e + ed], axis=0)
             * redge_w[off_e: off_e + ed, None])
        remote = remote.at[redge_dst[off_e: off_e + ed]].add(
            g, indices_are_sorted=True)
        off_s += sd
        off_e += ed
    return remote


def _pspmm_ragged_once(h, rsend_idx, ell_idx, ell_w,
                       ltail_dst, ltail_src, ltail_w,
                       redge_dst, redge_src, redge_w,
                       buckets, rr_sizes, rr_edge_sizes, axis_name,
                       halo_dtype):
    # local ELL aggregation has no data dependence on ANY round (overlap)
    local = spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, h, buckets)
    remote = _ragged_remote(h, rsend_idx, redge_dst, redge_src, redge_w,
                            rr_sizes, rr_edge_sizes, h.shape[0], axis_name,
                            halo_dtype)
    return local + remote


@partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13, 14))
def pspmm_ragged_sym(h, rsend_idx, ell_idx, ell_w,
                     ltail_dst, ltail_src, ltail_w,
                     redge_dst, redge_src, redge_w,
                     buckets, rr_sizes, rr_edge_sizes,
                     axis_name=AXIS, halo_dtype=None):
    """``PSpMM`` over the ragged ppermute ring for a SYMMETRIC Â.

    Same math as ``pspmm_ell_sym`` — ELL local aggregation plus the halo
    contribution — but the exchange is k−1 per-round-sized ppermutes
    instead of one globally-padded all_to_all, and the remote term folds
    round by round (see ``_ragged_remote``).  The custom backward reuses
    the forward form on ``g`` (Âᵀg = Âg for symmetric Â): the gradient
    rides the same ragged ring, same per-round sizes, same narrow-wire
    ``halo_dtype`` lever — the ragged analogue of the reference's swapped
    send/recv backward maps (``GPU/PGCN.py:93-97``).

    Only valid when ``plan.symmetric``; the trainer gates on it.
    """
    return _pspmm_ragged_once(h, rsend_idx, ell_idx, ell_w,
                              ltail_dst, ltail_src, ltail_w,
                              redge_dst, redge_src, redge_w,
                              buckets, rr_sizes, rr_edge_sizes, axis_name,
                              halo_dtype)


def _pspmm_ragged_sym_fwd(h, rsend_idx, ell_idx, ell_w,
                          ltail_dst, ltail_src, ltail_w,
                          redge_dst, redge_src, redge_w,
                          buckets, rr_sizes, rr_edge_sizes, axis_name,
                          halo_dtype):
    out = _pspmm_ragged_once(h, rsend_idx, ell_idx, ell_w,
                             ltail_dst, ltail_src, ltail_w,
                             redge_dst, redge_src, redge_w,
                             buckets, rr_sizes, rr_edge_sizes, axis_name,
                             halo_dtype)
    res = (rsend_idx, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
           redge_dst, redge_src, redge_w)
    return out, res


def _pspmm_ragged_sym_bwd(buckets, rr_sizes, rr_edge_sizes, axis_name,
                          halo_dtype, res, g):
    (rsend_idx, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
     redge_dst, redge_src, redge_w) = res
    gh = _pspmm_ragged_once(g, rsend_idx, ell_idx, ell_w,
                            ltail_dst, ltail_src, ltail_w,
                            redge_dst, redge_src, redge_w,
                            buckets, rr_sizes, rr_edge_sizes, axis_name,
                            halo_dtype)
    return (gh, *[None] * 9)


pspmm_ragged_sym.defvjp(_pspmm_ragged_sym_fwd, _pspmm_ragged_sym_bwd)


# ------------------------------------------------------------------ replicas
# Hot-halo replication (CaPGNN-style, arXiv:2508.13716): the plan's top-B
# boundary rows by λ·degree live as PERSISTENT REPLICAS on their consumer
# chips (``CommPlan.ensure_replicas``).  A replica step exchanges only the
# shrunken no-replica buckets (``nrep_*`` — replicated rows leave the wire
# entirely, forward AND backward) and fills the replica halo slots from a
# carried per-layer replica table; a refresh (sync) step runs EXACTLY the
# full exact exchange — same collectives, same fold order, f32-bit-identical
# math — and re-reads the replica rows out of the fresh halo as the next
# carry.  Gradient replicas mirror the structure through the same cotangent
# channel as ``pspmm_stale``'s ``ghalo_in``: differentiate the caller w.r.t.
# its ``greps`` carry and the "grad" that comes back IS next refresh's
# gradient-replica table (fresh on sync steps, the pass-through carry
# otherwise).  Unlike the stale mode, every exchange here is SYNCHRONOUS
# (same-step consumer): replication shrinks wire bytes, not exposure.
# Symmetric-Â only, like every custom-VJP op in this file.


def _replica_halo(x, rep, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
                  rep_slots, axis_name, halo_dtype, fresh):
    """One replica-aware halo exchange; returns ``(halo, rep_next)``.

    ``fresh``: the FULL exchange (bit-identical to ``halo_exchange``) plus
    the replica refresh ``halo[rep_slots]`` — PADDING carry slots
    (``rep_slots`` holds ``r`` there, out of range) are zeroed, not left
    with the clip-gather's junk row: they are never consumed (the ``.set``
    drops them), but the drift gauges sum over the whole carry, and
    step-varying junk in pad slots would masquerade as replica drift.
    Otherwise: the shrunken exchange, with replica slots overwritten from
    the carry and the carry passed through unchanged."""
    if fresh:
        halo = halo_exchange(x, send_idx, halo_src, axis_name, halo_dtype)
        valid = (rep_slots < halo.shape[0])[:, None].astype(halo.dtype)
        return halo, jnp.take(halo, rep_slots, axis=0, mode="clip") * valid
    halo = halo_exchange(x, nrep_send_idx, nrep_halo_src, axis_name,
                         halo_dtype)
    halo = halo.at[rep_slots].set(rep.astype(halo.dtype), mode="drop")
    return halo, rep


def _pspmm_replica_once(x, rep_in, send_idx, halo_src, nrep_send_idx,
                        nrep_halo_src, rep_slots, ell_idx, ell_w,
                        ltail_dst, ltail_src, ltail_w,
                        hedge_dst, hedge_src, hedge_w,
                        buckets, axis_name, halo_dtype, fresh):
    halo, rep_next = _replica_halo(
        x, rep_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
        rep_slots, axis_name, halo_dtype, fresh)
    # same dependence structure as the exact path: the local ELL pass has
    # no data dependence on the exchange (overlap), the halo fold waits
    local = spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, x,
                     buckets)
    remote = spmm_local(hedge_dst, hedge_src, hedge_w, halo, x.shape[0])
    return local + remote, rep_next


@partial(jax.custom_vjp, nondiff_argnums=(16, 17, 18, 19))
def pspmm_replica(x, rep_in, grep_in, send_idx, halo_src,
                  nrep_send_idx, nrep_halo_src, rep_slots,
                  ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                  hedge_dst, hedge_src, hedge_w, buckets,
                  axis_name=AXIS, halo_dtype=None, fresh=False):
    """``PSpMM`` with persistent hot-halo replicas on the dense a2a.

    Replica (``fresh=False``) step: the a2a ships the SHRUNKEN
    ``(k, S')`` buckets (replicated rows off the wire, both directions),
    the halo table's replica slots fill from ``rep_in``/``grep_in``, and
    both carries pass through unchanged.  Refresh (``fresh=True``) step:
    the full exact exchange — the program is the exact path plus the
    replica-row gathers, so a ``--sync-every 1`` trajectory is
    f32-bit-identical to the no-replica path — and both carries come back
    fresh (features via ``rep_next``, gradients via the ``grep_in``
    cotangent).  Returns ``(out, rep_next)``; the carry output's cotangent
    is structurally zero (it crosses the step boundary).
    """
    return _pspmm_replica_once(
        x, rep_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
        rep_slots, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
        hedge_dst, hedge_src, hedge_w, buckets, axis_name, halo_dtype,
        fresh)


def _pspmm_replica_fwd(x, rep_in, grep_in, send_idx, halo_src,
                       nrep_send_idx, nrep_halo_src, rep_slots,
                       ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                       hedge_dst, hedge_src, hedge_w, buckets,
                       axis_name, halo_dtype, fresh):
    out = _pspmm_replica_once(
        x, rep_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
        rep_slots, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
        hedge_dst, hedge_src, hedge_w, buckets, axis_name, halo_dtype,
        fresh)
    res = (grep_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
           rep_slots, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
           hedge_dst, hedge_src, hedge_w)
    return out, res


def _pspmm_replica_bwd(buckets, axis_name, halo_dtype, fresh, res, cts):
    (grep_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src, rep_slots,
     ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
     hedge_dst, hedge_src, hedge_w) = res
    g, _ = cts               # carry cotangent is structurally zero
    # gradient exchange mirrors the forward exactly: shrunken buckets +
    # gradient-replica carry on replica steps, the full exchange (whose
    # replica rows refresh the carry through this cotangent) on syncs
    ghalo, grep_next = _replica_halo(
        g, grep_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
        rep_slots, axis_name, halo_dtype, fresh)
    gx = (spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, g, buckets)
          + spmm_local(hedge_dst, hedge_src, hedge_w, ghalo, g.shape[0]))
    return (gx, None, grep_next, *[None] * 13)


pspmm_replica.defvjp(_pspmm_replica_fwd, _pspmm_replica_bwd)


def _replica_ring_halo(x, rep, rsend_idx, nrep_rsend_idx, nrep_rhalo_dst,
                       rep_slots, rep_ring_pos, rr_sizes, nrep_rr_sizes,
                       halo_r, axis_name, halo_dtype, fresh):
    """One replica-aware ragged-ring exchange.

    ``fresh``: ship the FULL per-round ring and return the round-major
    receive concat (the PR-6 carry layout — folding it through ``redge_*``
    is f32-bit-identical to the exact ragged path) plus the replica rows
    gathered at ``rep_ring_pos``.  Otherwise: ship the SHRUNKEN ring
    (``nrep_rr_sizes`` — live rounds per ``ragged_live_rounds``, the shared
    elision rule), scatter receives into the halo table, overwrite replica
    slots from the carry, and pass the carry through.  Returns
    ``(ring_concat_or_halo_table, rep_next)`` — the caller folds the first
    element per mode (``redge_*`` ring fold when fresh, dense ``hedge_*``
    fold otherwise)."""
    f = x.shape[-1]
    if fresh:
        segs = []
        live = ragged_live_rounds(rr_sizes)
        off = 0
        for d, sd in enumerate(rr_sizes, start=1):
            if d not in live:
                off += sd    # keep slice bookkeeping right under ANY rule
                continue
            buf = jnp.take(x, rsend_idx[off: off + sd], axis=0)
            if halo_dtype is not None:
                buf = buf.astype(halo_dtype)
            segs.append(ppermute_or_identity(buf, axis_name, d)
                        .astype(x.dtype))
            off += sd
        ring = (jnp.zeros((1, f), x.dtype) if not segs
                else (segs[0] if len(segs) == 1 else jnp.concatenate(segs)))
        # zero padding carry slots (rep_slots == r there) — same drift-gauge
        # hygiene as the a2a refresh: pad rows are never consumed, but junk
        # in them would pollute Σ(rep_next − rep_in)²
        valid = (rep_slots < halo_r)[:, None].astype(x.dtype)
        return ring, jnp.take(ring, rep_ring_pos, axis=0, mode="clip") * valid
    halo = jnp.zeros((halo_r, f), x.dtype)
    live = ragged_live_rounds(nrep_rr_sizes)
    off = 0
    for d, sd in enumerate(nrep_rr_sizes, start=1):
        if d not in live:
            off += sd        # keep slice bookkeeping right under ANY rule
            continue
        buf = jnp.take(x, nrep_rsend_idx[off: off + sd], axis=0)
        if halo_dtype is not None:
            buf = buf.astype(halo_dtype)
        recv = ppermute_or_identity(buf, axis_name, d).astype(x.dtype)
        halo = halo.at[nrep_rhalo_dst[off: off + sd]].set(recv, mode="drop")
        off += sd
    halo = halo.at[rep_slots].set(rep.astype(x.dtype), mode="drop")
    return halo, rep


def _pspmm_replica_ragged_once(x, rep_in, rsend_idx, nrep_rsend_idx,
                               nrep_rhalo_dst, rep_slots, rep_ring_pos,
                               ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                               hedge_dst, hedge_src, hedge_w,
                               redge_dst, redge_src, redge_w,
                               buckets, rr_sizes, rr_edge_sizes,
                               nrep_rr_sizes, halo_r, axis_name, halo_dtype,
                               fresh):
    tab, rep_next = _replica_ring_halo(
        x, rep_in, rsend_idx, nrep_rsend_idx, nrep_rhalo_dst, rep_slots,
        rep_ring_pos, rr_sizes, nrep_rr_sizes, halo_r, axis_name,
        halo_dtype, fresh)
    local = spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, x,
                     buckets)
    if fresh:
        # the full ring concat folds through the exact ragged path's
        # per-round redge_* scatter sequence (bit-identical — PR-6 contract)
        remote = _stale_ragged_fold(tab, redge_dst, redge_src, redge_w,
                                    rr_sizes, rr_edge_sizes, x.shape[0])
    else:
        # the shrunken ring lands in the halo TABLE (replica slots from the
        # carry), folded by the dense halo-src edge family — replica steps
        # are approximate between refreshes, so round-order parity is not a
        # contract here
        remote = spmm_local(hedge_dst, hedge_src, hedge_w, tab, x.shape[0])
    return local + remote, rep_next


@partial(jax.custom_vjp, nondiff_argnums=(19, 20, 21, 22, 23, 24, 25, 26))
def pspmm_replica_ragged(x, rep_in, grep_in, rsend_idx,
                         nrep_rsend_idx, nrep_rhalo_dst, rep_slots,
                         rep_ring_pos,
                         ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                         hedge_dst, hedge_src, hedge_w,
                         redge_dst, redge_src, redge_w,
                         buckets, rr_sizes, rr_edge_sizes, nrep_rr_sizes,
                         halo_r, axis_name=AXIS, halo_dtype=None,
                         fresh=False):
    """``PSpMM`` with persistent hot-halo replicas on the ragged ring.

    Replica (``fresh=False``) step: k−1 per-round ppermutes sized by the
    SHRUNKEN ``nrep_rr_sizes`` (replicated rows off every round's wire,
    both directions; empty rounds elided per ``ragged_live_rounds``), halo
    replica slots filled from the carries.  Refresh (``fresh=True``) step:
    the full ring whose round-major concat folds through ``redge_*`` —
    f32-bit-identical to the exact ragged path, so ``--sync-every 1``
    reproduces the no-replica trajectory — and both carries refresh
    (features via ``rep_next`` at ``rep_ring_pos``, gradients via the
    ``grep_in`` cotangent).  Returns ``(out, rep_next)``.  Symmetric-Â
    only.
    """
    return _pspmm_replica_ragged_once(
        x, rep_in, rsend_idx, nrep_rsend_idx, nrep_rhalo_dst, rep_slots,
        rep_ring_pos, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
        hedge_dst, hedge_src, hedge_w, redge_dst, redge_src, redge_w,
        buckets, rr_sizes, rr_edge_sizes, nrep_rr_sizes, halo_r, axis_name,
        halo_dtype, fresh)


def _pspmm_replica_ragged_fwd(x, rep_in, grep_in, rsend_idx,
                              nrep_rsend_idx, nrep_rhalo_dst, rep_slots,
                              rep_ring_pos, ell_idx, ell_w,
                              ltail_dst, ltail_src, ltail_w,
                              hedge_dst, hedge_src, hedge_w,
                              redge_dst, redge_src, redge_w,
                              buckets, rr_sizes, rr_edge_sizes,
                              nrep_rr_sizes, halo_r, axis_name, halo_dtype,
                              fresh):
    out = _pspmm_replica_ragged_once(
        x, rep_in, rsend_idx, nrep_rsend_idx, nrep_rhalo_dst, rep_slots,
        rep_ring_pos, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
        hedge_dst, hedge_src, hedge_w, redge_dst, redge_src, redge_w,
        buckets, rr_sizes, rr_edge_sizes, nrep_rr_sizes, halo_r, axis_name,
        halo_dtype, fresh)
    res = (grep_in, rsend_idx, nrep_rsend_idx, nrep_rhalo_dst, rep_slots,
           rep_ring_pos, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
           hedge_dst, hedge_src, hedge_w, redge_dst, redge_src, redge_w)
    return out, res


def _pspmm_replica_ragged_bwd(buckets, rr_sizes, rr_edge_sizes,
                              nrep_rr_sizes, halo_r, axis_name, halo_dtype,
                              fresh, res, cts):
    (grep_in, rsend_idx, nrep_rsend_idx, nrep_rhalo_dst, rep_slots,
     rep_ring_pos, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
     hedge_dst, hedge_src, hedge_w, redge_dst, redge_src, redge_w) = res
    g, _ = cts               # carry cotangent is structurally zero
    gtab, grep_next = _replica_ring_halo(
        g, grep_in, rsend_idx, nrep_rsend_idx, nrep_rhalo_dst, rep_slots,
        rep_ring_pos, rr_sizes, nrep_rr_sizes, halo_r, axis_name,
        halo_dtype, fresh)
    if fresh:
        gremote = _stale_ragged_fold(gtab, redge_dst, redge_src, redge_w,
                                     rr_sizes, rr_edge_sizes, g.shape[0])
    else:
        gremote = spmm_local(hedge_dst, hedge_src, hedge_w, gtab,
                             g.shape[0])
    gx = (spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, g, buckets)
          + gremote)
    return (gx, None, grep_next, *[None] * 16)


pspmm_replica_ragged.defvjp(_pspmm_replica_ragged_fwd,
                            _pspmm_replica_ragged_bwd)


# ------------------------------------------------------ replicas × staleness
# The COMPOSED mode (``--replica-budget B --halo-staleness 1``): the
# one-step-stale carry of ``pspmm_stale`` rides the SHRUNKEN no-replica
# exchange of ``pspmm_replica``.  The stale halo carry SUBSUMES the replica
# tables — no separate rep/grep carry exists: a stale step ships only the
# shrunken ``nrep_*`` buffers (with no same-step consumer, so the
# already-smaller exchange also leaves the critical path) and scatters its
# receives back into the carried halo table, leaving the replica slots at
# the values the last sync wrote; a sync step runs the FULL exchange
# consumed fresh — exactly ``pspmm_stale``'s sync program, so
# ``--sync-every 1`` is f32-bit-identical to the exact (and no-replica)
# path.  The ragged flavor carries the ring envelope of
# ``pspmm_stale_ragged`` and scatters shrunken-round receives into it at
# ``nrep_ring_dst`` (each kept slot's position in the FULL ring concat).
# Gradient carries mirror the structure through the ``ghalo_in`` cotangent
# channel.  Symmetric-Â only, like every composed op here.


def _replica_stale_exchange(x, halo_in, send_idx, halo_src, nrep_send_idx,
                            nrep_halo_src, rep_slots, axis_name, wire_dtype,
                            fresh):
    """Issue step t's exchange; return ``halo_next`` (the dense ``(R, f)``
    carry).  ``fresh``: the full exchange — bit-identical to
    ``halo_exchange``, every slot (replica slots included) refreshed.
    Otherwise: the shrunken ``nrep_*`` exchange scattered over the kept
    slots, replica slots re-seated from the carry (their values propagate
    sync → sync through the carried table)."""
    if fresh:
        return halo_exchange(x, send_idx, halo_src, axis_name, wire_dtype)
    halo = halo_exchange(x, nrep_send_idx, nrep_halo_src, axis_name,
                         wire_dtype)
    rep_vals = jnp.take(halo_in, rep_slots, axis=0, mode="clip")
    return halo.at[rep_slots].set(rep_vals, mode="drop")


def _pspmm_replica_stale_once(x, halo_in, send_idx, halo_src,
                              nrep_send_idx, nrep_halo_src, rep_slots,
                              ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                              hedge_dst, hedge_src, hedge_w,
                              buckets, axis_name, wire_dtype, fresh):
    halo_next = _replica_stale_exchange(
        x, halo_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
        rep_slots, axis_name, wire_dtype, fresh)
    # stale step: the fold reads the CARRY — the shrunken exchange has no
    # same-step consumer, so it rides behind compute like pspmm_stale's;
    # sync step: the fold waits for the full exchange (exact structure)
    halo_used = halo_next if fresh else halo_in
    local = spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, x,
                     buckets)
    remote = spmm_local(hedge_dst, hedge_src, hedge_w, halo_used,
                        x.shape[0])
    return local + remote, halo_next


@partial(jax.custom_vjp, nondiff_argnums=(17, 18, 19, 20, 21))
def pspmm_replica_stale(x, halo_in, ghalo_in, base_in, send_idx, halo_src,
                        nrep_send_idx, nrep_halo_src, rep_slots,
                        ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                        hedge_dst, hedge_src, hedge_w, buckets,
                        axis_name=AXIS, wire_dtype=None, gwire_dtype=None,
                        fresh=False):
    """``PSpMM`` composing hot-halo replication with the one-step-stale
    carry on the dense a2a (see the section comment above).

    Stale (``fresh=False``) step: the a2a ships the SHRUNKEN ``(k, S')``
    buckets with no in-step consumer; the consumed halo is the carry, and
    ``halo_next`` is the carry with the kept slots overwritten by this
    step's receives (replica slots keep their last-sync values).  Sync
    (``fresh=True``) step: exactly ``pspmm_stale``'s full-sync program —
    f32-bit-identical to the exact path.  ``base_in`` passes through
    untouched (the halo-delta cache does not compose with replication —
    the trainer gates it); returns ``(out, halo_next, base_next)`` with
    the same carry arity as ``pspmm_stale`` so the stale forward stays
    uniform.  The gradient ring mirrors the structure through the
    ``ghalo_in`` cotangent channel."""
    out, halo_next = _pspmm_replica_stale_once(
        x, halo_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
        rep_slots, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
        hedge_dst, hedge_src, hedge_w, buckets, axis_name, wire_dtype,
        fresh)
    return out, halo_next, base_in


def _pspmm_replica_stale_fwd(x, halo_in, ghalo_in, base_in, send_idx,
                             halo_src, nrep_send_idx, nrep_halo_src,
                             rep_slots, ell_idx, ell_w,
                             ltail_dst, ltail_src, ltail_w,
                             hedge_dst, hedge_src, hedge_w, buckets,
                             axis_name, wire_dtype, gwire_dtype, fresh):
    out, halo_next = _pspmm_replica_stale_once(
        x, halo_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
        rep_slots, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
        hedge_dst, hedge_src, hedge_w, buckets, axis_name, wire_dtype,
        fresh)
    res = (ghalo_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
           rep_slots, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
           hedge_dst, hedge_src, hedge_w)
    return (out, halo_next, base_in), res


def _pspmm_replica_stale_bwd(buckets, axis_name, wire_dtype, gwire_dtype,
                             fresh, res, cts):
    (ghalo_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src, rep_slots,
     ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
     hedge_dst, hedge_src, hedge_w) = res
    g, _, _ = cts            # carry cotangents are structurally zero
    # step t's gradient exchange mirrors the forward: shrunken buckets
    # merged into the carried table on stale steps (no same-step consumer),
    # the full exchange on syncs — it leaves via the ghalo_in channel
    gh_next = _replica_stale_exchange(
        g, ghalo_in, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
        rep_slots, axis_name, gwire_dtype, fresh)
    gh_used = gh_next if fresh else ghalo_in
    gx = (spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, g, buckets)
          + spmm_local(hedge_dst, hedge_src, hedge_w, gh_used, g.shape[0]))
    return (gx, None, gh_next, None, *[None] * 13)


pspmm_replica_stale.defvjp(_pspmm_replica_stale_fwd,
                           _pspmm_replica_stale_bwd)


def _replica_stale_ring_exchange(x, halo_in, rsend_idx, nrep_rsend_idx,
                                 nrep_ring_dst, rr_sizes, nrep_rr_sizes,
                                 axis_name, wire_dtype, fresh):
    """Issue step t's ring exchange; return the round-major
    ``(Σ_d S_d, f)`` ring-envelope carry.  ``fresh``: the full per-round
    ring concat (``_stale_ragged_exchange``'s non-delta path — bit-exact
    with the exact ragged wire).  Otherwise: the SHRUNKEN ring (live
    rounds of ``nrep_rr_sizes``) scattered into the carried envelope at
    each kept slot's full-ring position; replica positions keep their
    last-sync values."""
    if fresh:
        halo_next, _ = _stale_ragged_exchange(
            x, halo_in, halo_in, rsend_idx, rr_sizes, axis_name, False,
            wire_dtype, fresh)
        return halo_next
    halo_next = halo_in
    live = ragged_live_rounds(nrep_rr_sizes)
    off = 0
    for d, sd in enumerate(nrep_rr_sizes, start=1):
        if d not in live:
            off += sd      # keep slice bookkeeping right under ANY rule
            continue
        buf = jnp.take(x, nrep_rsend_idx[off: off + sd], axis=0)
        if wire_dtype is not None:
            buf = buf.astype(wire_dtype)
        recv = ppermute_or_identity(buf, axis_name, d).astype(x.dtype)
        halo_next = halo_next.at[nrep_ring_dst[off: off + sd]].set(
            recv, mode="drop")
        off += sd
    return halo_next


def _pspmm_replica_stale_ragged_once(x, halo_in, rsend_idx, nrep_rsend_idx,
                                     nrep_ring_dst, ell_idx, ell_w,
                                     ltail_dst, ltail_src, ltail_w,
                                     redge_dst, redge_src, redge_w,
                                     buckets, rr_sizes, rr_edge_sizes,
                                     nrep_rr_sizes, axis_name, wire_dtype,
                                     fresh):
    halo_next = _replica_stale_ring_exchange(
        x, halo_in, rsend_idx, nrep_rsend_idx, nrep_ring_dst, rr_sizes,
        nrep_rr_sizes, axis_name, wire_dtype, fresh)
    halo_used = halo_next if fresh else halo_in
    local = spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, x,
                     buckets)
    # the fold always consumes the FULL ring envelope through the exact
    # redge_* sequence — a sync step therefore reproduces the exact ragged
    # path's bits, and a stale step folds the carried mixture (kept slots
    # one step old, replica slots last-sync old)
    remote = _stale_ragged_fold(halo_used, redge_dst, redge_src, redge_w,
                                rr_sizes, rr_edge_sizes, x.shape[0])
    return local + remote, halo_next


@partial(jax.custom_vjp, nondiff_argnums=(15, 16, 17, 18, 19, 20, 21, 22))
def pspmm_replica_stale_ragged(x, halo_in, ghalo_in, base_in, rsend_idx,
                               nrep_rsend_idx, nrep_ring_dst,
                               ell_idx, ell_w, ltail_dst, ltail_src,
                               ltail_w, redge_dst, redge_src, redge_w,
                               buckets, rr_sizes, rr_edge_sizes,
                               nrep_rr_sizes, axis_name=AXIS,
                               wire_dtype=None, gwire_dtype=None,
                               fresh=False):
    """``PSpMM`` composing hot-halo replication with the round-structured
    stale carry on the ragged ring — the replica carry IS a region of the
    stale ring envelope (``nrep_ring_dst`` maps shrunken receives into the
    full concat; replica positions are simply never overwritten between
    syncs).

    Stale step: live rounds of the SHRUNKEN ``nrep_rr_sizes`` ring, no
    in-step consumer.  Sync step: the full ring consumed fresh —
    f32-bit-identical to the exact ragged path (``pspmm_stale_ragged``'s
    contract chains through).  ``base_in`` passes through (no delta
    composition); same carry arity as ``pspmm_stale_ragged``.
    Symmetric-Â only."""
    out, halo_next = _pspmm_replica_stale_ragged_once(
        x, halo_in, rsend_idx, nrep_rsend_idx, nrep_ring_dst, ell_idx,
        ell_w, ltail_dst, ltail_src, ltail_w, redge_dst, redge_src,
        redge_w, buckets, rr_sizes, rr_edge_sizes, nrep_rr_sizes,
        axis_name, wire_dtype, fresh)
    return out, halo_next, base_in


def _pspmm_replica_stale_ragged_fwd(x, halo_in, ghalo_in, base_in,
                                    rsend_idx, nrep_rsend_idx,
                                    nrep_ring_dst, ell_idx, ell_w,
                                    ltail_dst, ltail_src, ltail_w,
                                    redge_dst, redge_src, redge_w,
                                    buckets, rr_sizes, rr_edge_sizes,
                                    nrep_rr_sizes, axis_name, wire_dtype,
                                    gwire_dtype, fresh):
    out, halo_next = _pspmm_replica_stale_ragged_once(
        x, halo_in, rsend_idx, nrep_rsend_idx, nrep_ring_dst, ell_idx,
        ell_w, ltail_dst, ltail_src, ltail_w, redge_dst, redge_src,
        redge_w, buckets, rr_sizes, rr_edge_sizes, nrep_rr_sizes,
        axis_name, wire_dtype, fresh)
    res = (ghalo_in, rsend_idx, nrep_rsend_idx, nrep_ring_dst, ell_idx,
           ell_w, ltail_dst, ltail_src, ltail_w, redge_dst, redge_src,
           redge_w)
    return (out, halo_next, base_in), res


def _pspmm_replica_stale_ragged_bwd(buckets, rr_sizes, rr_edge_sizes,
                                    nrep_rr_sizes, axis_name, wire_dtype,
                                    gwire_dtype, fresh, res, cts):
    (ghalo_in, rsend_idx, nrep_rsend_idx, nrep_ring_dst, ell_idx, ell_w,
     ltail_dst, ltail_src, ltail_w, redge_dst, redge_src, redge_w) = res
    g, _, _ = cts            # carry cotangents are structurally zero
    gh_next = _replica_stale_ring_exchange(
        g, ghalo_in, rsend_idx, nrep_rsend_idx, nrep_ring_dst, rr_sizes,
        nrep_rr_sizes, axis_name, gwire_dtype, fresh)
    gh_used = gh_next if fresh else ghalo_in
    gx = (spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, g, buckets)
          + _stale_ragged_fold(gh_used, redge_dst, redge_src, redge_w,
                               rr_sizes, rr_edge_sizes, g.shape[0]))
    return (gx, None, gh_next, None, *[None] * 11)


pspmm_replica_stale_ragged.defvjp(_pspmm_replica_stale_ragged_fwd,
                                  _pspmm_replica_stale_ragged_bwd)


# --------------------------------------------------------- partial refresh
# Drift-driven PARTIAL replica refresh (``--refresh-band``, CaPGNN's cache
# policy, arXiv:2508.13716): instead of PR-10's all-or-nothing refresh, a
# refresh step ships ONLY the replica rows whose sender-side drift crosses
# the band, as a quantized DELTA against the refresh baseline — both ends
# accumulate the identical increment (the ``_stale_exchange`` lockstep
# contract), so refreshed rows land in exact sender/receiver agreement and
# un-refreshed rows ship exact zeros (no change on either end).  The wire
# is the shrunken replica-step exchange PLUS one replica-only side-channel
# a2a per direction (``ronly_*`` buckets: exactly the rows
# ``ensure_replicas`` deleted); the gradient side channel refreshes the
# gradient replicas for the SAME masked rows with set semantics (one extra
# 0/1 indicator lane tells the receiver which slots carry fresh values).
# Dense-a2a transport only — the trainer gates the composition.


def _partial_mask(x, base_in, rep_rows, rep_row_count, band):
    """Sender-side per-row refresh decision: row i refreshes iff
    ``‖x_i − base_i‖² > band² · ‖base_i‖²`` (relative drift — a zero
    baseline with any drift always refreshes).  Returns ``(diff, mask,
    row_valid)`` over the padded (RS, f) owned-replica table."""
    xr = jnp.take(x, rep_rows, axis=0)                       # (RS, f)
    row_valid = (jnp.arange(rep_rows.shape[0]) < rep_row_count)
    diff = (xr - base_in) * row_valid[:, None].astype(x.dtype)
    drift2 = jnp.sum(jnp.square(diff), axis=-1)
    ref2 = jnp.sum(jnp.square(base_in), axis=-1)
    mask = (drift2 > (band * band) * ref2) & row_valid
    return diff, mask, row_valid


def _pspmm_replica_partial_once(x, rep_in, base_in, nrep_send_idx,
                                nrep_halo_src, rep_slots, rep_rows,
                                rep_row_count, ronly_send_idx, ronly_counts,
                                ronly_base_pos, rep_recv_src,
                                ell_idx, ell_w, ltail_dst, ltail_src,
                                ltail_w, hedge_dst, hedge_src, hedge_w,
                                buckets, axis_name, halo_dtype, band):
    f = x.shape[-1]
    wdt = x.dtype if halo_dtype is None else jnp.dtype(halo_dtype)
    halo = halo_exchange(x, nrep_send_idx, nrep_halo_src, axis_name,
                         halo_dtype)
    diff, mask, _ = _partial_mask(x, base_in, rep_rows, rep_row_count, band)
    # the quantized increment, per OWNED replicated row: both ends add THIS
    # value, so sender baseline and every consumer replica stay in lockstep
    qinc = (diff * mask[:, None].astype(x.dtype)).astype(wdt).astype(x.dtype)
    slot_valid = (jnp.arange(ronly_send_idx.shape[-1])[None, :]
                  < ronly_counts[:, None])                    # (peers, RS')
    slot_active = (slot_valid
                   & jnp.take(mask, ronly_base_pos, axis=0))  # masked-in
    wire = (jnp.take(qinc, ronly_base_pos, axis=0)
            * slot_valid[..., None].astype(x.dtype)).astype(wdt)
    recv = a2a_or_identity(wire, axis_name)
    flat = recv.reshape(-1, f).astype(x.dtype)
    rep_valid = (rep_slots < halo.shape[0])[:, None].astype(x.dtype)
    inc = jnp.take(flat, rep_recv_src, axis=0) * rep_valid
    rep_next = rep_in + inc
    base_next = base_in + qinc
    halo = halo.at[rep_slots].set(rep_next.astype(halo.dtype), mode="drop")
    local = spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, x,
                     buckets)
    remote = spmm_local(hedge_dst, hedge_src, hedge_w, halo, x.shape[0])
    # per-chip count of side-channel slots that carried a fresh row — the
    # ACTUAL shipped true rows this layer (each consumer copy counts, like
    # every send-volume gauge); the trainer psums and books it
    nship = jnp.sum(slot_active.astype(jnp.int32))
    return local + remote, rep_next, base_next, nship, slot_active


@partial(jax.custom_vjp, nondiff_argnums=(21, 22, 23, 24))
def pspmm_replica_partial(x, rep_in, grep_in, base_in, nrep_send_idx,
                          nrep_halo_src, rep_slots, rep_rows, rep_row_count,
                          ronly_send_idx, ronly_counts, ronly_base_pos,
                          rep_recv_src, ell_idx, ell_w,
                          ltail_dst, ltail_src, ltail_w,
                          hedge_dst, hedge_src, hedge_w, buckets,
                          axis_name=AXIS, halo_dtype=None, band=0.0):
    """``PSpMM`` with a drift-driven PARTIAL replica refresh (the
    ``--refresh-band`` refresh step — see the section comment).

    Ships the shrunken replica-step exchange plus the replica-only side
    channel of masked deltas; consumers see ``rep_next`` (refreshed where
    shipped, carried otherwise) in their replica halo slots.  The backward
    mirrors it: the gradient side channel refreshes ``grep`` for the SAME
    masked rows (fresh values + indicator lane).  Returns ``(out,
    rep_next, base_next, nship)`` where ``nship`` is this chip's count of
    side-channel slots that actually carried a row — the booking figure
    for CommStats/step_cost.  Symmetric-Â, dense-a2a transport only."""
    out, rep_next, base_next, nship, _ = _pspmm_replica_partial_once(
        x, rep_in, base_in, nrep_send_idx, nrep_halo_src, rep_slots,
        rep_rows, rep_row_count, ronly_send_idx, ronly_counts,
        ronly_base_pos, rep_recv_src, ell_idx, ell_w, ltail_dst, ltail_src,
        ltail_w, hedge_dst, hedge_src, hedge_w, buckets, axis_name,
        halo_dtype, band)
    return out, rep_next, base_next, nship


def _pspmm_replica_partial_fwd(x, rep_in, grep_in, base_in, nrep_send_idx,
                               nrep_halo_src, rep_slots, rep_rows,
                               rep_row_count, ronly_send_idx, ronly_counts,
                               ronly_base_pos, rep_recv_src, ell_idx, ell_w,
                               ltail_dst, ltail_src, ltail_w,
                               hedge_dst, hedge_src, hedge_w, buckets,
                               axis_name, halo_dtype, band):
    out, rep_next, base_next, nship, slot_active = \
        _pspmm_replica_partial_once(
            x, rep_in, base_in, nrep_send_idx, nrep_halo_src, rep_slots,
            rep_rows, rep_row_count, ronly_send_idx, ronly_counts,
            ronly_base_pos, rep_recv_src, ell_idx, ell_w, ltail_dst,
            ltail_src, ltail_w, hedge_dst, hedge_src, hedge_w, buckets,
            axis_name, halo_dtype, band)
    res = (grep_in, slot_active, nrep_send_idx, nrep_halo_src, rep_slots,
           rep_rows, ronly_base_pos, rep_recv_src, ell_idx, ell_w,
           ltail_dst, ltail_src, ltail_w, hedge_dst, hedge_src, hedge_w)
    return (out, rep_next, base_next, nship), res


def _pspmm_replica_partial_bwd(buckets, axis_name, halo_dtype, band, res,
                               cts):
    (grep_in, slot_active, nrep_send_idx, nrep_halo_src, rep_slots,
     rep_rows, ronly_base_pos, rep_recv_src, ell_idx, ell_w,
     ltail_dst, ltail_src, ltail_w, hedge_dst, hedge_src, hedge_w) = res
    g, _, _, _ = cts         # carry/count cotangents are structurally zero
    f = g.shape[-1]
    wdt = g.dtype if halo_dtype is None else jnp.dtype(halo_dtype)
    ghalo = halo_exchange(g, nrep_send_idx, nrep_halo_src, axis_name,
                          halo_dtype)
    # gradient side channel, SAME mask as the forward: fresh gradient rows
    # for the masked slots plus one 0/1 indicator lane (set semantics —
    # the receiver cannot otherwise tell "not refreshed" from a zero row)
    grows = jnp.take(g, rep_rows, axis=0)                     # (RS, f)
    act = slot_active.astype(g.dtype)[..., None]              # (peers,RS',1)
    gsel = jnp.take(grows, ronly_base_pos, axis=0) * act
    gwire = jnp.concatenate([gsel, act], axis=-1).astype(wdt)
    grecv = a2a_or_identity(gwire, axis_name)
    gflat = grecv.reshape(-1, f + 1).astype(g.dtype)
    vals = jnp.take(gflat, rep_recv_src, axis=0)
    rep_valid = (rep_slots < ghalo.shape[0])[:, None].astype(g.dtype)
    refreshed = vals[:, f:] * rep_valid                       # (RP, 1)
    grep_next = grep_in * (1.0 - refreshed) + vals[:, :f] * refreshed
    gtab = ghalo.at[rep_slots].set(grep_next.astype(ghalo.dtype),
                                   mode="drop")
    gx = (spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, g, buckets)
          + spmm_local(hedge_dst, hedge_src, hedge_w, gtab, g.shape[0]))
    return (gx, None, grep_next, None, *[None] * 17)


pspmm_replica_partial.defvjp(_pspmm_replica_partial_fwd,
                             _pspmm_replica_partial_bwd)


# --------------------------------------------------------------------- stale
# Pipelined one-step-stale exchange (PipeGCN-style, arXiv:2203.10428): layer ℓ
# of step t aggregates with the halo received during step t−1, and step t's
# exchange is issued with NO consumer inside the step — XLA is free to
# schedule the all_to_all entirely behind local SpMM + dense compute, turning
# the per-layer exchange barrier into a background transfer.  The backward
# mirrors it: the gradient halo consumed at step t was exchanged at step t−1
# (bounded-staleness features AND gradients, the combination PipeGCN shows
# converges at no accuracy loss).  Symmetric-Â only, like ``pspmm_ell_sym``.


def _stale_exchange(x, halo_in, base_in, send_idx, halo_src, axis_name,
                    delta, wire_dtype, fresh):
    """Issue step t's halo exchange; return ``(halo_next, base_next)``.

    ``delta`` (CaPGNN-style halo-delta caching, arXiv:2508.13716): the wire
    carries ``x_t − base`` per boundary row, quantized to ``wire_dtype``
    (bf16 — half the a2a bytes), and BOTH ends accumulate the identical
    quantized increment — the sender into ``base`` (its model of what every
    receiver holds), the receiver into its cached halo — so the two stay in
    exact lockstep and quantization error never compounds into disagreement.
    A ``fresh`` step re-bases with the FULL f32 row on the wire: both ends
    reset to the exact value, so accumulated rounding drift goes to zero
    (not to one more bf16 rounding) and a delta run at ``sync_every=1`` is
    exact-mode math.  The attribution model charges these steps the f32
    wire itemsize (``obs/attribution.py`` — the per-step itemsize split).
    """
    full = jnp.take(x, send_idx, axis=0)                     # (k, S, f)
    if delta:
        if fresh:
            recv = a2a_or_identity(full, axis_name)
            flat = recv.reshape(-1, x.shape[-1])
            return jnp.take(flat, halo_src, axis=0), full
        wdt = jnp.bfloat16 if wire_dtype is None else jnp.dtype(wire_dtype)
        wire = (full - base_in).astype(wdt)
        recv = a2a_or_identity(wire, axis_name)
        flat = recv.reshape(-1, x.shape[-1]).astype(x.dtype)
        inc = jnp.take(flat, halo_src, axis=0)
        return halo_in + inc, base_in + wire.astype(base_in.dtype)
    halo_next = halo_exchange(x, send_idx, halo_src, axis_name, wire_dtype)
    return halo_next, base_in


def _pspmm_stale_once(x, halo_in, base_in, send_idx, halo_src, ell_idx, ell_w,
                      ltail_dst, ltail_src, ltail_w,
                      hedge_dst, hedge_src, hedge_w,
                      buckets, axis_name, delta, wire_dtype, fresh):
    halo_next, base_next = _stale_exchange(
        x, halo_in, base_in, send_idx, halo_src, axis_name, delta,
        wire_dtype, fresh)
    # stale step: the remote term reads the CARRY — nothing in this step
    # depends on the exchange just issued, so it runs behind the compute;
    # fresh (sync) step: the remote term waits for the exchange, exactly
    # the exact-mode dependence structure
    halo_used = halo_next if fresh else halo_in
    local = spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, x, buckets)
    remote = spmm_local(hedge_dst, hedge_src, hedge_w, halo_used, x.shape[0])
    return local + remote, halo_next, base_next


@partial(jax.custom_vjp, nondiff_argnums=(14, 15, 16, 17, 18, 19))
def pspmm_stale(x, halo_in, ghalo_in, base_in, send_idx, halo_src,
                ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                hedge_dst, hedge_src, hedge_w, buckets,
                axis_name=AXIS, delta=False, wire_dtype=None,
                gwire_dtype=None, fresh=False):
    """``PSpMM`` with a one-step-stale halo carry — the pipelined contract.

    Forward: ``out = Â_local·x + Â_halo·halo_in`` (the carry, exchanged last
    step) and step t's exchange is issued into ``halo_next`` with no
    in-step consumer.  Backward (symmetric Â): ``g_x = Â_local·g +
    Â_halo·ghalo_in`` — the stale GRADIENT halo — and the fresh gradient
    exchange ``halo_exchange(g)`` is emitted as the cotangent of the
    ``ghalo_in`` argument.  That channel is deliberate plumbing, not a real
    derivative: differentiate the caller w.r.t. its ``ghalo`` carry
    (``jax.value_and_grad(..., argnums=(params, ghalos))``) and the "grad"
    that comes back IS next step's gradient-halo carry.  ``fresh=True``
    compiles the periodic full-sync step: both halos are consumed fresh
    (exact-mode math) and the carries are refreshed as a byproduct.

    Returns ``(out, halo_next, base_next)``; the carries are aux outputs
    (their cotangents are ignored — they cross the step boundary, which
    per-step autodiff never differentiates through).
    """
    return _pspmm_stale_once(
        x, halo_in, base_in, send_idx, halo_src, ell_idx, ell_w,
        ltail_dst, ltail_src, ltail_w, hedge_dst, hedge_src, hedge_w,
        buckets, axis_name, delta, wire_dtype, fresh)


def _pspmm_stale_fwd(x, halo_in, ghalo_in, base_in, send_idx, halo_src,
                     ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                     hedge_dst, hedge_src, hedge_w, buckets,
                     axis_name, delta, wire_dtype, gwire_dtype, fresh):
    out = _pspmm_stale_once(
        x, halo_in, base_in, send_idx, halo_src, ell_idx, ell_w,
        ltail_dst, ltail_src, ltail_w, hedge_dst, hedge_src, hedge_w,
        buckets, axis_name, delta, wire_dtype, fresh)
    res = (ghalo_in, send_idx, halo_src, ell_idx, ell_w,
           ltail_dst, ltail_src, ltail_w, hedge_dst, hedge_src, hedge_w)
    return out, res


def _pspmm_stale_bwd(buckets, axis_name, delta, wire_dtype, gwire_dtype,
                     fresh, res, cts):
    (ghalo_in, send_idx, halo_src, ell_idx, ell_w,
     ltail_dst, ltail_src, ltail_w, hedge_dst, hedge_src, hedge_w) = res
    g, _, _ = cts            # carry cotangents are structurally zero
    # issue step t's gradient exchange; like the forward's, it has no
    # consumer in the stale step (g_x reads the CARRY), so it too rides
    # behind compute.  It leaves through the ghalo_in cotangent channel.
    gh_next = halo_exchange(g, send_idx, halo_src, axis_name, gwire_dtype)
    gh_used = gh_next if fresh else ghalo_in
    gx = (spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, g, buckets)
          + spmm_local(hedge_dst, hedge_src, hedge_w, gh_used, g.shape[0]))
    return (gx, None, gh_next, None, *[None] * 10)


pspmm_stale.defvjp(_pspmm_stale_fwd, _pspmm_stale_bwd)


# ------------------------------------------------------------- stale × ragged
# The composed mode (PipeGCN-complete): the one-step-stale carry of
# ``pspmm_stale`` ON the per-round ppermute ring of ``pspmm_ragged_sym`` —
# both perf levers at once.  The carry is ROUND-STRUCTURED: instead of the
# dense ``(R, f)`` halo table (gathered out of a globally-padded ``(k, S)``
# receive window), each layer carries the ring's receive buffers themselves,
# round-major — round d of the ring occupies slots ``[Σ_{d'<d} S_{d'},
# Σ_{d'<d} S_{d'} + S_d)`` of a ``(Σ_d S_d, f)`` table (``CommPlan.rr_sizes``
# sizes the rounds; empty rounds occupy zero slots and vanish at trace time).
# The fold consumes the carry through the SAME per-round ``redge_*``
# scatter-add sequence as ``_ragged_remote``, so a full-sync step is
# f32-bit-identical to the exact ragged path (and hence to the dense exact
# path — the PR-4 parity contract chains through), while a stale step's
# per-round exchanges have no same-step consumer at all: round d of step t's
# ppermute rides behind round d+1's fold of the CARRIED buffers and behind
# every local slot pass.  The bf16 halo-delta cache composes per round: each
# round's wire carries its own quantized increment against a round-slice of
# the (ring-shaped, not ``(k, S, f)``) baseline.


def _stale_ragged_exchange(x, halo_in, base_in, rsend_idx, rr_sizes,
                           axis_name, delta, wire_dtype, fresh):
    """Issue step t's per-round ring exchange; return ``(halo_next,
    base_next)`` in the round-major carry layout described above.

    Per live round: ``delta`` stale steps ship the bf16 increment against
    the round's baseline slice and BOTH ends accumulate it (the
    ``_stale_exchange`` lockstep contract, per round); a ``fresh`` delta
    step re-bases with the full f32 buffer (exact, drift reset to zero);
    non-delta rounds ship the full value at ``wire_dtype`` — exactly the
    exact-mode ring's wire, so a full-sync step receives the exact ragged
    exchange's bits."""
    segs_h, segs_b = [], []
    live = ragged_live_rounds(rr_sizes)
    off = 0
    for d, sd in enumerate(rr_sizes, start=1):
        if d not in live:
            off += sd      # keep slice bookkeeping right under ANY rule
            continue
        full = jnp.take(x, rsend_idx[off: off + sd], axis=0)   # (S_d, f)
        if delta and not fresh:
            wdt = (jnp.bfloat16 if wire_dtype is None
                   else jnp.dtype(wire_dtype))
            base = base_in[off: off + sd]
            wire = (full - base).astype(wdt)
            recv = ppermute_or_identity(wire, axis_name, d)
            segs_h.append(halo_in[off: off + sd]
                          + recv.astype(x.dtype))
            segs_b.append(base + wire.astype(base.dtype))
        else:
            buf = full
            if not delta and wire_dtype is not None:
                buf = buf.astype(wire_dtype)
            recv = ppermute_or_identity(buf, axis_name, d)
            segs_h.append(recv.astype(x.dtype))
            if delta:                       # fresh re-base: exact f32 wire
                segs_b.append(full)
        off += sd
    if not segs_h:                          # k=1 / all-empty ring: (1, f) dummy
        return halo_in, base_in
    halo_next = segs_h[0] if len(segs_h) == 1 else jnp.concatenate(segs_h)
    if not delta:
        return halo_next, base_in
    base_next = segs_b[0] if len(segs_b) == 1 else jnp.concatenate(segs_b)
    return halo_next, base_next


def _stale_ragged_fold(halo_tab, redge_dst, redge_src, redge_w,
                       rr_sizes, rr_edge_sizes, num_rows: int):
    """Σ_d (round-d scatter-add of Â_halo·carry_d): ``_ragged_remote``'s
    fold with the round receive buffers read from the round-major carry
    table instead of this step's wire — same per-slot addition sequence,
    so consuming a FRESH carry reproduces the exact ragged path's bits."""
    remote = jnp.zeros((num_rows, halo_tab.shape[-1]), halo_tab.dtype)
    live = ragged_live_rounds(rr_sizes)
    off_s = off_e = 0
    for d, (sd, ed) in enumerate(zip(rr_sizes, rr_edge_sizes), start=1):
        if d not in live:
            off_s += sd   # keep slice bookkeeping right under ANY rule
            off_e += ed
            continue
        recv = halo_tab[off_s: off_s + sd]
        g = (jnp.take(recv, redge_src[off_e: off_e + ed], axis=0)
             * redge_w[off_e: off_e + ed, None])
        remote = remote.at[redge_dst[off_e: off_e + ed]].add(
            g, indices_are_sorted=True)
        off_s += sd
        off_e += ed
    return remote


def _pspmm_stale_ragged_once(x, halo_in, base_in, rsend_idx, ell_idx, ell_w,
                             ltail_dst, ltail_src, ltail_w,
                             redge_dst, redge_src, redge_w,
                             buckets, rr_sizes, rr_edge_sizes, axis_name,
                             delta, wire_dtype, fresh):
    halo_next, base_next = _stale_ragged_exchange(
        x, halo_in, base_in, rsend_idx, rr_sizes, axis_name, delta,
        wire_dtype, fresh)
    # stale step: the fold reads the CARRY — no round of this step's ring
    # has a same-step consumer, so every ppermute rides behind compute;
    # fresh (sync) step: the fold waits round by round, exactly the exact
    # ragged path's fold-as-you-arrive dependence structure
    halo_used = halo_next if fresh else halo_in
    local = spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, x, buckets)
    remote = _stale_ragged_fold(halo_used, redge_dst, redge_src, redge_w,
                                rr_sizes, rr_edge_sizes, x.shape[0])
    return local + remote, halo_next, base_next


@partial(jax.custom_vjp, nondiff_argnums=(13, 14, 15, 16, 17, 18, 19, 20))
def pspmm_stale_ragged(x, halo_in, ghalo_in, base_in, rsend_idx,
                       ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                       redge_dst, redge_src, redge_w,
                       buckets, rr_sizes, rr_edge_sizes,
                       axis_name=AXIS, delta=False, wire_dtype=None,
                       gwire_dtype=None, fresh=False):
    """``PSpMM`` with a one-step-stale ROUND-STRUCTURED halo carry — the
    composition of ``pspmm_stale``'s pipelined contract with
    ``pspmm_ragged_sym``'s per-round ppermute ring.

    Forward: ``out = Â_local·x + fold(halo_in)`` where ``halo_in`` is the
    round-major receive-buffer carry exchanged during step t−1, and step
    t's k−1 per-round ppermutes are issued into ``halo_next`` with no
    in-step consumer.  Backward (symmetric Â): ``g_x = Â_local·g +
    fold(ghalo_in)`` and the fresh gradient ring exchange leaves through
    the ``ghalo_in`` cotangent channel — the same deliberate plumbing as
    ``pspmm_stale`` (differentiate the caller w.r.t. its ``ghalos`` carry
    and the "grad" that comes back IS next step's carry).  ``fresh=True``
    compiles the full-sync step: both carries are consumed fresh, which is
    f32-bit-identical to the exact ragged path (``tests/test_stale_ragged``
    pins the ``sync_every=1`` trajectory ``==`` the dense exact one).

    Returns ``(out, halo_next, base_next)``; the carries are aux outputs
    whose cotangents are structurally zero (they cross the step boundary).
    Symmetric-Â only, like every ragged/stale op.
    """
    return _pspmm_stale_ragged_once(
        x, halo_in, base_in, rsend_idx, ell_idx, ell_w,
        ltail_dst, ltail_src, ltail_w, redge_dst, redge_src, redge_w,
        buckets, rr_sizes, rr_edge_sizes, axis_name, delta, wire_dtype,
        fresh)


def _pspmm_stale_ragged_fwd(x, halo_in, ghalo_in, base_in, rsend_idx,
                            ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
                            redge_dst, redge_src, redge_w,
                            buckets, rr_sizes, rr_edge_sizes, axis_name,
                            delta, wire_dtype, gwire_dtype, fresh):
    out = _pspmm_stale_ragged_once(
        x, halo_in, base_in, rsend_idx, ell_idx, ell_w,
        ltail_dst, ltail_src, ltail_w, redge_dst, redge_src, redge_w,
        buckets, rr_sizes, rr_edge_sizes, axis_name, delta, wire_dtype,
        fresh)
    res = (ghalo_in, rsend_idx, ell_idx, ell_w, ltail_dst, ltail_src,
           ltail_w, redge_dst, redge_src, redge_w)
    return out, res


def _pspmm_stale_ragged_bwd(buckets, rr_sizes, rr_edge_sizes, axis_name,
                            delta, wire_dtype, gwire_dtype, fresh, res, cts):
    (ghalo_in, rsend_idx, ell_idx, ell_w, ltail_dst, ltail_src, ltail_w,
     redge_dst, redge_src, redge_w) = res
    g, _, _ = cts            # carry cotangents are structurally zero
    # step t's gradient ring exchange: full-value wire at gwire_dtype (the
    # delta cache is a feature-wire lever), no same-step consumer on stale
    # steps — it leaves through the ghalo_in cotangent channel
    gh_next, _ = _stale_ragged_exchange(
        g, ghalo_in, ghalo_in, rsend_idx, rr_sizes, axis_name, False,
        gwire_dtype, fresh)
    gh_used = gh_next if fresh else ghalo_in
    gx = (spmm_ell(ell_idx, ell_w, ltail_dst, ltail_src, ltail_w, g, buckets)
          + _stale_ragged_fold(gh_used, redge_dst, redge_src, redge_w,
                               rr_sizes, rr_edge_sizes, g.shape[0]))
    return (gx, None, gh_next, None, *[None] * 9)


pspmm_stale_ragged.defvjp(_pspmm_stale_ragged_fwd, _pspmm_stale_ragged_bwd)


# ----------------------------------------------------- typed (relational)
# the typed passes run beside each other in one program, so their scans
# unroll only as far as this many bytes of live slot temporaries: compiled
# for the v5e at the ogbn-mag shape the step's temporaries read 13.8 GB at
# the default 3 GB (PERF.md §6, PR 33)
_TYPED_SCAN_LIVE = 1024**3


def _typed_pass(out, arrays, layout, height: int, table, halo, weight: str):
    """``out`` (or nothing yet) plus ONE relation's slots — the layout
    ``models/rgcn.py`` builds for an ordered pair of node types: ELL buckets
    over the destination type's ``height`` rows, what lies past a bucket's
    width as virtual rows, the halo-source edges likewise.  Local slots
    gather ``table`` (the source type's own rows), halo slots ``halo``;
    ``weight`` picks the forward (``"wf"``) or the transposed (``"wb"``)
    weight array of the same slots."""
    buckets, tail_classes, halo_classes = layout
    f = table.shape[-1]
    with scope("agg_slots"):
        parts = bucketed_slot_reduce(
            arrays["e_idx"], arrays["e_" + weight], buckets,
            contrib=lambda idx, w: jnp.take(table, idx, axis=0) * w[:, None],
            init=lambda nb: jnp.zeros((nb, f), jnp.float32),
            **ell_policy(f, _TYPED_SCAN_LIVE))
        # buckets cover every row of the type, or there are none
        parts = parts or [jnp.zeros((height, f), jnp.float32)]
        ell = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
        out = ell if out is None else out + ell
    with scope("agg_tail"):
        out = fold_slots(out, table, arrays["t_idx"], arrays["t_" + weight],
                         arrays["t_row"], tail_classes)
    with scope("agg_halo_fold"):
        return fold_slots(out, halo, arrays["h_idx"], arrays["h_" + weight],
                          arrays["h_row"], halo_classes)


def _typed_exchange(parts, arrays, spec, axis_name):
    """The halo copy of one table a row — ``parts[t]`` the lanes of type t's
    rows (``None``: zeros), stacked in type order, the plan's exchange with
    its send rows renamed into that order.  Not called where no chip has a
    halo-source edge (k = 1: no table is stacked, nothing is sent)."""
    f = next(p.shape[-1] for p in parts if p is not None)
    with scope("dense"), subscope("rel_table"):
        table = jnp.concatenate(
            [jnp.zeros((h, f), jnp.float32) if p is None else p
             for p, h in zip(parts, spec.heights)], axis=0)
    return halo_exchange(table, arrays["send_rows"], arrays["halo_src"],
                         axis_name)


def _typed_forward(blocks, arrays, spec, axis_name):
    halo = (_typed_exchange(blocks, arrays, spec, axis_name)
            if spec.exchange else None)
    layouts = dict(spec.layouts)

    def one(s, d):
        with pair_scope(s, d):
            return _typed_pass(None, arrays["rels"][s, d], layouts[s, d],
                               spec.heights[d], blocks[s], halo, "wf")

    return tuple(tuple(one(s, d) for s in spec.sources[d]) for d in spec.dst)


def _typed_backward(cts, arrays, spec, axis_name):
    """The transposition: the backward of a relation s -> d walks the
    layout of the pair (d -> s) — the rows of s, their slots the same edges
    read from the other side — gathering d's cotangent block for s at the
    OTHER endpoint's weight.  One pass per relation whose forward ran and
    whose source needs a gradient; the blocks nobody reads are never
    formed."""
    ct_of = dict(zip(spec.dst, cts))
    layouts = dict(spec.layouts)
    f = cts[0][0].shape[-1]
    # per type the blocks some gradient type reads: side by side they are
    # what a row ships, every type padded to the most
    wanted = [[u for u in spec.sources[t] if u in spec.grad]
              if t in ct_of else [] for t in range(len(spec.heights))]
    most = max(map(len, wanted))

    def block(d, s):
        return ct_of[d][spec.sources[d].index(s)]

    halo = None
    if spec.exchange and most:
        halo = _typed_exchange(
            [jnp.concatenate([block(t, u) for u in us]
                             + [jnp.zeros((h, f), jnp.float32)]
                             * (most - len(us)), axis=1) if us else None
             for t, (us, h) in enumerate(zip(wanted, spec.heights))],
            arrays, spec, axis_name)
    grads = [None] * len(spec.heights)
    for s in spec.grad:
        for d in spec.dst:
            if s not in spec.sources[d]:
                continue
            at = wanted[d].index(s) * f
            # named after the layout walked: the pair (d -> s), rows of s
            with pair_scope(d, s):
                grads[s] = _typed_pass(
                    grads[s], arrays["rels"][d, s], layouts[d, s],
                    spec.heights[s], block(d, s),
                    None if halo is None else halo[:, at: at + f], "wb")
    return tuple(grads)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def typed_aggregate(blocks, arrays, spec, axis_name=AXIS):
    """Per-relation MEAN aggregation over one symmetric pattern whose rows
    have node types, a relation being an ordered pair of types (``D_r⁻¹ A_r``
    for every relation of a layer; ``models/rgcn.py``).

    ``blocks`` holds one ``(height_t, f)`` table per node type (``None``: a
    type the layer does not read), rows in the model's typed order; the
    result one tuple per destination type ``d`` of ``spec.dst``: array q,
    ``(height_d, f)``, the mean over d's neighbours of type
    ``sources[d][q]``.  Every relation has a slot layout of its own
    (``arrays["rels"][s, d]``: ``e_*`` / ``t_*`` / ``h_*`` indices, weights
    and fold rows; ``_typed_pass``), every directed edge lies in exactly one,
    and a pass runs the layouts of the relations live in it and no other.

    The operator is symmetric in PATTERN and not in values: ``(D_r⁻¹ A_r)ᵀ
    = A_rᵀ D_r⁻¹`` walks the reverse pair's slots with the other endpoint's
    degree (``*_wb`` beside ``*_wf``) and gathers the cotangent of the block
    its own type fills — a custom VJP on the same index arrays, gathers
    only, no scatter-add over edges.  Only the types of ``spec.grad`` get a
    gradient (the others' tables are data, or no relation out of them
    reaches ``spec.dst``); at k > 1 the backward exchange ships each row's
    wanted blocks side by side."""
    return _typed_forward(blocks, arrays, spec, axis_name)


def _typed_aggregate_fwd(blocks, arrays, spec, axis_name):
    return _typed_forward(blocks, arrays, spec, axis_name), arrays


def _typed_aggregate_bwd(spec, axis_name, arrays, cts):
    return _typed_backward(cts, arrays, spec, axis_name), None


typed_aggregate.defvjp(_typed_aggregate_fwd, _typed_aggregate_bwd)
