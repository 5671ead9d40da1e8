"""Partitioned GAT model: sharded edge-softmax attention over the halo exchange.

Reference being matched: ``GPU/PGAT.py`` — the paper's demonstration that the
partitioned halo exchange composes with graph attention.  Per layer the
reference computes ``Z = H·W``, scores ``e_ij = z1_i + z2_j`` with
``z1 = Z·a1, z2 = Z·a2``, masks by ``A > 0`` (here ``A != 0``, so
signed-weight graphs keep their edges — ADVICE r4), row-softmaxes, and aggregates
``H' = attention · Z`` (``GPU/PGAT.py:137-150``); Xavier init (``:132-135``);
gradients all-reduced like the GCN (``:152-157``).

Two deliberate capability upgrades over the reference (SURVEY.md §5.7):

  * the reference keeps a **dense global-shape** adjacency and softmaxes over
    the full row with zeros filled for non-edges (``:52-63,144-146``) — fine
    for a demo, unscalable and mass-leaking.  Here attention is a masked
    **edge-softmax over the local padded edge lists** (true neighbor softmax),
    so memory is O(local nnz), never O(n²);
  * the boundary exchange ships each boundary vertex's ``[Z_j, z2_j]`` (f+1
    floats) instead of raw H, so attention scores for halo neighbors are
    computed without a second exchange — one all_to_all per layer, same as GCN.

Per-chip code, meant to run inside ``shard_map`` over the 1D vertex mesh.
"""

from __future__ import annotations

import os as _os
from functools import partial

import jax
import jax.numpy as jnp

from ..ops.pspmm import (a2a_or_identity, halo_exchange, halo_exchange_ragged,
                         halo_exchange_ragged_multi)
from ..parallel.mesh import AXIS, vary
from .activations import get_activation

# plan arrays the GAT forward consumes (fullbatch ships exactly these):
# the bucketed combined-edge layout plus its hub tail
GAT_PLAN_FIELDS = ("send_idx", "halo_src", "cell_idx", "cell_w",
                   "ctail_dst", "ctail_src", "ctail_w", "row_valid")
# Under comm_schedule='ragged' the dense (k, S) send buckets are swapped for
# the per-round ppermute-ring layout (CommPlan.ensure_ragged) — the
# rsend_idx/rhalo_dst split is per-VERTEX and model-independent, so GAT
# reuses the exact arrays the GCN ring rides; only the table riding them
# (the (fout+1)-lane attention table) differs.
GAT_PLAN_FIELDS_RAGGED = ("rsend_idx", "rhalo_dst", "cell_idx", "cell_w",
                          "ctail_dst", "ctail_src", "ctail_w", "row_valid")
# Under the Pallas VMEM aggregator (``use_pallas_spmm`` fires for GAT too)
# the bucketed slot passes swap for mask-weighted runs of the dst-tile
# kernel over the COMBINED-edge tile classes
# (``CommPlan.ensure_pallas_cell_tiles``); the ragged flavor reads the
# ring's receive concat directly (``ptile_crsrc`` ring-re-based sources —
# no halo table, so ``rhalo_dst`` is NOT shipped).
GAT_PLAN_FIELDS_PALLAS = ("send_idx", "halo_src", "ptile_csrc", "ptile_cld",
                          "ptile_cw", "row_valid")
GAT_PLAN_FIELDS_PALLAS_RAGGED = ("rsend_idx", "ptile_crsrc", "ptile_cld",
                                 "ptile_cw", "row_valid")

# static comm spec threaded through the layer stack: ('a2a',) selects the
# dense all_to_all, ('ragged', rr_sizes, r) the per-round ppermute ring —
# hashable, so it rides custom_vjp's nondiff_argnums
COMM_A2A = ("a2a",)

_NEG = -1e30


def score_project(z, a2):
    """Per-row attention-score projection ``z2_i = z_i · a2`` as a ROW-LOCAL
    multiply-reduce instead of a matvec ``z @ a2``.

    Same math; the form matters for bit-reproducibility: XLA:CPU's gemv
    kernel makes each output element's accumulation order depend on the
    ROW's position and the matrix height (measured: permuting rows of a
    (339, 16) @ (16,) matvec changes bits, and sub-matrices disagree with
    the full product on scattered rows), while the elementwise-multiply +
    per-row reduce is position- and height-independent (each row reduces
    its own K-length chain).  The sub-graph serving path
    (``serve/subgraph.py``) recomputes boundary rows' scores from COMPACT
    receptive-set tables and pins f32 bit-identity (``==``) against
    ``evaluate()`` — only the row-local form can deliver that.  Every
    consumer (forward, backward remat, the serve stabilizer precompute)
    rides THIS helper so the projection cannot fork."""
    return jnp.sum(z * a2, axis=-1)


def gat_exchange_lane_widths(widths, compute_dtype: str | None = None):
    """Per-layer wire width of the GAT attention-table exchange, in
    f32-LANE equivalents — THE shared lane model for every byte-accounting
    consumer (``obs.attribution.step_cost``, ``CommStats`` — the
    schedule-selection ratio needs no lanes: they cancel, see
    ``resolve_comm_schedule``); change the forward's table forms and this
    together.

    Per layer (both exchange directions ship the same table shape):

      * f32 fused table ``[p ‖ u]``: ``fout + 1`` lanes;
      * f32 split pair (``fout`` features + 1 scalar, whether as the a2a's
        two dense dispatches or one two-lane ragged ring): the SAME
        ``fout + 1`` lanes across its buffers;
      * bf16 packed (even ``fout``): the bit-paired ``fout/2 + 1`` f32
        lanes;
      * bf16 unpacked (odd ``fout``): a ``(fout+1)``-lane bf16 table =
        ``(fout+1)/2`` f32-lane equivalents.

    Expressing narrow dtypes as f32-lane equivalents keeps one itemsize (4)
    for every downstream byte figure.
    """
    out = []
    for fout in widths:
        fout = int(fout)
        if compute_dtype == "bfloat16":
            out.append(fout // 2 + 1 if fout % 2 == 0 else (fout + 1) // 2)
        else:
            out.append(fout + 1)
    return out


def init_gat_params(rng: jax.Array, dims: list[tuple[int, int]]):
    """Xavier-normal params per layer: ``w`` (fin,fout), ``a1``/``a2`` (fout,).

    The reference's single (2·fout, 1) attention vector (``GPU/PGAT.py:129``)
    is split into its two halves ``a1``/``a2`` — algebraically identical
    (``e_ij = [z_i ‖ z_j]·a = z_i·a1 + z_j·a2``), and the halves are what the
    sharded score computation needs separately.
    """
    xavier = jax.nn.initializers.glorot_normal()
    xavier_vec = jax.nn.initializers.normal(stddev=1.0)
    params = []
    for k, (fin, fout) in zip(jax.random.split(rng, len(dims)), dims):
        kw, k1, k2 = jax.random.split(k, 3)
        params.append({
            "w": xavier(kw, (fin, fout), jnp.float32),
            "a1": xavier_vec(k1, (fout,), jnp.float32) / jnp.sqrt(fout),
            "a2": xavier_vec(k2, (fout,), jnp.float32) / jnp.sqrt(fout),
        })
    return params


def edge_softmax(scores, edge_mask, edge_dst, num_rows: int):
    """Numerically-stable softmax over incoming edges of each dst row.

    Segment-machinery form over a sorted COO edge list — for callers
    holding plain edge lists; unit-tested against a dense softmax.  The
    trainer path uses the streaming bucketed form in ``gat_layer_local``
    (itself parity-tested against the dense GAT oracle).
    """
    scores = jnp.where(edge_mask, scores, _NEG)
    row_max = jax.ops.segment_max(
        scores, edge_dst, num_segments=num_rows, indices_are_sorted=True)
    row_max = jnp.maximum(row_max, _NEG)            # empty segments: -inf → _NEG
    ex = jnp.where(edge_mask, jnp.exp(scores - row_max[edge_dst]), 0.0)
    denom = jax.ops.segment_sum(
        ex, edge_dst, num_segments=num_rows, indices_are_sorted=True)
    return ex / (denom[edge_dst] + 1e-9)


def gat_layer_local(
    w, a1, a2,
    h,                            # (B, fin) local rows
    send_idx, halo_src,           # halo plan
    cell_idx, cell_w,             # bucketed combined-edge layout (flat)
    ctail_dst, ctail_src, ctail_w,  # hub overflow tail (COO)
    row_valid=None,               # (B,) 1/0 — real vs pad rows
    buckets=((1, 1),),            # static ((nb, wb), ...) of cell layout
    axis_name: str = AXIS,
    comm=COMM_A2A,                # static transport spec (_exchange_table)
):
    """One sharded GAT layer for GENERAL (possibly asymmetric) edge
    patterns: the factored forward of ``gat_layer_sym`` with autodiff
    providing the backward.

    The factorization (see ``gat_layer_sym``) is pattern-independent:
    ``s_ij = z1_i + z2_j`` is shift-invariant under the row softmax, so
    ``out_i = (Σ_{j∈N(i)} u_j z_j) / (Σ_{j∈N(i)} u_j)`` with
    ``u_j = exp(z2_j − C)`` holds for any in-edge set — only the BACKWARD
    trick (transpose = the same gather passes) needs pattern symmetry.
    Routing this path through the same ``bucketed_slot_reduce`` core means
    the general path shares the GCN memory policy (budgeted unroll / scan
    over width slots) instead of hand-unrolling a Python loop per slot
    (the round-3 streaming form: ~7k ops/step at products scale).
    Autodiff's mechanical transpose (scatter-adds) carries the backward —
    slower than the symmetric custom VJP, and only taken when the plan's
    edge pattern genuinely is asymmetric.
    """
    if row_valid is None:
        row_valid = jnp.ones((h.shape[0],), jnp.float32)
    out, _, _, _, _ = _gat_factored_fwd_core(
        w, a2, h, send_idx, halo_src, cell_idx, cell_w,
        ctail_dst, ctail_src, ctail_w, row_valid, buckets, axis_name, comm)
    return out


@partial(jax.custom_vjp, nondiff_argnums=(12, 13, 14))
def gat_layer_sym(w, a1, a2, h, send_idx, halo_src, cell_idx, cell_w,
                  ctail_dst, ctail_src, ctail_w, row_valid, buckets,
                  axis_name=AXIS, comm=COMM_A2A):
    """``gat_layer_local`` in FACTORIZED form with a gather-only backward,
    for SYMMETRIC edge patterns (undirected graphs — the standing case).

    Two algebraic facts reshape the whole layer:

      * ``s_ij = z1_i + z2_j`` is SHIFT-INVARIANT under the row softmax: any
        per-row constant cancels, so ``z1``/``a1`` do not affect the output
        at all (``∂L/∂a1 = 0`` exactly; the reference's PGAT shares this —
        no LeakyReLU between the additive scores and the softmax,
        ``GPU/PGAT.py:137-150``) and α factorizes per SOURCE:
        ``α_ij = u_j / Σ_{j'∈N(i)} u_j'`` with ``u_j = exp(z2_j − C)``.
        The layer is exactly ``out_i = (Σ_j u_j z_j) / (Σ_j u_j)`` — two
        mask-weighted aggregations over the bucketed slots, both gathering
        128-lane rows (the v5e gather drops 3.2× the moment a row exceeds
        one 128-lane tile, so numerator rows ``u·z`` and a lane-broadcast
        denominator table are kept exactly 128 wide; the denominator pass
        row-sums its gathered tile, which also keeps XLA from narrowing the
        gather).  ``C`` is the global max of ``z2`` (one pmax): exact
        stabilization for score spreads < ~80 nats — beyond that f32
        attention is degenerate under ANY stabilization;

      * for a symmetric pattern, row ``j``'s in-edge slots enumerate exactly
        the rows ``i`` that aggregate ``j``, so the backward transposes
        ``N = P·(u z), D = P·u`` into the SAME gather passes over the
        exchanged ``[ḡ/D ‖ −(ḡ·out)/D]`` table — no scatter, no sort, and
        the halo's backward contribution arrives through a forward-style
        exchange (measured: autodiff's scatter transpose was ~223 ms of the
        320 ms online-softmax GAT epoch at ogbn-arxiv scale; this form
        benches 0.062 s).
    """
    out, _, _, _, _ = _gat_factored_fwd_core(
        w, a2, h, send_idx, halo_src, cell_idx, cell_w,
        ctail_dst, ctail_src, ctail_w, row_valid, buckets, axis_name, comm)
    return out


# Tail gathers above this size stream through a chunked scan instead of one
# shot: a power-law graph at products scale spills ~29M hub edges past the
# bucket width cap, and the one-shot tail gather materialized a 29.8 GB
# (tail, fout+1 -> 256-lane-padded) temp — an instant compile-time OOM on a
# 16 GB chip (measured round 4).  Chunking bounds the temp like the slot
# scan bounds bucket temps.  SGCN_GAT_TAIL_CHUNK overrides (bytes); read at
# call time so setting it after import (monkeypatch, A/B) works — ADVICE r4.
def _tail_chunk_bytes() -> int:
    return int(_os.environ.get("SGCN_GAT_TAIL_CHUNK", 256 * 1024**2))


# GAT programs run several slot reduces back to back (num+den, fwd+bwd), so
# each gets HALF the default scan-unroll liveness budget — one pass at the
# full budget measured as the margin of a 264 MB products-scale OOM.
_GAT_SCAN_LIVE = 3 * 1024**3 // 2

# Row count above which the denominator pass gathers the 1-D u directly
# instead of a (rows, 128) broadcast table (see _pair_slot_pass).
_ONED_U_ROWS = 1_000_000


def _edge_pass(cell_idx, cell_w, ctail_dst, ctail_src, ctail_w, buckets,
               b, contrib, init, slot_bytes):
    """Shared scaffold for every masked in-edge aggregation: bucketed slot
    reduce + hub-tail fold, generic over the per-slot ``contrib``'s output
    pytree (which also decodes the tail — the tail IS one more masked
    slot)."""
    from ..ops.pspmm import bucketed_slot_reduce

    outs = bucketed_slot_reduce(cell_idx, cell_w, buckets, contrib=contrib,
                                init=init, slot_bytes=slot_bytes,
                                scan_live_limit=_GAT_SCAN_LIVE)
    if len(outs) == 1:
        out = outs[0]
    else:
        out = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)

    t = ctail_src.shape[0]
    tail_chunk = _tail_chunk_bytes()
    if slot_bytes(t) <= tail_chunk:
        tc = contrib(ctail_src, ctail_w)
        return jax.tree.map(
            lambda acc, x: acc + jax.ops.segment_sum(
                x, ctail_dst, num_segments=b, indices_are_sorted=True),
            out, tc)

    # chunked tail: pad with weight-0 edges on the last (already-max) dst so
    # each chunk stays dst-sorted, then scan chunk-wise segment-sums.  The
    # carry IS the bucket output — fresh zero accumulators would hold
    # another (b, fout) array live (1.17 GB at products scale) for no reason.
    nchunks = -(-slot_bytes(t) // tail_chunk)
    chunk = -(-t // nchunks)
    pad = nchunks * chunk - t
    cd = jnp.pad(ctail_dst, (0, pad), constant_values=b - 1)
    cs = jnp.pad(ctail_src, (0, pad))
    cw = jnp.pad(ctail_w, (0, pad))

    def body(carry, xs):
        d_i, s_i, w_i = xs
        tc = contrib(s_i, w_i)
        return jax.tree.map(
            lambda acc, x: acc + jax.ops.segment_sum(
                x, d_i, num_segments=b, indices_are_sorted=True),
            carry, tc), None

    out, _ = jax.lax.scan(
        body, out,
        (cd.reshape(nchunks, chunk), cs.reshape(nchunks, chunk),
         cw.reshape(nchunks, chunk)))
    return out


# The FUSED one-gather-per-edge form applies ONLY while the (fout+1)-lane
# row fits one 128-lane tile.  Past a tile the micro numbers flatter it (a
# lone 2-tile gather out-rates two 1-tile gathers at GB tables, 142 vs
# 2×209 Mrows/s) but the REAL program pays XLA's tile padding: every
# (x, 129) f32 array physically doubles (measured 2.34 GB for the products
# table, "2.0x expansion"), and at products scale that padding alone tipped
# the step from fitting to a 17.07 GB compile-time OOM.  SGCN_GAT_FUSED=0
# forces the split form everywhere (A/B lever).


def _fused_form(fout: int) -> bool:
    """One-gather-per-edge only while the (fout+1)-lane row fits one tile
    (SGCN_GAT_FUSED: 0 forces split everywhere, 2 forces fused even past a
    tile — A/B levers; read at call time per ADVICE r4)."""
    mode = _os.environ.get("SGCN_GAT_FUSED", "1")   # 0=never, 2=always
    if mode == "0":
        return False
    if mode == "2":
        return True
    return fout + 1 <= 128


def _exchange_table(table, send_idx, halo_src, axis_name, comm=COMM_A2A):
    """Ship one boundary row table over the SELECTED transport and return
    its (R, d) halo block — the single dispatch point of the GAT exchange
    (``docs/comm_schedule.md``).  Under ``('a2a',)`` ``send_idx``/
    ``halo_src`` are the plan's dense ``(k, S)`` layout; under
    ``('ragged', rr_sizes, r)`` they are ``rsend_idx``/``rhalo_dst`` and
    the table rides the per-round-sized ppermute ring.  Halo rows are
    bit-identical either way (the ragged scatter writes each real slot
    exactly once), so every slot pass downstream is schedule-blind."""
    if comm[0] == "ragged":
        return halo_exchange_ragged(table, send_idx, halo_src,
                                    comm[1], comm[2], axis_name)
    return halo_exchange(table, send_idx, halo_src, axis_name)


def _exchange_rows_scalar(p, u, send_idx, halo_src, axis_name,
                          comm=COMM_A2A):
    """Exchange feature rows AND a per-row scalar without ever building a
    ``(B, fout+1)``-lane table: on the dense schedule the scalar rides its
    own (k, S) buffer (second all_to_all of negligible bytes), dodging the
    2× tile-padding tax a 129-lane f32 array pays.  On the ragged schedule
    both lanes ride ONE ring (``halo_exchange_ragged_multi``): the
    ``(S_d, fout+1)`` concatenation exists only at round size — never the
    (B, ·) table the split form is dodging — so the two dense dispatches
    per exchange collapse into one ppermute per live round.  Returns the
    concatenated ``[local; halo]`` pair
    ``(full_p (B+R, fout), full_u (B+R,))``."""
    if comm[0] == "ragged":
        halo_p, halo_u = halo_exchange_ragged_multi(
            (p, u), send_idx, halo_src, comm[1], comm[2], axis_name)
    else:
        halo_p = halo_exchange(p, send_idx, halo_src, axis_name)
        buf_u = jnp.take(u, send_idx, axis=0)                    # (k, S)
        recv_u = a2a_or_identity(buf_u, axis_name)
        halo_u = jnp.take(recv_u.reshape(-1), halo_src, axis=0)  # (R,)
    return (jnp.concatenate([p, halo_p], axis=0),
            jnp.concatenate([u, halo_u]))


def _mask_slot_pass(table, fout, cell_idx, cell_w, ctail_dst, ctail_src,
                    ctail_w, buckets, b):
    """FUSED masked Σ over in-edge slots of the ``(fout+1)``-wide ``[p ‖ u]``
    table: one gather per edge; both slices of the gathered row are consumed
    so XLA keeps a single full-row gather.  Callers use this only under
    ``_fused_form`` (row within one tile).
    Returns ``(N, D)``: (b, fout) feature sums and (b,) scalar sums."""
    def contrib(idx, wv):
        mask = (wv != 0).astype(jnp.float32)
        g = jnp.take(table, idx, axis=0).astype(jnp.float32)
        return g[:, :fout] * mask[:, None], g[:, fout] * mask

    return _edge_pass(cell_idx, cell_w, ctail_dst, ctail_src, ctail_w,
                      buckets, b, contrib,
                      init=lambda nb: (jnp.zeros((nb, fout), jnp.float32),
                                       jnp.zeros((nb,), jnp.float32)),
                      slot_bytes=lambda nb: nb * (fout + 1) * 4)


def _pair_slot_pass(full_p, full_u, fout, cell_idx, cell_w, ctail_dst,
                    ctail_src, ctail_w, buckets, b):
    """SPLIT masked Σ: feature-table gather + 128-lane broadcast-u gather
    (the row-sum consumes every lane, keeping that gather a fast full-tile
    fetch).  Taken when the fused row would cross a tile (fout ≥ 128):
    the 2-tile row out-rates two 1-tile gathers in isolation, but every
    129-lane f32 array physically DOUBLES under tile padding (measured
    2.0× at products scale) and that padding tipped the step into a
    compile-time OOM — so past one tile the split form wins end-to-end.

    The two aggregations run as SEPARATE edge passes, not one combined
    contrib: per-pass slot temps halve (one gather each), which doubles the
    scan-unroll headroom and lets the broadcast-u table die before the next
    pass's temps peak."""
    def contrib_n(idx, wv):
        mask = (wv != 0).astype(jnp.float32)
        return jnp.take(full_p, idx, axis=0).astype(jnp.float32) \
            * mask[:, None]

    n_out = _edge_pass(cell_idx, cell_w, ctail_dst, ctail_src, ctail_w,
                       buckets, b, contrib_n,
                       init=lambda nb: jnp.zeros((nb, fout), jnp.float32),
                       slot_bytes=lambda nb: nb * fout * 4)

    rows = full_p.shape[0]
    if rows >= _ONED_U_ROWS:
        # huge tables: gather the scalar u directly (1-D, no tile padding).
        # A narrow gather runs ~1.45× slower per row than a 128-lane one
        # (143 vs 209 Mrows/s measured at 2.45M rows), but the (rows, 128)
        # broadcast-u table it replaces is 1.6 GB per pass at products
        # scale — the difference between fitting and the round-4 OOMs.
        def contrib_d(idx, wv):
            mask = (wv != 0).astype(jnp.float32)
            return jnp.take(full_u, idx, axis=0).astype(jnp.float32) * mask

        d_out = _edge_pass(cell_idx, cell_w, ctail_dst, ctail_src, ctail_w,
                           buckets, b, contrib_d,
                           init=lambda nb: jnp.zeros((nb,), jnp.float32),
                           slot_bytes=lambda nb: nb * 8)
        return n_out, d_out

    # small tables: 128-lane broadcast-u gather (full-tile fetch at the fast
    # 1-tile row rate; the row-sum consumes every lane)
    ub = jnp.broadcast_to(full_u[:, None], (rows, 128))

    def contrib_d(idx, wv):
        mask = (wv != 0).astype(jnp.float32)
        return jnp.take(ub, idx, axis=0).astype(jnp.float32).sum(axis=-1) \
            * (mask / 128)

    d_out = _edge_pass(cell_idx, cell_w, ctail_dst, ctail_src, ctail_w,
                       buckets, b, contrib_d,
                       init=lambda nb: jnp.zeros((nb,), jnp.float32),
                       slot_bytes=lambda nb: nb * 128 * 4)
    return n_out, d_out


def _pack_rows(x16):
    """(B, f) bf16 → (B, f/2) f32 by bit-pairing adjacent lanes."""
    b, f = x16.shape
    return jax.lax.bitcast_convert_type(
        x16.reshape(b, f // 2, 2), jnp.float32)


def _unpack_rows(xp, f):
    """(B, f/2) f32 → (B, f) bf16 (inverse of ``_pack_rows``)."""
    return jax.lax.bitcast_convert_type(xp, jnp.bfloat16).reshape(
        xp.shape[0], f)


def _packed_aggregate(rows16, scalar, fout, send_idx, halo_src, cell_idx,
                      cell_w, ctail_dst, ctail_src, ctail_w, buckets, b,
                      axis_name, comm=COMM_A2A):
    """Masked Σ over in-edges of ``(rows16[src], scalar[src])`` — ONE gather
    per edge: the bf16 feature row bit-packs into ``fout/2`` f32 lanes and
    the scalar rides the next lane, so the whole (fout/2 + 1)-wide gathered
    row stays inside one 128-lane tile for fout ≤ 254 (the v5e gather drops
    3.2× past one tile).  Exchange ships the same packed table — half the
    ICI bytes of the f32 path — over whichever transport ``comm`` selects.
    Used by the bf16 compute path; masked slots contribute exactly 0 either
    way."""
    half = fout // 2
    table = jnp.concatenate([_pack_rows(rows16), scalar[:, None]], axis=-1)
    halo = _exchange_table(table, send_idx, halo_src, axis_name, comm)
    full = jnp.concatenate([table, halo], axis=0)     # (B+R, half+1)

    def contrib(idx, wv):
        mask = (wv != 0).astype(jnp.float32)
        g = jnp.take(full, idx, axis=0)               # (nb, half+1)
        rows = _unpack_rows(g[:, :half], fout).astype(jnp.float32)
        return rows * mask[:, None], g[:, half] * mask

    return _edge_pass(cell_idx, cell_w, ctail_dst, ctail_src, ctail_w,
                      buckets, b, contrib,
                      init=lambda nb: (jnp.zeros((nb, fout), jnp.float32),
                                       jnp.zeros((nb,), jnp.float32)),
                      slot_bytes=lambda nb: nb * (half + 1 + fout) * 4)


def _is_pallas_comm(comm) -> bool:
    return comm[0] in ("a2a+pallas", "ragged+pallas")


def _gat_pallas_aggregate(p, s, fout, form, send_idx, halo_src,
                          csrc, cw, cld, axis_name, comm):
    """The GAT attention slot pass on the VMEM kernel: masked Σ of the
    ``[p ‖ s]`` table over combined-edge tile classes.  The WIRE is
    form-for-form the slot-pass path's (``gat_table_form`` — the audit's
    census does not change): ``fused`` ships one ``(·, fout+1)`` table and
    runs ONE kernel pass whose trailing lane is the scalar sum; ``split``
    ships the feature table and the scalar separately (two dense
    dispatches / one two-lane ring) and runs two kernel passes.  The
    ragged flavor feeds the ring's round-major receive concat to the
    kernel directly (``pallas_ring_concat`` — no halo-table scatter), with
    tile sources ring-re-based at plan time, so its bits equal the a2a
    flavor's (same tile fold order).  Returns ``(N (b, fout), D (b,))``.
    """
    from ..ops.pallas_spmm import gat_pallas_pass, pallas_ring_concat

    tbp, cclasses, pemu = comm[-1]
    b = p.shape[0]
    ragged = comm[0] == "ragged+pallas"
    if form == "fused":
        table = jnp.concatenate([p, s[:, None]], axis=-1)
        halo = (pallas_ring_concat(table, send_idx, comm[1], axis_name)
                if ragged
                else halo_exchange(table, send_idx, halo_src, axis_name))
        full = jnp.concatenate([table, halo], axis=0)
        out = gat_pallas_pass(csrc, cld, cw, full.astype(jnp.float32),
                              cclasses, tbp, pemu, axis_name, b)
        return out[:, :fout], out[:, fout]
    if form != "split":
        raise ValueError(
            f"the Pallas slot pass takes the fused/split table forms, not "
            f"{form!r} (use_pallas_spmm gates the packed bf16 form out)")
    if ragged:
        # one two-lane ring per exchange, exactly _exchange_rows_scalar's
        # ragged wire; the concat exists only at round size
        pair = jnp.concatenate([p, s[:, None]], axis=-1)
        ring = pallas_ring_concat(pair, send_idx, comm[1], axis_name)
        full_p = jnp.concatenate([p, ring[:, :fout]], axis=0)
        full_u = jnp.concatenate([s, ring[:, fout]])
    else:
        # the dense split wire has ONE home — the slot-pass path's helper
        full_p, full_u = _exchange_rows_scalar(p, s, send_idx, halo_src,
                                               axis_name)
    num = gat_pallas_pass(csrc, cld, cw, full_p.astype(jnp.float32),
                          cclasses, tbp, pemu, axis_name, b)
    den = gat_pallas_pass(csrc, cld, cw,
                          full_u[:, None].astype(jnp.float32),
                          cclasses, tbp, pemu, axis_name, b)[:, 0]
    return num, den


def _use_packed(dtype, fout: int) -> bool:
    return dtype == jnp.bfloat16 and fout % 2 == 0


def gat_table_form(fout: int, compute_dtype=None) -> str:
    """The table form one GAT exchange ships at width ``fout`` —
    ``'fused'`` (one ``(·, fout+1)`` table), ``'split'`` (feature rows +
    scalar as separate dense dispatches / one two-lane ring) or
    ``'packed'`` (the bit-paired ``(·, fout/2+1)`` f32 table of the bf16
    compute path).  THE shared encoding of the layer's dispatch selection
    (``_gat_factored_fwd_core`` / ``_gat_layer_sym_bwd`` branch on it, both
    directions ship the same form) — the static-analysis collective census
    (``sgcn_tpu/analysis``) derives the expected per-exchange dispatch
    count and wire shape from it, so the forward cannot change form
    without the HLO audit noticing.  ``compute_dtype`` accepts the
    trainer-level string, a jnp/np dtype, or ``None`` (f32)."""
    bf16 = (compute_dtype is not None
            and jnp.dtype(compute_dtype) == jnp.bfloat16)
    if _use_packed(jnp.bfloat16 if bf16 else jnp.float32, fout):
        return "packed"
    return "fused" if _fused_form(fout) else "split"


def _gat_factored_fwd_core(w, a2, h, send_idx, halo_src, cell_idx, cell_w,
                           ctail_dst, ctail_src, ctail_w, row_valid, buckets,
                           axis_name, comm=COMM_A2A):
    b = h.shape[0]
    z = h @ w
    fout = z.shape[-1]
    z2 = score_project(z, a2)
    # global stabilizer over REAL rows only: pad rows carry z2 = 0, which
    # would floor the max at 0 and turn the underflow guard into an absolute
    # threshold instead of the documented relative-spread limit
    z2m = jnp.where(row_valid > 0, z2.astype(jnp.float32), -jnp.inf)
    # C shifts every score equally, so `out` is EXACTLY invariant to it
    # (∂out/∂C = 0 analytically) — stop_gradient both encodes that and lets
    # the general path autodiff through this core (pmax has no diff rule)
    cg = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(z2m)), axis_name)
    u = jnp.exp(z2.astype(jnp.float32) - cg)         # (B,) in (0, 1]
    form = gat_table_form(fout, z.dtype)
    if _is_pallas_comm(comm):
        # VMEM-kernel slot pass: under the Pallas comm spec the cell_idx/
        # cell_w/ctail_dst slots carry the combined TILE arrays
        # (ptile_c[r]src / ptile_cw / ptile_cld — see gat_forward_local)
        p = u.astype(z.dtype)[:, None] * z
        num, den = _gat_pallas_aggregate(
            p, u.astype(z.dtype), fout, form, send_idx, halo_src,
            cell_idx, cell_w, ctail_dst, axis_name, comm)
    elif form == "packed":
        # bf16 compute: ONE gather per edge carries [u·z ‖ u] bit-packed
        p16 = u.astype(jnp.bfloat16)[:, None] * z
        num, den = _packed_aggregate(
            p16, u, fout, send_idx, halo_src, cell_idx, cell_w,
            ctail_dst, ctail_src, ctail_w, buckets, b, axis_name, comm)
    else:
        # table stays in the compute dtype (bf16 under mixed precision,
        # halving exchange bytes); u itself is f32 for stabilizer exactness
        p = u.astype(z.dtype)[:, None] * z           # (B, fout)
        if form == "fused":
            table = jnp.concatenate([p, u.astype(z.dtype)[:, None]], axis=-1)
            halo = _exchange_table(table, send_idx, halo_src, axis_name,
                                   comm)
            full = jnp.concatenate([table, halo], axis=0)   # (B+R, fout+1)
            num, den = _mask_slot_pass(full, fout, cell_idx, cell_w,
                                       ctail_dst, ctail_src, ctail_w,
                                       buckets, b)
        else:
            full_p, full_u = _exchange_rows_scalar(
                p, u.astype(z.dtype), send_idx, halo_src, axis_name, comm)
            num, den = _pair_slot_pass(full_p, full_u, fout, cell_idx,
                                       cell_w, ctail_dst, ctail_src,
                                       ctail_w, buckets, b)
    # max(den, tiny): u > 0 for every real edge, so this stays exact until
    # genuine f32 underflow (~68-nat spread); an ABSOLUTE eps would zero
    # rows whose neighborhoods sit merely ~20 nats below the global max.
    # 1e-30, not 1e-38: subnormals are flushed to zero on TPU/XLA, so a
    # sub-`tiny` guard silently becomes max(den, 0) -> 0/0 = NaN
    out = num / jnp.maximum(den, 1e-30)[:, None]
    return out, z, u, den, cg


def _gat_layer_sym_fwd(w, a1, a2, h, send_idx, halo_src, cell_idx, cell_w,
                       ctail_dst, ctail_src, ctail_w, row_valid, buckets,
                       axis_name, comm):
    out, _, _, den, cg = _gat_factored_fwd_core(
        w, a2, h, send_idx, halo_src, cell_idx, cell_w,
        ctail_dst, ctail_src, ctail_w, row_valid, buckets, axis_name, comm)
    # z and u are NOT stored: at products scale each stored (B, fout) array
    # is 1.25 GB and the fwd+bwd step measured 17.07 GB of HLO temps on a
    # 16 GB chip with them resident; the backward recomputes z = h·w (one
    # MXU matmul, ~0.4 ms at products scale — noise next to the gather
    # streams) and u from the stored scalar stabilizer cg.
    res = (w, a1, a2, h, cg, den, out, send_idx, halo_src, cell_idx,
           cell_w, ctail_dst, ctail_src, ctail_w)
    return out, res


def _gat_layer_sym_bwd(buckets, axis_name, comm, res, gbar):
    (w, a1, a2, h, cg, den, out, send_idx, halo_src, cell_idx, cell_w,
     ctail_dst, ctail_src, ctail_w) = res
    b = h.shape[0]
    z = h @ w                                        # remat (see fwd)
    fout = z.shape[-1]
    u = jnp.exp(score_project(z, a2).astype(jnp.float32) - cg)
    # out = N/(D+ε): cotangents of the two aggregations, per dst row
    dng = jnp.maximum(den, 1e-30)                    # same guard as forward
    dn = gbar / dng[:, None]                         # (B, fout)
    dd = -(gbar * out).sum(axis=-1) / dng            # (B,)
    # transpose of a symmetric pattern = the same aggregation: for src row
    # j, Σ_i mask_ij·dn_i over j's in-edge slots (aggregators of j) — the
    # backward's [ḡ/D ‖ −(ḡ·out)/D] table rides the SAME transport (comm)
    # as the forward's, so the ragged ring carries both directions
    form = gat_table_form(fout, z.dtype)
    if _is_pallas_comm(comm):
        # backward table rides the SAME transport and kernel as the
        # forward's (symmetric pattern: transpose = the same passes)
        dp, du_agg = _gat_pallas_aggregate(
            dn, dd, fout, form, send_idx, halo_src,
            cell_idx, cell_w, ctail_dst, axis_name, comm)
    elif form == "packed":
        dp, du_agg = _packed_aggregate(
            dn.astype(jnp.bfloat16), dd, fout, send_idx, halo_src,
            cell_idx, cell_w, ctail_dst, ctail_src, ctail_w, buckets, b,
            axis_name, comm)
    elif form == "fused":
        table = jnp.concatenate([dn, dd[:, None]], axis=-1)
        halo = _exchange_table(table, send_idx, halo_src, axis_name, comm)
        full = jnp.concatenate([table, halo], axis=0)
        dp, du_agg = _mask_slot_pass(full, fout, cell_idx, cell_w,
                                     ctail_dst, ctail_src, ctail_w,
                                     buckets, b)
    else:
        full_dn, full_dd = _exchange_rows_scalar(
            dn, dd, send_idx, halo_src, axis_name, comm)
        dp, du_agg = _pair_slot_pass(full_dn, full_dd, fout, cell_idx,
                                     cell_w, ctail_dst, ctail_src, ctail_w,
                                     buckets, b)
    # p = u·z, u = exp(z2 − C): chain rules (C is a pmax — constant a.e.)
    dz = u[:, None] * dp
    du = (dp * z).sum(axis=-1) + du_agg
    dz2 = u * du
    dz_total = dz + dz2[:, None] * a2[None, :]
    dh = dz_total @ w.T
    dW = h.T @ dz_total
    da2 = z.T @ dz2
    da1 = jnp.zeros_like(a1)       # softmax shift-invariance: exactly zero
    return (dW, da1, da2, dh,
            None, None, None, None, None, None, None, None)


gat_layer_sym.defvjp(_gat_layer_sym_fwd, _gat_layer_sym_bwd)


def estimate_gat_hbm_bytes(b: int, r: int, fin: int, widths: list[int],
                           nnz: int = 0, tail: int = 0,
                           dtype: str | None = None) -> int:
    """Per-chip peak-HBM model of one GAT fwd+bwd step, CALIBRATED on the
    round-3/4 measured capacity edges.

    ``r`` (true per-chip halo rows) is currently unused: every calibration
    point is single-chip (r=0), so a halo coefficient would be a guess.
    Callers pass the real value (``plan.halo_counts.max()``) so a fitted
    term can be added the moment multi-chip capacity data exists.

    f32 model ``7.08·B·(fin+Σfout) + 64·nnz + 90·tail`` reproduces the
    measured capacity points (products shape, 15.75 GB v5e):
      * BA 3-layer f32 (tail 29M): est 17.25 GB == the measured compile
        OOM ("Used 17.25G");
      * ER 3-layer f32 (tail 3.7M): est 15.13 GB — RUNS (15.9 s/epoch);
      * bf16-packed BA 3-layer: est 16.76 == measured compile OOM;
      * bf16-packed at B=1M: est 6.7 GB — ran (5.69 s, round 3).
    The per-tail-edge coefficient is large (90 B) because the chunked tail
    scans keep full-width gather temps and carries live; nnz carries the
    slot arrays + working set of the bucketed passes.

    KNOWN BLIND SPOT: the BA 2-layer f32 step estimates 15.2 GB (below the
    ER-3L running point), compiled — and then crashed the WORKER at
    runtime.  That crash is not separable by any capacity ranking
    (2-layer < ER-3L which runs), so it is likely a kernel fault, not
    capacity; a capacity guard cannot catch it.
    """
    ftot = fin + sum(widths)
    if dtype == "bfloat16":
        # packed path: fitted to the 16.76 GB BA-3L compile OOM and the
        # running 1M-vertex point (6.7 GB est) — the packed tables halve
        # but mixed precision double-books activations via casts, so the
        # per-row coefficient is NOT half of f32's
        return int(7.4 * b * ftot + 56 * nnz + 70 * tail)
    return int(7.08 * b * ftot + 64 * nnz + 90 * tail)


def check_gat_memory(device, b: int, r: int, fin: int, widths: list[int],
                     nnz: int = 0, tail: int = 0,
                     dtype: str | None = None) -> None:
    """Pre-flight guard for the GAT capacity edge: raise a
    clear error instead of letting the compile OOM or — worse — the TPU
    worker die at runtime (both observed; the 2-layer BA-products f32 step
    passed compile and then crashed the worker).

    The threshold is sharp by necessity — the largest RUNNING config
    estimates 15.13 GB of the chip's 15.75 GB and the smallest compile-OOM
    16.76 — so the guard raises above 0.97·HBM and tells the user the
    levers.  The HBM size is what ``device`` (a device of the trainer's
    mesh) reports as ``memory_stats()["bytes_limit"]``; a backend that
    reports none (CPU) has no capacity to guard and skips that check — a
    figure is never assumed for a device that could not be asked.
    ``SGCN_HBM_BYTES`` overrides the reported size (set it huge to bypass
    the guard for capacity experiments); ``SGCN_GAT_UNSAFE=1`` skips both
    guards outright."""
    if _os.environ.get("SGCN_GAT_UNSAFE") == "1":
        return
    env = _os.environ.get("SGCN_HBM_BYTES")
    if env:
        hbm_bytes = int(env)
    else:
        hbm_bytes = (device.memory_stats() or {}).get("bytes_limit")
    # Secondary fence for the runtime-crash blind spot: the 2-layer BA
    # products step (tail 29M) passed both compile and this capacity model
    # and then KILLED the worker, while an 11.9M-tail run (B=1M) was fine —
    # so huge hub tails are fenced outright until the fault is understood.
    if tail > 20_000_000:
        raise RuntimeError(
            f"GAT hub tail of {tail / 1e6:.1f}M edges exceeds the measured "
            f"single-chip safety fence (20M): a products-scale run with a "
            f"29M-edge tail crashed the TPU worker AT RUNTIME despite "
            f"fitting the capacity model, while 11.9M ran fine.  Shard "
            f"over more chips (the per-chip tail shrinks ~k-fold) or set "
            f"SGCN_GAT_UNSAFE=1 to bypass both guards knowingly.")
    if hbm_bytes is None:
        return
    est = estimate_gat_hbm_bytes(b, r, fin, widths, nnz, tail, dtype)
    if est > 0.97 * hbm_bytes:
        raise RuntimeError(
            f"GAT at this shape is past the measured single-chip capacity "
            f"edge: estimated ~{est / 1024**3:.1f} GB of per-chip peak HBM "
            f"vs {hbm_bytes / 1024**3:.1f} GB available (guard at 97%; "
            f"calibrated on the measured compile-OOM points — see "
            f"estimate_gat_hbm_bytes).  Levers: shard over more chips "
            f"(per-chip B, nnz and tail all shrink ~k-fold), reduce "
            f"layers/width, or SGCN_HBM_BYTES to override.")


def gat_forward_local(
    params,
    h,
    pa,                           # plan arrays dict (GAT_PLAN_FIELDS)
    activation: str = "none",
    final_activation: str = "none",
    symmetric: bool = False,      # True selects the factored custom-backward
                                  # layer, which REQUIRES a symmetric edge
                                  # PATTERN (attention VALUES need not be)
    cell_buckets: tuple | None = None,   # static plan.cell_buckets
    comm_schedule: str = "a2a",   # static: 'a2a' (dense all_to_all) or
                                  # 'ragged' (per-round ppermute ring,
                                  # docs/comm_schedule.md)
    rr_sizes: tuple | None = None,  # static plan.rr_sizes (ragged)
    halo_r: int | None = None,      # static plan.r — halo table height
                                    # (ragged; not derivable from rhalo_dst)
    pallas_tb: int | None = None,   # static: VMEM-kernel tile height —
                                    # selects the Pallas slot pass
    pallas_emulate: bool = False,   # static: jnp emulation (off-TPU CI)
    pallas_cclasses: tuple | None = None,  # static: combined tile classes
                                    # ((T, Emax, kern), ...)
    axis_name: str = AXIS,
    halo_carry=None,              # stale-halo carries (trainer contract slot)
    collect_stabilizers: bool = False,  # static: also return the per-layer
                                  # softmax stabilizers cg (serving's
                                  # sub-graph precompute — see below)
):
    """Per-chip forward: stacked GAT layers.

    The reference stacks bare PGAT modules with no inter-layer nonlinearity
    (softmax-weighted aggregation is the nonlinearity, ``GPU/PGAT.py:202-213``);
    ``activation='elu'`` gives the standard GAT variant.

    GAT streams the combined ``[local; halo]`` bucketed edge layout (not the
    split overlap form): the edge-softmax normalizes each row over local AND
    halo edges together, so the aggregation genuinely depends on the
    exchange.

    ``halo_carry`` is the trainer's stale-halo carry slot (the pipelined
    exchange of ``ops.pspmm.pspmm_stale``).  GAT's exchange ships per-layer
    attention tables ``[Z_j, z2_j]`` whose staleness interacts with the
    edge-softmax normalization — carrying them is future work, so only the
    exact mode (``halo_carry=None``) is accepted here; the trainer gates
    ``halo_staleness`` to the GCN model accordingly.
    """
    if halo_carry is not None:
        raise NotImplementedError(
            "stale-halo pipelining is implemented for the GCN hot path only; "
            "run GAT with halo_staleness=0")
    if cell_buckets is None:
        raise ValueError("GAT forward needs the plan's static cell_buckets")
    if comm_schedule not in ("a2a", "ragged"):
        raise ValueError(f"unknown comm_schedule {comm_schedule!r} "
                         "(the trainer resolves 'auto' before the forward)")
    cell_arrays = (pa.get("cell_idx"), pa.get("cell_w"),
                   pa.get("ctail_dst"), pa.get("ctail_src"),
                   pa.get("ctail_w"))
    if pallas_tb is not None:
        # VMEM-kernel slot pass (schedule-agnostic, docs/comm_schedule.md):
        # the cell_idx/cell_w/ctail_dst slots of the layer signature carry
        # the combined TILE arrays; the tail slots ride unused dummies (the
        # tiles already cover every combined edge, hub tail included)
        if not symmetric:
            raise ValueError(
                "the Pallas GAT slot pass rides the symmetric custom "
                "backward; asymmetric plans run the slot-pass path")
        pspec = (int(pallas_tb), pallas_cclasses, bool(pallas_emulate))
        dummy_i = jnp.zeros((1,), jnp.int32)
        dummy_f = jnp.zeros((1,), jnp.float32)
        if comm_schedule == "ragged":
            if rr_sizes is None:
                raise ValueError(
                    "ragged Pallas GAT forward needs the plan's static "
                    "rr_sizes (CommPlan.ensure_ragged)")
            comm = ("ragged+pallas", tuple(rr_sizes), pspec)
            send_idx, halo_src = pa["rsend_idx"], dummy_i
            csrc = pa["ptile_crsrc"]
        else:
            comm = ("a2a+pallas", pspec)
            send_idx, halo_src = pa["send_idx"], pa["halo_src"]
            csrc = pa["ptile_csrc"]
        cell_arrays = (csrc, pa["ptile_cw"], pa["ptile_cld"],
                       dummy_i, dummy_f)
    elif comm_schedule == "ragged":
        # per-round ppermute ring: the attention tables ride the plan's
        # model-independent per-vertex layout (rsend_idx/rhalo_dst); same
        # math, f32 bit-identical (tests/test_gat_ragged.py)
        if not symmetric:
            raise ValueError(
                "comm_schedule='ragged' uses the symmetric custom backward "
                "(the gradient table rides the same ring); asymmetric "
                "plans run the a2a schedule")
        if rr_sizes is None or halo_r is None:
            raise ValueError(
                "ragged GAT forward needs the plan's static rr_sizes + "
                "halo table height r (CommPlan.ensure_ragged)")
        comm = ("ragged", tuple(rr_sizes), int(halo_r))
        send_idx, halo_src = pa["rsend_idx"], pa["rhalo_dst"]
    else:
        comm = COMM_A2A
        send_idx, halo_src = pa["send_idx"], pa["halo_src"]
    act = get_activation(activation)
    fact = get_activation(final_activation)
    nl = len(params)
    # symmetric edge pattern (undirected graphs): gather-only custom
    # backward; general pattern: autodiff through the streaming forward
    layer = gat_layer_sym if symmetric else gat_layer_local
    if symmetric:
        # custom_vjp cotangents must carry the same varying-axes type as
        # the primals; params arrive replicated (unvarying) but the bwd
        # produces per-chip PARTIAL grads (varying — the trainer completes
        # them with its psum), so cast the primals to varying first
        params = vary(params, axis_name)
    cgs = []
    for i, p in enumerate(params):
        if collect_stabilizers:
            # the layer's own stabilizer, recomputed from the SAME
            # expressions _gat_factored_fwd_core evaluates (z = h·w,
            # z2 = score_project, real-row mask, global pmax) — XLA CSEs
            # the duplicate matmul away, and determinism makes the value
            # bit-equal to the one the layer uses internally.  Serving's
            # sub-graph forward (``serve/subgraph.py``) consumes these as
            # INPUTS: cg is a full-graph max, the one quantity a
            # receptive-set program cannot derive locally, but it is
            # constant per (params, features) — precomputed once per
            # weight swap, it keeps the compact u = exp(z2 − cg) values
            # bit-identical to the full program's.
            z2 = score_project(h @ p["w"], p["a2"])
            z2m = jnp.where(pa["row_valid"] > 0, z2.astype(jnp.float32),
                            -jnp.inf)
            cgs.append(jax.lax.pmax(jnp.max(z2m), axis_name))
        h = layer(
            p["w"], p["a1"], p["a2"], h,
            send_idx, halo_src,
            cell_arrays[0], cell_arrays[1],
            cell_arrays[2], cell_arrays[3], cell_arrays[4],
            pa["row_valid"], cell_buckets, axis_name, comm)
        h = fact(h) if i == nl - 1 else act(h)
        if i < nl - 1:
            # the softmax-weighted aggregation accumulates in f32 and
            # returns f32 rows; under mixed precision the NEXT layer must
            # see the compute dtype again or every layer past the first
            # silently runs the full-width f32 table forms — an f32 wire
            # under a bf16 request that no loss-parity test notices (found
            # by the sgcn_tpu/analysis wire audit; the byte gauges'
            # gat_exchange_lane_widths always assumed all layers narrow)
            h = h.astype(p["w"].dtype)
    if collect_stabilizers:
        return h, jnp.stack(cgs)
    return h
