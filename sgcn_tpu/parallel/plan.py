"""Communication-plan construction: partition vector → static all_to_all layout.

The reference computes, at trainer start-up, per-rank send/recv index maps from
the adjacency nonzero pattern and the part vector: a rank must *receive* the
feature rows of every remote vertex its local nonzeros reference, and *send*
each of its owned boundary vertices to exactly the ranks whose nonzeros
reference it (``GPU/PGCN.py:37-51``; offline flavor ``GCN-HP/main.cpp:147-211``
emitting ``conn.r`` / ``buff.r``).  The exchange itself is ragged point-to-point
(``GPU/PGCN.py:85-119``, ``Parallel-GCN/main.c:238-266``).

On TPU, shapes under ``jit`` are static, so we lower the ragged exchange to a
**padded all_to_all layout** computed once per (graph, partvec):

  * vertices are relabeled so chip ``p`` owns local slots ``0..B-1``
    (``B`` = max part size, parts padded with dummy vertices),
  * ``send_idx[p, q, s]`` — the ``S`` local rows chip ``p`` ships to chip ``q``
    (``send_counts[p, q]`` masks the tail, whose entries name distinct
    in-bounds rows: ``padding_rows`` — every padding index of every array
    below follows that one rule, because a gather of ONE row a million times
    over is the slowest work the step does),
  * one ``lax.all_to_all`` of a ``(k, S, f)`` buffer per layer replaces the
    whole two-phase send/recv protocol (deadlock-freedom is structural),
  * ``halo_src[p, r]`` gathers chip ``p``'s ``R`` halo rows out of the received
    ``(k*S, f)`` buffer, in (owner, vertex-id) order,
  * the local adjacency block becomes padded edge lists ``(dst, src, w)`` with
    ``src`` indexing the concatenated ``[local rows; halo rows]`` table —
    SpMM is a masked segment-sum, fully fused by XLA.

The transposed (backward) exchange is obtained for free: JAX transposes
``all_to_all`` to the reverse all_to_all and gathers to scatter-adds, which is
exactly the reference's swap of send/recv maps for the gradient
(``GPU/PGCN.py:93-97``, ``Parallel-GCN/main.c:350-372``).

Everything here is offline numpy; nothing is traced.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np
import scipy.sparse as sp

# Every CommPlan array field stacked per chip along a leading ``k`` axis —
# THE explicit classification anything slicing a plan per chip must use
# (``parallel/proxy.py::shard_proxy_plan``), instead of inferring per-chip-ness
# from a ``shape[0] == plan.k`` coincidence (round-5 advisor finding: a
# global-vertex field of an n==k graph, or a future (k_something, ...) field,
# would silently mis-slice).  Optional fields (the lazy cell/pallas layouts)
# are listed too and skipped while ``None``.  Fields NOT here and not in
# ``_GLOBAL_ARRAY_FIELDS`` must never carry a leading per-chip axis — the
# proxy enforces that loudly.
PER_CHIP_ARRAY_FIELDS = (
    "part_sizes",
    "send_idx", "send_counts", "halo_src", "halo_counts",
    "edge_dst", "edge_src", "edge_w", "nnz", "row_valid",
    "ledge_dst", "ledge_src", "ledge_w",
    "hedge_dst", "hedge_src", "hedge_w", "lnnz", "hnnz",
    "ell_idx", "ell_w", "ltail_dst", "ltail_src", "ltail_w", "ltail_nnz",
    "cell_idx", "cell_w", "ctail_dst", "ctail_src", "ctail_w", "ctail_nnz",
    "ptile_lsrc", "ptile_lld", "ptile_lw",
    "ptile_hsrc", "ptile_hld", "ptile_hw", "ptile_hrsrc",
    "ptile_csrc", "ptile_cld", "ptile_cw", "ptile_crsrc",
    "rsend_idx", "rhalo_dst", "redge_dst", "redge_src", "redge_w",
    "nrep_send_idx", "nrep_send_counts", "nrep_halo_src",
    "rep_slots", "rep_counts", "nrep_rsend_idx", "nrep_rhalo_dst",
    "rep_ring_pos", "nrep_ring_dst",
    "rep_rows", "rep_row_counts", "ronly_send_idx", "ronly_send_counts",
    "ronly_base_pos", "rep_recv_src",
    "ft_idx", "ft_w", "ft_row", "fh_idx", "fh_w", "fh_row",
)

# slots a virtual row of the attention layer holds: the one width that ran
# at 512 lanes (PERF.md §6, PR 27: hub tail of the products-eighth graph,
# 2.04 M edges as 92,344 rows; its own optimum is open, PERF.md §7)
VROW_WIDTH = 32

# the widths a fold store's virtual rows may take, and what one virtual row
# costs beside one executed slot: the row's share of the sorted scatter-add
# that folds the rows' sums into their destinations, in slots (measured at
# 128 lanes f32 on the v5e: 7.8–8.1 ns a row of 0.65–2.26 M scattered into
# 630,624, against 5.1–6.9 ns a scanned slot; PERF.md §6, PR 30, step 1)
FOLD_WIDTHS = (4, 8, 16, 32)
FOLD_ROW_COST = 1.4

# the row counts a bucket or fold class of ROW_PERIOD rows and more may take,
# as residues modulo ROW_PERIOD, both ends included: the v5e's slot gather
# prices a slot by its bucket's row count modulo 1,024 (``snap_rows``).
# Set by ``scripts/row_residue_micro.py`` (my chip run, PR 36;
# ``bench_artifacts/row_residue_micro.json``; PERF.md §6): one bucket of
# width 32 at 128 lanes, 100 and 600 periods of rows, scanned and unrolled —
# residues 8 … 768 read 4.9–5.4 ns a slot, 832 … 896 a fixed ~0.17 ms more a
# slot pass (6.7–7.0 ns at 100 periods, 5.5–5.7 at 600), and 897 … 1,023 AND
# 0 read 10.9–11.3: a bucket is dear when its count of 128-row tiles is a
# multiple of 8, so rounding up to a multiple of 1,024 lands on the dear
# side.  The upper end stays a tile below the bare bucket's 768: in the
# deep-stack cell's step an unrolled bucket at residue 768 (``48896x31``,
# its slots starting mid-tile in the flat array) read the 832 … 896 price,
# and at 640 its neighbours' (same PR, calls 2 and 3).  The lower end is a
# multiple of 8 so that a fold class stays one.
ROW_PERIOD = 1024
ROW_WINDOW = (8, 640)

# Auto-selection threshold for SGCN_COMM_SCHEDULE=auto: below this dense-a2a
# padding efficiency (Σ send_counts / (k²·S)) the per-round-sized ragged
# ppermute ring ships strictly fewer wire bytes by a margin worth its k−1
# rounds; above it the single dense all_to_all's one-shot latency wins.
RAGGED_AUTO_EFFICIENCY = 0.5

# Global-vertex-indexed arrays (plus the proxy's chip-identity record):
# pass through a per-chip slice untouched.
_GLOBAL_ARRAY_FIELDS = ("owner", "local_idx", "chip_ids")

# Plan arrays the COMPOSED stale × ragged step ships to devices
# (``ops.pspmm.pspmm_stale_ragged``): the ragged ring's send/edge layout —
# the round-structured carries replace the dense send_idx/halo_src pair
# entirely (receives live in the carry, the fold rides redge_*).  Kept as
# its own contract tuple (same lint coverage as the model tuples,
# ``tests/test_plan_contract.py``) even though it currently equals the
# ragged GCN forward's field set — the two evolve for different reasons.
STALE_PLAN_FIELDS_RAGGED = (
    "rsend_idx", "ell_idx", "ell_w",
    "ltail_dst", "ltail_src", "ltail_w",
    "redge_dst", "redge_src", "redge_w",
)

# Plan arrays the hot-halo REPLICATION step ships (``--replica-budget B``,
# ``ops.pspmm.pspmm_replica`` / ``pspmm_replica_ragged``): the UNION of the
# full exchange layout (the sync/refresh program is exactly the exact
# program plus the replica-carry gathers) and the shrunken no-replica
# layout (``ensure_replicas`` — top-B boundary rows by λ·degree leave the
# per-layer wire; their halo slots fill from the carried replica table).
# jit prunes whichever half a given program does not consume; the
# plan-contract lint (tests/test_plan_contract.py, via analysis/registry)
# covers both tuples.
REPLICA_PLAN_FIELDS = (
    "send_idx", "halo_src",
    "nrep_send_idx", "nrep_halo_src", "rep_slots",
    "ell_idx", "ell_w", "ltail_dst", "ltail_src", "ltail_w",
    "hedge_dst", "hedge_src", "hedge_w",
)
REPLICA_PLAN_FIELDS_RAGGED = (
    "rsend_idx", "nrep_rsend_idx", "nrep_rhalo_dst", "rep_slots",
    "rep_ring_pos",
    "ell_idx", "ell_w", "ltail_dst", "ltail_src", "ltail_w",
    "hedge_dst", "hedge_src", "hedge_w",
    "redge_dst", "redge_src", "redge_w",
)

# Plan arrays the COMPOSED replica × stale step ships
# (``ops.pspmm.pspmm_replica_stale`` / ``pspmm_replica_stale_ragged``,
# docs/comm_schedule.md): the stale halo carry subsumes the replica tables
# (replica slots/positions propagate through it between syncs), so unlike
# the pure replica mode there is no separate rep/grep carry — the shipped
# fields are the full exchange layout (sync steps) plus the SHRUNKEN
# no-replica layout (stale steps, which scatter their receives back into
# the carried table).  The a2a tuple currently EQUALS ``REPLICA_PLAN_FIELDS``
# — kept as its own contract tuple anyway (the STALE_PLAN_FIELDS_RAGGED
# precedent): the pure-replica step ships per-slot rep gathers the
# composed mode may drop, so the two evolve for different reasons.  The
# ragged flavor rides the ring-envelope carry of ``pspmm_stale_ragged``:
# ``nrep_ring_dst`` maps each shrunken receive slot to its position in
# the FULL ring's round-major concat.
REPLICA_STALE_PLAN_FIELDS = (
    "send_idx", "halo_src",
    "nrep_send_idx", "nrep_halo_src", "rep_slots",
    "ell_idx", "ell_w", "ltail_dst", "ltail_src", "ltail_w",
    "hedge_dst", "hedge_src", "hedge_w",
)
REPLICA_STALE_PLAN_FIELDS_RAGGED = (
    "rsend_idx", "nrep_rsend_idx", "nrep_ring_dst",
    "ell_idx", "ell_w", "ltail_dst", "ltail_src", "ltail_w",
    "redge_dst", "redge_src", "redge_w",
)

# Plan arrays the PARTIAL refresh step ships (``--refresh-band``,
# ``ops.pspmm.pspmm_replica_partial``, docs/replication.md): the shrunken
# replica-step layout plus the replica-only side channel — the owned
# replicated rows and their sender-side baseline positions
# (``rep_rows``/``ronly_base_pos``), the replica-only per-pair buckets
# (``ronly_*``: exactly the rows ``ensure_replicas`` deleted from the
# ``nrep_*`` layout), and the receive routing of refreshed rows into the
# carried replica table (``rep_recv_src``).
REPLICA_PARTIAL_PLAN_FIELDS = REPLICA_PLAN_FIELDS + (
    "rep_rows", "rep_row_counts",
    "ronly_send_idx", "ronly_send_counts", "ronly_base_pos",
    "rep_recv_src",
)


@dataclass
class CommPlan:
    """Static halo-exchange + local-SpMM plan for one (graph, partvec) pair.

    All per-chip arrays are stacked along a leading ``k`` axis so they can be
    sharded over a 1D device mesh with ``PartitionSpec('v')``.
    """

    n: int                    # global vertex count
    k: int                    # number of parts / chips
    b: int                    # padded local rows per chip (max part size)
    s: int                    # padded send-bucket size per (src, dst) pair
    r: int                    # padded halo rows per chip
    e: int                    # padded local nnz per chip

    # vertex relabeling
    owner: np.ndarray         # (n,) chip owning each global vertex
    local_idx: np.ndarray     # (n,) local slot of each global vertex on its owner
    part_sizes: np.ndarray    # (k,) true part sizes (<= b)

    # halo exchange layout (stacked over chips)
    # Padding entries of every index array gathered through (send_idx,
    # halo_src, edge_src, ledge_src, hedge_src, ell_idx, ltail_src) hold
    # ``padding_rows``: distinct in-bounds rows of the table indexed, never
    # one row repeated.  The counts and the zero weights say what is padding.
    send_idx: np.ndarray      # (k, k, S) int32: local rows p sends to q
    send_counts: np.ndarray   # (k, k) int32: valid prefix of send_idx[p, q]
    halo_src: np.ndarray      # (k, R) int32: flat (q*S + t) recv-buffer gather
    halo_counts: np.ndarray   # (k,) int32: valid prefix of halo_src[p]

    # local sparse block as padded edge lists (sorted by dst for segment_sum)
    edge_dst: np.ndarray      # (k, E) int32 local row in [0, B)
    edge_src: np.ndarray      # (k, E) int32 index into [local; halo] in [0, B+R)
    edge_w: np.ndarray        # (k, E) float32, 0 on padding
    nnz: np.ndarray           # (k,) true local nnz

    row_valid: np.ndarray     # (k, B) float32 1/0 mask of real (non-pad) rows

    # The same edges split by source locality — the overlap structure of the
    # reference's forward (``Parallel-GCN/main.c:238-299``): the local-src
    # segment-sum depends only on ``h``, so XLA can run it while the halo
    # all_to_all is in flight, then the halo-src segment-sum folds the remote
    # contribution in (``AH = Â·H_local + Σ Â·Ĥ_r``).  ``ledge_src`` indexes
    # local rows [0, B); ``hedge_src`` indexes the halo block [0, R).
    el: int                   # padded local-src nnz per chip
    eh: int                   # padded halo-src nnz per chip
    ledge_dst: np.ndarray     # (k, EL) int32
    ledge_src: np.ndarray     # (k, EL) int32
    ledge_w: np.ndarray       # (k, EL) float32, 0 on padding
    hedge_dst: np.ndarray     # (k, EH) int32
    hedge_src: np.ndarray     # (k, EH) int32
    hedge_w: np.ndarray       # (k, EH) float32, 0 on padding
    lnnz: np.ndarray          # (k,) true local-src nnz
    hnnz: np.ndarray          # (k,) true halo-src nnz

    # The local-src edges again, in BUCKETED ELL layout.  Rows are stored in
    # degree buckets: bucket j covers the next ``nb_j`` rows at fixed width
    # ``wb_j`` (``ell_buckets = ((nb_0, wb_0), ...)``, Σ nb_j = B), and row
    # r's in-edges occupy ``wb_j`` flat slots starting at its bucket base.
    # The hot SpMM is, per bucket, ONE 2D-index gather + dense weighted
    # reduce over the width axis — no segment machinery, no scatter.  Under
    # ``row_order='degree'`` (the trainer default) rows are relabeled
    # descending by local in-degree, so bucket widths hug the degree profile
    # and padding drops from the single-width ELL's ~1.7× (Poisson graphs)
    # to ~1.1-1.2×; the gather is row-rate-bound on v5e (~350-400 Mrows/s
    # regardless of index pattern or dtype), so fewer gathered rows is the
    # only lever that pays.  Under ``row_order='id'`` a single bucket plus
    # the COO overflow tail reproduces the classic ELL+tail layout.
    ell_k: int                # max bucket width (informational; >= 1)
    tl: int                   # padded tail length
    ell_buckets: tuple        # ((nb, wb), ...) static bucket structure
    ell_idx: np.ndarray       # (k, ET) int32 flat local src; padding slots
    #                           (weight 0) name distinct rows: padding_rows
    ell_w: np.ndarray         # (k, ET) float32 flat, 0 on padding
    ltail_dst: np.ndarray     # (k, TL) int32
    ltail_src: np.ndarray     # (k, TL) int32
    ltail_w: np.ndarray       # (k, TL) float32, 0 on padding
    ltail_nnz: np.ndarray     # (k,) true tail nnz
    row_order: str            # 'degree' (bucketed) or 'id' (emit-compatible)

    # True when the global adjacency is numerically symmetric (Â = Âᵀ) —
    # verified at plan-build time.  Lets the SpMM backward reuse the forward
    # structure (Âᵀg = Âg) instead of JAX's mechanical transpose, whose
    # scatter-add is ~3.6× slower than the gather form on v5e.  The
    # reference makes the same assumption (backward uses A, not Aᵀ —
    # Parallel-GCN/main.c:374-404).
    symmetric: bool

    # The two COO stores above — the hub tail and the halo-source edges —
    # again in SLOT form (lazy, ``ensure_fold_slots``): virtual rows in
    # width classes ``fold_*_classes = ((nv_c, W_c), ...)`` chosen from the
    # store's run lengths, flat width-major like the ELL.  What the exact
    # symmetric GCN step ships INSTEAD of ``ltail_*`` / ``hedge_*``.
    fold_tail_classes: tuple | None = None
    fold_halo_classes: tuple | None = None
    ft_idx: np.ndarray | None = None    # (k, Σ nv·W) int32 local src
    ft_w: np.ndarray | None = None      # (k, Σ nv·W) float32, 0 on padding
    ft_row: np.ndarray | None = None    # (k, Σ nv) int32 destination row
    fh_idx: np.ndarray | None = None    # (k, Σ nv·W) int32 halo rank
    fh_w: np.ndarray | None = None      # (k, Σ nv·W) float32, 0 on padding
    fh_row: np.ndarray | None = None    # (k, Σ nv) int32 destination row

    # What ``snap_rows`` moved where this plan's shapes were chosen, per
    # store (``slot_edges`` from ``_build_ell``; ``tail_edges`` /
    # ``halo_edges`` once ``ensure_fold_slots`` has run): ``shapes`` changed,
    # ``rows`` moved or added, ``slots`` added — ``work_counts()["snapped"]``.
    snapped: dict = field(default_factory=dict)

    # The COMBINED edge list (src in [0, B+R), local ‖ halo) in the same
    # bucketed width-major layout — for ops that must see every in-edge of a
    # row at once: the GAT edge-softmax normalizes over local AND halo
    # neighbors together, so it streams these slots with an online-softmax
    # (running max / denominator) instead of segment machinery.  Built
    # LAZILY (``ensure_cell()``) — only the GAT model ships these arrays,
    # and they duplicate the edge storage.
    ctl: int | None = None            # padded combined-tail length
    cell_buckets: tuple | None = None  # ((nb, wb), ...) static structure
    cell_idx: np.ndarray | None = None   # (k, CET) int32 flat src
    cell_w: np.ndarray | None = None     # (k, CET) float32, 0 on padding
    ctail_dst: np.ndarray | None = None  # (k, CTL) int32
    ctail_src: np.ndarray | None = None  # (k, CTL) int32
    ctail_w: np.ndarray | None = None    # (k, CTL) float32, 0 on padding
    ctail_nnz: np.ndarray | None = None  # (k,) true combined-tail nnz

    # Pallas dst-tile layout (lazy, ``ensure_pallas_tiles``): the local-src
    # and halo-src edge families regrouped into tb-row tiles, tiles binned
    # into DEGREE-ALIGNED CLASSES (``tile_classes_from_buckets`` over the
    # plan's ell_buckets histogram) each padded to its OWN Emax_c, stored
    # FLAT per chip (class c owns the next T_c·Emax_c slots) with the
    # static structure in ``pallas_lclasses``/``pallas_hclasses`` — for
    # the VMEM-resident SpMM kernel (``ops/pallas_spmm.py``), selected by
    # the trainer when per-chip tables fit the kernel's VMEM budget, which
    # is exactly what k-way sharding produces as k grows.  The ragged
    # variant (``ensure_pallas_ragged_tiles``) re-bases the halo tile
    # sources from halo RANKS to RING positions (the round-major receive
    # concat of the ppermute ring), so the kernel folds receive buffers
    # directly — no HBM halo table.  The combined-edge family
    # (``ensure_pallas_cell_tiles``, GAT) carries 0/1 MASK weights
    # (attention ignores Â's values) over [local ‖ halo] sources.
    pallas_tb: int | None = None          # static tile height
    pallas_lclasses: tuple | None = None  # ((T_c, Emax_c), ...) local
    pallas_hclasses: tuple | None = None  # ((T_c, Emax_c), ...) halo
    ptile_lsrc: np.ndarray | None = None  # (k, ΣT_c·Emax_c) int32
    ptile_lld: np.ndarray | None = None   # (k, ΣT_c·Emax_c) int32 local dst
    ptile_lw: np.ndarray | None = None    # (k, ΣT_c·Emax_c) float32
    ptile_hsrc: np.ndarray | None = None  # (k, ΣT_c·Emax_c) int32 halo rank
    ptile_hld: np.ndarray | None = None   # (k, ΣT_c·Emax_c) int32
    ptile_hw: np.ndarray | None = None    # (k, ΣT_c·Emax_c) float32
    ptile_hrsrc: np.ndarray | None = None  # (k, ΣT_c·Emax_c) int32 RING pos
    pallas_ctb: int | None = None          # static combined tile height
    pallas_cclasses: tuple | None = None   # ((T_c, Emax_c), ...) combined
    ptile_csrc: np.ndarray | None = None   # (k, ·) int32 src in [0, B+R)
    ptile_cld: np.ndarray | None = None    # (k, ·) int32 local dst
    ptile_cw: np.ndarray | None = None     # (k, ·) float32 0/1 edge mask
    ptile_crsrc: np.ndarray | None = None  # (k, ·) int32 src in
    #                                        [0, B+ΣS_d): halo part re-based
    #                                        to B + ring position

    # Ragged ppermute-ring exchange layout (lazy, ``ensure_ragged``): the
    # reference's point-to-point halo protocol re-expressed as k−1 rounds of
    # ``lax.ppermute`` where round d carries chip p → chip (p+d)%k in a
    # buffer statically sized to S_d = max_p send_counts[p, (p+d)%k] — a
    # PER-ROUND pad instead of the dense all_to_all's global S, so skewed
    # partitions stop paying k²·S wire slots for a Σ(λ−1) exchange.  All
    # round segments are flattened along the trailing axis (round d's slots
    # start at Σ_{d'<d} S_{d'}); ``rr_sizes``/``rr_edge_sizes`` are the
    # static per-round offsets the op unrolls over (rounds with S_d = 0 are
    # skipped at trace time).  ``redge_*`` is the halo-src edge family split
    # per owner (= per round) at plan time with src re-based to the round's
    # receive buffer — the fold-as-you-arrive structure of the reference's
    # post-Irecv accumulate loop (``Parallel-GCN/main.c:238-299``).
    rr_sizes: tuple | None = None        # (k-1,) static per-round send size S_d
    rr_edge_sizes: tuple | None = None   # (k-1,) static per-round edge pad
    rsend_idx: np.ndarray | None = None  # (k, ΣS_d) int32 local rows to ship
    rhalo_dst: np.ndarray | None = None  # (k, ΣS_d) int32 halo rank per recv
    #                                      slot (r = padding, dropped)
    redge_dst: np.ndarray | None = None  # (k, ΣE_d) int32 local dst row
    redge_src: np.ndarray | None = None  # (k, ΣE_d) int32 round recv-buffer row
    redge_w: np.ndarray | None = None    # (k, ΣE_d) float32, 0 on padding

    # Hot-halo replication layout (lazy, ``ensure_replicas``): the top-B
    # boundary rows by λ·degree (λ = consumer chips per row, degree = remote
    # edges consuming it — both straight from the comm plan) are promoted to
    # PERSISTENT REPLICAS on their consumer chips (CaPGNN-style,
    # arXiv:2508.13716).  Replicated rows leave the per-layer wire entirely:
    # the ``nrep_*`` layout is the send/receive structure with those rows
    # deleted (per-pair buckets re-packed to the shrunken pad ``nrep_s``;
    # per-round ring sizes shrunk to ``nrep_rr_sizes``), and ``rep_slots``
    # names the halo-table ranks each chip fills from its carried replica
    # table instead.  Refresh rides the FULL exchange on sync steps — the
    # sync program IS the exact program plus carry gathers (``rep_ring_pos``
    # locates each replica row in the full ring's round-major receive
    # concat), which is what makes ``--sync-every 1`` f32-bit-identical to
    # the no-replica path (docs/replication.md).
    replica_budget: int | None = None     # the budget B ensure_replicas ran at
    rp: int | None = None                 # padded replica slots per chip
    replica_rows: int = 0                 # global replicated rows (<= B)
    replica_send_saving: int = 0          # Σ λ_v — true rows off the wire
    #                                       per exchange
    rep_slots: np.ndarray | None = None   # (k, RP) halo ranks; r = pad (drop)
    rep_counts: np.ndarray | None = None  # (k,) true replica slots per chip
    nrep_s: int | None = None             # shrunken per-pair bucket pad
    nrep_send_idx: np.ndarray | None = None     # (k, k, S') int32
    nrep_send_counts: np.ndarray | None = None  # (k, k) int32
    nrep_halo_src: np.ndarray | None = None     # (k, R) int32; replica slots
    #                                             point at 0 (overwritten)
    nrep_rr_sizes: tuple | None = None          # shrunken per-round sizes
    nrep_rsend_idx: np.ndarray | None = None    # (k, ΣS'_d) int32
    nrep_rhalo_dst: np.ndarray | None = None    # (k, ΣS'_d) int32; r = pad
    rep_ring_pos: np.ndarray | None = None      # (k, RP) int32 into the full
    #                                             (ΣS_d) ring concat
    nrep_ring_dst: np.ndarray | None = None     # (k, ΣS'_d) int32: each
    #                                             shrunken receive slot's
    #                                             position in the FULL ring
    #                                             concat (ΣS_d = pad, dropped)
    #                                             — the composed replica ×
    #                                             stale carry scatter map
    # Partial-refresh side channel (``--refresh-band``): the SENDER's view
    # of its own replicated rows (local ids + per-pair replica-only buckets
    # = exactly the rows deleted from ``nrep_*``) and the RECEIVER's routing
    # of refreshed rows into the carried replica table.
    rs: int | None = None                       # padded owned-replicated rows
    rep_rows: np.ndarray | None = None          # (k, RS) int32 local row ids
    rep_row_counts: np.ndarray | None = None    # (k,) int32 true counts
    ronly_s: int | None = None                  # replica-only bucket pad
    ronly_send_idx: np.ndarray | None = None    # (k, k, RS') int32 local rows
    ronly_send_counts: np.ndarray | None = None  # (k, k) int32
    ronly_base_pos: np.ndarray | None = None    # (k, k, RS') int32 into
    #                                             rep_rows (baseline row)
    rep_recv_src: np.ndarray | None = None      # (k, RP) int32 flat
    #                                             (o·RS' + pos) receive index
    #                                             per carried replica slot

    # identities of the chips this (possibly sliced) plan's rows describe —
    # set by the shard proxy (``parallel/proxy.py``) so the comm-stat
    # properties zero each row's TRUE self-slot rather than assuming row i
    # talks to itself at column i.  None = the full square plan.
    chip_ids: np.ndarray | None = None

    def _pallas_family(self, dst, src, w, tb: int, class_tiles):
        """Stack one edge family's per-chip tile classes into flat
        ``(k, ΣT_c·Emax_c)`` arrays (per class, Emax_c padded to the max
        across chips so the arrays shard) + the static class structure."""
        from ..ops.pallas_spmm import build_dst_tile_classes

        per = [build_dst_tile_classes(dst[p], src[p], w[p], self.b, tb,
                                      class_tiles)
               for p in range(self.k)]
        fills = (0, tb - 1, 0.0)           # src, local dst, weight pads
        dtypes = (np.int32, np.int32, np.float32)
        flats: list[list] = [[], [], []]
        classes = []
        for c, tc in enumerate(class_tiles):
            emax = max(x[c][0].shape[1] for x in per)
            classes.append((int(tc), int(emax)))
            for i in range(3):
                flats[i].append(np.stack([
                    np.pad(x[c][i], ((0, 0), (0, emax - x[c][i].shape[1])),
                           constant_values=fills[i]).astype(dtypes[i])
                    .reshape(-1) for x in per]))
        return tuple(np.concatenate(f, axis=1) for f in flats) \
            + (tuple(classes),)

    def ensure_pallas_tiles(self, tb: int = 256) -> "CommPlan":
        """Build the Pallas dst-tile layout on first use.

        Per chip, ``build_dst_tile_classes`` regroups the dst-sorted
        local-src and halo-src edge lists into ``tb``-row tiles binned
        into degree-aligned classes (``tile_classes_from_buckets`` over
        ``ell_buckets`` — each class pads to its OWN Emax_c instead of the
        hub tile's global max); per class, Emax_c is padded to the max
        across chips so the flat arrays stack into the usual (k, ...)
        sharded form.  Padding edges carry weight 0 (no-ops in the
        kernel).
        """
        if self.pallas_tb == tb and self.ptile_lsrc is not None:
            return self
        from ..ops.pallas_spmm import tile_classes_from_buckets

        class_tiles = tile_classes_from_buckets(self.ell_buckets, self.b, tb)
        (self.ptile_lsrc, self.ptile_lld, self.ptile_lw,
         self.pallas_lclasses) = self._pallas_family(
            self.ledge_dst, self.ledge_src, self.ledge_w, tb, class_tiles)
        (self.ptile_hsrc, self.ptile_hld, self.ptile_hw,
         self.pallas_hclasses) = self._pallas_family(
            self.hedge_dst, self.hedge_src, self.hedge_w, tb, class_tiles)
        self.pallas_tb = tb
        self.ptile_hrsrc = None            # ring re-base follows the layout
        return self

    def _ring_pos_of_rank(self) -> np.ndarray:
        """(k, R+1) map halo rank → position in the ragged ring's
        round-major receive concat (``ensure_ragged``'s rhalo_dst,
        inverted; the extra slot absorbs the pad rank R)."""
        if self.rhalo_dst is None:
            raise ValueError(
                "ring positions need the ragged layout (ensure_ragged)")
        st = self.rsend_idx.shape[1]
        pos = np.zeros((self.k, self.r + 1), np.int64)
        ar = np.arange(st)
        for p in range(self.k):
            pos[p, self.rhalo_dst[p]] = ar
        return pos

    def ensure_pallas_ragged_tiles(self) -> "CommPlan":
        """Re-base the halo tile sources from halo RANKS to RING positions
        (``ptile_hrsrc``) so the Pallas kernel reads the ppermute ring's
        round-major receive concat directly — same tiles, same per-tile
        edge order as the a2a flavor's, which is the f32 bit-parity
        contract of ``pspmm_pallas_ragged``; no (R, f) halo table is ever
        materialized.  Needs ``ensure_pallas_tiles`` + ``ensure_ragged``.
        """
        if self.ptile_hrsrc is not None:
            return self
        if self.ptile_hsrc is None:
            raise ValueError(
                "ragged pallas tiles need the tile layout first "
                "(ensure_pallas_tiles)")
        pos = self._ring_pos_of_rank()
        self.ptile_hrsrc = np.stack([
            pos[p][self.ptile_hsrc[p]] for p in range(self.k)
        ]).astype(np.int32)
        return self

    def ensure_pallas_cell_tiles(self, tb: int = 256) -> "CommPlan":
        """Build the COMBINED-edge Pallas tile layout on first use (GAT):
        the ``[local ‖ halo]``-sourced edge family in the same
        degree-binned tile classes (histogram: ``cell_buckets``), with 0/1
        MASK weights — the GAT slot passes aggregate by edge presence, not
        Â's values (``models/gat.py``)."""
        if self.pallas_ctb == tb and self.ptile_csrc is not None:
            return self
        from ..ops.pallas_spmm import tile_classes_from_buckets

        self.ensure_cell()
        class_tiles = tile_classes_from_buckets(self.cell_buckets, self.b,
                                                tb)
        mask = (np.asarray(self.edge_w) != 0).astype(np.float32)
        (self.ptile_csrc, self.ptile_cld, self.ptile_cw,
         self.pallas_cclasses) = self._pallas_family(
            self.edge_dst, self.edge_src, mask, tb, class_tiles)
        self.pallas_ctb = tb
        self.ptile_crsrc = None            # ring re-base follows the layout
        return self

    def ensure_pallas_cell_ragged_tiles(self) -> "CommPlan":
        """Combined-tile sources for the ragged ring: local sources stay,
        halo sources (≥ B) re-base to ``B +`` their ring position — the
        kernel table is ``[local table ‖ ring concat]``, no halo-table
        scatter (cf. ``ensure_pallas_ragged_tiles``)."""
        if self.ptile_crsrc is not None:
            return self
        if self.ptile_csrc is None:
            raise ValueError(
                "ragged pallas cell tiles need the combined tile layout "
                "first (ensure_pallas_cell_tiles)")
        pos = self._ring_pos_of_rank()
        out = []
        for p in range(self.k):
            src = self.ptile_csrc[p]
            halo = src >= self.b
            out.append(np.where(halo, self.b + pos[p][np.where(
                halo, src - self.b, 0)], src))
        self.ptile_crsrc = np.stack(out).astype(np.int32)
        return self

    def ensure_cell(self, buckets: tuple | None = None,
                    ctl: int | None = None,
                    max_buckets: int | None = None) -> "CommPlan":
        """Build the combined-edge bucketed layout on first use (GAT).

        ``max_buckets`` overrides the bucket-count cap (A/B lever).  Keep
        the default: the round-4 trace showed ~2,500 small slot gathers and
        suggested merging buckets, but the A/B measured the 2-bucket layout
        WORSE (18.8 s vs 15.9 s products ER GAT) — the scheduler overlaps
        the unrolled small gathers well, and wider buckets pay real padded
        rows.  Recorded so the next round does not retry it.
        """
        if (self.cell_buckets is None
                or buckets not in (None, self.cell_buckets)
                or (ctl is not None and ctl != self.ctl)):
            if max_buckets is None:
                max_buckets = 6
            fields = _cell_fields(_build_ell(
                self.edge_dst, self.edge_src, self.edge_w, self.nnz, self.b,
                row_order=self.row_order, buckets=buckets, tl=ctl,
                max_buckets=max_buckets))
            for name, val in fields.items():
                setattr(self, name, val)
        return self

    def _fold_stores(self) -> dict:
        """The two COO edge stores a slot-form fold covers, as
        ``_build_virtual_rows`` takes them (the source table's height last)."""
        return {
            "tail": (self.ltail_dst, self.ltail_src, self.ltail_w,
                     self.ltail_nnz, self.b, self.b),
            "halo": (self.hedge_dst, self.hedge_src, self.hedge_w,
                     self.hnnz, self.b, self.r)}

    def virtual_rows(self, widths: tuple | None = (VROW_WIDTH,)) -> dict:
        """The slot form of the two COO edge stores (``_build_virtual_rows``):
        ``{"tail": layout | None, "halo": layout | None}`` from ``ltail_*``
        and ``hedge_*``, at the class ``widths`` given — the attention
        layer's one width by default, ``None`` for the widths each store's
        own run lengths choose.  Built on each call, kept by the caller (the
        multi-head attention layer's setup; ``ensure_fold_slots`` for the
        exact GCN step)."""
        return {store: _build_virtual_rows(*args, widths=widths)
                for store, args in self._fold_stores().items()}

    def ensure_fold_slots(self) -> "CommPlan":
        """Build, on first use, the slot form of the hub tail (``ft_*``) and
        of the halo-source edges (``fh_*``) that the exact symmetric GCN
        aggregation folds (``ops.pspmm.pspmm_ell_sym``), each store at the
        class widths its own run-length histogram chooses
        (``choose_fold_widths``).  A store without an edge on any chip has
        no classes and arrays of length 0: its pass does not exist.
        ``work_counts()`` then reports what these passes execute, and the
        counter ``plan.work_counts`` is left anew."""
        if self.fold_tail_classes is None:
            from ..obs.tracing import set_counter, span
            with span("plan.fold"):
                layouts = self.virtual_rows(widths=None)
            for store, pre in (("tail", "ft"), ("halo", "fh")):
                lay = layouts[store] or {
                    "idx": np.zeros((self.k, 0), np.int32),
                    "w": np.zeros((self.k, 0), np.float32),
                    "row": np.zeros((self.k, 0), np.int32), "classes": (),
                    "snapped": dict(UNSNAPPED)}
                setattr(self, f"fold_{store}_classes", lay["classes"])
                self.snapped[f"{store}_edges"] = lay["snapped"]
                for name in ("idx", "w", "row"):
                    setattr(self, f"{pre}_{name}", lay[name])
            set_counter("plan.work_counts", self.work_counts())
        return self

    def fold_counts(self, slots: bool) -> dict:
        """What the step folds its two COO stores as — the program counter
        ``fold``: per store the ``form`` (``"slots"``, ``"coo"``, or ``None``
        where no chip has an edge and the pass does not exist), the class
        shapes, the virtual rows and slots every chip executes, and the true
        edges per chip; ``row_cost`` is the constant the widths were chosen
        by.  ``slots`` says whether the caller's program takes the
        ``ensure_fold_slots`` layouts (the exact full-batch GCN setup) or the
        COO lists."""
        out = {"row_cost": FOLD_ROW_COST}
        for store, true, coo in (("tail", self.ltail_nnz, self.tl),
                                 ("halo", self.hnnz, self.eh)):
            classes = getattr(self, f"fold_{store}_classes") if slots else ()
            live = bool(np.asarray(true).sum())
            out[store] = {
                "form": ("slots" if slots else "coo") if live else None,
                "classes": [list(c) for c in classes],
                "virtual_rows": int(sum(nv for nv, _ in classes)),
                "executed_slots": (int(sum(nv * w for nv, w in classes))
                                   if slots else int(coo) * live),
                "true_edges": np.asarray(true).tolist()}
        return out

    # -------------------------------------------------------- ragged schedule
    def ragged_round_sizes(self) -> tuple:
        """Natural per-round send sizes S_d = max_p send_counts[p, (p+d)%k]
        for d = 1..k−1 — the static buffer sizes of the ragged ppermute ring
        (round d carries chip p → chip (p+d)%k).  Needs the full square
        plan; a shard-proxy slice keeps the tuple built before slicing."""
        sc = np.asarray(self.send_counts)
        if sc.ndim != 2 or sc.shape[0] != sc.shape[1]:
            raise ValueError(
                f"ragged_round_sizes needs the full square plan "
                f"(send_counts {sc.shape}); build the ragged layout with "
                "ensure_ragged() BEFORE shard_proxy_plan slicing")
        k = sc.shape[0]
        idx = np.arange(k)
        return tuple(int(sc[idx, (idx + d) % k].max()) for d in range(1, k))

    def padding_efficiency(self) -> float:
        """Σ send_counts / (k²·S): the fraction of the dense all_to_all's
        padded wire slots that carry real boundary rows.  The auto-select
        gauge of ``SGCN_COMM_SCHEDULE=auto`` (``RAGGED_AUTO_EFFICIENCY``)
        and the ``padding_efficiency`` field of the obs event stream.  On a
        shard-proxy slice the numerator covers the rows in view and the
        denominator scales with them, so the figure stays comparable."""
        wire = self.wire_rows_per_exchange("a2a")
        return float(self.send_counts.sum()) / wire if wire else 1.0

    def work_counts(self) -> dict:
        """Per chip, the true counts of one aggregation pass beside what
        EVERY chip executes for them (all chips run one program over the
        padded shapes): ``slot_edges`` in the ELL buckets (Σ nb·wb slots),
        ``tail_edges`` (``tl``) and ``halo_edges`` (``eh``) — or, once
        ``ensure_fold_slots`` has built their slot form, the slots of the
        virtual rows the exact step executes for them — ``halo_rows``
        received into the halo table (``r``) and ``rows_sent`` (``k·s`` send
        slots) — and what the difference is made of: ``padding``, the
        padding entries of the store's gathered index array (``ell_idx``,
        ``ltail_src``, ``hedge_src``, ``halo_src``, ``send_idx``), and
        ``padding_fanin``, the largest number of them naming one row.  A
        gather of one row a million times over is the slowest work the step
        does (``padding_rows``), so the fan-in is ⌈padding ÷ table height⌉
        by construction, and it is COUNTED here from the arrays, with
        padding told by the counts (by weight 0 in the ELL, whose padding is
        not a suffix).  ``snapped`` says how far the executed row counts
        were moved off the residues modulo 1,024 the v5e runs dear
        (``snap_rows``; window measured in PERF.md §6, PR 36): per slot
        store the ``shapes`` changed, the ``rows`` moved across bucket
        boundaries (ELL) or added (fold classes), and the padding ``slots``
        that added — all 0 where every chosen shape was cheap already, under
        ``ROW_PERIOD`` rows, or forced by the caller.  Plain ints and lists:
        it is left in ``obs.tracing.counters()`` by ``build_comm_plan``."""
        rows = self.ell_idx.shape[0]
        unsent = _unsent_slots(self.send_counts, self.send_idx.shape[2])
        folded = self.fold_tail_classes is not None
        pads = {
            "slot_edges": [self.ell_idx[p][self.ell_w[p] == 0]
                           for p in range(rows)],
            "tail_edges": [self.ft_idx[p][self.ft_w[p] == 0] if folded
                           else self.ltail_src[p, int(self.ltail_nnz[p]):]
                           for p in range(rows)],
            "halo_edges": [self.fh_idx[p][self.fh_w[p] == 0] if folded
                           else self.hedge_src[p, int(self.hnnz[p]):]
                           for p in range(rows)],
            "halo_rows": [self.halo_src[p, int(self.halo_counts[p]):]
                          for p in range(rows)],
            "rows_sent": [self.send_idx[p][unsent[p]] for p in range(rows)],
        }
        return {
            "true": {
                "slot_edges": (self.lnnz - self.ltail_nnz).tolist(),
                "tail_edges": self.ltail_nnz.tolist(),
                "halo_edges": self.hnnz.tolist(),
                "halo_rows": self.halo_counts.tolist(),
                "rows_sent": self.send_counts.sum(axis=1).tolist(),
            },
            "executed": {
                "slot_edges": int(sum(nb * wb for nb, wb in self.ell_buckets)),
                "tail_edges": int(self.ft_idx.shape[1] if folded
                                  else self.tl),
                "halo_edges": int(self.fh_idx.shape[1] if folded
                                  else self.eh),
                "halo_rows": int(self.r),
                "rows_sent": int(self.k * self.s),
            },
            "padding": {store: [int(x.size) for x in per]
                        for store, per in pads.items()},
            "padding_fanin": {store: [padding_fanin(x) for x in per]
                              for store, per in pads.items()},
            "snapped": {store: dict(self.snapped.get(store, UNSNAPPED))
                        for store in ("slot_edges", "tail_edges",
                                      "halo_edges")},
        }

    def wire_rows_per_exchange(self, schedule: str = "a2a",
                               replica: bool = False) -> int:
        """Padded rows the selected schedule puts on the wire per exchange,
        over the chips in view (full plan: all k).  Dense a2a ships the
        whole (k, S) buffer per chip = k²·S rows; the ragged ring ships
        Σ_d S_d rows per chip = k·Σ_d S_d — the padded-vs-true accounting
        the roofline and CommStats report against ``predicted_send_volume``
        (= Σ(λ−1), the true rows).  ``replica=True`` prices the shrunken
        NO-REPLICA exchange of a ``--replica-budget`` step
        (``ensure_replicas``): the ``nrep_*`` pads replace ``s`` /
        ``rr_sizes``."""
        rows, peers = np.asarray(self.send_counts).shape
        if replica and self.rep_slots is None:
            raise ValueError("build the replication layout first "
                             "(ensure_replicas)")
        if schedule == "a2a":
            return int(rows * peers * (self.nrep_s if replica else self.s))
        if schedule == "ragged":
            if replica:
                if self.nrep_rr_sizes is None:
                    raise ValueError(
                        "ragged replica wire needs ensure_ragged() before "
                        "ensure_replicas()")
                sizes = self.nrep_rr_sizes
            else:
                sizes = (self.rr_sizes if self.rr_sizes is not None
                         else self.ragged_round_sizes())
            return int(rows * sum(sizes))
        raise ValueError(f"unknown comm schedule {schedule!r}")

    def wire_buffer_shapes(self, schedule: str = "a2a",
                           replica: bool = False) -> list:
        """Static per-DISPATCH wire-buffer shapes of ONE halo exchange,
        WITHOUT the trailing lane axis (the per-layer table width is the
        model's business — ``models.gcn.exchange_widths`` /
        ``models.gat.gat_exchange_lane_widths``).

        ``'a2a'``: one dispatch of the globally-padded ``(peers, S)`` bucket
        per exchange.  ``'ragged'``: one dispatch of ``(S_d,)`` per LIVE
        round (``ops.pspmm.ragged_live_rounds`` — empty rounds ship nothing
        and vanish from the traced program).  ``replica=True``: the
        shrunken no-replica exchange of a ``--replica-budget`` step — the
        ``nrep_s`` pad / live rounds of ``nrep_rr_sizes`` (same elision
        rule).  This is the shape side of the compiled-program wire
        contract the HLO audit (``sgcn_tpu/analysis``) checks against
        every lowered step.
        """
        if replica and self.rep_slots is None:
            raise ValueError("build the replication layout first "
                             "(ensure_replicas)")
        if schedule == "a2a":
            peers = int(np.asarray(self.send_counts).shape[1])
            return [(peers, self.nrep_s if replica else self.s)]
        if schedule == "ragged":
            # deferred: ops.pspmm imports jax; this module stays numpy-only
            from ..ops.pspmm import ragged_live_rounds

            if replica:
                if self.nrep_rr_sizes is None:
                    raise ValueError(
                        "ragged replica wire needs ensure_ragged() before "
                        "ensure_replicas()")
                sizes = self.nrep_rr_sizes
            else:
                sizes = (self.rr_sizes if self.rr_sizes is not None
                         else self.ragged_round_sizes())
            return [(int(sizes[d - 1]),)
                    for d in ragged_live_rounds(sizes)]
        raise ValueError(f"unknown comm schedule {schedule!r}")

    def ensure_ragged(self, rr_sizes: tuple | None = None,
                      rr_edge_sizes: tuple | None = None) -> "CommPlan":
        """Build the ragged ppermute-ring layout on first use.

        ``rr_sizes`` / ``rr_edge_sizes`` force larger per-round envelopes
        (the mini-batch trainer pads every batch plan to shared round sizes
        so one compiled step serves all batches, like ``pad_comm_plan``).

        Receive-side invariant: the plan's halo order is (owner, vertex) and
        each send list p→q is id-sorted, so round d's received rows land
        EXACTLY in chip q's contiguous per-owner halo slice, in order — the
        per-round edge split (``redge_*``) therefore re-bases hedge src
        straight to the round's receive buffer, and because ``hedge_*`` is
        sorted by (dst, round, recv-pos) at build time, folding round
        contributions into the output accumulator in round order applies
        per-row updates in the SAME sequence as the dense path's single
        halo-src segment-sum — the f32 bit-parity contract of the two
        schedules (tests/test_ragged.py).
        """
        if (self.rr_sizes is not None
                and rr_sizes in (None, self.rr_sizes)
                and rr_edge_sizes in (None, self.rr_edge_sizes)):
            return self
        nat_sizes = self.ragged_round_sizes()
        k, s, r = self.k, self.s, self.r
        sc = np.asarray(self.send_counts)
        if rr_sizes is None:
            rr_sizes = nat_sizes
        elif (len(rr_sizes) != len(nat_sizes)
                or any(a < b for a, b in zip(rr_sizes, nat_sizes))):
            raise ValueError(
                f"forced rr_sizes {rr_sizes} smaller than natural "
                f"{nat_sizes}")
        rr_sizes = tuple(int(x) for x in rr_sizes)
        owner_rank = np.asarray(self.halo_src) // s       # (k, R) owner per
        pos_rank = np.asarray(self.halo_src) % s          # halo rank + pos
        st = max(1, sum(rr_sizes))
        rsend_idx = np.zeros((k, st), np.int32)
        rhalo_dst = np.full((k, st), r, np.int32)         # r = dropped pad
        off = 0
        for d, sd in enumerate(rr_sizes, start=1):
            for p in range(k):
                cnt = int(sc[p, (p + d) % k])             # send side: p → p+d
                rsend_idx[p, off: off + cnt] = self.send_idx[p, (p + d) % k,
                                                             :cnt]
                o = (p - d) % k                           # recv side: o → p
                rc = int(sc[o, p])
                if rc:
                    hs = int(self.halo_counts[p])
                    ranks = np.nonzero(owner_rank[p, :hs] == o)[0]
                    if len(ranks) != rc:                  # plan invariant
                        raise ValueError(
                            f"halo sublist of owner {o} on chip {p} has "
                            f"{len(ranks)} rows, send list says {rc}")
                    rhalo_dst[p, off: off + rc] = ranks.astype(np.int32)
            off += sd
        # per-round halo-src edge families: hedge is (dst, round, pos)-sorted
        # at build time, so each round's subsequence is (dst, pos)-sorted
        per_chip_rounds: list[list] = []
        for q in range(k):
            cnt = int(self.hnnz[q])
            d_ = self.hedge_dst[q, :cnt]
            s_ = self.hedge_src[q, :cnt]
            w_ = self.hedge_w[q, :cnt]
            fold = (q - owner_rank[q, s_]) % k            # arrival round
            per_chip_rounds.append(
                [(d_[fold == d], pos_rank[q, s_[fold == d]], w_[fold == d])
                 for d in range(1, k)])
        nat_es = tuple(
            max((len(per_chip_rounds[q][d][0]) for q in range(k)), default=0)
            for d in range(max(k - 1, 0)))
        if rr_edge_sizes is None:
            rr_edge_sizes = nat_es
        elif (len(rr_edge_sizes) != len(nat_es)
                or any(a < b for a, b in zip(rr_edge_sizes, nat_es))):
            raise ValueError(
                f"forced rr_edge_sizes {rr_edge_sizes} smaller than natural "
                f"{nat_es}")
        rr_edge_sizes = tuple(int(x) for x in rr_edge_sizes)
        et = max(1, sum(rr_edge_sizes))
        redge_dst = np.full((k, et), self.b - 1, np.int32)
        redge_src = np.zeros((k, et), np.int32)
        redge_w = np.zeros((k, et), np.float32)
        off = 0
        for d, ed in enumerate(rr_edge_sizes):
            for q in range(k):
                dd, ss, ww = per_chip_rounds[q][d]
                redge_dst[q, off: off + len(dd)] = dd
                redge_src[q, off: off + len(ss)] = ss
                redge_w[q, off: off + len(ww)] = ww
            off += ed
        self.rr_sizes = rr_sizes
        self.rr_edge_sizes = rr_edge_sizes
        self.rsend_idx = rsend_idx
        self.rhalo_dst = rhalo_dst
        self.redge_dst = redge_dst
        self.redge_src = redge_src
        self.redge_w = redge_w
        return self

    # ----------------------------------------------------- hot-halo replicas
    def replica_scores(self) -> tuple:
        """Per (owner chip, local row): ``(λ, consumer-edge count)`` of every
        owned row, straight from the comm plan — λ is the number of consumer
        chips the row ships to per exchange (its send-list multiplicity) and
        the edge count is how many remote halo-src edges reference it (the
        aggregation work its replica would feed).  ``λ·edges`` is THE
        replica ranking (ISSUE/ROADMAP: λ·degree); the native partitioner's
        cache-aware objective ranks nets by the same quantity
        ((λ−1)·pins in hypergraph terms — the owner part is a pin there).
        Needs the full square plan."""
        sc = np.asarray(self.send_counts)
        if sc.ndim != 2 or sc.shape[0] != sc.shape[1]:
            raise ValueError(
                "replica selection needs the full square plan "
                f"(send_counts {sc.shape}); build replicas with "
                "ensure_replicas() BEFORE shard_proxy_plan slicing")
        k, b, s = self.k, self.b, self.s
        lam = np.zeros((k, b), np.int64)
        cons = np.zeros((k, b), np.int64)
        for q in range(k):
            hs = int(self.halo_counts[q])
            if not hs:
                continue
            hedge_cnt = np.bincount(self.hedge_src[q, : int(self.hnnz[q])],
                                    minlength=self.r)
            slots = np.asarray(self.halo_src[q, :hs])
            o = slots // s
            j = slots % s
            rows = self.send_idx[o, q, j]
            np.add.at(lam, (o, rows), 1)
            np.add.at(cons, (o, rows), hedge_cnt[:hs])
        return lam, cons

    def ensure_replicas(self, budget: int) -> "CommPlan":
        """Build the hot-halo replication layout for ``budget`` rows.

        Selects the top-``budget`` boundary rows globally by λ·degree
        (``replica_scores``; deterministic tie-break on (owner, row)), then
        derives the shrunken no-replica exchange layout: per-pair send
        buckets with those rows deleted (a2a) and, when the ragged layout
        exists, the shrunken per-round ring (``nrep_rr_sizes`` +
        send/receive maps).  Kept rows preserve their relative order on
        both ends, so the shrunken receive side stays aligned with the
        shrunken send side by construction.  A budget above the boundary
        row count clamps (everything replicated — the communication-free
        limit).  Idempotent per budget; call ``ensure_ragged()`` FIRST when
        the ragged schedule is in play (the ring shrink needs the round
        envelope, and ``rep_ring_pos`` indexes the full ring's concat).
        """
        if budget < 0:
            raise ValueError(f"replica budget must be >= 0, got {budget}")
        ring = self.rr_sizes is not None
        if (self.replica_budget == budget and self.rep_slots is not None
                and (not ring or self.nrep_rsend_idx is not None)):
            return self
        k, b, s, r = self.k, self.b, self.s, self.r
        sc = np.asarray(self.send_counts)
        lam, cons = self.replica_scores()
        score = (lam * cons).ravel()
        boundary = np.nonzero(lam.ravel() > 0)[0]
        order = boundary[np.lexsort((boundary, -score[boundary]))]
        chosen = order[:budget]
        rep_mask = np.zeros(k * b, bool)
        rep_mask[chosen] = True
        rep_mask = rep_mask.reshape(k, b)
        self.replica_rows = int(len(chosen))
        self.replica_send_saving = int(lam.ravel()[chosen].sum())
        # shrunken send buckets: kept entries keep their id-sorted order
        nrep_counts = np.zeros((k, k), np.int32)
        kept_lists: dict[tuple[int, int], np.ndarray] = {}
        for p in range(k):
            for q in range(k):
                cnt = int(sc[p, q])
                if not cnt:
                    continue
                rows = self.send_idx[p, q, :cnt]
                kept = np.nonzero(~rep_mask[p, rows])[0]
                kept_lists[(p, q)] = kept
                nrep_counts[p, q] = len(kept)
        nrep_s = max(1, int(nrep_counts.max()) if k else 1)
        nrep_send_idx = np.zeros((k, k, nrep_s), np.int32)
        for (p, q), kept in kept_lists.items():
            nrep_send_idx[p, q, : len(kept)] = self.send_idx[p, q, kept]
        # partial-refresh side channel (``--refresh-band``): the sender's
        # owned replicated rows (drift is measured against a baseline per
        # OWNED row, not per consumer copy) and the replica-only per-pair
        # buckets — exactly the complement of the kept lists above, order
        # preserved so the receive side stays aligned by construction
        rows_lists = [np.nonzero(rep_mask[p])[0] for p in range(k)]
        rs = max(1, max((len(x) for x in rows_lists), default=0))
        rep_rows = np.zeros((k, rs), np.int32)
        rep_row_counts = np.zeros(k, np.int32)
        for p in range(k):
            rep_rows[p, : len(rows_lists[p])] = rows_lists[p]
            rep_row_counts[p] = len(rows_lists[p])
        ronly_counts = (sc.astype(np.int32) - nrep_counts)
        ronly_s = max(1, int(ronly_counts.max()) if k else 1)
        ronly_send_idx = np.zeros((k, k, ronly_s), np.int32)
        ronly_base_pos = np.zeros((k, k, ronly_s), np.int32)
        for p in range(k):
            for q in range(k):
                cnt = int(sc[p, q])
                if not cnt:
                    continue
                rows_pq = self.send_idx[p, q, :cnt]
                deleted = np.nonzero(rep_mask[p, rows_pq])[0]
                if not len(deleted):
                    continue
                ronly_send_idx[p, q, : len(deleted)] = rows_pq[deleted]
                ronly_base_pos[p, q, : len(deleted)] = np.searchsorted(
                    rows_lists[p], rows_pq[deleted]).astype(np.int32)
        # receive side: shrunken halo gather + replica slot lists.  Ring
        # positions: round d's receive slice starts at Σ_{d'<d} S_d' and a
        # slot's within-round position is its send-list position j
        # (ensure_ragged's receive invariant).
        offsets = (np.concatenate([[0], np.cumsum(self.rr_sizes)])
                   if ring else None)
        nrep_halo_src = np.zeros((k, r), np.int32)
        rep_slot_lists, rep_ring_lists, rep_recv_lists = [], [], []
        for q in range(k):
            hs = int(self.halo_counts[q])
            if not hs:
                rep_slot_lists.append(np.zeros(0, np.int64))
                rep_ring_lists.append(np.zeros(0, np.int64))
                rep_recv_lists.append(np.zeros(0, np.int64))
                continue
            slots = np.asarray(self.halo_src[q, :hs])
            o = slots // s
            j = slots % s
            rows = self.send_idx[o, q, j]
            keep = ~rep_mask[o, rows]
            newpos = np.zeros(hs, np.int64)
            npos_del = np.zeros(hs, np.int64)
            for oo in np.unique(o):
                m = o == oo
                newpos[m] = np.cumsum(keep[m]) - 1
                npos_del[m] = np.cumsum(~keep[m]) - 1
            nrep_halo_src[q, :hs] = np.where(
                keep, o * nrep_s + newpos, 0).astype(np.int32)
            reps = np.nonzero(~keep)[0]
            rep_slot_lists.append(reps)
            # partial refresh routes each carried replica slot to its row's
            # position in the replica-only receive buffer (same ordering as
            # the ronly send buckets — deleted rows keep send-list order)
            rep_recv_lists.append(o[reps] * ronly_s + npos_del[reps])
            if ring:
                d = (q - o) % k
                rep_ring_lists.append(offsets[d[reps] - 1] + j[reps])
            else:
                rep_ring_lists.append(np.zeros(0, np.int64))
        rp = max(1, max((len(x) for x in rep_slot_lists), default=0))
        rep_slots = np.full((k, rp), r, np.int32)
        rep_ring_pos = np.zeros((k, rp), np.int32)
        rep_recv_src = np.zeros((k, rp), np.int32)
        for q in range(k):
            rep_slots[q, : len(rep_slot_lists[q])] = rep_slot_lists[q]
            rep_recv_src[q, : len(rep_recv_lists[q])] = rep_recv_lists[q]
            if ring:
                rep_ring_pos[q, : len(rep_ring_lists[q])] = \
                    rep_ring_lists[q]
        self.rep_counts = np.array([len(x) for x in rep_slot_lists],
                                   np.int64)
        self.rep_slots = rep_slots
        self.rp = rp
        self.nrep_s = nrep_s
        self.nrep_send_idx = nrep_send_idx
        self.nrep_send_counts = nrep_counts
        self.nrep_halo_src = nrep_halo_src
        self.rep_ring_pos = rep_ring_pos if ring else None
        self.rs = rs
        self.rep_rows = rep_rows
        self.rep_row_counts = rep_row_counts
        self.ronly_s = ronly_s
        self.ronly_send_idx = ronly_send_idx
        self.ronly_send_counts = ronly_counts
        self.ronly_base_pos = ronly_base_pos
        self.rep_recv_src = rep_recv_src
        if ring:
            idxk = np.arange(k)
            nrr = tuple(int(nrep_counts[idxk, (idxk + d) % k].max())
                        for d in range(1, k))
            st = max(1, sum(nrr))
            full_total = int(sum(self.rr_sizes))
            nrep_rsend_idx = np.zeros((k, st), np.int32)
            nrep_rhalo_dst = np.full((k, st), r, np.int32)
            # pad slots point one past the full ring concat — dropped by the
            # composed replica × stale carry scatter (mode='drop')
            nrep_ring_dst = np.full((k, st), full_total, np.int32)
            off = 0
            for d, sd in enumerate(nrr, start=1):
                for p in range(k):
                    q2 = (p + d) % k
                    cnt = int(nrep_counts[p, q2])
                    if cnt:
                        nrep_rsend_idx[p, off: off + cnt] = \
                            nrep_send_idx[p, q2, :cnt]
                    o = (p - d) % k
                    rc = int(nrep_counts[o, p])
                    if rc:
                        hs = int(self.halo_counts[p])
                        slots = np.asarray(self.halo_src[p, :hs])
                        oarr = slots // s
                        rows = self.send_idx[oarr, p, slots % s]
                        m = (oarr == o) & ~rep_mask[oarr, rows]
                        ranks = np.nonzero(m)[0]
                        if len(ranks) != rc:         # plan invariant
                            raise ValueError(
                                f"kept halo sublist of owner {o} on chip "
                                f"{p} has {len(ranks)} rows, shrunken send "
                                f"list says {rc}")
                        nrep_rhalo_dst[p, off: off + rc] = \
                            ranks.astype(np.int32)
                        # each kept receive slot's home in the FULL ring
                        # concat: its round offset + full send-list position
                        # (the ring receive invariant of ensure_ragged)
                        nrep_ring_dst[p, off: off + rc] = (
                            offsets[d - 1]
                            + (slots % s)[ranks]).astype(np.int32)
                off += sd
            self.nrep_rr_sizes = nrr
            self.nrep_rsend_idx = nrep_rsend_idx
            self.nrep_rhalo_dst = nrep_rhalo_dst
            self.nrep_ring_dst = nrep_ring_dst
        self.replica_budget = int(budget)
        return self

    def replica_carry_shapes(self, fin: int, widths,
                             partial: bool = False) -> dict:
        """Per-layer replica-carry shapes (WITHOUT the stacked leading k
        axis): one ``(RP, f_ℓ)`` feature-replica table and one gradient-
        replica table per layer, at the layer's EXCHANGED width
        (``models.gcn.exchange_widths`` — same lockstep rule as the stale
        carries).  ``partial=True`` (``--refresh-band``) adds the per-layer
        SENDER-side refresh baselines ``rep_base[ℓ]`` — one ``(RS, f_ℓ)``
        table of each chip's own replicated rows as of the last refresh,
        the reference the per-row drift band is measured against.
        Requires ``ensure_replicas()`` first."""
        from ..models.gcn import exchange_widths   # deferred: avoids a cycle

        if self.rep_slots is None:
            raise ValueError(
                "replica carries need the replication layout; call "
                "ensure_replicas() before replica_carry_shapes()")
        fs = exchange_widths(fin, list(widths))
        out = {
            "reps": [(self.rp, f) for f in fs],
            "greps": [(self.rp, f) for f in fs],
        }
        if partial:
            out["rep_base"] = [(self.rs, f) for f in fs]
        return out

    @property
    def partial_refresh_wire_rows(self) -> int:
        """Padded wire rows of ONE partial-refresh side-channel exchange
        (the replica-only a2a of ``--refresh-band`` refresh steps): the
        dense ``(k, RS')`` bucket per chip, on top of the shrunken
        ``nrep_*`` exchange those steps also ship."""
        if self.ronly_send_counts is None:
            raise ValueError("build the replication layout first "
                             "(ensure_replicas)")
        rows, peers = np.asarray(self.ronly_send_counts).shape
        return int(rows * peers * self.ronly_s)

    @property
    def replica_send_volume(self) -> np.ndarray:
        """Per-chip TRUE boundary rows shipped per NO-REPLICA exchange (k,)
        — ``predicted_send_volume`` minus each chip's replicated shipments
        (send lists never hold self-slots, so no diagonal correction)."""
        if self.nrep_send_counts is None:
            raise ValueError("build the replication layout first "
                             "(ensure_replicas)")
        return self.nrep_send_counts.astype(np.int64).sum(axis=1)

    # ------------------------------------------------------------ stale halo
    def stale_carry_shapes(self, fin: int, widths, delta: bool = False,
                           comm_schedule: str = "a2a") -> dict:
        """Per-layer carry shapes (WITHOUT the stacked leading k axis) for
        the pipelined stale-halo mode, SCHEDULE-AWARE.

        ``comm_schedule='a2a'`` (``ops.pspmm.pspmm_stale``):
        ``halos[ℓ]`` / ``ghalos[ℓ]`` are the ``(R, f_ℓ)`` feature- and
        gradient-halo buffers carried across steps, where ``f_ℓ`` is the
        layer's EXCHANGED row width under the trainer's project-first rule
        (``models.gcn.exchange_widths`` — the single shared encoding of that
        rule, so the carries stay in lockstep with the forward's schedule).
        ``bases[ℓ]``: the sender-side ``(k, S, f_ℓ)`` delta baseline when
        ``delta`` (the halo-delta cache), else a ``(1, 1, 1)`` placeholder
        so the carry pytree keeps one static structure per mode.

        ``comm_schedule='ragged'`` (``ops.pspmm.pspmm_stale_ragged``): the
        carries are ROUND-STRUCTURED — ``(Σ_d S_d, f_ℓ)`` round-major ring
        receive buffers (round d occupies its own ``rr_sizes[d-1]``-row
        slice), NOT the dense ``(R, f)`` halo table, and the delta baseline
        shrinks from ``(k, S, f_ℓ)`` to the same ``(Σ_d S_d, f_ℓ)`` ring
        envelope (placeholder ``(1, 1)``).  Requires ``ensure_ragged()``
        first — the round sizes ARE the carry layout.
        """
        from ..models.gcn import exchange_widths   # deferred: avoids a cycle

        fs = exchange_widths(fin, list(widths))
        if comm_schedule == "ragged":
            if self.rr_sizes is None:
                raise ValueError(
                    "round-structured stale carries need the ragged layout; "
                    "call ensure_ragged() before stale_carry_shapes("
                    "comm_schedule='ragged')")
            st = max(1, sum(self.rr_sizes))
            return {
                "halos": [(st, f) for f in fs],
                "ghalos": [(st, f) for f in fs],
                "bases": [((st, f) if delta else (1, 1)) for f in fs],
            }
        if comm_schedule != "a2a":
            raise ValueError(f"unknown comm_schedule {comm_schedule!r}")
        peers = self.send_idx.shape[1]   # == k on a full plan; kept explicit
                                         # so a shard-proxy slice stays right
        return {
            "halos": [(self.r, f) for f in fs],
            "ghalos": [(self.r, f) for f in fs],
            "bases": [((peers, self.s, f) if delta else (1, 1, 1))
                      for f in fs],
        }

    # ------------------------------------------------------------------ stats
    def offwire_send_counts(self) -> np.ndarray:
        """``send_counts`` with each row's SELF-slot zeroed — the rows that
        actually cross the wire.  On the full square plan row i's self-slot
        is column i; a shard-proxy slice records the true chip identity in
        ``chip_ids`` (row 0 of chip c's proxy self-sends at column c)."""
        off = self.send_counts.astype(np.int64).copy()
        if self.chip_ids is not None:
            off[np.arange(off.shape[0]), np.asarray(self.chip_ids)] = 0
        else:
            np.fill_diagonal(off, 0)
        return off

    @property
    def predicted_send_volume(self) -> np.ndarray:
        """Per-chip boundary rows shipped per exchange (k,).

        Matches the trainers' measured ``send_comm_volume``
        (``GPU/PGCN.py:105-114``, ``Parallel-GCN/main.c:264-265``) and the
        partitioners' connectivity metric Σ(λ−1)
        (``GCN-HP/main.cpp:335-345``).
        """
        return self.offwire_send_counts().sum(axis=1)

    @property
    def predicted_message_count(self) -> np.ndarray:
        """Per-chip count of non-empty peer messages (k,)."""
        return (self.offwire_send_counts() > 0).sum(axis=1)

    # --------------------------------------------------------- data placement
    def scatter_rows(self, x: np.ndarray, fill: float = 0.0,
                     chips=None) -> np.ndarray:
        """Global (n, f) row data → stacked per-chip (k, B, f) padded blocks.

        ``chips`` restricts the stack to those chip positions (multi-host
        placement builds only the local run, reading only rows those chips
        own)."""
        x = np.asarray(x)
        f = x.shape[1] if x.ndim > 1 else 1
        if chips is None:
            out = np.full((self.k, self.b, f), fill, dtype=x.dtype)
            out[self.owner, self.local_idx] = x.reshape(self.n, f)
            return out
        chips = list(chips)
        out = np.full((len(chips), self.b, f), fill, dtype=x.dtype)
        x2 = x.reshape(self.n, f)
        for i, p in enumerate(chips):
            sel = self.owner == p
            out[i, self.local_idx[sel]] = x2[sel]
        return out

    def gather_rows(self, blocks: np.ndarray) -> np.ndarray:
        """Stacked per-chip (k, B, f) blocks → global (n, f) row data."""
        return np.asarray(blocks)[self.owner, self.local_idx]

    # ------------------------------------------- receptive-set helpers (serve)
    def global_row_ids(self) -> np.ndarray:
        """(k, B) int64: the GLOBAL vertex id living in each (chip, local
        slot) — the inverse of ``(owner, local_idx)``; −1 on padding slots.
        The sub-graph serving path (``serve/subgraph.py``) uses this to
        express each chip's per-row fold recipes in global row space."""
        out = np.full((self.k, self.b), -1, dtype=np.int64)
        out[self.owner, self.local_idx] = np.arange(self.n, dtype=np.int64)
        return out

    def halo_global_rows(self) -> np.ndarray:
        """(k, R) int64: the GLOBAL vertex id each halo rank holds after one
        exchange; −1 on padding ranks.  Halo rank ``j`` of chip ``c`` gathers
        receive-buffer slot ``halo_src[c, j] = q·S + t``, which owner ``q``
        filled from its local row ``send_idx[q, c, t]`` — so the mapping is
        derivable from the plan alone, without running an exchange.  Needs
        the full square plan (a shard-proxy slice has no peers' send
        lists)."""
        si = np.asarray(self.send_idx)
        if si.ndim != 3 or si.shape[0] != si.shape[1]:
            raise ValueError(
                f"halo_global_rows needs the full square plan "
                f"(send_idx {si.shape}); compute it before "
                "shard_proxy_plan slicing")
        glob = self.global_row_ids()
        out = np.full((self.k, self.r), -1, dtype=np.int64)
        for c in range(self.k):
            hs = int(self.halo_counts[c])
            flat = np.asarray(self.halo_src[c, :hs], dtype=np.int64)
            q = flat // self.s
            t = flat % self.s
            out[c, :hs] = glob[q, si[q, c, t]]
        return out


def choose_replica_budget(plan, decision: dict | None = None) -> int:
    """Auto-tune the replica budget B from the plan's λ·degree curve — the
    ``--replica-budget auto`` rule.

    Ranks every boundary row by its replica score λ·edges
    (``replica_scores``, the quantity ``ensure_replicas`` selects on),
    then picks the KNEE of the descending score curve: the prefix length
    at which the normalized cumulative score sits farthest above the
    diagonal (max-gap elbow — deterministic, scale-free, and exactly the
    "few hub rows own most of the exchange" shape of a power-law
    boundary).  A flat curve (every boundary row equally hot) has its max
    gap at ~0 and picks a small B rather than replicating everything.
    Returns the chosen B; ``decision`` (filled in place) records the
    scoring inputs so the pick is reconstructible from the run manifest
    (``comm_schedule.replica_auto`` block)."""
    lam, cons = plan.replica_scores()
    score = (lam.astype(np.float64) * cons).ravel()
    boundary = np.sort(score[lam.ravel() > 0])[::-1]
    log = decision if decision is not None else {}
    m = int(len(boundary))
    log.update(rule="lambda-degree-knee", boundary_rows=m)
    if m == 0 or boundary[0] <= 0:
        log.update(chosen=0, score_covered=0.0)
        return 0
    cum = np.cumsum(boundary)
    gap = cum / cum[-1] - np.arange(1, m + 1) / m
    b = int(np.argmax(gap)) + 1
    log.update(chosen=b, score_total=float(cum[-1]),
               score_covered=float(cum[b - 1] / cum[-1]),
               knee_gap=float(gap[b - 1]))
    return b


def resolve_comm_schedule(schedule: str | None, plans, model: str,
                          halo_staleness: int = 0,
                          fin: int | None = None, widths=None,
                          compute_dtype: str | None = None,
                          replica_budget: int = 0,
                          decision: dict | None = None) -> str:
    """Resolve a ``comm_schedule`` knob to a concrete transport — THE one
    selection rule shared by both trainers (a second copy would drift).

    ``None`` reads ``$SGCN_COMM_SCHEDULE`` (default ``'a2a'``).  ``'auto'``
    is a PREFERENCE: it picks ``'ragged'`` only when every plan supports it
    (symmetric, full square counts or a pre-built ragged layout, k > 1) and
    the cost rule below says so; everything else resolves to ``'a2a'``
    silently.  An explicit ``'ragged'`` is a CONTRACT — callers validate it
    loudly themselves.

    TWO cost rules, because staleness changes what the wire costs:

    * **exact mode** (``halo_staleness=0``): the latency trade — the ring
      issues k−1 collectives where the dense schedule issues one, so ragged
      only pays when the aggregate dense padding efficiency falls below
      ``RAGGED_AUTO_EFFICIENCY``.  (The Pallas VMEM aggregator is
      schedule-agnostic since ``pspmm_pallas_ragged`` — the old "ragged
      forfeits the VMEM kernel" carve-out is gone: kernel choice is made
      per degree bucket AFTER the transport is picked,
      ``ops/pallas_spmm.py::choose_pallas_dispatch``.)
    * **stale mode** (``halo_staleness=1``): the exchange is HIDDEN — no
      same-step consumer, so its latency (the k−1 dispatches included) is
      off the critical path and the padding-efficiency threshold would be
      measuring a cost that is not being paid.  The only remaining cost is
      wire bytes (ICI occupancy/energy, and the sync steps' exposed
      exchange), so ragged wins whenever it ships strictly fewer wire rows
      than the dense pad.  (The stale trainer never selects the Pallas
      aggregator, so no VMEM exception applies.)

    The scored quantity is the wire-byte efficiency of the model's real
    exchange tables in both rules: every exchange of a plan ships the same
    row set at every lane width (GCN's ``exchange_widths`` rows, GAT's
    ``gat_exchange_lane_widths`` tables), so the per-layer lane weights
    multiply true and wire bytes uniformly and the byte ratio REDUCES
    EXACTLY to the row ratio — the lane arithmetic lives in the
    attribution/CommStats byte gauges.  ``compute_dtype`` is accepted for
    signature stability with those byte models; it cannot change the ratio.

    ``replica_budget`` (B > 0, already resolved from ``auto`` by the
    caller): score the wire rows WITH the replica shrink — a
    ``--replica-budget`` run ships the shrunken ``nrep_*`` exchange on
    every non-refresh step, so comparing the transports on the FULL pads
    would score a wire the run never pays.  Builds the ragged + replica
    layouts on each plan as a side effect (both are lazy and idempotent;
    ``resolve_forward_setup`` would build them right after anyway).

    ``decision`` (optional dict, filled in place): the selection's inputs
    and the rule that fired — the trainers stash it and ``attach_recorder``
    logs it into the run manifest (``comm_schedule`` block), so an ``auto``
    pick is reconstructible from the run directory alone.
    """
    import os
    del compute_dtype       # lane weights cancel in the ratio (see above)
    log = decision if decision is not None else {}
    asked = schedule
    if schedule is None:
        schedule = os.environ.get("SGCN_COMM_SCHEDULE", "a2a")
        asked = f"${{SGCN_COMM_SCHEDULE}}={schedule}"
    if schedule not in ("a2a", "ragged", "auto"):
        raise ValueError(
            f"comm_schedule must be 'a2a', 'ragged' or 'auto', got "
            f"{schedule!r}")
    log.update(asked=asked, model=model, halo_staleness=int(halo_staleness),
               replica_budget=int(replica_budget))

    def resolved(value: str, rule: str) -> str:
        log.update(resolved=value, rule=rule)
        return value

    if schedule != "auto":
        return resolved(schedule, "explicit")
    if model not in ("gcn", "gat"):
        return resolved("a2a", "model has no ragged transport")
    true = wire = wire_ragged = 0
    for p in plans:
        sc = np.asarray(p.send_counts)
        ragged_ready = (p.rr_sizes is not None
                        or (sc.ndim == 2 and sc.shape[0] == sc.shape[1]))
        if not (p.symmetric and ragged_ready and sc.shape[1] > 1):
            return resolved("a2a", "plan does not support the ragged ring "
                                   "(asymmetric, sliced, or k == 1)")
        if replica_budget:
            # replica-aware scoring: the steady-state step ships the
            # SHRUNKEN exchange, so the transports are compared at the
            # shrunken pads (the full figures are logged alongside)
            p.ensure_ragged()
            p.ensure_replicas(replica_budget)
        true += int(sc.sum())
        wire += p.wire_rows_per_exchange("a2a")
        wire_ragged += p.wire_rows_per_exchange("ragged")
    log.update(true_rows=true, wire_rows_a2a=wire,
               wire_rows_ragged=wire_ragged)
    if replica_budget:
        true = sum(int(np.asarray(p.nrep_send_counts).sum()) for p in plans)
        wire = sum(p.wire_rows_per_exchange("a2a", replica=True)
                   for p in plans)
        wire_ragged = sum(p.wire_rows_per_exchange("ragged", replica=True)
                          for p in plans)
        log.update(replica_rows=sum(int(p.replica_rows) for p in plans),
                   true_rows_replica=true,
                   wire_rows_a2a_replica=wire,
                   wire_rows_ragged_replica=wire_ragged)
    log.update(padding_efficiency=(true / wire if wire else 1.0),
               threshold=RAGGED_AUTO_EFFICIENCY)
    if halo_staleness:
        # hidden exchange: bytes-only rule (see docstring)
        if wire_ragged < wire:
            return resolved("ragged", "hidden-exchange wire-byte rule: "
                                      "ragged ships fewer wire rows")
        return resolved("a2a", "hidden-exchange wire-byte rule: ragged "
                               "ships no fewer wire rows")
    if not wire or true / wire >= RAGGED_AUTO_EFFICIENCY:
        return resolved("a2a", "padding efficiency at/above threshold")
    # no Pallas exception: the VMEM aggregator rides BOTH transports since
    # pspmm_pallas_ragged (schedule-agnostic kernel family; per-bucket
    # kernel choice happens after transport selection)
    return resolved("ragged", "padding efficiency below threshold")


def padding_rows(count: int, height: int) -> np.ndarray:
    """Where the padding entries of one chip's index array point: entry ``i``
    names row ``i mod height`` of the table the array indexes.

    THE rule for every gathered index array a plan ships (PERF.md §6, PR 28).
    A padding entry does no useful work (its weight or mask is 0, or no valid
    reader gathers it) but the device still executes its gather, and where it
    points sets what that costs: written as 0, millions of consecutive
    gathers read ONE 512-byte row, which the v5e serves at about half the
    rate of distinct rows — the chip with the most padding set the pace.
    Consecutive padding entries therefore name consecutive distinct rows, in
    bounds, and no row is named by more than ``padding_fanin_bound(count,
    height)`` = ⌈count ÷ height⌉ of them — the least any rule can reach.
    Nothing may recognise padding by its index: the counts (``lnnz``,
    ``ltail_nnz``, ``hnnz``, ``send_counts``, ``halo_counts``) and the zero
    weights / masks say what is padding.  The slots ``snap_rows`` adds —
    rows that join a wider bucket, virtual rows that bring a fold class onto
    a row count the gather runs cheaply (residues modulo 1,024 measured on
    the v5e: PERF.md §6, PR 36) — are padding of this kind and follow this
    rule."""
    return (np.arange(count, dtype=np.int64) % max(height, 1)).astype(np.int32)


def padding_fanin_bound(count: int, height: int) -> int:
    """The most padding entries ``padding_rows`` lets name one row."""
    return -(-int(count) // max(int(height), 1))


def padding_fanin(idx: np.ndarray) -> int:
    """The largest number of the given (padding) entries naming one row."""
    return int(np.bincount(idx).max()) if idx.size else 0


def rows_cheap(rows):
    """Whether a bucket or fold class of ``rows`` rows (an int or an array)
    runs at the cheap price of a slot: under ``ROW_PERIOD`` rows, or with a
    residue modulo ``ROW_PERIOD`` inside ``ROW_WINDOW`` (``snap_rows``)."""
    res = rows % ROW_PERIOD
    return (rows < ROW_PERIOD) | ((res >= ROW_WINDOW[0])
                                  & (res <= ROW_WINDOW[1]))


UNSNAPPED = {"shapes": 0, "rows": 0, "slots": 0}


def snap_rows(shapes: tuple, cover: bool) -> tuple:
    """Move the row counts of ``shapes = ((rows, width), ...)`` that the plan
    CHOSE onto residues modulo ``ROW_PERIOD`` the v5e runs cheaply; returns
    ``(shapes, {"shapes": changed, "rows": moved or added, "slots": added})``.

    Why: ``bucketed_slot_reduce`` gathers a bucket's slot as one ``(rows,)``
    run, and what the gather costs a slot follows ``rows mod 1,024``, not
    width, form, lanes or the indices — ~5 ns inside ``ROW_WINDOW``, ~11 ns
    from residue 897 up to AND INCLUDING the next multiple of 1,024
    (PERF.md §5's per-bucket tables, PR 35, in the cells; the curve over
    the residue that set the window: ``scripts/row_residue_micro.py``,
    ``bench_artifacts/row_residue_micro.json``, PERF.md §6, PR 36).
    A shape under ``ROW_PERIOD`` rows is left alone, and so is every shape
    a caller FORCES (``buckets=`` / ``widths=``: the mini-batch envelope) —
    this is applied where shapes are chosen, nowhere else.

    ``cover=True``: ELL buckets, which must go on covering exactly Σ rows in
    descending degree order.  A boundary may move LATER at no cost in
    correctness: δ rows of bucket i+1 join bucket i at width w_i ≥ their
    degree (no new tail edge; the slots of every row keep their order),
    adding δ · (w_i − w_{i+1}) padding slots.  The moves (each under
    ``ROW_PERIOD`` rows and within the next bucket) that leave the fewest
    buckets outside the window, then add the fewest slots, are found
    exactly, boundary by boundary; ``rows`` counts the rows moved.
    ``cover=False``: fold classes, whose virtual rows may simply grow
    (padding rows point at ``b − 1`` with weight 0): each count goes up to
    the window; ``rows`` counts the rows added."""
    if all(rows_cheap(n) for n, _ in shapes):
        return tuple(shapes), dict(UNSNAPPED)
    rows = np.array([n for n, _ in shapes], np.int64)
    widths = np.array([w for _, w in shapes], np.int64)
    if not cover:
        res = rows % ROW_PERIOD
        up = np.where(rows_cheap(rows), rows, rows - res + ROW_WINDOW[0]
                      + ROW_PERIOD * (res > ROW_WINDOW[1]))
        moved = up - rows
    else:
        m, d = len(shapes), np.arange(ROW_PERIOD)
        dear, never = 1 << 40, 1 << 60      # a shape left dear; not allowed
        grow = d[None] - d[:, None] + ROW_PERIOD - 1    # [previous, this]
        cost = np.where(d == 0, 0, never)   # by the previous boundary's move
        back = []
        for i in range(m):
            by = rows[i] + np.arange(1 - ROW_PERIOD, ROW_PERIOD)
            price = np.where(by < 1, never,
                             np.where(rows_cheap(by), 0, dear))
            # this boundary's move: within the next bucket, and only down
            # the widths; the last bucket ends at Σ rows
            gap = widths[i] - widths[i + 1] if i < m - 1 else 0
            room = rows[i + 1] if i < m - 1 and gap >= 0 else 0
            total = (cost[:, None] + price[grow]
                     + np.where(d <= room, d * gap, never)[None])
            back.append(total.argmin(0))
            cost = np.minimum(never, total[back[-1], d])
        moved = np.zeros(m, np.int64)       # boundary i moves by moved[i]
        for i in range(m - 1, 0, -1):
            moved[i - 1] = back[i][moved[i]]
        up = rows + moved - np.concatenate([[0], moved[:-1]])
    return (tuple(zip(up.tolist(), widths.tolist())),
            {"shapes": int((up != rows).sum()), "rows": int(moved.sum()),
             "slots": int(((up - rows) * widths).sum())})


def _unsent_slots(send_counts, s: int) -> np.ndarray:
    """Mask ``(..., peers, S)`` of the padding slots of the send buckets."""
    return np.arange(s) >= np.asarray(send_counts)[..., None]


def _pad_exchange(send_idx, send_counts, halo_src, halo_counts, b: int):
    """Write the ``padding_rows`` into the exchange layout, in place: per chip
    the unused tail of every send bucket (rows of the ``b`` local rows, one
    numbering across the chip's buckets) and of the halo gather (slots of the
    ``peers·S`` receive buffer)."""
    k, peers, s = send_idx.shape
    unsent = _unsent_slots(send_counts, s)
    for p in range(k):
        send_idx[p][unsent[p]] = padding_rows(int(unsent[p].sum()), b)
        hc = int(halo_counts[p])
        halo_src[p, hc:] = padding_rows(halo_src.shape[1] - hc, peers * s)


def _relabel(n: int, partvec: np.ndarray, k: int, pad_rows_to: int,
             order_key: np.ndarray | None = None):
    """Shared vertex relabeling: (owner, local_idx, part_sizes, b, row_valid).

    Chip ``p`` owns local slots 0..B-1.  Within a part, vertices are ranked
    by global id (``order_key=None``) or descending by ``order_key`` with
    global id as the tie-break — the degree ordering that makes the bucketed
    ELL layout tight.  Single source of truth for both plan builders below.
    """
    owner = np.asarray(partvec, dtype=np.int64)
    if owner.shape[0] != n:
        raise ValueError(f"partvec length {owner.shape[0]} != n {n}")
    if n and (owner.min() < 0 or owner.max() >= k):
        raise ValueError("partvec entries out of range")
    part_sizes = np.bincount(owner, minlength=k)
    b = int(part_sizes.max()) if n else 1
    b = max(1, -(-b // pad_rows_to) * pad_rows_to)
    if order_key is None:
        order = np.lexsort((np.arange(n), owner))
    else:
        order = np.lexsort((np.arange(n), -np.asarray(order_key), owner))
    local_idx = np.empty(n, dtype=np.int64)
    starts = np.zeros(k + 1, dtype=np.int64)
    np.cumsum(part_sizes, out=starts[1:])
    local_idx[order] = np.arange(n) - starts[owner[order]]
    row_valid = np.zeros((k, b), dtype=np.float32)
    for p in range(k):
        row_valid[p, : part_sizes[p]] = 1.0
    return owner, local_idx, part_sizes, b, row_valid


def _split_edges(edge_dst, edge_src, edge_w, nnz, b, r,
                 el: int | None = None, eh: int | None = None,
                 halo_fold_key=None):
    """Split padded (k, E) edge lists into local-src and halo-src lists.

    Local edges (``src < b``) keep their src; halo edges re-base src to the
    halo block (``src - b``).  Filtering preserves the sorted-by-dst
    invariant.  ``el`` / ``eh`` force a larger padded width (shared
    compilation envelopes); padding edges carry weight 0, dst ``b-1`` (the
    lists stay sorted) and the sources ``padding_rows`` gives — distinct rows
    of the local block (``b``) and of the halo block (``r``), never one row a
    million times.

    ``halo_fold_key`` (optional, (k, R) int): per-chip fold position of each
    halo rank — the ragged ring's arrival round ``(chip − owner) mod k``.
    When given, each chip's halo edges are re-sorted by (dst, fold, rank) so
    the dense halo-src segment-sum applies per-row updates in the SAME
    sequence as the ragged schedule's round-order fold — the f32 bit-parity
    contract between the two exchange schedules (``CommPlan.ensure_ragged``).
    Within a (dst, round) run the rank order equals the receive-buffer
    order, so each round's subsequence stays (dst, pos)-sorted too.
    """
    k = edge_dst.shape[0]
    parts = []
    for p in range(k):
        cnt = int(nnz[p])
        d, s0, w = edge_dst[p, :cnt], edge_src[p, :cnt], edge_w[p, :cnt]
        lm = s0 < b
        hd, hs, hw = d[~lm], s0[~lm] - b, w[~lm]
        if halo_fold_key is not None and len(hd):
            fk = halo_fold_key[p]
            o = np.lexsort((hs, fk[hs], hd))
            hd, hs, hw = hd[o], hs[o], hw[o]
        parts.append((d[lm], s0[lm], w[lm], hd, hs, hw))
    lnnz = np.array([len(t[0]) for t in parts], dtype=np.int64)
    hnnz = np.array([len(t[3]) for t in parts], dtype=np.int64)
    el_nat = max(1, int(lnnz.max()) if k else 1)
    eh_nat = max(1, int(hnnz.max()) if k else 1)
    el = el_nat if el is None else el
    eh = eh_nat if eh is None else eh
    if el < el_nat or eh < eh_nat:
        raise ValueError("split envelope smaller than natural edge counts")
    ld = np.full((k, el), b - 1, dtype=np.int32)
    ls = np.empty((k, el), dtype=np.int32)
    lw = np.zeros((k, el), dtype=np.float32)
    hd = np.full((k, eh), b - 1, dtype=np.int32)
    hs = np.empty((k, eh), dtype=np.int32)
    hw = np.zeros((k, eh), dtype=np.float32)
    for p, (d1, s1, w1, d2, s2, w2) in enumerate(parts):
        ld[p, : len(d1)] = d1
        ls[p, : len(s1)] = s1
        ls[p, len(s1):] = padding_rows(el - len(s1), b)
        lw[p, : len(w1)] = w1
        hd[p, : len(d2)] = d2
        hs[p, : len(s2)] = s2
        hs[p, len(s2):] = padding_rows(eh - len(s2), r)
        hw[p, : len(w2)] = w2
    return dict(el=el, eh=eh, ledge_dst=ld, ledge_src=ls, ledge_w=lw,
                hedge_dst=hd, hedge_src=hs, hedge_w=hw, lnnz=lnnz, hnnz=hnnz)


def ell_degree_profile(ledge_dst, lnnz, b) -> np.ndarray:
    """Pointwise max over chips of the per-row local in-degree, (b,)."""
    k = ledge_dst.shape[0]
    prof = np.zeros(b, dtype=np.int64)
    for p in range(k):
        np.maximum(prof,
                   np.bincount(ledge_dst[p, : int(lnnz[p])], minlength=b),
                   out=prof)
    return prof


def _choose_buckets(profile: np.ndarray, max_buckets: int = 6,
                    width_cap: int = 64) -> tuple:
    """Optimal ≤``max_buckets`` contiguous row buckets for a DESCENDING
    degree profile, minimizing total padded slots Σ nb·wb (wb = max degree
    in the bucket = degree at its first row).  DP over degree-change points,
    subsampled to 64 candidates on graphs with many distinct degrees.

    ``width_cap`` bounds every bucket width: the SpMM unrolls one fused
    gather per width slot, so program size scales with Σ wb — a power-law
    hub (ogbn-arxiv hubs reach ~13k in-degree) must NOT set the width.
    Rows beyond the cap spill their overflow edges to the COO tail
    (``ltail_*``) — not a small store: 13 % of the edges, 16.2 M, on the
    products stand-in (391,884 hub rows; PERF.md §5).  The exact GCN step
    and the attention layer fold it as slot passes over virtual rows
    (``_build_virtual_rows``); the programs that keep the COO list fold it
    by scatter-add at about two slots an edge.

    The row counts returned are the DP's, not yet the ones executed: every
    caller that CHOOSES a layout by this function (``_build_ell``,
    ``shared_ell_buckets``, the typed layouts' ``_relation_buckets``) passes
    them through ``snap_rows``, which moves the boundaries of buckets of
    ``ROW_PERIOD`` rows and more off the residues modulo 1,024 that the
    v5e's gather runs at twice the price (window measured by
    ``scripts/row_residue_micro.py``; PERF.md §6, PR 36)."""
    b = len(profile)
    d = np.minimum(np.maximum(np.asarray(profile, dtype=np.int64), 0),
                   width_cap)
    cuts = [0] + [i for i in range(1, b) if d[i] != d[i - 1]] + [b]
    if len(cuts) > 65:
        keep = np.unique(np.linspace(0, len(cuts) - 1, 65).astype(int))
        cuts = [cuts[i] for i in keep]
    m = len(cuts)
    # bucket width = MAX degree inside the segment (profiles are descending
    # for the local-degree relabel key, but only near-descending for e.g.
    # the combined local+halo degree — take the true segment max, not d[start])
    segmax = [[0] * m for _ in range(m)]
    for i in range(m - 1):
        run = 0
        for j in range(i + 1, m):
            run = max(run, int(d[cuts[j - 1]: cuts[j]].max()))
            segmax[i][j] = run
    inf = float("inf")
    best = [[inf] * (max_buckets + 1) for _ in range(m)]
    back = [[0] * (max_buckets + 1) for _ in range(m)]
    best[0][0] = 0.0
    for j in range(1, m):
        for q in range(1, max_buckets + 1):
            for i in range(j):
                if best[i][q - 1] == inf:
                    continue
                w = max(segmax[i][j], 1)
                c = best[i][q - 1] + (cuts[j] - cuts[i]) * w
                if c < best[j][q]:
                    best[j][q] = c
                    back[j][q] = i
    q = min(range(1, max_buckets + 1), key=lambda t: best[m - 1][t])
    segs = []
    j = m - 1
    while j > 0:
        i = back[j][q]
        segs.append((cuts[j] - cuts[i], max(segmax[i][j], 1)))
        j, q = i, q - 1
    return tuple(reversed(segs))


def _single_bucket_width(alldeg: np.ndarray, tail_frac: float) -> int:
    """Classic ELL width choice: smallest multiple of 4 whose overflow tail
    holds at most ``tail_frac`` of the edges (capped at the max degree)."""
    maxdeg = int(alldeg.max()) if alldeg.size else 0
    total = max(1, int(alldeg.sum()))
    ell_k = 4
    while ell_k < maxdeg:
        if int(np.maximum(alldeg - ell_k, 0).sum()) <= tail_frac * total:
            break
        ell_k += 4
    return min(ell_k, max(maxdeg, 1))


def _build_ell(ledge_dst, ledge_src, ledge_w, lnnz, b,
               row_order: str = "degree",
               buckets: tuple | None = None, tl: int | None = None,
               tail_frac: float = 0.02, max_buckets: int = 6):
    """Bucketed-ELL layout of the local-src edge lists (see CommPlan).

    ``row_order='degree'`` (rows pre-sorted descending by local degree):
    bucket structure from ``_choose_buckets`` — or ``buckets`` forced, for
    mini-batch plans sharing one compiled envelope — with width-capped
    buckets; only hub rows past the cap spill edges to the COO tail.
    ``row_order='id'``: one bucket of the classic tail-bounded width plus
    the COO overflow tail (emit-compatible row numbering).

    Padding slots and padding tail edges carry weight 0 and the sources
    ``padding_rows`` gives (rows of ``[0, b)``: in bounds for the local table
    and for the combined ``[local ‖ halo]`` table of ``ensure_cell`` alike).
    """
    k = ledge_dst.shape[0]
    degs = [np.bincount(ledge_dst[p, : int(lnnz[p])], minlength=b)
            for p in range(k)]
    snapped = dict(UNSNAPPED)
    if buckets is None:
        if row_order == "degree":
            prof = np.zeros(b, dtype=np.int64)
            for dg in degs:
                np.maximum(prof, dg, out=prof)
            buckets, snapped = snap_rows(
                _choose_buckets(prof, max_buckets=max_buckets), cover=True)
        else:
            alldeg = (np.concatenate(degs) if k else np.zeros(1, np.int64))
            buckets = ((b, _single_bucket_width(alldeg, tail_frac)),)
    if sum(nb for nb, _ in buckets) != b:
        raise ValueError(f"buckets {buckets} do not cover {b} rows")
    et = sum(nb * wb for nb, wb in buckets)
    # WIDTH-MAJOR flat layout: bucket at base `off` stores slot t of row r
    # (local rank r-r0 in the bucket) at off + t·nb + (r-r0), so the SpMM's
    # per-slot gathers read contiguous (nb,) index runs — one fused
    # gather·w + add per slot, no (nb, wb, f) intermediate to relayout
    # (the row-major form cost ~17 ms/epoch of data formatting in the
    # round-3 trace at ogbn-arxiv scale).
    row_base = np.empty(b, dtype=np.int64)   # off + (r - r0), stride nb
    row_stride = np.empty(b, dtype=np.int64)
    row_cap = np.empty(b, dtype=np.int64)
    off = r0 = 0
    for nb, wb in buckets:
        row_base[r0: r0 + nb] = off + np.arange(nb, dtype=np.int64)
        row_stride[r0: r0 + nb] = nb
        row_cap[r0: r0 + nb] = wb
        off += nb * wb
        r0 += nb
    ell_idx = np.empty((k, et), dtype=np.int32)
    ell_wv = np.zeros((k, et), dtype=np.float32)
    tails = []
    for p in range(k):
        cnt = int(lnnz[p])
        d = ledge_dst[p, :cnt].astype(np.int64)
        s0 = ledge_src[p, :cnt]
        w = ledge_w[p, :cnt]
        # position of each edge within its (sorted) dst run
        starts = np.zeros(b + 1, dtype=np.int64)
        np.cumsum(degs[p], out=starts[1:])
        pos = np.arange(cnt) - starts[d]
        # beyond-width edges (hub rows past the width cap, or a forced
        # envelope narrower than a row) spill to the COO tail
        main = pos < row_cap[d]
        slots = row_base[d[main]] + pos[main] * row_stride[d[main]]
        ell_wv[p][slots] = w[main]
        pad = ell_wv[p] == 0
        ell_idx[p][pad] = padding_rows(int(pad.sum()), b)
        ell_idx[p][slots] = s0[main]
        tails.append((d[~main].astype(np.int32), s0[~main], w[~main]))
    ltail_nnz = np.array([len(t[0]) for t in tails], dtype=np.int64)
    tl_nat = max(1, int(ltail_nnz.max()) if k else 1)
    tl = tl_nat if tl is None else tl
    if tl < tl_nat:
        raise ValueError("tail envelope smaller than natural tail size")
    ltail_dst = np.full((k, tl), b - 1, dtype=np.int32)
    ltail_src = np.empty((k, tl), dtype=np.int32)
    ltail_w = np.zeros((k, tl), dtype=np.float32)
    for p, (d, s0, w) in enumerate(tails):
        ltail_dst[p, : len(d)] = d
        ltail_src[p, : len(s0)] = s0
        ltail_src[p, len(s0):] = padding_rows(tl - len(s0), b)
        ltail_w[p, : len(w)] = w
    return dict(ell_k=max(wb for _, wb in buckets), tl=tl,
                ell_buckets=buckets, ell_idx=ell_idx, ell_w=ell_wv,
                ltail_dst=ltail_dst, ltail_src=ltail_src, ltail_w=ltail_w,
                ltail_nnz=ltail_nnz, snapped={"slot_edges": snapped})


def _run_lengths(dst, w, counts, b: int) -> list:
    """Per chip, the real (weight != 0) edges each of the ``b`` destinations
    has in a dst-sorted COO store."""
    return [np.bincount(dst[p, :int(c)][w[p, :int(c)] != 0], minlength=b)
            for p, c in enumerate(counts)]


def _class_rows(dg: np.ndarray, widths: tuple) -> tuple:
    """How a destination's ``dg`` edges are cut into virtual rows of the
    ascending class ``widths``: full runs of the widest class, the remainder
    as one row of the narrowest class that holds it.  Returns ``(full,
    cls)``: full runs per destination, and the class of its remainder (−1
    where there is none)."""
    wmax = widths[-1]
    full, rem = dg // wmax, dg % wmax
    cls = np.where(rem > 0, np.searchsorted(widths, rem), -1)
    return full, cls


def _fold_class_rows(degs: list, widths: tuple) -> tuple:
    """``((nv_c, W_c), ...)`` as the run lengths alone give them: per class
    of ``widths`` the virtual rows of the fullest chip (all chips run one
    program), a multiple of 8; classes no chip uses are left out."""
    nv = np.zeros(len(widths), np.int64)
    for dg in degs:
        hist = np.bincount(dg)                     # by run length
        full, cls = _class_rows(np.arange(len(hist)), widths)
        rows = np.bincount(cls[cls >= 0], weights=hist[cls >= 0],
                           minlength=len(widths)).astype(np.int64)
        rows[-1] += int((full * hist).sum())
        np.maximum(nv, rows, out=nv)
    return tuple((int(-(-n // 8) * 8), w) for n, w in zip(nv, widths) if n)


def fold_class_shapes(degs: list, widths: tuple) -> tuple:
    """``((nv_c, W_c), ...)`` a store of per-chip run lengths ``degs`` takes
    at the class ``widths``: the rows of the fullest chip in each class, a
    multiple of 8 (``_fold_class_rows``), and then, from ``ROW_PERIOD`` rows
    up, raised onto a row count the slot gather runs cheaply (``snap_rows``:
    the residues measured dear on the v5e are avoided, at under
    ``ROW_PERIOD`` more virtual rows a class)."""
    return snap_rows(_fold_class_rows(degs, widths), cover=False)[0]


def choose_fold_widths(degs: list, row_cost: float = FOLD_ROW_COST) -> tuple:
    """The class widths for a fold store, from the store's own run-length
    histogram: the ascending subset of ``FOLD_WIDTHS`` with the least
    ``executed slots + row_cost · virtual rows`` at the shapes every chip
    executes.  A hub tail (long runs) takes wide rows, a store of short
    runs over nearly every destination (the halo-source edges) narrow ones;
    one width too wide pads, one too narrow pays a scatter row per few
    slots."""
    best = None
    for bits in range(1, 1 << len(FOLD_WIDTHS)):
        widths = tuple(w for i, w in enumerate(FOLD_WIDTHS) if bits >> i & 1)
        cost = sum(nv * (w + row_cost)
                   for nv, w in fold_class_shapes(degs, widths))
        if best is None or cost < best[0]:
            best = (cost, widths)
    return best[1]


def _build_virtual_rows(dst, src, w, counts, b: int, height: int,
                        widths: tuple | None = None) -> dict | None:
    """The slot form of a dst-sorted COO edge store — the hub tail
    (``ltail_*``) or the halo-source edges (``hedge_*``) — beside
    ``_build_ell``'s: ``(k, E)`` lists with ``counts[p]`` real edges a chip
    become **virtual rows**, a destination's edges cut into runs (full runs
    of the widest class of ``widths``, the remainder one row of the
    narrowest class that holds it), each class one bucket of a width-major
    slot layout, so the store goes through
    ``ops.pspmm.bucketed_slot_reduce`` like every other slot and ONE sorted
    scatter a class and pass adds the virtual rows' sums to their
    destinations (instead of one scatter-add per edge).  ``widths=None``
    takes ``choose_fold_widths`` of the store's run lengths.

    Returns ``classes = ((nv_c, W_c), ...)`` (static; ascending widths,
    shapes the maximum over chips, moved onto cheap row counts by
    ``snap_rows``, which ``snapped`` counts), ``idx`` / ``w`` ``(k, Σ nv_c·W_c)``
    (class after class; slot t of a class's virtual row v at ``off_c + t·nv_c
    + v``; ``w`` the edge weights, 0 on padding, where ``idx`` holds the
    ``padding_rows`` of the source table's ``height``) and ``row`` ``(k, Σ
    nv_c)`` the destination of each virtual row (ascending within a class;
    padding rows point at ``b − 1`` with weights 0) — or ``None`` where no
    chip has a real edge in the store (k = 1 has no halo edges; a graph
    without hubs no tail), so the caller skips the pass."""
    k = dst.shape[0]
    degs = _run_lengths(dst, w, counts, b)
    if widths is None:
        widths = choose_fold_widths(degs)
    classes, snapped = snap_rows(_fold_class_rows(degs, widths), cover=False)
    if not classes:
        return None
    widths = tuple(wd for _, wd in classes)
    nvs = np.array([nv for nv, _ in classes], np.int64)
    sizes = nvs * np.array(widths)                  # slots of each class
    offs, roffs = np.cumsum(sizes) - sizes, np.cumsum(nvs) - nvs
    total = int(sizes.sum())
    idx = np.empty((k, total), np.int32)
    wv = np.zeros((k, total), np.float32)
    row = np.full((k, int(nvs.sum())), b - 1, np.int32)
    last = len(widths) - 1
    for p, dg in enumerate(degs):
        cnt = int(counts[p])
        real = w[p, :cnt] != 0
        s0, wt = src[p, :cnt][real], w[p, :cnt][real]   # dst-sorted, as dg
        full, cls = _class_rows(dg, widths)
        first = np.cumsum(dg) - dg                  # first edge of a row
        for c, wd in enumerate(widths):
            # a virtual row is a contiguous run of its destination's edges:
            # where it starts, how many it holds
            per = (cls == c).astype(np.int64) + (full if c == last else 0)
            dests = np.repeat(np.arange(b), per)
            row[p, roffs[c]: roffs[c] + len(dests)] = dests
            nth = np.arange(len(dests)) - np.repeat(np.cumsum(per) - per, per)
            skip = nth * wd if c == last else full[dests] * widths[-1]
            start, length = first[dests] + skip, dg[dests] - skip
            seg = slice(offs[c], offs[c] + sizes[c])
            iv, wvv = (x[p, seg].reshape(wd, nvs[c]) for x in (idx, wv))
            for t in range(wd):
                v = np.flatnonzero(length > t)
                iv[t, v], wvv[t, v] = s0[start[v] + t], wt[start[v] + t]
        pad = wv[p] == 0
        idx[p, pad] = padding_rows(int(pad.sum()), height)
    return {"idx": idx, "w": wv, "row": row, "classes": classes,
            "snapped": snapped}


def shared_ell_buckets(plans: list, b: int, combined: bool = False) -> tuple:
    """Bucket structure covering every plan's degree profile — the shared
    compiled-envelope companion to ``pad_comm_plan`` for mini-batch plans
    (all padded to ``b`` rows).  ``combined=True`` covers the combined
    local+halo edge lists (the GAT layout) instead of the local-src ones."""
    prof = np.zeros(b, dtype=np.int64)
    for pl in plans:
        q = (ell_degree_profile(pl.edge_dst, pl.nnz, pl.b) if combined
             else ell_degree_profile(pl.ledge_dst, pl.lnnz, pl.b))
        np.maximum(prof[: pl.b], q, out=prof[: pl.b])
    if all(pl.row_order == "degree" for pl in plans):
        return snap_rows(_choose_buckets(prof), cover=True)[0]
    # id-ordered rows: one classic tail-bounded width shared by all.
    # Derive each plan's natural combined width from its degree counts
    # directly — materializing the full cell layout just to read the width
    # would double the O(nnz) build the caller is about to redo anyway.
    if combined:
        widths = []
        for pl in plans:
            alldeg = np.concatenate(
                [np.bincount(pl.edge_dst[p, : int(pl.nnz[p])], minlength=pl.b)
                 for p in range(pl.k)])
            widths.append(_single_bucket_width(alldeg, tail_frac=0.02))
        return ((b, max(widths)),)
    return ((b, max(pl.ell_k for pl in plans)),)


def _cell_fields(ell: dict) -> dict:
    """Rename a ``_build_ell`` result into the combined-edge field names."""
    return dict(ctl=ell["tl"], cell_buckets=ell["ell_buckets"],
                cell_idx=ell["ell_idx"], cell_w=ell["ell_w"],
                ctail_dst=ell["ltail_dst"], ctail_src=ell["ltail_src"],
                ctail_w=ell["ltail_w"], ctail_nnz=ell["ltail_nnz"])


def _check_symmetric(a: sp.spmatrix) -> bool:
    a = sp.csr_matrix(a)
    a.eliminate_zeros()
    a.sort_indices()
    at = sp.csr_matrix(a.T)
    at.eliminate_zeros()
    at.sort_indices()
    # misclassifying an asymmetric matrix as symmetric would silently flip
    # gradients to Â·g, so the sparsity pattern must match EXACTLY; the
    # tolerance applies to stored values only (normalization round-off)
    if not (np.array_equal(a.indptr, at.indptr)
            and np.array_equal(a.indices, at.indices)):
        return False
    if a.nnz == 0:
        return True
    scale = max(float(np.abs(a.data).max()), 1e-30)
    return float(np.abs(a.data - at.data).max()) <= 1e-6 * scale


def relabel_plan(a: sp.spmatrix, partvec: np.ndarray, k: int,
                 pad_rows_to: int = 1) -> CommPlan:
    """Vertex relabeling + padding fields only — no halo/send construction.

    For algorithms with no boundary exchange (the broadcast-1D baseline ships
    everything every layer), building the full halo plan is dead work; this
    fills owner/local_idx/part_sizes/b/e/nnz/row_valid and leaves the comm
    fields trivial.
    """
    a = sp.coo_matrix(a)
    n = a.shape[0]
    owner, local_idx, part_sizes, b, row_valid = _relabel(
        n, partvec, k, pad_rows_to)
    nnz = np.bincount(owner[a.row], minlength=k)
    e = max(1, int(nnz.max()) if len(nnz) else 1)
    z = np.zeros
    return CommPlan(
        n=n, k=k, b=b, s=1, r=1, e=e,
        owner=owner, local_idx=local_idx,
        part_sizes=part_sizes.astype(np.int64),
        send_idx=z((k, k, 1), np.int32), send_counts=z((k, k), np.int32),
        halo_src=z((k, 1), np.int32), halo_counts=z(k, np.int32),
        edge_dst=z((k, e), np.int32), edge_src=z((k, e), np.int32),
        edge_w=z((k, e), np.float32), nnz=nnz.astype(np.int64),
        row_valid=row_valid,
        el=1, eh=1,
        ledge_dst=z((k, 1), np.int32), ledge_src=z((k, 1), np.int32),
        ledge_w=z((k, 1), np.float32),
        hedge_dst=z((k, 1), np.int32), hedge_src=z((k, 1), np.int32),
        hedge_w=z((k, 1), np.float32),
        lnnz=z(k, np.int64), hnnz=z(k, np.int64),
        ell_k=1, tl=1, ell_buckets=((b, 1),),
        ell_idx=z((k, b), np.int32), ell_w=z((k, b), np.float32),
        ltail_dst=z((k, 1), np.int32), ltail_src=z((k, 1), np.int32),
        ltail_w=z((k, 1), np.float32), ltail_nnz=z(k, np.int64),
        ctl=1, cell_buckets=((b, 1),),
        cell_idx=z((k, b), np.int32), cell_w=z((k, b), np.float32),
        ctail_dst=z((k, 1), np.int32), ctail_src=z((k, 1), np.int32),
        ctail_w=z((k, 1), np.float32), ctail_nnz=z(k, np.int64),
        symmetric=_check_symmetric(a), row_order="id",
    )


def pad_comm_plan(plan: CommPlan, b: int, s: int, r: int, e: int,
                  el: int | None = None, eh: int | None = None,
                  tl: int | None = None, ctl: int | None = None,
                  ell_buckets: tuple | None = None,
                  cell_buckets: tuple | None = None) -> CommPlan:
    """Re-pad a plan to a larger (B, S, R, E) envelope.

    Lets many plans (one per mini-batch) share ONE compiled train step: the
    reference pre-samples all batches and builds per-batch comm maps up front
    (``GPU/PGCN-Mini-batch.py:220-230``); under XLA the analogous move is
    padding every batch plan to the max envelope so shapes are static
    (SURVEY.md §7.3).  Padding preserves the plan invariants: pad edges carry
    weight 0 and dst ``b-1`` (keeps ``edge_dst`` non-decreasing), pad send /
    halo slots are never read by valid gathers, and every padding index is
    written anew for the larger tables by the rule of ``padding_rows``
    (distinct in-bounds rows; the old padding is told by the counts).  For the
    shared ELL layout pass ``ell_buckets`` covering every plan's degree
    profile (see ``ell_degree_profile`` / ``_choose_buckets``).
    """
    el = plan.el if el is None else el
    eh = plan.eh if eh is None else eh
    tl = plan.tl if tl is None else tl
    if ctl is None:
        ctl = plan.ctl
    if (b, s, r, e, el, eh, tl) == (
            plan.b, plan.s, plan.r, plan.e, plan.el, plan.eh, plan.tl) \
            and ctl == plan.ctl \
            and ell_buckets in (None, plan.ell_buckets) \
            and cell_buckets in (None, plan.cell_buckets):
        return plan
    if (b < plan.b or s < plan.s or r < plan.r or e < plan.e
            or el < plan.el or eh < plan.eh or tl < plan.tl
            or (ctl is not None and plan.ctl is not None and ctl < plan.ctl)):
        raise ValueError("pad_comm_plan cannot shrink an envelope")
    k = plan.k

    send_idx = np.zeros((k, k, s), dtype=np.int32)
    send_idx[:, :, : plan.s] = plan.send_idx
    halo_src = np.zeros((k, r), dtype=np.int32)
    # remap old flat recv slots q*S_old + t -> q*S_new + t
    q_old, t_old = plan.halo_src // plan.s, plan.halo_src % plan.s
    halo_src[:, : plan.r] = (q_old * s + t_old).astype(np.int32)
    _pad_exchange(send_idx, plan.send_counts, halo_src, plan.halo_counts, b)
    edge_dst = np.full((k, e), b - 1, dtype=np.int32)
    edge_dst[:, : plan.e] = plan.edge_dst
    # old pad edges pointed at plan.b-1; retarget them to b-1 to keep the
    # non-decreasing invariant (weight 0 either way)
    for p in range(k):
        edge_dst[p, plan.nnz[p]: plan.e] = b - 1
    edge_src = np.zeros((k, e), dtype=np.int32)
    # halo table shifts from plan.b to b: remap src indices >= plan.b
    old_src = plan.edge_src
    edge_src[:, : plan.e] = np.where(
        old_src >= plan.b, old_src - plan.b + b, old_src)
    for p in range(k):
        edge_src[p, plan.nnz[p]:] = padding_rows(e - int(plan.nnz[p]), b + r)
    edge_w = np.zeros((k, e), dtype=np.float32)
    edge_w[:, : plan.e] = plan.edge_w
    row_valid = np.zeros((k, b), dtype=np.float32)
    row_valid[:, : plan.b] = plan.row_valid

    chips = (np.asarray(plan.chip_ids) if plan.chip_ids is not None
             else np.arange(k))
    peers = plan.send_counts.shape[1]
    split = _split_edges(edge_dst, edge_src, edge_w, plan.nnz, b, r,
                         el=el, eh=eh,
                         halo_fold_key=(chips[:, None] - halo_src // s) % peers)
    ell = _build_ell(split["ledge_dst"], split["ledge_src"], split["ledge_w"],
                     split["lnnz"], b, row_order=plan.row_order,
                     buckets=ell_buckets, tl=tl)
    padded = CommPlan(
        n=plan.n, k=k, b=b, s=s, r=r, e=e,
        owner=plan.owner, local_idx=plan.local_idx, part_sizes=plan.part_sizes,
        send_idx=send_idx, send_counts=plan.send_counts.copy(),
        halo_src=halo_src, halo_counts=plan.halo_counts.copy(),
        edge_dst=edge_dst, edge_src=edge_src, edge_w=edge_w,
        nnz=plan.nnz.copy(), row_valid=row_valid,
        symmetric=plan.symmetric, row_order=plan.row_order,
        **split, **ell,
    )
    if cell_buckets is not None or plan.cell_buckets is not None:
        padded.ensure_cell(buckets=cell_buckets, ctl=ctl)
    return padded


def build_comm_plan(
    a: sp.spmatrix,
    partvec: np.ndarray,
    k: int,
    pad_rows_to: int = 1,
    pad_send_to: int = 1,
    row_order: str = "degree",
) -> CommPlan:
    """Compute the static plan from adjacency + part vector.

    ``pad_rows_to`` / ``pad_send_to`` round B and S up to a multiple (e.g. 8
    for TPU sublane alignment). The recv side of the reference's map predicate
    (nonzero with local row, remote col → receive that col's row;
    ``GPU/PGCN.py:37-51``) defines the halo; the send side is its transpose.

    ``row_order='degree'`` (default) relabels each part's rows descending by
    local in-degree so the bucketed ELL layout is tight; any consistent
    order is correct (all row data routes through owner/local_idx), so this
    is purely a layout choice.  ``row_order='id'`` ranks by global id —
    required by the ``.r``-file emitter whose text formats assume it.
    """
    from ..obs.tracing import set_counter, span

    if row_order not in ("degree", "id"):
        raise ValueError(f"unknown row_order {row_order!r}")
    with span("plan.relabel"):
        a = sp.coo_matrix(a)
        n = a.shape[0]
        key = None
        if row_order == "degree":
            ow = np.asarray(partvec, dtype=np.int64)
            local_edge = ow[a.row] == ow[a.col]
            key = np.bincount(a.row[local_edge], minlength=n)
        owner, local_idx, part_sizes, b, row_valid = _relabel(
            n, partvec, k, pad_rows_to, order_key=key)

        src_g, dst_g, w_g = a.col, a.row, a.data.astype(np.float32)
        eo = owner[dst_g]                   # chip owning each edge (by row)

    with span("plan.halo"):
        # per-chip halo vertex lists, sorted by (owner, id)
        halo_lists: list[np.ndarray] = []
        for p in range(k):
            em = eo == p
            cols = src_g[em]
            remote = cols[owner[cols] != p]
            uniq = np.unique(remote)
            uniq = uniq[np.lexsort((uniq, owner[uniq]))]
            halo_lists.append(uniq)
        halo_counts = np.array([len(h) for h in halo_lists], dtype=np.int32)
        r = max(1, int(halo_counts.max()) if k else 1)

        # send lists per ordered pair (p → q): vertices owned by p in q's halo
        send_lists: dict[tuple[int, int], np.ndarray] = {}
        s = 1
        for q in range(k):
            hq = halo_lists[q]
            ho = owner[hq]
            for p in range(k):
                if p == q:
                    continue
                vs = hq[ho == p]                       # already sorted by id
                if len(vs):
                    send_lists[(p, q)] = vs
                    s = max(s, len(vs))
        s = max(1, -(-s // pad_send_to) * pad_send_to)

        send_idx = np.zeros((k, k, s), dtype=np.int32)
        send_counts = np.zeros((k, k), dtype=np.int32)
        for (p, q), vs in send_lists.items():
            send_idx[p, q, : len(vs)] = local_idx[vs]
            send_counts[p, q] = len(vs)

        # halo gather: chip p's halo row t' (owner q, position t in p's
        # per-owner sublist == position in q→p send list) reads recv-flat
        # slot q*S + t
        halo_src = np.zeros((k, r), dtype=np.int32)
        for p in range(k):
            hp = halo_lists[p]
            if not len(hp):
                continue
            ho = owner[hp]
            pos = np.zeros(len(hp), dtype=np.int64)
            for q in np.unique(ho):
                m = ho == q
                pos[m] = q * s + np.arange(m.sum())
            halo_src[p, : len(hp)] = pos
        _pad_exchange(send_idx, send_counts, halo_src, halo_counts, b)

    with span("plan.edges"):
        # per-chip padded edge lists
        nnz = np.bincount(eo, minlength=k)
        e = max(1, int(nnz.max()) if len(nnz) else 1)
        # pad dst with the last row (b-1) so each chip's edge_dst stays
        # globally non-decreasing — segment_sum is told
        # indices_are_sorted=True
        edge_dst = np.full((k, e), b - 1, dtype=np.int32)
        edge_src = np.empty((k, e), dtype=np.int32)
        edge_w = np.zeros((k, e), dtype=np.float32)
        for p in range(k):
            em = eo == p
            rows = local_idx[dst_g[em]].astype(np.int32)
            cols = src_g[em]
            vals = w_g[em]
            co = owner[cols]
            csrc = np.empty(len(cols), dtype=np.int32)
            lm = co == p
            csrc[lm] = local_idx[cols[lm]].astype(np.int32)
            if (~lm).any():
                # halo position via searchsorted on the (owner, id)-sorted
                # halo list
                hp = halo_lists[p]
                keys = owner[hp] * (n + 1) + hp
                qkeys = co[~lm] * (n + 1) + cols[~lm]
                csrc[~lm] = b + np.searchsorted(keys, qkeys).astype(np.int32)
            srt = np.argsort(rows, kind="stable")      # sorted dst → fast segsum
            cnt = em.sum()
            edge_dst[p, :cnt] = rows[srt]
            edge_src[p, :cnt] = csrc[srt]
            edge_src[p, cnt:] = padding_rows(e - cnt, b + r)
            edge_w[p, :cnt] = vals[srt]

        split = _split_edges(edge_dst, edge_src, edge_w, nnz, b, r,
                             halo_fold_key=(np.arange(k)[:, None]
                                            - halo_src // s) % k)
    with span("plan.ell"):
        ell = _build_ell(split["ledge_dst"], split["ledge_src"],
                         split["ledge_w"], split["lnnz"], b,
                         row_order=row_order)
    with span("plan.symmetric"):
        symmetric = _check_symmetric(a)
    plan = CommPlan(
        n=n, k=k, b=b, s=s, r=r, e=e,
        owner=owner, local_idx=local_idx, part_sizes=part_sizes.astype(np.int64),
        send_idx=send_idx, send_counts=send_counts,
        halo_src=halo_src, halo_counts=halo_counts,
        edge_dst=edge_dst, edge_src=edge_src, edge_w=edge_w,
        nnz=nnz.astype(np.int64), row_valid=row_valid,
        symmetric=symmetric, row_order=row_order,
        **split, **ell,
    )
    # the padding happens here: leave what every chip executes beside what is
    # true where a reader in this process finds it (obs.tracing.counters)
    set_counter("plan.work_counts", plan.work_counts())
    return plan
