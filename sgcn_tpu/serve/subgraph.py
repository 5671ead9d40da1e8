"""L-hop induced sub-graph serving: receptive sets, fold recipes, compact
forwards (phase 2 of ``docs/serving.md``).

PR-8's engine recomputes the FULL partitioned forward for every
micro-batch — correct, but graph-proportional: the per-query FLOP bill is
``k·B·L`` computed rows regardless of how few vertices the batch names.  A
routed batch of query vertices has an exactly-L-hop receptive field, so
this module makes serving QUERY-proportional:

  * :class:`SubgraphIndex` (built once per plan) re-expresses every chip's
    per-row fold recipe in GLOBAL row space: for each vertex, the ordered
    (source, weight) slot sequence of its owner chip's ELL row (ALL
    ``wb`` slots of its degree bucket, weight-0 padding included), its
    local-tail and halo-edge lists (GCN), or its combined cell slots and
    hub-tail edges (GAT).  Orders are taken verbatim from the plan arrays
    — the halo family is (dst, round, recv-pos)-sorted at plan build time,
    which is what makes one recipe valid for BOTH the a2a and ragged
    schedules (the two transports already fold every row in that same
    sequence, the PR-4 bit-parity contract).
  * :meth:`SubgraphIndex.receptive` computes, per chip, the L-hop closed
    neighborhood of that chip's routed queries (``VertexRouter.route`` —
    this is where the router's co-location grouping becomes load-bearing:
    queries sharing a chip share receptive rows, so routed batches spill
    less).
  * :func:`build_batch` compacts the recipes onto the receptive set:
    per-chip padded tables in a compact row space ordered BY DEGREE-BUCKET
    CLASS (each row keeps its original bucket width), padded to
    doubling-ladder buckets (:func:`pad_pow2`) so neither query count nor
    receptive-set size ever recompiles the program.  The last class always
    carries at least one padding row; the FINAL compact row is the all-zero
    dump row every padding slot/edge points at.
  * :func:`subgraph_forward_gcn` / :func:`subgraph_forward_gat` run the
    compact forward per chip with NO inter-chip exchange: every source row
    a chip needs is computed locally from host-gathered input features, and
    the only collective in the program is the final logit-gather ``psum``
    (the audited contract of the ``serve_subgraph`` analysis mode).

**Parity contract.**  Routed logits match the trainer's ``evaluate()`` to
the ulp (within 2 ulp of a row's largest logit;
``tests/test_serve_subgraph.py::PARITY_ULPS``, 1 measured) because every
per-row reduction reproduces the full program's per-row addition sequence
AND op structure exactly — what is left is XLA:CPU's choice of FMA
contraction, which it makes per compiled shape (under the JAX these
mechanisms were tuned against the result was ``==``; under 0.9.0 a GCN logit
differs by an ulp at some batch shapes):

  * the compact aggregations call the REAL kernels (``ops.pspmm.spmm_ell``
    / ``spmm_local``, ``models.gat._edge_pass`` slot passes) on compact
    bucket structures whose per-row chain lengths equal the full
    program's.  Chain-length fidelity is not pedantry: XLA:CPU contracts
    multiply-add chains into FMAs opportunistically per compiled shape, so
    a row folded through a LONGER (or zero-seeded) chain can round
    differently by an ulp even though the math is identical — measured on
    the 48-vertex fixture, and the reason each row keeps all ``wb`` slots
    of its original degree bucket (a weight-0 slot is exact under any
    contraction: ``fma(0, x, acc) = acc`` for finite ``x``);
  * dense projections are ordinary ``(M, K) @ (K, N)`` matmuls, whose
    per-row bits are position- and M-independent on this backend for
    ``N ≥ 2`` (measured; the one exception — the attention score matvec —
    was moved to the row-local ``models.gat.score_project`` form for
    exactly this reason);
  * the GAT per-layer softmax stabilizer ``cg`` is supplied as an INPUT —
    it is a full-graph ``pmax`` the compact program cannot derive, but it
    is constant per (params, features), so the engine precomputes it once
    per weight swap (``gat_forward_local(collect_stabilizers=True)``);
  * remote-sourced GCN contributions take the ``halo_dtype`` wire
    round-trip cast when the engine narrows the wire.

Differences confined to padding arithmetic can flip only the SIGN of a
zero, which ``==`` treats as equal; rows on the receptive set's outer
shell are computed with incomplete neighborhoods and may hold garbage, but
no complete row (and no query) ever reads them — consumers gather strictly
inside the previous level's closed neighborhood.  Two full-program regimes
are out of the compact mirror's scope and documented rather than silently
wrong: the Pallas VMEM aggregator (the engine refuses subgraph mode under
it) and the products-scale GAT paths (``_ONED_U_ROWS`` denominator form,
chunked hub tails) whose branch points depend on full-table sizes.

Everything host-side here is numpy; the forward functions are per-chip jax
code the engine wraps in ``shard_map``.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

# the ONE doubling-ladder rule, shared with the query-count buckets —
# each compact-array dimension takes at most log2 distinct values, so a
# repeated (or smaller) workload never recompiles
from .batcher import pad_pow2

# CommPlan fields the sub-graph index reads (host-side, full square plan) —
# registered in analysis/registry.py like every consumer tuple.  The
# per-chip fold arrays are read on the HOST to build global recipes; the
# GAT cell family is materialized by ensure_cell() first.
SERVE_SUBGRAPH_FIELDS = (
    "owner", "local_idx", "send_idx", "halo_src",
    "ell_idx", "ell_w", "ltail_dst", "ltail_src", "ltail_w",
    "hedge_dst", "hedge_src", "hedge_w",
    "cell_idx", "cell_w", "ctail_dst", "ctail_src", "ctail_w",
)




def _row_class_table(buckets) -> tuple:
    """Per-LOCAL-row (class, width) of one bucketed width-major layout."""
    cls = []
    wid = []
    for j, (nb, wb) in enumerate(buckets):
        cls += [j] * nb
        wid += [wb] * nb
    return np.asarray(cls, np.int8), np.asarray(wid, np.int32)


def _row_slot_lists(flat_idx, flat_w, buckets, full: bool):
    """Per-row (srcs, ws) of one chip's bucketed width-major layout, in
    slot order.  ``full=True`` keeps every slot of the row's bucket width
    (weight-0 padding included — the chain-length contract of the module
    docstring); ``full=False`` keeps only real (weight ≠ 0) slots (the
    adjacency/gauge view).  Returns ``(counts (B,), srcs, ws)`` with the
    kept entries concatenated row-major."""
    counts, srcs, ws = [], [], []
    off = 0
    for nb, wb in buckets:
        blk_i = flat_idx[off: off + nb * wb].reshape(wb, nb).T  # (nb, wb)
        blk_w = flat_w[off: off + nb * wb].reshape(wb, nb).T
        keep = (np.ones_like(blk_w, bool) if full else blk_w != 0)
        counts.append(keep.sum(axis=1))
        srcs.append(blk_i[keep])        # row-major flatten = slot order
        ws.append(blk_w[keep])
        off += nb * wb
    return (np.concatenate(counts), np.concatenate(srcs),
            np.concatenate(ws))


def _csr_from_rows(n: int, row_glob, src_glob, w):
    """Assemble a global CSR from (row, src, w) triples whose per-row
    relative order must be preserved (stable sort by row)."""
    order = np.argsort(row_glob, kind="stable")
    row_s = row_glob[order]
    counts = np.bincount(row_s, minlength=n)
    ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=ptr[1:])
    return ptr, src_glob[order].astype(np.int64), w[order].astype(np.float32)


class SubgraphIndex:
    """Host-side per-row fold recipes in GLOBAL row space (one per plan)."""

    def __init__(self, plan, model: str = "gcn"):
        if model not in ("gcn", "gat"):
            raise ValueError(f"unknown model {model!r}")
        if model == "gcn" and not plan.symmetric:
            raise ValueError(
                "sub-graph serving reproduces the symmetric ELL fold "
                "(spmm_ell + halo-edge family); this plan is asymmetric — "
                "serve with the full-forward engine")
        self.model = model
        self.n = int(plan.n)
        self.k = int(plan.k)
        glob = plan.global_row_ids()            # (k, B), -1 pad
        k, b = self.k, plan.b

        if model == "gcn":
            self.buckets = tuple(plan.ell_buckets)
            slot_arrays = (plan.ell_idx, plan.ell_w)
            tail_fams = (("ltail_dst", "ltail_src", "ltail_w"),
                         ("hedge_dst", "hedge_src", "hedge_w"))
            src_is_combined = False
        else:
            plan.ensure_cell()
            self.buckets = tuple(plan.cell_buckets)
            slot_arrays = (plan.cell_idx, plan.cell_w)
            tail_fams = (("ctail_dst", "ctail_src", "ctail_w"),)
            src_is_combined = True
        halo_glob = plan.halo_global_rows()     # (k, R), -1 pad
        full_glob = (np.concatenate([glob, halo_glob], axis=1)
                     if src_is_combined else None)
        row_cls, _ = _row_class_table(self.buckets)

        sr, ss, sw = [], [], []                 # FULL slot chains
        ar, asrc = [], []                       # real-edge adjacency
        fams = [([], [], []) for _ in tail_fams]
        cls_rows, cls_vals = [], []
        for c in range(k):
            g = glob[c]
            real = g >= 0
            cnt, srcs, ws = _row_slot_lists(
                np.asarray(slot_arrays[0][c]), np.asarray(slot_arrays[1][c]),
                self.buckets, full=True)
            rows = np.repeat(np.arange(b), cnt)
            keep = real[rows]
            src_map = full_glob[c] if src_is_combined else g
            sr.append(g[rows[keep]])
            ss.append(src_map[srcs[keep]])
            sw.append(ws[keep])
            cls_rows.append(g[real])
            cls_vals.append(row_cls[real])
            # real-edge view (adjacency + gauges): weight-0 slots dropped
            rk = keep & (ws != 0)
            ar.append(g[rows[rk]])
            asrc.append(src_map[srcs[rk]])
            for fam, (fr, fs, fw) in zip(tail_fams, fams):
                d = np.asarray(getattr(plan, fam[0])[c])
                s = np.asarray(getattr(plan, fam[1])[c])
                w = np.asarray(getattr(plan, fam[2])[c])
                fmap = (src_map if src_is_combined else
                        (g if fam[0] == "ltail_dst" else halo_glob[c]))
                fkeep = (w != 0) & real[d]
                fr.append(g[d[fkeep]])
                fs.append(fmap[s[fkeep]])
                fw.append(w[fkeep])
        self.slots = _csr_from_rows(self.n, np.concatenate(sr),
                                    np.concatenate(ss), np.concatenate(sw))
        self.tails = [
            _csr_from_rows(self.n, np.concatenate(fr), np.concatenate(fs),
                           np.concatenate(fw))
            for fr, fs, fw in fams]
        # per-global-row degree-bucket class (the chain-length contract)
        self.row_class = np.zeros(self.n, np.int8)
        self.row_class[np.concatenate(cls_rows)] = np.concatenate(cls_vals)
        adj_rows = [np.concatenate(ar)]
        adj_srcs = [np.concatenate(asrc)]
        for fr, fs, _fw in fams:
            adj_rows.append(np.concatenate(fr))
            adj_srcs.append(np.concatenate(fs))
        adj_rows = np.concatenate(adj_rows)
        adj_srcs = np.concatenate(adj_srcs)
        self.adj = _csr_from_rows(
            self.n, adj_rows, adj_srcs,
            np.zeros(len(adj_srcs), np.float32))[:2]

    # ------------------------------------------------------------ receptive
    def receptive(self, qids, nhops: int) -> np.ndarray:
        """Sorted global ids of the ``nhops``-hop CLOSED neighborhood of
        ``qids`` (the rows a ``nhops``-layer forward for these queries
        touches)."""
        ptr, src = self.adj
        u = np.unique(np.asarray(qids, dtype=np.int64))
        for _ in range(nhops):
            cnt = ptr[u + 1] - ptr[u]
            tot = int(cnt.sum())
            if tot == 0:
                break
            flat = (np.repeat(ptr[u], cnt)
                    + np.arange(tot) - np.repeat(np.cumsum(cnt) - cnt, cnt))
            u = np.unique(np.concatenate([u, src[flat]]))
        return u

    def edges_in(self, rows: np.ndarray) -> int:
        """True recipe edges folded when computing ``rows`` (the analytic
        per-batch SpMM-work gauge — real edges only, padding slots
        excluded)."""
        ptr, src = self.adj
        return int((ptr[rows + 1] - ptr[rows]).sum())


def _take_rows(csr, rows):
    """``(counts, srcs, ws)`` of ``rows`` from a global CSR, per-row order
    preserved, concatenated row-major."""
    ptr, src, w = csr
    cnt = ptr[rows + 1] - ptr[rows]
    tot = int(cnt.sum())
    flat = (np.repeat(ptr[rows], cnt)
            + np.arange(tot) - np.repeat(np.cumsum(cnt) - cnt, cnt))
    return cnt, src[flat], w[flat]


@dataclass
class SubgraphBatch:
    """One routed batch's compact device inputs + analytic gauges."""

    key: tuple                   # static shape key → compiled program
    arrays: dict = field(default_factory=dict)   # name → (k, ...) stacked
    q_owner: np.ndarray = None   # (Qb,) i32, −1 pad
    q_pos: np.ndarray = None     # (Qb,) i32 position in owner's compact set
    nq: int = 0
    touched_rows: int = 0        # Σ_c |U_c| (true, unpadded)
    recipe_edges: int = 0        # Σ_c true edges folded
    per_chip_rows: tuple = ()


def _compact_layout(index: SubgraphIndex, sets, class_pads):
    """Per-chip compact ordering: rows grouped by degree-bucket class (the
    plan's bucket order), padded to the shared ``class_pads`` counts.
    Returns per chip ``(compact_rows, pos_map)`` where ``pos_map`` maps a
    global id to its compact index (dump row for ids outside the set)."""
    total = int(sum(class_pads))
    dump = total - 1
    out = []
    for u in sets:
        cls = index.row_class[u] if len(u) else np.zeros(0, np.int8)
        pos_map = np.full(index.n, dump, np.int32)
        compact = np.full(total, -1, np.int64)
        off = 0
        for j, pad in enumerate(class_pads):
            rows_j = u[cls == j]
            compact[off: off + len(rows_j)] = rows_j
            pos_map[rows_j] = off + np.arange(len(rows_j), dtype=np.int32)
            off += pad
        out.append((compact, pos_map))
    return out, dump


def _class_counts(index: SubgraphIndex, u) -> np.ndarray:
    m = len(index.buckets)
    if not len(u):
        return np.zeros(m, np.int64)
    return np.bincount(index.row_class[u], minlength=m)


def _pack_slots(index, u, compact, pos_map, class_pads):
    """Flat WIDTH-MAJOR compact slot arrays mirroring the plan's bucketed
    layout at compact class counts: class ``j`` stores slot ``t`` of its
    ``class_pads[j]`` rows contiguously — exactly the shape
    ``ops.pspmm.bucketed_slot_reduce`` (via ``spmm_ell`` / the GAT slot
    passes) consumes, so the compiled fold has the full program's per-row
    chain structure."""
    widths = [wb for _, wb in index.buckets]
    total_slots = int(sum(p * w for p, w in zip(class_pads, widths)))
    dump = int(sum(class_pads)) - 1
    flat_i = np.full(total_slots, dump, np.int32)
    flat_w = np.zeros(total_slots, np.float32)
    off = row0 = 0
    for j, (pad, wb) in enumerate(zip(class_pads, widths)):
        rows_j = compact[row0: row0 + pad]
        real = rows_j >= 0
        rj = rows_j[real]
        if len(rj):
            cnt, srcs, ws = _take_rows(index.slots, rj)
            if not (cnt == wb).all():
                raise ValueError(
                    f"class-{j} recipe rows carry {set(cnt.tolist())} slots, "
                    f"bucket width is {wb} — the index and the plan's "
                    "bucket structure drifted")
            blk_i = pos_map[srcs].reshape(len(rj), wb)
            blk_w = ws.reshape(len(rj), wb)
            ri = np.nonzero(real)[0]
            for t in range(wb):
                flat_i[off + t * pad + ri] = blk_i[:, t]
                flat_w[off + t * pad + ri] = blk_w[:, t]
        off += pad * wb
        row0 += pad
    return flat_i, flat_w


def _pack_edges(csr, u, compact, pos_map, pad_to: int, dump: int):
    """Compact dst-sorted edge list ``(dst, src, w)`` padded to ``pad_to``
    (padding edges: dst = src = dump row, weight 0 — the dump row is the
    LAST compact row, so ``indices_are_sorted`` stays true)."""
    dst = np.full(pad_to, dump, np.int32)
    src = np.full(pad_to, dump, np.int32)
    w = np.zeros(pad_to, np.float32)
    real = compact >= 0
    rows = compact[real]
    if len(rows):
        cnt, srcs, ws = _take_rows(csr, rows)
        tot = int(cnt.sum())
        if tot > pad_to:
            raise ValueError(f"edge list {tot} exceeds pad {pad_to}")
        dst[:tot] = np.repeat(np.nonzero(real)[0], cnt).astype(np.int32)
        src[:tot] = pos_map[srcs]
        w[:tot] = ws
    return dst, src, w


def build_batch(index: SubgraphIndex, router, features: np.ndarray,
                qids, nhops: int, edge_lo: int = 16,
                rows_lo: int = 2) -> SubgraphBatch:
    """Route ``qids``, compute per-chip receptive sets, compact the
    recipes, pad to ladder buckets; see module docstring."""
    qids = np.asarray(qids, dtype=np.int64).reshape(-1)
    owners, _ = router.lookup(qids)
    by_chip = router.route(qids)
    sets = [index.receptive(by_chip[c], nhops) if c in by_chip
            else np.zeros(0, np.int64) for c in range(index.k)]
    counts = np.stack([_class_counts(index, u) for u in sets]).max(axis=0)
    m = len(index.buckets)
    class_pads = tuple(
        pad_pow2(int(counts[j]) + (1 if j == m - 1 else 0), rows_lo)
        for j in range(m))
    layout, dump = _compact_layout(index, sets, class_pads)
    total = int(sum(class_pads))
    feats = np.zeros((index.k, total, features.shape[1]), np.float32)
    valid = np.zeros((index.k, total), np.float32)
    for c, (compact, _) in enumerate(layout):
        real = compact >= 0
        feats[c, real] = features[compact[real]]
        valid[c, real] = 1.0
    arrays = {"feats": feats, "valid": valid}
    slot = [_pack_slots(index, u, compact, pos_map, class_pads)
            for u, (compact, pos_map) in zip(sets, layout)]
    tname = "slots" if index.model == "gcn" else "cell"
    arrays[f"{tname}_idx"] = np.stack([s[0] for s in slot])
    arrays[f"{tname}_w"] = np.stack([s[1] for s in slot])
    fam_names = (("tail", "rem") if index.model == "gcn" else ("ctail",))
    epads = []
    for csr, name in zip(index.tails, fam_names):
        ep = pad_pow2(max(
            (int(_take_rows(csr, compact[compact >= 0])[0].sum())
             if (compact >= 0).any() else 0)
            for compact, _ in layout), edge_lo)
        epads.append(ep)
        packed = [_pack_edges(csr, u, compact, pos_map, ep, dump)
                  for u, (compact, pos_map) in zip(sets, layout)]
        arrays[f"{name}_dst"] = np.stack([p[0] for p in packed])
        arrays[f"{name}_src"] = np.stack([p[1] for p in packed])
        arrays[f"{name}_w"] = np.stack([p[2] for p in packed])
    qb = pad_pow2(len(qids), 1)
    key = (index.model, qb) + class_pads + tuple(epads)
    q_owner = np.full(qb, -1, np.int32)
    q_pos = np.zeros(qb, np.int32)
    q_owner[:len(qids)] = owners
    for i, (g, c) in enumerate(zip(qids, owners)):
        q_pos[i] = int(layout[c][1][g])
    return SubgraphBatch(
        key=key, arrays=arrays, q_owner=q_owner, q_pos=q_pos, nq=len(qids),
        touched_rows=int(sum(len(u) for u in sets)),
        recipe_edges=int(sum(index.edges_in(u) for u in sets if len(u))),
        per_chip_rows=tuple(len(u) for u in sets))


def representative_key(index: SubgraphIndex, qb: int = 8,
                       rows_lo: int = 2, edge_lo: int = 16) -> tuple:
    """A smallest-ladder shape key for ``index`` — what the static-analysis
    audit lowers (``ServeEngine.lower_subgraph``): the module is identical
    for every key up to array extents, and the audited contract
    (collective census / donation / host callbacks) is extent-independent."""
    m = len(index.buckets)
    class_pads = tuple(pad_pow2(2 if j == m - 1 else 1, rows_lo)
                       for j in range(m))
    n_fams = 2 if index.model == "gcn" else 1
    return (index.model, qb) + class_pads + (edge_lo,) * n_fams


def key_buckets(index: SubgraphIndex, key: tuple) -> tuple:
    """The compact ``((nb, wb), ...)`` bucket structure one shape key
    compiles — class pads from the key × the plan's bucket widths (the
    static argument of the compact slot passes)."""
    m = len(index.buckets)
    class_pads = key[2: 2 + m]
    return tuple((int(p), int(wb))
                 for p, (_, wb) in zip(class_pads, index.buckets))


def batch_struct(index: SubgraphIndex, key: tuple, fin: int) -> dict:
    """ShapeDtypeStruct-shaped numpy zeros for one shape key — what
    ``ServeEngine.lower_subgraph`` feeds ``.lower()`` so the audited module
    is exactly the program a real batch of this key runs."""
    k = index.k
    m = len(index.buckets)
    class_pads = key[2: 2 + m]
    epads = key[2 + m:]
    total = int(sum(class_pads))
    slots = int(sum(p * wb for p, (_, wb) in zip(class_pads,
                                                 index.buckets)))
    tname = "slots" if index.model == "gcn" else "cell"
    out = {"feats": np.zeros((k, total, fin), np.float32),
           "valid": np.zeros((k, total), np.float32),
           f"{tname}_idx": np.zeros((k, slots), np.int32),
           f"{tname}_w": np.zeros((k, slots), np.float32)}
    fam_names = (("tail", "rem") if index.model == "gcn" else ("ctail",))
    for name, ep in zip(fam_names, epads):
        out[f"{name}_dst"] = np.zeros((k, int(ep)), np.int32)
        out[f"{name}_src"] = np.zeros((k, int(ep)), np.int32)
        out[f"{name}_w"] = np.zeros((k, int(ep)), np.float32)
    return out


# ---------------------------------------------------------------- forwards
def subgraph_forward_gcn(params, feats, arrays, buckets,
                         activation: str, final_activation: str,
                         halo_dtype=None):
    """Per-chip compact GCN forward over the receptive set (no exchange).

    Mirrors ``gcn_forward_local``'s layer loop (project-first rule,
    activations) by calling the REAL kernels on the compact tables:
    ``spmm_ell`` for the bucketed slot chain + local tail,
    ``spmm_local`` for the halo-edge family (remote sources taking the
    ``halo_dtype`` wire round-trip), combined exactly as
    ``_pspmm_ell_once`` combines them: ``z = local + remote``."""
    from ..models.activations import get_activation
    from ..models.gcn import PROJECT_FIRST_MIN_FIN
    from ..ops.pspmm import spmm_ell, spmm_local

    act = get_activation(activation)
    fact = get_activation(final_activation)
    nl = len(params)
    h = feats                                   # (T, fin)
    for i, w in enumerate(params):
        project_first = (w.shape[1] < h.shape[1]
                         and h.shape[1] >= PROJECT_FIRST_MIN_FIN)
        x = (h @ w) if project_first else h
        local = spmm_ell(arrays["slots_idx"], arrays["slots_w"],
                         arrays["tail_dst"], arrays["tail_src"],
                         arrays["tail_w"], x, buckets)
        xr = (x.astype(halo_dtype).astype(x.dtype)
              if halo_dtype is not None else x)
        remote = spmm_local(arrays["rem_dst"], arrays["rem_src"],
                            arrays["rem_w"], xr, x.shape[0])
        z = local + remote
        if not project_first:
            z = z @ w
        h = fact(z) if i == nl - 1 else act(z)
    return h


def subgraph_forward_gat(params, cgs, feats, arrays, buckets,
                         activation: str, final_activation: str):
    """Per-chip compact GAT forward over the receptive set (no exchange,
    no pmax — the per-layer stabilizers arrive as the ``cgs`` input).

    Mirrors ``_gat_factored_fwd_core`` at f32 by calling the REAL slot
    passes (``_mask_slot_pass`` / ``_pair_slot_pass`` via
    ``gat_table_form(fout, None)`` — the serve engine has no compute_dtype
    lever) on the compact cell tables.  ``valid`` pins the pad/dump rows'
    score at the stabilizer (``u = 1``): ``exp(−cg)`` can overflow for a
    very negative global max, and a NaN pad-table row would poison every
    masked gather that points at it."""
    import jax.numpy as jnp

    from ..models.activations import get_activation
    from ..models.gat import (_mask_slot_pass, _pair_slot_pass,
                              gat_table_form, score_project)

    act = get_activation(activation)
    fact = get_activation(final_activation)
    nl = len(params)
    h = feats
    rows = h.shape[0]
    valid = arrays["valid"]
    for i, p in enumerate(params):
        z = h @ p["w"]
        fout = z.shape[-1]
        z2 = score_project(z, p["a2"])
        z2 = jnp.where(valid > 0, z2, cgs[i])   # pad rows: u = exp(0) = 1
        u = jnp.exp(z2.astype(jnp.float32) - cgs[i])
        form = gat_table_form(fout, None)
        pfeat = u.astype(z.dtype)[:, None] * z
        if form == "fused":
            table = jnp.concatenate(
                [pfeat, u.astype(z.dtype)[:, None]], axis=-1)
            num, den = _mask_slot_pass(
                table, fout, arrays["cell_idx"], arrays["cell_w"],
                arrays["ctail_dst"], arrays["ctail_src"],
                arrays["ctail_w"], buckets, rows)
        else:
            num, den = _pair_slot_pass(
                pfeat, u.astype(z.dtype), fout, arrays["cell_idx"],
                arrays["cell_w"], arrays["ctail_dst"],
                arrays["ctail_src"], arrays["ctail_w"], buckets, rows)
        out = num / jnp.maximum(den, 1e-30)[:, None]
        h = fact(out) if i == nl - 1 else act(out)
        if i < nl - 1:
            h = h.astype(p["w"].dtype)          # f32 no-op (engine is f32)
    return h
