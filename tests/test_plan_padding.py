"""Where a plan's padding points (``parallel/plan.py::padding_rows``, PR 28).

Every index array the step gathers through is padded to one static shape, and
the device executes the padding entries like the real ones.  Written as 0,
millions of consecutive gathers read one row, which the v5e serves at little
more than half the rate of distinct rows (PERF.md §6, PR 28): the chip with
the most padding set the pace.  So:

  * per store and k: padding entries are in bounds for the table indexed,
    their weights / masks are 0, no row is named by more of them than the
    rule's bound ⌈padding ÷ table height⌉, and the real entries still hold Â;
  * nothing recognises padding by its value: with every padding index drawn
    anew at random the true counts, the virtual rows, a re-padded plan, a
    shard-proxy slice and a training step's loss are what they were;
  * the change is data, not program: the lowered exact step is byte for byte
    the parent commit's.

CPU, tiny graphs, one to four virtual devices.
"""

import copy
import hashlib
import os

import numpy as np
import pytest
import scipy.sparse as sp

from sgcn_tpu.io.datasets import dcsbm_graph, load_npz_dataset
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
from sgcn_tpu.parallel.plan import (pad_comm_plan, padding_fanin,
                                    padding_fanin_bound)
from sgcn_tpu.parallel.proxy import shard_proxy_plan
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.partition.emit import read_partvec
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, make_train_data

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
N = 640


@pytest.fixture(scope="module")
def ahat():
    """Communities, power-law degrees and a hub joined to every vertex, so
    every store has real entries AND padding: hub rows spill into the tail
    at k = 1 and 4, and a random partition cuts edges."""
    a = sp.lil_matrix(dcsbm_graph(N, ncomm=4, avg_deg=5, seed=0))
    a[3, :] = 1.0
    a[:, 3] = 1.0
    return normalize_adjacency(sp.csr_matrix(a))


def _partvec(k):
    return (np.zeros(N, np.int64) if k == 1
            else balanced_random_partition(N, k, seed=1))


@pytest.fixture(scope="module")
def plans(ahat):
    return {k: build_comm_plan(ahat, _partvec(k), k) for k in (1, 4)}


# ------------------------------------------------------------- the stores
def _slot_rows(plan):
    """Destination row of every flat ELL slot (width-major buckets)."""
    out, row = [], 0
    for nb, wb in plan.ell_buckets:
        out.append(np.tile(np.arange(row, row + nb), wb))
        row += nb
    return np.concatenate(out)


def _suffix(length, count):
    return np.arange(length) >= int(count)


def _coo(plan, p, dst, src, w, halo: bool):
    """Real edges of chip ``p`` as a global (n, n) matrix: local rows through
    ``global_row_ids``, halo ranks through ``halo_global_rows`` — which reads
    the real entries of ``send_idx`` and ``halo_src``."""
    rows = plan.global_row_ids()[p][dst]
    cols = (plan.halo_global_rows()[p] if halo
            else plan.global_row_ids()[p])[src]
    assert (rows >= 0).all() and (cols >= 0).all()
    return sp.coo_matrix((w, (rows, cols)), shape=(plan.n, plan.n)).tocsr()


def _block(ahat, plan, p, remote: bool):
    """Â's entries whose row chip ``p`` owns and whose column it owns
    (``remote=False``) or does not."""
    a = sp.coo_matrix(ahat)
    keep = (plan.owner[a.row] == p) & ((plan.owner[a.col] != p) == remote)
    return sp.coo_matrix((a.data[keep].astype(np.float32),
                          (a.row[keep], a.col[keep])), shape=a.shape).tocsr()


def _same(x, y):
    d = (x - y).tocoo()
    return d.nnz == 0 or float(np.abs(d.data).max()) == 0.0


def _local_edges(plan, p):
    """(dst, src, w) of the ELL's real slots and the tail's real edges."""
    real = plan.ell_w[p] != 0
    t = int(plan.ltail_nnz[p])
    return (np.concatenate([_slot_rows(plan)[real], plan.ltail_dst[p, :t]]),
            np.concatenate([plan.ell_idx[p][real], plan.ltail_src[p, :t]]),
            np.concatenate([plan.ell_w[p][real], plan.ltail_w[p, :t]]))


def _vrow_edges(lay, p):
    """(dst, src) pairs a virtual-row layout holds on chip ``p``."""
    (nv, wd), = lay["classes"]          # the attention layer's one width
    real = lay["w"][p] != 0
    return sorted(zip(np.tile(lay["row"][p], wd)[real].tolist(),
                      lay["idx"][p][real].tolist()))


# store -> (index array, padding mask, the weights / masks that must be 0 on
# padding or None, table height, check of the real entries), all of chip p
def _local_ok(plan, p, ahat):
    """ELL slots and tail together hold chip ``p``'s local block of Â."""
    return _same(_coo(plan, p, *_local_edges(plan, p), halo=False),
                 _block(ahat, plan, p, remote=False))


def _ell_idx(plan, p, ahat):
    return (plan.ell_idx[p], plan.ell_w[p] == 0, plan.ell_w[p], plan.b,
            _local_ok(plan, p, ahat))


def _ltail_src(plan, p, ahat):
    t = int(plan.ltail_nnz[p])
    return (plan.ltail_src[p], _suffix(plan.tl, t), plan.ltail_w[p], plan.b,
            (plan.ltail_w[p, :t] != 0).all() and _local_ok(plan, p, ahat))


def _ledge_src(plan, p, ahat):
    c = int(plan.lnnz[p])
    return (plan.ledge_src[p], _suffix(plan.el, c), plan.ledge_w[p], plan.b,
            _same(_coo(plan, p, plan.ledge_dst[p, :c], plan.ledge_src[p, :c],
                       plan.ledge_w[p, :c], halo=False),
                  _block(ahat, plan, p, remote=False)))


def _hedge_src(plan, p, ahat):
    c = int(plan.hnnz[p])
    return (plan.hedge_src[p], _suffix(plan.eh, c), plan.hedge_w[p], plan.r,
            _same(_coo(plan, p, plan.hedge_dst[p, :c], plan.hedge_src[p, :c],
                       plan.hedge_w[p, :c], halo=True),
                  _block(ahat, plan, p, remote=True)))


def _edge_src(plan, p, ahat):
    c = int(plan.nnz[p])
    src = plan.edge_src[p, :c]
    loc = src < plan.b
    both = (_coo(plan, p, plan.edge_dst[p, :c][loc], src[loc],
                 plan.edge_w[p, :c][loc], halo=False)
            + _coo(plan, p, plan.edge_dst[p, :c][~loc], src[~loc] - plan.b,
                   plan.edge_w[p, :c][~loc], halo=True))
    return (plan.edge_src[p], _suffix(plan.e, c), plan.edge_w[p],
            plan.b + plan.r,
            _same(both, _block(ahat, plan, p, remote=False)
                  + _block(ahat, plan, p, remote=True)))


def _halo_vertices(plan, p, ahat):
    """Global ids chip ``p`` must receive, in the plan's (owner, id) order."""
    a = sp.coo_matrix(ahat)
    cols = np.unique(a.col[(plan.owner[a.row] == p)
                           & (plan.owner[a.col] != p)])
    return cols[np.lexsort((cols, plan.owner[cols]))]


def _send_idx(plan, p, ahat):
    pad = (np.arange(plan.s)[None, :] >= plan.send_counts[p][:, None]).ravel()
    ok = True
    for q in range(plan.k):
        want = _halo_vertices(plan, q, ahat)
        want = want[plan.owner[want] == p] if q != p else want[:0]
        got = plan.global_row_ids()[p][
            plan.send_idx[p, q, : int(plan.send_counts[p, q])]]
        ok = ok and np.array_equal(got, want)
    return plan.send_idx[p].ravel(), pad, None, plan.b, ok


def _halo_src(plan, p, ahat):
    c = int(plan.halo_counts[p])
    return (plan.halo_src[p], _suffix(plan.r, c), None, plan.k * plan.s,
            np.array_equal(plan.halo_global_rows()[p, :c],
                           _halo_vertices(plan, p, ahat)))


def _vrow(store, counts, edges, height):
    def case(plan, p, ahat):
        lay = plan.virtual_rows()[store]
        if lay is None:                      # no chip has an edge in the store
            assert int(np.asarray(getattr(plan, counts)).sum()) == 0
            return np.zeros(0, np.int32), np.zeros(0, bool), None, 1, True
        dst, src, _ = edges
        c = int(getattr(plan, counts)[p])
        want = sorted(zip(getattr(plan, dst)[p, :c].tolist(),
                          getattr(plan, src)[p, :c].tolist()))
        return (lay["idx"][p], lay["w"][p] == 0, lay["w"][p],
                getattr(plan, height), _vrow_edges(lay, p) == want)
    return case


STORES = {
    "ell_idx": _ell_idx, "ltail_src": _ltail_src, "ledge_src": _ledge_src,
    "hedge_src": _hedge_src, "edge_src": _edge_src, "send_idx": _send_idx,
    "halo_src": _halo_src,
    "vrow_tail_idx": _vrow("tail", "ltail_nnz",
                           ("ltail_dst", "ltail_src", "ltail_w"), "b"),
    "vrow_halo_idx": _vrow("halo", "hnnz",
                           ("hedge_dst", "hedge_src", "hedge_w"), "r"),
}


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("store", list(STORES))
def test_padding_names_distinct_rows_in_bounds(plans, ahat, store, k):
    plan = plans[k]
    padded = 0
    for p in range(k):
        idx, pad, zero, height, real_ok = STORES[store](plan, p, ahat)
        assert real_ok, f"chip {p}: the real entries no longer hold Â"
        padding = idx[pad]
        padded += padding.size
        assert ((0 <= padding) & (padding < height)).all()
        assert zero is None or not np.asarray(zero)[pad].any()
        assert (padding_fanin(padding)
                <= padding_fanin_bound(padding.size, height))
    # not vacuous: on four chips every store is padded to its largest chip;
    # one chip pads only the slot layouts (its lists have their natural length)
    assert padded or (k == 1 and store not in ("ell_idx", "vrow_tail_idx"))


@pytest.mark.parametrize("k", [1, 4])
def test_work_counts_count_the_padding(plans, k):
    """``padding`` = executed − true per store and chip, and the fan-in the
    arrays show is the rule's: single digits where the parent's plans read
    the whole padding count (every entry named row 0)."""
    plan, work = plans[k], plans[k].work_counts()
    heights = {"slot_edges": plan.b, "tail_edges": plan.b,
               "halo_edges": plan.r, "halo_rows": plan.k * plan.s,
               "rows_sent": plan.b}
    assert set(work) == {"true", "executed", "padding", "padding_fanin",
                         "snapped"}
    for store, height in heights.items():
        for p in range(k):
            pad = work["executed"][store] - work["true"][store][p]
            assert work["padding"][store][p] == pad
            fanin = work["padding_fanin"][store][p]
            assert (pad > 0) <= (1 <= fanin <= padding_fanin_bound(pad,
                                                                   height))
    assert max(work["padding"]["slot_edges"]) > plan.b // 2   # not vacuous
    assert max(max(v) for v in work["padding_fanin"].values()) < 10


# ---------------------------------------- nothing reads padding by its value
def _rerandomised(plan, seed=0):
    """A copy of ``plan`` with every padding index drawn anew, in bounds."""
    rng = np.random.default_rng(seed)
    out = copy.deepcopy(plan)
    lists = (("ltail_src", "ltail_nnz", plan.b), ("ledge_src", "lnnz", plan.b),
             ("hedge_src", "hnnz", plan.r), ("halo_src", "halo_counts",
                                             plan.k * plan.s),
             ("edge_src", "nnz", plan.b + plan.r))
    for p in range(plan.k):
        pad = out.ell_w[p] == 0
        out.ell_idx[p][pad] = rng.integers(0, plan.b, int(pad.sum()))
        for name, counts, height in lists:
            arr, c = getattr(out, name), int(getattr(plan, counts)[p])
            arr[p, c:] = rng.integers(0, height, arr.shape[1] - c)
        for q in range(plan.k):
            c = int(plan.send_counts[p, q])
            out.send_idx[p, q, c:] = rng.integers(0, plan.b, plan.s - c)
    return out


def _real_equal(a, b):
    """Two plans agree on every real entry of the gathered index arrays."""
    for p in range(a.ell_idx.shape[0]):
        real = a.ell_w[p] != 0
        assert np.array_equal(a.ell_idx[p][real], b.ell_idx[p][real])
        for name, counts in (("ltail_src", "ltail_nnz"), ("hedge_src", "hnnz"),
                             ("halo_src", "halo_counts")):
            c = int(getattr(a, counts)[p])
            assert np.array_equal(getattr(a, name)[p, :c],
                                  getattr(b, name)[p, :c])
        for q in range(a.send_idx.shape[1]):
            c = int(a.send_counts[p, q])
            assert np.array_equal(a.send_idx[p, q, :c], b.send_idx[p, q, :c])


def test_nothing_recognises_padding_by_its_value():
    a, feats, labels = load_npz_dataset(os.path.join(FIX, "cora2708.npz"))
    ahat = normalize_adjacency(a)
    plan = build_comm_plan(ahat, read_partvec(
        os.path.join(FIX, "cora2708.4.hp")), 4)
    other = _rerandomised(plan)
    assert not np.array_equal(plan.ell_idx, other.ell_idx)
    assert not np.array_equal(plan.send_idx, other.send_idx)
    assert plan.work_counts()["true"] == other.work_counts()["true"]
    assert plan.work_counts()["padding"] == other.work_counts()["padding"]
    # derived layouts rewrite their own padding, so they come out identical
    for store, lay in plan.virtual_rows().items():
        theirs = other.virtual_rows()[store]
        assert (lay is None) == (theirs is None)
        for key in lay or ():
            assert np.array_equal(lay[key], theirs[key]), (store, key)
    env = dict(b=plan.b + 8, s=plan.s + 3, r=plan.r + 5, e=plan.e + 7,
               el=plan.el + 2, eh=plan.eh + 4, tl=plan.tl + 6)
    mine, theirs = pad_comm_plan(plan, **env), pad_comm_plan(other, **env)
    for name in ("send_idx", "halo_src", "edge_src", "ledge_src", "hedge_src",
                 "ell_idx", "ell_w", "ltail_src", "ltail_dst", "ltail_w",
                 "hedge_dst", "hedge_w"):
        assert np.array_equal(getattr(mine, name), getattr(theirs, name)), name
    fan = mine.work_counts()["padding_fanin"]
    assert max(max(v) for v in fan.values()) < 10
    _real_equal(shard_proxy_plan(plan, 2), shard_proxy_plan(other, 2))
    # one exact full-batch step (aggregate first, layer 0 hoisted: the path
    # the benchmark's GCN cells take), losses equal to the bit
    x = feats[:, :32].astype(np.float32)
    y = labels.astype(np.int32)
    losses = []
    for pl in (plan, other):
        tr = FullBatchTrainer(pl, fin=x.shape[1], widths=[16, 7], seed=5,
                              mesh=make_mesh_1d(4))
        assert tr.hoist is not None
        data = make_train_data(pl, x, y)
        losses.append([tr.step(data) for _ in range(2)])
    assert losses[0] == losses[1] and np.isfinite(losses[0]).all()


# ------------------------------------------------- the change is data only
# sha256 of ``lower_step().as_text()``, made by this file's ``step_sha``.
# The two ``gcn`` pins are PR 30's, which changed that step on purpose (its
# hub tail and halo-source edges fold as slot passes); the two ``mhgat`` pins
# stood from the commit 8eecc9a (PR 27) until PR 32 re-made them, which
# changed THAT step on purpose (a forward slot spreads its head coefficients
# once, signed, as one bfloat16 pass over exact splits) and left the GCN's as
# it was.  A later PR that changes a step program on purpose re-pins it.
PARENT_STEP_SHA = {
    ("gcn", 1):
        "cf9c1918e2b939eaa2e59396c0bca0e08c3c180620b3caca452d5560a8ec66be",
    ("gcn", 4):
        "66465023282b49fa5af441c46774d869f47a037a82163474eb6df7e5157be6eb",
    ("mhgat", 1):
        "c34b240991b16c9697cc662850e9e172df9b9a9a5840008ee5e3a0e5b672053b",
    ("mhgat", 4):
        "79975ecbcb8bd4970cb90a9ace575f21f026e234f45ea96fb71742b24778749b",
    # pinned before the trainer learnt to hand a forward label ids: a model
    # without a label input lowers the same program
    ("deepergcn", 1):
        "b15e22f54ecd43fe27e309c3647035b915199532b6eb6894589b7415b602c5f3",
    ("deepergcn", 4):
        "a2e5142cb3405435eebbcde148820fbe1b030770fefc0584ef8618136583f14b",
}
MODEL_KW = {"gcn": {},
            "mhgat": {"model_args": {"heads": (4, 2), "concat": (True, False)},
                      "activation": "elu"},
            "deepergcn": {"model_args": {"layers": 1, "hidden": 8}}}


def step_sha(plan, model):
    tr = FullBatchTrainer(plan, fin=6, widths=[8, 5], seed=3, model=model,
                          mesh=make_mesh_1d(plan.k), **MODEL_KW[model])
    return hashlib.sha256(tr.lower_step().as_text().encode()).hexdigest()


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("model", ["gcn", "mhgat", "deepergcn"])
def test_lowered_exact_step_is_the_parents(plans, model, k):
    assert step_sha(plans[k], model) == PARENT_STEP_SHA[model, k], (
        "the lowered exact step differs from the one pinned (mhgat: PR 32; "
        "gcn: PR 30): a PR that does not mean to change the program must "
        "not; one that changes it on purpose re-pins PARENT_STEP_SHA")
