"""The row counts a plan CHOOSES avoid the residues modulo 1,024 that the
v5e's slot gather runs dear (``parallel/plan.py::snap_rows``, PR 36).

One parametrised family, ``test_row_snap[check-k]``, over plans large enough
to hold buckets and fold classes of 1,024 rows and more (seeded DC-SBM
graphs at k = 1 and on 4 virtual devices, a typed graph for ``rgcn``), each
built twice: as the program builds it, and with the helper patched to the
identity (the parent's shapes):

  * ``window``: every chosen ELL bucket, combined-edge bucket and fold class
    of ≥ 1,024 rows lies in ``ROW_WINDOW``;
  * ``cover``: the buckets cover exactly ``b`` rows, widths do not increase,
    every row keeps its edges in the same slot order and no edge moves to
    the tail (``ltail_nnz`` equal to the unsnapped plan's);
  * ``slots``: a shape gains at most 1,023 rows, i.e. 1,023 · w slots;
  * ``untouched``: plans whose shapes are all under 1,024 rows, and shapes a
    caller forces (``buckets=``, the mini-batch envelope), keep their bytes;
  * ``counter``: ``work_counts()["snapped"]`` equals the difference of the
    two builds; ``typed``: so does the typed layouts' ``counts[pair]
    ["snapped"]`` (the ``rel.work`` counter), pair by pair;
  * ``gcn`` / ``mhgat`` / ``deepergcn`` / ``rgcn``: logits and every
    gradient leaf of one SGD step on the snapped plan equal, to 1e-6, those
    on the unsnapped plan.

CPU, one and four virtual devices.
"""

import jax
import numpy as np
import optax
import pytest
import scipy.sparse as sp

from sgcn_tpu.io.datasets import dcsbm_graph
from sgcn_tpu.models import rgcn
from sgcn_tpu.obs import tracing
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
from sgcn_tpu.parallel import plan as plan_mod
from sgcn_tpu.parallel.plan import (ROW_PERIOD, ROW_WINDOW, UNSNAPPED,
                                    _build_ell, fold_class_shapes,
                                    pad_comm_plan, rows_cheap,
                                    shared_ell_buckets, snap_rows)
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

# graphs whose chosen shapes land on dear residues (asserted: not vacuous)
GRAPHS = {1: dict(n=9000, ncomm=8, avg_deg=10, seed=4),
          4: dict(n=18000, ncomm=8, avg_deg=10, seed=4)}
SMALL = dict(n=1200, ncomm=4, avg_deg=30, seed=0)     # every shape < 1,024
FIN, NCLS = 6, 4
RATE = 0.1          # one SGD step of this rate moves a parameter by -RATE·g
ATOL = 1e-6

COUNTS = {"paper": 8000, "author": 9000, "inst": 140, "fos": 100}
TYPES = [{"name": n, "count": c,
          "input": "features" if n == "paper" else "embedding"}
         for n, c in COUNTS.items()]
RELS = [("author", "writes", "paper"), ("paper", "cites", "paper"),
        ("paper", "has_topic", "fos"), ("author", "affiliated_with", "inst"),
        ("paper", "rev_writes", "author"), ("fos", "rev_has_topic", "paper"),
        ("inst", "rev_affiliated_with", "author")]
MODELS = {
    "gcn": dict(widths=[8, NCLS]),
    "mhgat": dict(widths=[NCLS], activation="elu",
                  model_args={"heads": (2,), "concat": (False,)}),
    "deepergcn": dict(widths=[8, 8, 8, NCLS],
                      model_args={"layers": 3, "hidden": 8, "t": 0.1,
                                  "eps": 1e-7}),
    "rgcn": dict(widths=[5, NCLS],
                 model_args={"types": TYPES, "relations": RELS,
                             "label_type": "paper", "hidden": 5,
                             "layers": 2}),
}


def _identity(shapes, cover):
    return tuple(shapes), dict(UNSNAPPED)


def _typed_adjacency():
    rng = np.random.default_rng(0)
    start = dict(zip(COUNTS, np.concatenate(
        [[0], np.cumsum(list(COUNTS.values()))[:-1]])))

    def pairs(s, d, m):
        return (start[s] + rng.integers(0, COUNTS[s], m),
                start[d] + rng.integers(0, COUNTS[d], m))

    src, dst = (np.concatenate(x) for x in zip(
        pairs("author", "paper", 18000), pairs("paper", "paper", 14000),
        pairs("paper", "fos", 18000), pairs("author", "inst", 6600)))
    n = sum(COUNTS.values())
    keep = src != dst
    a = sp.coo_matrix((np.ones(keep.sum()), (src[keep], dst[keep])),
                      shape=(n, n)).tocsr()
    return ((a + a.T) > 0).astype(np.float32)


def _ahat(kind, k):
    if kind == "typed":
        return normalize_adjacency(sp.csr_matrix(_typed_adjacency()))
    return normalize_adjacency(dcsbm_graph(
        **(SMALL if kind == "small" else GRAPHS[k])))


_PLANS: dict = {}


def _plans(kind, k):
    """``(snapped, unsnapped)``: the plan as the program builds it and with
    ``snap_rows`` the identity, fold slots and the combined layout built."""
    if (kind, k) not in _PLANS:
        ahat = _ahat(kind, k)
        n = ahat.shape[0]
        partvec = (np.zeros(n, np.int64) if k == 1
                   else balanced_random_partition(n, k, seed=1))
        pair = []
        for helper in (snap_rows, _identity):
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(plan_mod, "snap_rows", helper)
                pair.append(build_comm_plan(ahat, partvec, k)
                            .ensure_fold_slots().ensure_cell())
        _PLANS[kind, k] = tuple(pair)
    return _PLANS[kind, k]


def _shape_sets(plan) -> dict:
    """Every set of shapes the plan chose: store -> ((rows, width), ...)."""
    att = plan.virtual_rows()               # the attention layer's one width
    return {"slot_edges": plan.ell_buckets, "cell": plan.cell_buckets,
            "tail_edges": plan.fold_tail_classes,
            "halo_edges": plan.fold_halo_classes,
            **{f"att_{s}": lay["classes"] for s, lay in att.items() if lay}}


def _ell_rows(plan, p, width):
    """Chip ``p``'s ELL as ``(b, width)`` sources and weights, row by row
    in slot order (0 past a row's bucket width)."""
    idx = np.zeros((plan.b, width), np.int64)
    w = np.zeros((plan.b, width), np.float32)
    off = r0 = 0
    for nb, wb in plan.ell_buckets:
        seg = slice(off, off + nb * wb)
        idx[r0: r0 + nb, :wb] = plan.ell_idx[p, seg].reshape(wb, nb).T
        w[r0: r0 + nb, :wb] = plan.ell_w[p, seg].reshape(wb, nb).T
        off, r0 = off + nb * wb, r0 + nb
    return np.where(w != 0, idx, -1), w


# ----------------------------------------------------------------- the plan
def check_window(k):
    new, old = _plans("dcsbm", k)
    sets, was = _shape_sets(new), _shape_sets(old)
    for store, shapes in sets.items():
        widths = [w for _, w in shapes]
        if store == "cell" and widths != sorted(widths, reverse=True):
            # the combined profile only nearly descends: a boundary moves
            # down the widths alone (no new tail edge), so a bucket may stay
            assert (sum(not rows_cheap(n) for n, _ in shapes)
                    <= sum(not rows_cheap(n) for n, _ in was[store]))
            continue
        assert all(rows_cheap(n) for n, _ in shapes), (store, shapes)
        big = [n % ROW_PERIOD for n, _ in shapes if n >= ROW_PERIOD]
        assert all(ROW_WINDOW[0] <= r <= ROW_WINDOW[1] for r in big)
    # not vacuous: the unsnapped builds hold dear ELL buckets, and on four
    # chips a dear fold class
    assert not all(rows_cheap(n) for n, _ in was["slot_edges"])
    if k == 4:
        assert not all(rows_cheap(n) for s in ("tail_edges", "halo_edges")
                       for n, _ in was[s])


def check_cover(k):
    new, old = _plans("dcsbm", k)
    for name in ("ell_buckets", "cell_buckets"):
        got, was = getattr(new, name), getattr(old, name)
        assert sum(n for n, _ in got) == new.b == sum(n for n, _ in was)
        assert [w for _, w in got] == [w for _, w in was]
        widths = [w for _, w in got]
        assert name == "cell_buckets" or widths == sorted(widths,
                                                          reverse=True)
        # a boundary only moves later, by less than a period
        ends, were = (np.cumsum([n for n, _ in x]) for x in (got, was))
        assert ((ends >= were) & (ends - were < ROW_PERIOD)).all()
    np.testing.assert_array_equal(new.ltail_nnz, old.ltail_nnz)
    np.testing.assert_array_equal(new.ctail_nnz, old.ctail_nnz)
    for name in ("ltail_dst", "ltail_src", "ltail_w"):
        np.testing.assert_array_equal(getattr(new, name), getattr(old, name))
    width = new.ell_buckets[0][1]
    for p in range(k):
        for a, b in zip(_ell_rows(new, p, width), _ell_rows(old, p, width)):
            np.testing.assert_array_equal(a, b)
    # the fold stores hold the same virtual rows, then padding
    for pre, store in (("ft", "tail"), ("fh", "halo")):
        off = [0, 0]
        for (nv, wd), (nv0, wd0) in zip(
                getattr(new, f"fold_{store}_classes"),
                getattr(old, f"fold_{store}_classes")):
            assert wd == wd0 and nv >= nv0
            blocks = [getattr(pl, f"{pre}_w")[:, o: o + n * wd].reshape(
                k, wd, n) for pl, o, n in ((new, off[0], nv),
                                           (old, off[1], nv0))]
            np.testing.assert_array_equal(blocks[0][:, :, :nv0], blocks[1])
            assert not blocks[0][:, :, nv0:].any()
            off = [off[0] + nv * wd, off[1] + nv0 * wd]


def check_slots(k):
    new, old = _plans("dcsbm", k)
    for store, shapes in _shape_sets(new).items():
        for (n, w), (n0, w0) in zip(shapes, _shape_sets(old)[store]):
            assert w == w0 and n - n0 < ROW_PERIOD, (store, shapes)
            assert (n - n0) * w <= (ROW_PERIOD - 1) * w
    # a fold class on its own: from an exact multiple (dear) and from past
    # the window up to the window's first row count, a multiple of 8 still
    lo, hi = ROW_WINDOW
    for rows, want in ((2048, 2048 + lo), (2040, 2048 + lo),
                       (1024 + hi + 8, 2048 + lo), (1024 + hi, 1024 + hi),
                       (1024 + lo, 1024 + lo), (1016, 1016)):
        assert fold_class_shapes([np.full(rows, 3)], (4,)) == ((want, 4),)


def check_untouched(k):
    new, old = _plans("small", k)
    assert all(n < ROW_PERIOD for s in _shape_sets(new).values()
               for n, _ in s)
    for name in ("ell_buckets", "fold_tail_classes", "fold_halo_classes",
                 "cell_buckets"):
        assert getattr(new, name) == getattr(old, name)
    for name in ("ell_idx", "ell_w", "ft_idx", "ft_w", "ft_row", "fh_idx",
                 "fh_w", "fh_row", "cell_idx", "cell_w"):
        assert getattr(new, name).tobytes() == getattr(old, name).tobytes()
    assert new.work_counts()["snapped"] == {
        s: UNSNAPPED for s in ("slot_edges", "tail_edges", "halo_edges")}
    # shapes a caller forces stay as given, dear or not: the unsnapped
    # buckets through ``_build_ell`` and through the mini-batch envelope
    new, old = _plans("dcsbm", k)
    forced = old.ell_buckets
    assert not all(rows_cheap(n) for n, _ in forced)
    ell = _build_ell(new.ledge_dst, new.ledge_src, new.ledge_w, new.lnnz,
                     new.b, buckets=forced)
    assert ell["ell_buckets"] == forced
    assert ell["ell_idx"].tobytes() == old.ell_idx.tobytes()
    assert ell["snapped"] == {"slot_edges": UNSNAPPED}
    padded = pad_comm_plan(new, new.b, new.s, new.r, new.e,
                           ell_buckets=forced)
    assert padded.ell_buckets == forced
    assert padded.ell_w.tobytes() == old.ell_w.tobytes()
    # an envelope the program chooses for several plans is snapped
    assert shared_ell_buckets([old], old.b) == new.ell_buckets


def check_counter(k):
    new, old = _plans("dcsbm", k)
    got = new.work_counts()["snapped"]
    assert old.work_counts()["snapped"] == {s: UNSNAPPED for s in got}
    sets, was = _shape_sets(new), _shape_sets(old)
    for store in ("slot_edges", "tail_edges", "halo_edges"):
        rows, rows0 = (np.array([n for n, _ in x[store]], np.int64)
                       for x in (sets, was))
        widths = np.array([w for _, w in sets[store]], np.int64)
        moved = (np.cumsum(rows - rows0)[:-1].sum() if store == "slot_edges"
                 else (rows - rows0).sum())
        assert got[store] == {"shapes": int((rows != rows0).sum()),
                              "rows": int(moved),
                              "slots": int(((rows - rows0) * widths).sum())}
        executed = sum(n * w for n, w in sets[store])
        assert new.work_counts()["executed"][store] == executed
    assert got["slot_edges"]["shapes"] > 0
    assert k == 1 or got["tail_edges"]["shapes"] + got["halo_edges"][
        "shapes"] > 0
    # the program counter carries it
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tracing, "_counters", {})
        tracing.set_counter("plan.work_counts", new.work_counts())
        assert tracing.counters()["plan.work_counts"]["snapped"] == got


# --------------------------------------------------------------- the models
def _step(model, plan):
    """Logits and, from one SGD step, every gradient leaf in global row
    order."""
    kw = dict(MODELS[model])
    tr = FullBatchTrainer(plan, fin=FIN, widths=list(kw.pop("widths")),
                          mesh=make_mesh_1d(plan.k), seed=3, model=model,
                          optimizer=optax.sgd(RATE), **kw)
    rng = np.random.default_rng(1)
    mask = (rng.random(plan.n) < 0.5).astype(np.float32)
    data = make_train_data(
        plan, rng.standard_normal((plan.n, FIN)).astype(np.float32),
        rng.integers(0, NCLS, plan.n).astype(np.int32), train_mask=mask)
    data = TrainData(**shard_stacked(tr.mesh, vars(data)))
    before, _ = tr.host_state()
    logits = tr.predict(data)
    tr.step(data)
    after, _ = tr.host_state()
    return logits, jax.tree.map(lambda a, b: (np.asarray(a) - np.asarray(b))
                                / RATE, before, after)


def check_model(model, k):
    kind = "typed" if model == "rgcn" else "dcsbm"
    new, old = _plans(kind, k)
    logits, grads = _step(model, new)
    with pytest.MonkeyPatch.context() as mp:
        # the typed layouts choose their own buckets, by the same helper
        mp.setattr(rgcn, "snap_rows", _identity)
        mp.setattr(plan_mod, "snap_rows", _identity)
        want_logits, want_grads = _step(model, old)
    np.testing.assert_allclose(logits, want_logits, rtol=0, atol=ATOL)
    assert np.abs(want_logits).max() > 1e-3
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    assert flat and max(np.abs(g).max() for _, g in flat) > 1e-4
    for path, g in flat:
        np.testing.assert_allclose(g, want[path], rtol=0, atol=ATOL,
                                   err_msg=jax.tree_util.keystr(path))


def check_typed(k):
    """The typed layouts (one per ordered pair of types): buckets chosen on
    a block profile, snapped on the rows."""
    new, old = _plans("typed", k)
    args = rgcn.resolve_args(FIN, MODELS["rgcn"]["widths"],
                             MODELS["rgcn"]["model_args"])
    lay = rgcn.build_typed_layout(new, args)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(rgcn, "snap_rows", _identity)
        mp.setattr(plan_mod, "snap_rows", _identity)
        lay0 = rgcn.build_typed_layout(old, args)
    engaged = 0
    for (pair, stores), (pair0, stores0) in zip(lay["layouts"],
                                                lay0["layouts"]):
        assert pair == pair0
        got = lay["counts"][pair]["snapped"]
        assert lay0["counts"][pair]["snapped"] == {s: UNSNAPPED for s in got}
        height = lay["heights"][pair[1]]
        for name, shapes, shapes0 in zip(got, stores, stores0):
            rows, rows0 = (np.array([n for n, _ in x], np.int64)
                           for x in (shapes, shapes0))
            widths = np.array([w for _, w in shapes], np.int64)
            if name == "slot_edges":
                assert not len(rows) or rows.sum() == height
            else:
                assert rows_cheap(rows).all()
            engaged += got[name]["shapes"]
            if [w for _, w in shapes0] != widths.tolist():
                # the widths are chosen by the slots EXECUTED, so a snap may
                # change the choice itself: nothing to difference against
                continue
            if name == "slot_edges":
                moved = np.cumsum(rows - rows0)[:-1].sum()
                # a bucket no boundary move can reach stays: never more dear
                assert (~rows_cheap(rows)).sum() <= (~rows_cheap(rows0)).sum()
            else:
                assert (rows >= rows0).all()
                moved = (rows - rows0).sum()
            assert (np.abs(rows - rows0) < ROW_PERIOD).all()
            assert got[name] == {
                "shapes": int((rows != rows0).sum()), "rows": int(moved),
                "slots": int(((rows - rows0) * widths).sum())}
        assert lay["counts"][pair]["edges"] == lay0["counts"][pair]["edges"]
    assert engaged


CHECKS = {"window": check_window, "cover": check_cover, "slots": check_slots,
          "untouched": check_untouched, "counter": check_counter,
          "typed": check_typed}


@pytest.mark.parametrize("check,k", [
    *((c, k) for c in CHECKS for k in (1, 4)),
    ("gcn", 1), *((m, 4) for m in MODELS)])
def test_row_snap(check, k):
    if check in CHECKS:
        CHECKS[check](k)
    else:
        check_model(check, k)
