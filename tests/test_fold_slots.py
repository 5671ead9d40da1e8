"""The exact GCN step folds its two COO edge stores as slot passes (PR 30).

The hub tail (``ltail_*``) and the halo-source edges (``hedge_*``) of the
exact symmetric aggregation (``ops.pspmm.pspmm_ell_sym``) go through
``bucketed_slot_reduce`` as virtual rows in width classes chosen from each
store's own run lengths (``CommPlan.ensure_fold_slots``), and the exact
full-batch setup ships that form instead of the COO lists:

  * (a) the slot form holds every real edge of a store exactly once, with
    its weight; padding has weight 0 and the sources ``padding_rows`` gives;
    rows ascend within a class; shapes are one per store, for every chip;
  * (b) the op matches the dense ``Â·H`` forward and backward, and a trained
    ``FullBatchTrainer`` matches the COO form of the same step (the parent's
    program) to f32 summation tolerance;
  * (c) the width rule: short runs take narrower classes than a hub tail, a
    store without edges has no pass;
  * (d) mini-batch, stale, replica, ragged, sub-graph serving and ``mhgat``
    lower the scatters they lowered at the parent (854e6c8); the exact step
    and the full serving forward lower row scatters only;
  * (e) the counter ``fold`` and ``work_counts()`` say what ran.

CPU, tiny DC-SBM graph with hubs past the ELL cap, one and four devices.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from sgcn_tpu.io.datasets import dcsbm_graph
from sgcn_tpu.models.gcn import GCN_PLAN_FIELDS_SLOTS, GCN_PLAN_FIELDS_SYM
from sgcn_tpu.obs import tracing
from sgcn_tpu.ops import pspmm_ell_sym
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
from sgcn_tpu.parallel.plan import (FOLD_WIDTHS, _build_virtual_rows,
                                    choose_fold_widths, fold_class_shapes,
                                    padding_rows)
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

N, FIN, WIDTHS = 1200, 12, [8, 4]
# the exact step with both stores as COO lists: the parent's program
COO_FORM = {"shared_envelope": True}


@pytest.fixture(scope="module")
def ahat():
    # one chip: 1,433 tail edges; four: a tail on one chip only, and some
    # 6,000 halo-source edges a chip
    return normalize_adjacency(dcsbm_graph(N, ncomm=4, avg_deg=30, seed=0))


def _plan(ahat, k):
    return build_comm_plan(
        ahat, np.zeros(N, np.int64) if k == 1
        else balanced_random_partition(N, k, seed=1), k)


@pytest.fixture(scope="module")
def plans(ahat):
    return {k: _plan(ahat, k).ensure_fold_slots() for k in (1, 4)}


@pytest.fixture(autouse=True)
def fresh_counters(monkeypatch):
    monkeypatch.setattr(tracing, "_counters", {})


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((N, FIN)).astype(np.float32),
            rng.integers(0, WIDTHS[-1], N).astype(np.int32))


def _trainer(plan, **kw):
    return FullBatchTrainer(plan, fin=FIN, widths=list(WIDTHS),
                            mesh=make_mesh_1d(plan.k), seed=3, **kw)


def _data(tr, feats, labels):
    data = make_train_data(tr.plan, feats, labels)
    return TrainData(**shard_stacked(tr.mesh, vars(data)))


STORES = {   # store -> (slot-form prefix, COO fields, count field, table)
    "tail": ("ft", ("ltail_dst", "ltail_src", "ltail_w"), "ltail_nnz", "b"),
    "halo": ("fh", ("hedge_dst", "hedge_src", "hedge_w"), "hnnz", "r")}


# ------------------------------------------------------------ (a) the layout
@pytest.mark.parametrize("store", sorted(STORES))
@pytest.mark.parametrize("k", [1, 4])
def test_slot_form_holds_every_edge_of_the_store_once(plans, k, store):
    plan = plans[k]
    pre, coo, counts, table = STORES[store]
    idx, w, row = (getattr(plan, f"{pre}_{x}") for x in ("idx", "w", "row"))
    classes = getattr(plan, f"fold_{store}_classes")
    true = np.asarray(getattr(plan, counts))
    if not true.sum():                       # no edge on any chip: no pass
        assert classes == () and idx.shape == w.shape == row.shape == (k, 0)
        return
    widths = [wd for _, wd in classes]
    assert widths == sorted(set(widths)) and set(widths) <= set(FOLD_WIDTHS)
    assert all(nv % 8 == 0 and nv > 0 for nv, _ in classes)
    # one shape a store, whatever a chip holds
    assert idx.shape == w.shape == (k, sum(nv * wd for nv, wd in classes))
    assert row.shape == (k, sum(nv for nv, _ in classes))
    dst, src, wt = (getattr(plan, f) for f in coo)
    for p in range(k):
        got, off, roff = [], 0, 0
        for nv, wd in classes:
            seg = slice(off, off + nv * wd)
            real = w[p, seg] != 0
            rows = row[p, roff: roff + nv]
            assert np.all(np.diff(rows) >= 0)            # a sorted scatter
            got += zip(np.tile(rows, wd)[real].tolist(),
                       idx[p, seg][real].tolist(), w[p, seg][real].tolist())
            # a virtual row without a real edge is padding, at b − 1
            empty = (w[p, seg].reshape(wd, nv) != 0).sum(axis=0) == 0
            assert np.all(rows[empty] == plan.b - 1)
            off, roff = off + nv * wd, roff + nv
        c = int(true[p])
        want = list(zip(dst[p, :c].tolist(), src[p, :c].tolist(),
                        wt[p, :c].tolist()))
        assert sorted(got) == sorted(want) and len(got) == c
        pad = w[p] == 0
        assert np.array_equal(idx[p][pad], padding_rows(
            int(pad.sum()), getattr(plan, table)))


def test_a_destination_fills_the_widest_class_before_its_remainder(plans):
    plan = plans[1]
    classes = plan.fold_tail_classes
    (nv, wd), off = classes[-1], sum(n * w for n, w in classes[:-1])
    per_row = (plan.ft_w[0, off:].reshape(wd, nv) != 0).sum(axis=0)
    rows = plan.ft_row[0, sum(n for n, _ in classes[:-1]):]
    for r in np.unique(rows[per_row > 0]):
        runs = per_row[(rows == r) & (per_row > 0)]
        assert np.all(runs[:-1] == wd)        # full but for the last
    deg = np.bincount(plan.ltail_dst[0, :int(plan.ltail_nnz[0])],
                      minlength=plan.b)
    assert int((deg // wd).sum()) <= nv


# ----------------------------------------------------- (b) the same numbers
@pytest.mark.parametrize("k", [1, 4])
def test_op_matches_the_dense_product_forward_and_backward(ahat, plans, k):
    plan, mesh = plans[k], make_mesh_1d(k)
    rng = np.random.default_rng(4)
    h = rng.standard_normal((N, 5)).astype(np.float32)
    g = rng.standard_normal((N, 5)).astype(np.float32)
    pa = shard_stacked(mesh, {f: getattr(plan, f)
                              for f in GCN_PLAN_FIELDS_SLOTS})
    hb, gb = (shard_stacked(mesh, plan.scatter_rows(x)) for x in (h, g))

    def per_chip(pa, h, g):
        pa, h, g = jax.tree.map(lambda x: x[0], (pa, h, g))

        def agg(x):
            return pspmm_ell_sym(
                x, *(pa[f] for f in GCN_PLAN_FIELDS_SLOTS), plan.ell_buckets,
                plan.fold_tail_classes, plan.fold_halo_classes)

        return agg(h)[None], jax.grad(lambda x: jnp.sum(agg(x) * g))(h)[None]

    out, grad = jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P("v"),) * 3,
        out_specs=(P("v"), P("v"))))(pa, hb, gb)
    np.testing.assert_allclose(plan.gather_rows(np.asarray(out)), ahat @ h,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(plan.gather_rows(np.asarray(grad)),
                               ahat.T @ g, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("k", [1, 4])
def test_trained_trainer_matches_the_coo_form(ahat, k):
    runs = {}
    for form, kw in (("slots", {}), ("coo", COO_FORM)):
        tr = _trainer(_plan(ahat, k), **kw)
        assert ("fold_classes" in tr._fwd_static) == (form == "slots")
        assert tr.plan_fields == (GCN_PLAN_FIELDS_SLOTS if form == "slots"
                                  else GCN_PLAN_FIELDS_SYM)
        # the slot form is shipped INSTEAD of the COO lists
        assert ("ltail_src" in tr.pa) == ("ft_idx" not in tr.pa)
        data = _data(tr, *_inputs())
        runs[form] = ([tr.step(data) for _ in range(6)], tr.predict(data),
                      tr.evaluate(data))
    (ls, ps, es), (lc, pc, ec) = runs["slots"], runs["coo"]
    assert ls[-1] < ls[0]
    np.testing.assert_allclose(ls, lc, rtol=2e-6)
    np.testing.assert_allclose(ps, pc, rtol=1e-4, atol=2e-6)
    np.testing.assert_allclose(es, ec, rtol=1e-5)


# ----------------------------------------------------------- (c) the widths
def _halo_like(rng, rows=4000):
    """Short runs over nearly every row (mean 10, as gp4's halo store)."""
    return np.minimum(rng.geometric(0.1, rows), 60)


def _hub_tail(rng, rows=4000, hubs=600):
    """Long runs on a sixth of the rows (median ~20, some past 1,000)."""
    deg = np.zeros(rows, np.int64)
    deg[:hubs] = (rng.pareto(1.2, hubs) * 20 + 1).astype(np.int64)
    return deg


def _case_short_runs_narrower(rng):
    short = choose_fold_widths([_halo_like(rng)])
    long = choose_fold_widths([_hub_tail(rng)])
    assert max(short) < max(long) and min(short) <= min(long)


def _case_uniform_runs_take_their_width(rng):
    for wd in FOLD_WIDTHS:
        assert choose_fold_widths([np.full(500, wd)]) == (wd,)


def _case_a_dearer_row_never_narrows(rng):
    degs = [_hub_tail(rng)]
    tops = [max(choose_fold_widths(degs, rc)) for rc in (0.0, 1.5, 6.0, 40.0)]
    assert tops == sorted(tops) and tops[-1] == FOLD_WIDTHS[-1]


def _case_no_edges_no_pass(rng):
    z = np.zeros((2, 1), np.int32)
    assert fold_class_shapes([np.zeros(50, np.int64)] * 2, (8,)) == ()
    assert _build_virtual_rows(z, z, z.astype(np.float32), [0, 0], 50,
                               height=50) is None


def _case_shapes_are_the_fullest_chips(rng):
    a, b = _halo_like(rng), _hub_tail(rng)
    widths = choose_fold_widths([a, b])
    both = dict((wd, nv) for nv, wd in fold_class_shapes([a, b], widths))
    for one in (a, b):
        for nv, wd in fold_class_shapes([one], widths):
            assert nv <= both[wd]


WIDTH_CASES = {f.__name__[len("_case_"):]: f for f in (
    _case_short_runs_narrower, _case_uniform_runs_take_their_width,
    _case_a_dearer_row_never_narrows, _case_no_edges_no_pass,
    _case_shapes_are_the_fullest_chips)}


@pytest.mark.parametrize("case", sorted(WIDTH_CASES))
def test_width_rule(case):
    WIDTH_CASES[case](np.random.default_rng(7))


def test_the_two_stores_of_one_plan_get_their_own_widths(plans):
    # four chips: a 10-edge tail on one chip, ~6,000 short halo runs a chip
    assert plans[4].fold_tail_classes != plans[4].fold_halo_classes
    assert plans[1].fold_halo_classes == ()
    assert plans[1].fold_tail_classes and plans[4].fold_halo_classes


# -------------------------------------------- (d) what each program lowers
SCATTER = re.compile(
    r'"stablehlo\.scatter"\(.*?\n?.*?\}\) : '
    r'\(tensor<[^>]*>, tensor<[^>]*>, tensor<(\d+)x', re.S)


def _scatters(lowered) -> list:
    """Update rows of every scatter in a lowered program, sorted."""
    return sorted(int(m) for m in SCATTER.findall(lowered.as_text()))


def _lower(case, ahat):
    plan = _plan(ahat, 4)
    if case == "minibatch":
        from sgcn_tpu.train.minibatch import MiniBatchTrainer

        return MiniBatchTrainer(
            ahat, np.asarray(plan.owner), 4, fin=FIN, widths=list(WIDTHS),
            batch_size=N // 2, nbatches=2, mesh=make_mesh_1d(4),
            seed=3).lower_step()
    if case.startswith("serve"):
        from sgcn_tpu.serve.engine import ServeEngine
        from sgcn_tpu.serve.subgraph import representative_key

        eng = ServeEngine(
            plan, fin=FIN, widths=list(WIDTHS), mesh=make_mesh_1d(4),
            max_batch=8, buckets=(8,), precompile=False,
            mode="subgraph" if case == "serve-subgraph" else "full")
        return (eng.lower_subgraph(representative_key(eng.sgindex))
                if case == "serve-subgraph" else eng.lower_bucket(8))
    kw, kind = {
        "exact": ({}, "step"),
        "stale": ({"halo_staleness": 1}, "stale"),
        "stale-sync": ({"halo_staleness": 1}, "sync"),
        "replica": ({"replica_budget": 8}, "rep_sync"),
        "ragged": ({"comm_schedule": "ragged"}, "step"),
        "mhgat": ({"model": "mhgat", "model_args": {"heads": (2, 1)}},
                  "step")}[case]
    return _trainer(plan, **kw).lower_step(kind=kind)


# update rows of every scatter of each lowered program on the parent commit
# (854e6c8), this graph, k = 4: 6,470 = eh and 10 = tl are the per-edge
# folds (2,125 / 2,207 the ragged rounds', 1,729 / 1 the batch envelope's),
# 300 the loss's
PARENT_SCATTERS = {
    "stale": [10, 10, 10, 300, 6470, 6470, 6470],
    "stale-sync": [10, 10, 10, 300, 6470, 6470, 6470],
    "replica": [10, 10, 10, 300, 6470, 6470, 6470],
    "ragged": [10, 10, 300, 2125, 2125, 2207, 2207, 2207, 2207],
    "minibatch": [1, 1, 1, 168, 1729, 1729, 1729],
    "serve-subgraph": [16, 16, 16, 16],
    "mhgat": [8] * 14 + [300] + [352] * 14,
}


@pytest.mark.parametrize("case", sorted(PARENT_SCATTERS))
def test_other_paths_lower_the_scatters_they_lowered(case, ahat):
    assert _scatters(_lower(case, ahat)) == PARENT_SCATTERS[case]


@pytest.mark.parametrize("case,passes", [("exact", 2), ("serve-full", 2)])
def test_exact_paths_lower_row_scatters_only(case, passes, ahat, plans):
    """Parent: [10, 10, 300, 6470, 6470] (step) and [10, 10, 6470, 6470]
    (serving): a scatter-add per edge.  Now one per class and pass, of a
    class's virtual rows."""
    plan = plans[4]
    rows = [nv for nv, _ in plan.fold_tail_classes + plan.fold_halo_classes]
    loss = [N // 4] if case == "exact" else []
    assert _scatters(_lower(case, ahat)) == sorted(rows * passes + loss)
    assert plan.eh not in rows and plan.tl not in rows


# ------------------------------------------------------------- (e) counters
@pytest.mark.parametrize("k", [1, 4])
def test_fold_counter_and_work_counts_say_what_ran(ahat, k):
    plan = _plan(ahat, k)
    before = plan.work_counts()
    assert before["executed"]["tail_edges"] == plan.tl
    tr = _trainer(plan)
    fold = tracing.counters()["fold"]
    work = tracing.counters()["plan.work_counts"]
    assert work == plan.work_counts() and work["true"] == before["true"]
    assert fold["row_cost"] > 0
    for store, key, true in (("tail", "tail_edges", plan.ltail_nnz),
                             ("halo", "halo_edges", plan.hnnz)):
        classes = getattr(plan, f"fold_{store}_classes")
        c = fold[store]
        assert c["form"] == ("slots" if true.sum() else None)
        assert c["classes"] == [list(x) for x in classes]
        assert c["virtual_rows"] == sum(nv for nv, _ in classes)
        assert c["executed_slots"] == sum(nv * wd for nv, wd in classes) \
            == work["executed"][key]
        assert c["true_edges"] == true.tolist() == work["true"][key]
        assert work["padding"][key] == [c["executed_slots"] - t
                                        for t in true.tolist()]
        assert tr._fwd_static["fold_classes"] == (plan.fold_tail_classes,
                                                  plan.fold_halo_classes)


def test_a_coo_program_says_coo(ahat):
    plan = _plan(ahat, 4)
    _trainer(plan, **COO_FORM)
    fold = tracing.counters()["fold"]
    assert fold["tail"]["form"] == fold["halo"]["form"] == "coo"
    assert fold["tail"]["executed_slots"] == plan.tl
    assert fold["halo"]["executed_slots"] == plan.eh
    assert fold["halo"]["classes"] == [] and plan.fold_tail_classes is None
    assert tracing.counters()["plan.work_counts"]["executed"][
        "halo_edges"] == plan.eh
