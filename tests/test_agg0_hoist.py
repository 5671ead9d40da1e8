"""Layer 0's ``Â·h0`` is made once per data set, not every step (PR 26).

On the exact full-batch GCN path with an aggregate-first layer 0 the trainer
hoists the loop-invariant aggregation out of the step (``gcn``'s
``models/setup.py::Hoist``, ``FullBatchTrainer.hoist`` and ``_agg0_for``;
``gcn_forward_local(hoisted=...)``):

  * (a) same arithmetic: losses, trained weights and ``predict()`` logits are
    bit-identical to the same trainer with the mechanism switched off before
    any program is traced (what ``MiniBatchTrainer.evaluate_fullgraph`` does
    to its trainer; a test-local reference, not a user option);
  * (b) ``step``, ``run_epochs``, ``evaluate`` and ``predict`` agree with
    each other as they always did;
  * (c) the lowered step holds 2·L − 2 exchanges (2·L − 1 before), the build
    is one dropped executable, and the counter / span say what happened;
  * (d) everything else — project-first widths, GAT, the stale and replica
    families, mini-batch, serving — lowers the exchanges it lowered before;
  * (e) ``remat`` and ``compute_dtype='bfloat16'`` compose.

CPU, tiny graphs, one and four virtual devices.
"""

import gc

import jax
import numpy as np
import pytest

from sgcn_tpu.io.datasets import dcsbm_graph
from sgcn_tpu.models.gcn import exchange_widths, gcn_forward_local
from sgcn_tpu.obs import tracing
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

N, FIN, WIDTHS = 600, 12, [8, 4]


@pytest.fixture(scope="module")
def ahat():
    # hubs past the ELL cap (a tail) and, split four ways, halo edges
    return normalize_adjacency(dcsbm_graph(N, ncomm=4, avg_deg=12, seed=0))


@pytest.fixture(scope="module")
def plans(ahat):
    return {k: build_comm_plan(
        ahat, np.zeros(N, np.int64) if k == 1
        else balanced_random_partition(N, k, seed=1), k) for k in (1, 4)}


def _inputs(fin=FIN, classes=WIDTHS[-1], seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((N, fin)).astype(np.float32),
            rng.integers(0, classes, N).astype(np.int32))


def _trainer(plan, hoist=True, fin=FIN, widths=WIDTHS, **kw):
    tr = FullBatchTrainer(plan, fin=fin, widths=list(widths),
                          mesh=make_mesh_1d(plan.k), seed=3, **kw)
    if not hoist:       # the reference: before any program is traced
        tr.hoist = None
    return tr


def _data(tr, feats, labels):
    data = make_train_data(tr.plan, feats, labels)
    return TrainData(**shard_stacked(tr.mesh, vars(data)))


def _run(plan, hoist, steps=6, **kw):
    tr = _trainer(plan, hoist, **kw)
    data = _data(tr, *_inputs())
    losses = [tr.step(data) for _ in range(steps)]
    return (losses, [np.asarray(w) for w in tr.params], tr.predict(data),
            tr.evaluate(data))


def _a2a(lowered) -> int:
    return lowered.as_text().count("stablehlo.all_to_all")


@pytest.fixture(autouse=True)
def fresh_tables(monkeypatch):
    tracing.reset_spans()
    monkeypatch.setattr(tracing, "_counters", {})


# ------------------------------------------------------- (a) same arithmetic
@pytest.mark.parametrize("k", [1, 4])
def test_hoisted_training_is_bit_identical(plans, k):
    """``==``, not ``allclose``: the dense product consumes the same f32
    ``agg(h0)``, made by the same aggregator in the same addition order; only
    the program it is made in differs."""
    got, want = _run(plans[k], True), _run(plans[k], False)
    assert got[0] == want[0] and got[0][-1] < got[0][0]
    for a, b in zip(got[1], want[1]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(got[2], want[2])
    assert got[3] == want[3]


# ------------------------------------------- (b) the entry points still agree
@pytest.mark.parametrize("k", [1, 4])
def test_step_run_epochs_evaluate_predict_agree(plans, k):
    feats, labels = _inputs()
    a, b = _trainer(plans[k]), _trainer(plans[k])
    da, db = _data(a, feats, labels), _data(b, feats, labels)
    stepped = [a.step(da) for _ in range(4)]
    fused = b.run_epochs(db, 4)
    np.testing.assert_allclose(stepped, fused, rtol=1e-6)
    for wa, wb in zip(a.params, b.params):
        np.testing.assert_allclose(np.asarray(wa), np.asarray(wb), rtol=1e-5,
                                   atol=1e-7)
    loss, acc = a.evaluate(da)
    logits = a.predict(da)
    assert logits.shape == (N, WIDTHS[-1])
    assert acc == pytest.approx(float((logits.argmax(1) == labels).mean()))
    lse = np.log(np.exp(logits.astype(np.float64)).sum(1))
    assert loss == pytest.approx(
        float((lse - logits[np.arange(N), labels]).mean()), rel=1e-5)
    # one array served all of it: 4 steps + evaluate + predict; the fused
    # trainer's 4 epochs came from one build too
    assert tracing.counters()["agg0"]["builds"] == 1
    assert a._agg0_served == 6 and b._agg0_served == 4


# ------------------------------ (c) the lowered step, the counter, the span
def test_exact_step_ships_one_exchange_fewer_and_counts_builds(ahat, plans):
    L = len(WIDTHS)
    tr, ref = _trainer(plans[4]), _trainer(plans[4], hoist=False)
    assert tr.hoist is not None and exchange_widths(FIN, WIDTHS)[0] == FIN
    assert _a2a(ref.lower_step()) == 2 * L - 1      # the parent's step
    assert _a2a(tr.lower_step()) == 2 * L - 2
    assert "agg0" not in tracing.counters()
    feats, labels = _inputs()
    data = _data(tr, feats, labels)
    client = jax.devices()[0].client
    gc.collect()        # executables other tests left for the collector
    before = len(client.live_executables())
    for _ in range(3):
        tr.step(data)
    # the step's executable stays loaded; the build's was dropped
    assert len(client.live_executables()) == before + 1
    want = {"engaged": True, "builds": 1, "steps_served": 3,
            "rows": int(plans[4].b * plans[4].k), "width": FIN}
    assert tracing.counters()["agg0"] == want
    assert tracing.span_totals()["agg0.build"]["count"] == 1
    assert "agg0.build" in tr.timer.report()
    # the same features again, as another array: identity, not value
    again = _data(tr, feats, labels)
    tr.step(again)
    tr.step(again)
    assert tracing.counters()["agg0"] == dict(want, builds=2, steps_served=5)
    assert tracing.span_totals()["agg0.build"]["count"] == 2
    # the caller's h0 is untouched, and what is kept is Â·h0
    np.testing.assert_array_equal(
        plans[4].gather_rows(np.asarray(again.h0)), feats)
    np.testing.assert_allclose(
        plans[4].gather_rows(np.asarray(tr._agg0[0])),
        np.asarray(ahat @ feats), rtol=2e-5, atol=2e-6)


def test_the_forward_refuses_a_hoisted_project_first_layer():
    w = [np.zeros((300, 8), np.float32), np.zeros((8, 4), np.float32)]
    with pytest.raises(ValueError, match="aggregate-first layer 0"):
        gcn_forward_local(w, None, {},
                          hoisted=(np.zeros((5, 300), np.float32),))


# --------------------------------------------------------------- (d) bypass
def _bypass(case, ahat, plan):
    """``(trainer, MiniBatchTrainer or engine, lowered program)`` of one path
    the hoist must leave alone."""
    if case == "minibatch":
        from sgcn_tpu.train.minibatch import MiniBatchTrainer

        mb = MiniBatchTrainer(ahat, np.asarray(plan.owner), plan.k, fin=FIN,
                              widths=list(WIDTHS), batch_size=N // 2,
                              nbatches=2, mesh=make_mesh_1d(plan.k), seed=3)
        return mb.inner, mb, mb.lower_step()
    if case == "serve":
        from sgcn_tpu.serve.engine import ServeEngine

        eng = ServeEngine(plan, fin=FIN, widths=list(WIDTHS),
                          mesh=make_mesh_1d(plan.k), max_batch=8,
                          buckets=(8,), precompile=False)
        return None, eng, eng.lower_bucket(8)
    kw, kind = {"project-first": ({"fin": 300}, "step"),
                "gat": ({"model": "gat", "activation": "none"}, "step"),
                "stale": ({"halo_staleness": 1}, "stale"),
                "replica": ({"replica_budget": 8}, "rep_sync")}[case]
    tr = _trainer(plan, **kw)
    return tr, None, tr.lower_step(kind=kind)


# exchanges of each lowered program on the parent commit (974a9bc), L = 2
BYPASS = {"project-first": 4,    # layer 0's backward exchange feeds dW
          "gat": 4, "stale": 4, "replica": 4,
          "minibatch": 3, "serve": 2}


@pytest.mark.parametrize("case", sorted(BYPASS))
def test_bypassed_paths_lower_what_they_lowered(case, ahat, plans):
    tr, other, lowered = _bypass(case, ahat, plans[4])
    assert _a2a(lowered) == BYPASS[case]
    assert tr is None or tr.hoist is None
    if case in ("project-first", "stale", "replica"):
        data = _data(tr, *_inputs(fin=tr.fin))
        assert np.all(np.isfinite([tr.step(data) for _ in range(2)]))
        tr.evaluate(data)
    elif case == "minibatch":
        feats, labels = _inputs()
        report = other.fit(feats, labels, epochs=2, verbose=False)
        assert np.all(np.isfinite(report["loss_history"]))
        other.evaluate_fullgraph(feats, labels)
    # not engaged: the counter is absent, or says so
    assert not tracing.counters().get("agg0", {}).get("engaged", False)
    assert "agg0.build" not in tracing.span_totals()


# -------------------------------------------------------------- (e) compose
@pytest.mark.parametrize("kw", [{"remat": True},
                                {"compute_dtype": "bfloat16"},
                                {"halo_dtype": "bfloat16"},
                                {"comm_schedule": "ragged"}],
                         ids=lambda kw: "-".join(map(str, kw.values())))
def test_hoist_composes(plans, kw):
    """Each lever narrows or reroutes the aggregation; the array is made
    under the same lever, so the pair stays bit-identical."""
    got = _run(plans[4], True, steps=4, **kw)
    assert tracing.counters()["agg0"]["engaged"]
    want = _run(plans[4], False, steps=4, **kw)
    assert not tracing.counters()["agg0"]["engaged"]
    assert got[0] == want[0]
    for a, b in zip(got[1], want[1]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(got[2], want[2])


def test_remat_step_holds_no_layer0_aggregation(plans):
    """Under ``remat`` the parent lowers the forward twice — layer 0's
    exchange too, once more for the backward's recomputation; hoisted,
    neither copy holds it."""
    L = len(WIDTHS)
    hoisted = _a2a(_trainer(plans[4], remat=True).lower_step())
    parent = _a2a(_trainer(plans[4], hoist=False, remat=True).lower_step())
    assert (hoisted, parent) == (3 * L - 3, 3 * L - 1)
