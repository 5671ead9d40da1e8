"""Serving subsystem tests (tier-1): ``sgcn_tpu/serve/``.

The contracts pinned here:

  * **router ownership** — every vertex routes to the chip owning its plan
    row (the plan's relabeling IS the routing table);
  * **forward parity** — the AOT-compiled serve program's logits are
    f32-BIT-identical (``==``) to the trainer's ``evaluate()``/``predict``
    path on the cora fixture, for GCN and GAT under BOTH comm schedules
    (the shared ``resolve_forward_setup`` is what makes this hold — a
    drifted second copy of the selection rules would break it here first);
  * **bucket/no-recompile** — pre-compiled padded batch-size buckets serve
    every batch size without a runtime compile (``compile_count`` pinned);
  * **deadline batching** — the micro-batcher flushes on max-batch OR the
    oldest query's latency budget, deterministically (injected clock);
  * **checkpoint provenance** — a wrong-plan / wrong-config restore fails
    with a clear message at load (the PR-8 satellite), never as a deep
    tree-shape error or a cleanly-restored wrong model;
  * **serve telemetry** — the schema-v3 ``serve`` event round-trips through
    ``RunRecorder``/``load_run`` and rejects quantile inversions, and the
    CLI (``python -m sgcn_tpu.serve``) produces a loadable run directory.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures")

from conftest import er_graph  # noqa: E402
from sgcn_tpu.io.datasets import load_npz_dataset  # noqa: E402
from sgcn_tpu.parallel import build_comm_plan  # noqa: E402
from sgcn_tpu.partition import balanced_random_partition  # noqa: E402
from sgcn_tpu.partition.emit import read_partvec  # noqa: E402
from sgcn_tpu.prep import normalize_adjacency  # noqa: E402
from sgcn_tpu.serve import (MicroBatcher, ServeEngine, VertexRouter,  # noqa: E402
                            default_buckets, run_loadgen,
                            synthetic_query_ids)
from sgcn_tpu.train import FullBatchTrainer, make_train_data  # noqa: E402
from sgcn_tpu.utils.checkpoint import save_checkpoint  # noqa: E402


@pytest.fixture(scope="module")
def cora():
    """The committed cora-format fixture under its 4-part hp partition —
    the dataset the parity acceptance criterion names."""
    a, feats, labels = load_npz_dataset(os.path.join(FIX, "cora_like.npz"))
    ahat = normalize_adjacency(a)
    pv = read_partvec(os.path.join(FIX, "cora_like.4.hp"))
    plan = build_comm_plan(ahat, pv, 4)
    return {"plan": plan, "feats": np.asarray(feats, np.float32),
            "labels": labels, "widths": [16, 7]}


@pytest.fixture(scope="module")
def tiny():
    """48-vertex plan for the cheap mechanical tests."""
    ahat = normalize_adjacency(er_graph())
    pv = balanced_random_partition(48, 4, seed=0)
    plan = build_comm_plan(ahat, pv, 4)
    feats = np.random.default_rng(0).standard_normal((48, 8)).astype(
        np.float32)
    labels = (np.arange(48) % 3).astype(np.int32)
    return {"plan": plan, "feats": feats, "labels": labels,
            "widths": [8, 3]}


# ---------------------------------------------------------------- router
def test_router_ownership_matches_plan(cora):
    plan = cora["plan"]
    router = VertexRouter(plan)
    qids = np.arange(plan.n)
    owners, locals_ = router.lookup(qids)
    np.testing.assert_array_equal(owners, plan.owner)
    np.testing.assert_array_equal(locals_, plan.local_idx)
    groups = router.route(np.arange(0, plan.n, 7))
    for chip, ids in groups.items():
        assert (plan.owner[ids] == chip).all()
    # every grouped id appears exactly once
    allids = np.concatenate(list(groups.values()))
    np.testing.assert_array_equal(np.sort(allids), np.arange(0, plan.n, 7))
    with pytest.raises(ValueError, match="out of range"):
        router.lookup([plan.n])


# ----------------------------------------------------------------- parity
@pytest.mark.parametrize("model,sched", [
    ("gcn", "a2a"), ("gcn", "ragged"),
    ("gat", "a2a"), ("gat", "ragged"),
])
def test_forward_parity_bit_identical(cora, model, sched, tmp_path):
    """Serve logits ``==`` trainer evaluate/predict logits (f32 bit
    identity) on the cora fixture — the acceptance criterion.  The gcn/a2a
    case additionally round-trips through a real checkpoint (training
    steps + provenance-verified engine load); the others share params
    directly, which pins the same program-level parity without re-paying
    the optimizer compile per config."""
    plan, feats, labels = cora["plan"], cora["feats"], cora["labels"]
    widths = cora["widths"]
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths,
                          model=model, comm_schedule=sched,
                          activation="none" if model == "gat" else "relu",
                          seed=1)
    data = make_train_data(plan, feats, labels)
    if (model, sched) == ("gcn", "a2a"):
        for _ in range(2):
            tr.step(data)
        ckpt = save_checkpoint(tr, str(tmp_path / "ckpt.npz"), step=2)
        eng = ServeEngine(plan, fin=feats.shape[1], widths=widths,
                          model=model, comm_schedule=sched, checkpoint=ckpt,
                          max_batch=plan.n, buckets=(plan.n,))
        assert eng.checkpoint_meta["step"] == 2
    else:
        import jax
        eng = ServeEngine(plan, fin=feats.shape[1], widths=widths,
                          model=model, comm_schedule=sched,
                          params=jax.tree.map(np.asarray, tr.params),
                          max_batch=plan.n, buckets=(plan.n,))
    eng.set_features(feats)
    expected = tr.predict(data).astype(np.float32)     # eval-path logits
    got = eng.query(np.arange(plan.n))
    assert got.dtype == np.float32
    assert np.array_equal(got, expected), (
        f"{model}/{sched}: serve logits differ from evaluate() "
        f"(max |diff| {np.abs(got - expected).max()})")
    # a shuffled sub-batch returns the same rows, in query order
    sel = np.random.default_rng(0).permutation(plan.n)[:17]
    np.testing.assert_array_equal(eng.query(sel), expected[sel])
    # the forward exchange is serving's whole comm cost: the engine books
    # its schedule's wire rows, and on this skewed hp partition the ring's
    # per-round pads ship strictly fewer than the dense pad
    wire = eng.gauges()["wire_rows_per_exchange"]
    assert wire == plan.wire_rows_per_exchange(sched)
    plan.ensure_ragged()
    assert (plan.wire_rows_per_exchange("ragged")
            < plan.wire_rows_per_exchange("a2a"))


# ----------------------------------------------------- buckets / recompile
def test_bucket_ladder_and_no_recompile(tiny):
    plan, feats = tiny["plan"], tiny["feats"]
    assert default_buckets(16) == (1, 2, 4, 8, 16)
    assert default_buckets(12) == (1, 2, 4, 8, 12)
    eng = ServeEngine(plan, fin=feats.shape[1], widths=tiny["widths"],
                      max_batch=8, buckets=(2, 8))
    eng.set_features(feats)
    assert eng.compile_count == 2          # every bucket pre-compiled
    for nq in (1, 2, 3, 8, 5, 2, 8):
        out = eng.query(np.arange(nq))
        assert out.shape == (nq, tiny["widths"][-1])
    assert eng.compile_count == 2, (
        "a served batch size triggered a recompile — the bucket contract "
        "is exactly that no query count may")
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        eng.batcher.bucket_for(9)
    g = eng.gauges()
    assert g["compiles"] == 2
    assert g["wire_rows_per_batch"] == 2 * plan.wire_rows_per_exchange(
        eng.comm_schedule)


def test_batcher_deadline_and_full_flush():
    """Deterministic deadline semantics on an injected clock: flush fires
    on max-batch immediately, else once the OLDEST pending query has
    waited the budget."""
    now = [0.0]
    b = MicroBatcher(max_batch=3, latency_budget_ms=100.0, buckets=(1, 3),
                     clock=lambda: now[0])
    assert b.submit(1) is None
    assert b.poll() is None                      # budget not reached
    now[0] = 0.05
    assert b.poll() is None
    assert b.submit(2) is None
    now[0] = 0.1                                 # head is 100 ms old
    flushed = b.poll()
    assert [p.qid for p in flushed] == [1, 2]
    assert b.deadline_flushes == 1 and b.full_flushes == 0
    # max-batch flush: third submit returns the batch synchronously
    assert b.submit(3) is None
    assert b.submit(4) is None
    flushed = b.submit(5)
    assert [p.qid for p in flushed] == [3, 4, 5]
    assert b.full_flushes == 1
    assert len(b) == 0 and b.flush() is None
    with pytest.raises(ValueError, match="below max_batch"):
        MicroBatcher(max_batch=8, buckets=(1, 4))


# ---------------------------------------------------------------- loadgen
class _FakeEngine:
    """Deterministic engine stand-in: executing a batch takes a fixed
    simulated service time on the injected clock."""

    def __init__(self, batcher, clock_box, service_s=0.01):
        self.batcher = batcher
        self._clock = clock_box
        self._service = service_s
        self.batches = []

    def query(self, qids):
        self._clock[0] += self._service
        self.batches.append(list(qids))
        return np.zeros((len(qids), 2), np.float32)


def test_loadgen_open_loop_latency_accounting():
    """Open loop on a fake clock: arrivals on the offered schedule, flushes
    by max-batch, latency measured from the SCHEDULED arrival (queue time
    counts)."""
    now = [0.0]

    def clock():
        return now[0]

    def sleep(dt):
        now[0] += dt

    b = MicroBatcher(max_batch=4, latency_budget_ms=1000.0, buckets=(4,),
                     clock=clock)
    eng = _FakeEngine(b, now, service_s=0.01)
    res = run_loadgen(eng, np.arange(8), offered_qps=100.0,
                      clock=clock, sleep=sleep)
    assert res.queries == 8
    assert res.batches == 2 and res.batch_sizes == [4, 4]
    assert b.full_flushes == 2 and b.deadline_flushes == 0
    # batch 1 executes at t=0.03 (arrival of q3) + 0.01 service = 0.04;
    # q0 arrived at t=0 → 40 ms, q3 at t=0.03 → 10 ms
    assert res.latencies_ms[0] == pytest.approx(40.0)
    assert res.latencies_ms[3] == pytest.approx(10.0)
    assert res.p99_ms >= res.p95_ms >= res.p50_ms > 0
    assert res.achieved_qps > 0


def test_loadgen_deadline_drains_partial_batch():
    """An OPEN-loop trickle below max-batch must still complete within
    ~the budget: the deadline flush serves it (the server cannot know the
    trace ended)."""
    now = [0.0]

    def clock():
        return now[0]

    def sleep(dt):
        now[0] += dt

    b = MicroBatcher(max_batch=8, latency_budget_ms=50.0, buckets=(8,),
                     clock=clock)
    eng = _FakeEngine(b, now, service_s=0.001)
    res = run_loadgen(eng, np.arange(3), offered_qps=1000.0,
                      clock=clock, sleep=sleep)
    assert res.queries == 3
    assert b.deadline_flushes == 1          # budget fired, not max-batch
    # head waited exactly its 50 ms budget + 1 ms service
    assert max(res.latencies_ms) == pytest.approx(51.0)


def test_loadgen_closed_loop_tail_drains_immediately():
    """The CLOSED-loop tail is an ordinary flush, not a budget wait: the
    generator knows no further query is coming, so waiting out the
    latency budget would deflate the ceiling QPS the probe publishes."""
    now = [0.0]

    def clock():
        return now[0]

    def sleep(dt):
        now[0] += dt

    b = MicroBatcher(max_batch=8, latency_budget_ms=50.0, buckets=(8,),
                     clock=clock)
    eng = _FakeEngine(b, now, service_s=0.001)
    res = run_loadgen(eng, np.arange(3), offered_qps=None,
                      clock=clock, sleep=sleep)
    assert res.queries == 3 and res.batches == 1
    assert b.deadline_flushes == 0 and b.full_flushes == 0
    # no budget wait anywhere in the window: just the one service time
    assert res.window_s == pytest.approx(0.001)
    assert max(res.latencies_ms) == pytest.approx(1.0)


def test_batcher_shed_split_is_explicit_and_counted():
    """Deadline shedding (PR-13, docs/resilience.md): a flushed query whose
    age already exceeds budget × shed_factor at dispatch is returned as an
    explicit shed marker — never served, never a silent p99 outlier."""
    now = [0.0]
    b = MicroBatcher(max_batch=4, latency_budget_ms=100.0, buckets=(4,),
                     clock=lambda: now[0], shed_factor=2.0)
    b.submit(1, t_arrival=0.0)          # will be 0.25 s old: past 2×budget
    b.submit(2, t_arrival=0.2)          # 0.05 s old: within budget
    now[0] = 0.25
    keep, shed = b.split_shed(b.flush())
    assert [p.qid for p in keep] == [2]
    assert [p.qid for p in shed] == [1]
    assert b.shed_count == 1
    # no shed_factor → pre-existing behavior: everything dispatches
    b2 = MicroBatcher(max_batch=4, latency_budget_ms=100.0, buckets=(4,),
                      clock=lambda: now[0])
    b2.submit(1, t_arrival=0.0)
    keep, shed = b2.split_shed(b2.flush())
    assert [p.qid for p in keep] == [1] and shed == []
    assert b2.shed_count == 0
    # shedding below the deadline flush itself is rejected loudly
    with pytest.raises(ValueError, match="shed_factor"):
        MicroBatcher(max_batch=4, buckets=(4,), shed_factor=0.5)


def test_loadgen_sheds_overdue_queries_out_of_quantiles():
    """The loadgen path: shed queries are counted in ``ServeResult.shed``
    (and the serve-event ``shed`` key) but excluded from the served count
    and every latency quantile — under overload the published p99
    describes queries that were actually answered."""
    now = [0.0]

    def clock():
        return now[0]

    def sleep(dt):
        now[0] += dt

    # service time far above the arrival spacing: an open-loop overload.
    # budget 10 ms, shed_factor 2 → anything older than 20 ms at dispatch
    # sheds instead of blowing the tail.
    b = MicroBatcher(max_batch=2, latency_budget_ms=10.0, buckets=(2,),
                     clock=clock, shed_factor=2.0)
    eng = _FakeEngine(b, now, service_s=0.1)
    res = run_loadgen(eng, np.arange(6), offered_qps=1000.0,
                      clock=clock, sleep=sleep)
    assert res.shed > 0
    assert res.queries + res.shed == 6
    # every SERVED latency beat the shed cutoff at its dispatch; the shed
    # ones would have been >= 20 ms and appear in no quantile
    assert res.queries == len(res.latencies_ms)
    assert res.summary()["shed"] == res.shed


def test_synthetic_query_ids_range_and_skew():
    q = synthetic_query_ids(100, 500, seed=1)
    assert q.min() >= 0 and q.max() < 100
    qs = synthetic_query_ids(100, 500, seed=1, skew=1.2)
    assert qs.min() >= 0 and qs.max() < 100
    # a power-law draw concentrates: its top vertex count dominates uniform's
    assert np.bincount(qs).max() > np.bincount(q).max()


# ----------------------------------------------------- checkpoint provenance
def test_checkpoint_digest_mismatch_raises(tiny, tmp_path):
    plan, feats, labels = tiny["plan"], tiny["feats"], tiny["labels"]
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=tiny["widths"],
                          seed=0)
    ckpt = save_checkpoint(tr, str(tmp_path / "c.npz"))
    other_pv = balanced_random_partition(48, 4, seed=9)
    other = build_comm_plan(normalize_adjacency(er_graph()), other_pv, 4)
    with pytest.raises(ValueError, match="plan digest mismatch"):
        ServeEngine(other, fin=feats.shape[1], widths=tiny["widths"],
                    checkpoint=ckpt, precompile=False)
    with pytest.raises(ValueError, match="model config mismatch"):
        ServeEngine(plan, fin=feats.shape[1], widths=[16, 3],
                    checkpoint=ckpt, precompile=False)
    # activation is part of the served function: the same params under a
    # different activation would serve different logits — must fail loudly
    with pytest.raises(ValueError, match="mismatch on 'activation'"):
        ServeEngine(plan, fin=feats.shape[1], widths=tiny["widths"],
                    activation="none", checkpoint=ckpt, precompile=False)
    # the matching plan+config loads (and records the saved step)
    eng = ServeEngine(plan, fin=feats.shape[1], widths=tiny["widths"],
                      checkpoint=ckpt, precompile=False, max_batch=8)
    assert eng.checkpoint_meta["plan_digest"] is not None


# ------------------------------------------------------------- telemetry
def test_serve_event_schema_roundtrip(tmp_path):
    from sgcn_tpu.obs import RunRecorder, load_run
    from sgcn_tpu.obs.schema import validate_event

    with RunRecorder(str(tmp_path), run_kind="serve") as rec:
        rec.record_serve(queries=100, achieved_qps=42.5,
                         latency_p50_ms=3.0, latency_p95_ms=9.0,
                         latency_p99_ms=12.0, mode="open", offered_qps=50.0,
                         batches=10, mean_batch=10.0, compiles=0,
                         buckets=[1, 8], comm_schedule="ragged",
                         wire_rows_per_query=12.5)
    log = load_run(str(tmp_path))
    (sv,) = log.serves()
    assert sv["achieved_qps"] == 42.5 and sv["comm_schedule"] == "ragged"
    # quantile inversion is a writer bug the schema rejects
    bad = dict(sv, latency_p50_ms=20.0)
    with pytest.raises(ValueError, match="quantiles out of order"):
        validate_event(bad)
    # the serve kind is v3-only: a v2 stream must not carry it
    with pytest.raises(ValueError, match="unknown event kind"):
        validate_event(dict(sv, v=2))


def test_serve_cli_smoke(tmp_path):
    """End-to-end CLI on the committed cora fixture: closed-loop window,
    one-line JSON with measured provenance, loadable run directory with a
    serve event, rendered by obs_report."""
    rundir = str(tmp_path / "run")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # let -b cpu set its own device count
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [sys.executable, "-m", "sgcn_tpu.serve",
         "--npz", os.path.join(FIX, "cora_like.npz"), "--normalize",
         "-p", os.path.join(FIX, "cora_like.4.hp"),
         "-b", "cpu", "-s", "4", "--random-init",
         "-l", "2", "--hidden", "16",
         "--qps", "0", "--queries", "24", "--max-batch", "8",
         "--buckets", "8", "--latency-budget-ms", "100",
         "--metrics-out", rundir],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=420)
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["metric"] == "serve_qps" and rep["measured"] is True
    assert rep["value"] > 0 and rep["queries"] == 24
    assert rep["latency_p50_ms"] <= rep["latency_p99_ms"]
    assert rep["compiles"] == 1          # one bucket, zero runtime compiles
    from sgcn_tpu.obs import load_run
    log = load_run(rundir)
    (sv,) = log.serves()
    assert sv["queries"] == 24 and sv["mode"] == "closed"
    assert sv["compiles"] == 1
    spans = {e["name"] for e in log.events if e["kind"] == "span"}
    assert {"serve:route", "serve:batch", "serve:compile_lookup",
            "serve:forward"} <= spans
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         rundir],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "serve windows: 1" in out.stdout
    assert "no-recompile contract" in out.stdout
