"""Sub-graph serving tests (tier-1): ``sgcn_tpu/serve/subgraph.py`` +
engine ``mode='subgraph'`` (docs/serving.md phase 2).

The contracts pinned here:

  * **routed-logit parity to the ulp** — the compact L-hop receptive-set
    forward's logits match the trainer's ``evaluate()``/``predict`` path
    on the cora fixture within ``PARITY_ULPS`` ulps of a row's largest
    logit, for GCN and GAT under BOTH comm schedules.  The per-row fold
    recipes reproduce each owner chip's addition sequence exactly and the
    GAT stabilizers arrive precomputed, but the two are different compiled
    shapes, and XLA:CPU contracts multiply-add chains into FMAs per
    compiled shape: under jax 0.9.0 a GCN logit rounds differently by one
    ulp of its row's scale at some batch shapes (max |diff| 6e-8 on
    logits of 0.5–0.9), so ``==`` is not a contract this backend keeps.
    A wrong neighbour, weight or wire cast moves a logit by 1e-3 or more;
  * **no-recompile across growth** — the doubling-ladder shape keys mean a
    repeated traffic sweep (any query count, any receptive-set size seen
    before) never compiles again: ``compile_count`` pinned over a replayed
    sweep;
  * **weight hot-swap** — ``swap_weights`` verifies provenance (plan
    digest + model config) BEFORE touching engine state, swaps with ZERO
    re-compiles (``compile_count`` pinned), bumps ``weights_rev``, and the
    served logits flip to the new checkpoint's values (same ulp contract);
  * **checkpoint watch** — ``--watch-checkpoint-dir``'s poller picks up
    the newest intact checkpoint from a PR-13 rotation directory once per
    flush window;
  * **concurrent dispatch** — ``submit``/``result`` double-buffering
    returns the same bits as sequential ``query`` calls, in order, and the
    concurrent loadgen accounts deterministically on an injected clock;
  * **telemetry** — the v5 ``swap`` event round-trips and older streams
    reject it; serve events carry the sub-graph gauges.
"""

import os

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
from sgcn_tpu.serve import (MicroBatcher, ServeEngine,  # noqa: E402
                            SubgraphIndex, run_loadgen)
from sgcn_tpu.train import FullBatchTrainer, make_train_data  # noqa: E402
from sgcn_tpu.utils.checkpoint import save_checkpoint  # noqa: E402


# ulps of a row's largest logit a compact-shape forward may differ from
# the full-shape one by (module docstring): 1 measured, 2 allowed
PARITY_ULPS = 2


def assert_logits_match(got, want, err_msg=""):
    tol = PARITY_ULPS * np.spacing(
        np.abs(want).max(axis=-1, keepdims=True).astype(np.float32))
    diff = np.abs(got - want)
    assert got.shape == want.shape and np.all(diff <= tol), (
        f"{err_msg} max |diff| {diff.max()} = "
        f"{(diff / (tol / PARITY_ULPS)).max():.1f} ulp of the row scale "
        f"(allowed {PARITY_ULPS})")


@pytest.fixture(scope="module")
def cora():
    a, feats, labels = load_npz_dataset(os.path.join(FIX, "cora_like.npz"))
    ahat = normalize_adjacency(a)
    pv = read_partvec(os.path.join(FIX, "cora_like.4.hp"))
    plan = build_comm_plan(ahat, pv, 4)
    return {"plan": plan, "feats": np.asarray(feats, np.float32),
            "labels": labels, "widths": [16, 7]}


@pytest.fixture(scope="module")
def tiny():
    ahat = normalize_adjacency(er_graph())
    pv = balanced_random_partition(48, 4, seed=0)
    plan = build_comm_plan(ahat, pv, 4)
    feats = np.random.default_rng(0).standard_normal((48, 8)).astype(
        np.float32)
    labels = (np.arange(48) % 3).astype(np.int32)
    return {"plan": plan, "feats": feats, "labels": labels,
            "widths": [8, 3]}


# ----------------------------------------------------------------- parity
@pytest.mark.parametrize("model,sched,halo_dtype", [
    ("gcn", "a2a", None), ("gcn", "ragged", None),
    ("gat", "a2a", None), ("gat", "ragged", None),
    # the third audited serve_subgraph mode: the bf16 wire round-trip on
    # remote-sourced contributions must mirror the full exchange's cast
    # placement exactly, or == breaks only in the narrowed configuration
    ("gcn", "a2a", "bfloat16"),
])
def test_subgraph_parity_to_the_ulp(cora, model, sched, halo_dtype):
    """The acceptance criterion: sub-graph routed logits match the
    trainer's eval-path logits to ``PARITY_ULPS`` for every (model,
    schedule, wire-dtype) combination — across several batch shapes, so
    multiple receptive-set buckets are exercised."""
    import jax

    plan, feats, labels = cora["plan"], cora["feats"], cora["labels"]
    widths = cora["widths"]
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths,
                          model=model, comm_schedule=sched,
                          halo_dtype=halo_dtype,
                          activation="none" if model == "gat" else "relu",
                          seed=1)
    data = make_train_data(plan, feats, labels)
    expected = tr.predict(data).astype(np.float32)
    eng = ServeEngine(plan, fin=feats.shape[1], widths=widths, model=model,
                      comm_schedule=sched, halo_dtype=halo_dtype,
                      activation="none" if model == "gat" else "relu",
                      params=jax.tree.map(np.asarray, tr.params),
                      max_batch=32, mode="subgraph")
    eng.set_features(feats)
    rng = np.random.default_rng(0)
    for nq in (1, 5, 17, 32):
        sel = rng.permutation(plan.n)[:nq]
        got = eng.query(sel)
        assert got.dtype == np.float32
        assert_logits_match(
            got, expected[sel],
            f"{model}/{sched}: sub-graph logits differ from evaluate() at "
            f"nq={nq}:")
    g = eng.gauges()
    assert g["serve_mode"] == "subgraph"
    # query-proportionality on the fixture itself: the receptive sets are
    # far below the k·B rows the full forward computes per batch
    assert 0 < g["touched_rows_per_query"] < g["full_rows_per_forward"]
    assert 0 < g["subgraph_flops_per_query"] < g["full_forward_flops"]


def test_subgraph_per_query_cut_over_fixed_chunks(cora):
    """Query-proportionality as counts, no clock and no engine: over a
    fixed chunking of one seeded query trace (5 = the batch an open loop
    at 50 queries/s fills in a 100 ms budget), the routed queries' 2-hop
    receptive sets touch ≥ 10× fewer rows and cost ≥ 10× fewer analytic
    FLOPs per query than the k·B rows a full forward computes per batch."""
    from sgcn_tpu.obs.attribution import forward_flops, subgraph_batch_flops
    from sgcn_tpu.serve import VertexRouter, synthetic_query_ids

    plan, widths = cora["plan"], cora["widths"]
    fin, nl = cora["feats"].shape[1], len(cora["widths"])
    index, router = SubgraphIndex(plan, "gcn"), VertexRouter(plan)
    qids = synthetic_query_ids(plan.n, 200, seed=0)
    touched = edges = batches = 0
    for i in range(0, len(qids), 5):
        sets = [index.receptive(q, nl)
                for q in router.route(qids[i: i + 5]).values()]
        touched += sum(len(u) for u in sets)
        edges += sum(index.edges_in(u) for u in sets)
        batches += 1
    assert touched == 1252                # 6.26 rows a query, this trace
    assert plan.k * plan.b * batches >= 10 * touched
    assert (forward_flops(plan, fin, widths) * batches
            >= 10 * subgraph_batch_flops(touched, edges, fin, widths))


# ----------------------------------------------------- buckets / recompile
def test_subgraph_no_recompile_across_replayed_growth(tiny):
    """The doubling-ladder contract, on BOTH axes at once: a sweep that
    grows the query count AND (via hub-adjacent queries) the receptive-set
    size compiles its shape keys once — replaying the whole sweep compiles
    nothing."""
    plan, feats = tiny["plan"], tiny["feats"]
    eng = ServeEngine(plan, fin=feats.shape[1], widths=tiny["widths"],
                      max_batch=8, buckets=(2, 8), mode="subgraph")
    eng.set_features(feats)
    rng = np.random.default_rng(1)
    sweep = [rng.integers(0, plan.n, size=nq) for nq in
             (1, 2, 3, 5, 8, 2, 8, 1)]
    outs = [eng.query(q) for q in sweep]
    warm = eng.compile_count
    assert warm > 0
    replay = [eng.query(q) for q in sweep]
    assert eng.compile_count == warm, (
        "replaying an already-served sweep recompiled — the ladder "
        "contract is that no seen (query count, receptive size) may")
    for a, b in zip(outs, replay):
        np.testing.assert_array_equal(a, b)
    # the gauges expose the ladder: every compiled key is recorded
    assert len(eng.gauges()["buckets"]) == warm


def test_subgraph_index_receptive_sets(tiny):
    """The receptive helper itself: 0 hops = the queries; each hop adds
    exactly the recipe neighbors (closed neighborhood, sorted, deduped)."""
    plan = tiny["plan"]
    idx = SubgraphIndex(plan, "gcn")
    q = np.array([3, 7])
    r0 = idx.receptive(q, 0)
    np.testing.assert_array_equal(r0, np.unique(q))
    r1 = idx.receptive(q, 1)
    r2 = idx.receptive(q, 2)
    assert set(r0) <= set(r1) <= set(r2)
    assert (np.sort(r2) == r2).all()
    # 1-hop closure agrees with the adjacency matrix
    ahat = normalize_adjacency(er_graph())
    dense = ahat.toarray()
    nbrs = set(q.tolist())
    for v in q:
        nbrs |= set(np.nonzero(dense[v])[0].tolist())
    assert set(r1) == nbrs


# ---------------------------------------------------------------- hot-swap
def test_hot_swap_provenance_and_pinned_compiles(tiny, tmp_path):
    plan, feats, labels = tiny["plan"], tiny["feats"], tiny["labels"]
    widths = tiny["widths"]
    data = make_train_data(plan, feats, labels)
    tr_a = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths, seed=0)
    tr_b = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths, seed=9)
    tr_b.step(data)
    ckpt_a = save_checkpoint(tr_a, str(tmp_path / "a.npz"), step=0)
    ckpt_b = save_checkpoint(tr_b, str(tmp_path / "b.npz"), step=1)
    exp_a = tr_a.predict(data).astype(np.float32)
    exp_b = tr_b.predict(data).astype(np.float32)

    eng = ServeEngine(plan, fin=feats.shape[1], widths=widths,
                      checkpoint=ckpt_a, max_batch=8, mode="subgraph")
    eng.set_features(feats)
    sel = np.arange(0, plan.n, 5)[:8]
    assert_logits_match(eng.query(sel), exp_a[sel])
    warm = eng.compile_count
    assert eng.weights_rev == 0

    # provenance rejection BEFORE any state change: wrong plan digest
    other = build_comm_plan(normalize_adjacency(er_graph()),
                            balanced_random_partition(48, 4, seed=9), 4)
    tr_o = FullBatchTrainer(other, fin=feats.shape[1], widths=widths,
                            seed=0)
    ckpt_o = save_checkpoint(tr_o, str(tmp_path / "o.npz"))
    with pytest.raises(ValueError, match="plan digest mismatch"):
        eng.swap_weights(ckpt_o)
    # wrong model config
    tr_w = FullBatchTrainer(plan, fin=feats.shape[1], widths=[16, 3],
                            seed=0)
    ckpt_w = save_checkpoint(tr_w, str(tmp_path / "w.npz"))
    with pytest.raises(ValueError, match="model config mismatch"):
        eng.swap_weights(ckpt_w)
    assert eng.weights_rev == 0 and eng.compile_count == warm
    assert_logits_match(eng.query(sel), exp_a[sel])

    # the real swap: zero recompiles, bumped rev, the new weights' logits
    meta = eng.swap_weights(ckpt_b)
    assert meta["step"] == 1
    assert eng.weights_rev == 1
    got = eng.query(sel)
    assert eng.compile_count == warm, (
        "swap_weights recompiled — params are AOT-program inputs and the "
        "swap must be zero re-lowering by contract")
    assert_logits_match(got, exp_b[sel])
    assert not np.allclose(exp_a[sel], exp_b[sel], atol=1e-4)


def test_hot_swap_refreshes_gat_stabilizers(tiny, tmp_path):
    """The GAT-specific swap hazard: the per-layer stabilizers are a
    function of (params, features), so a swap that kept the old cg values
    would break bit-parity — the engine must recompute them."""
    import jax

    plan, feats, labels = tiny["plan"], tiny["feats"], tiny["labels"]
    widths = tiny["widths"]
    data = make_train_data(plan, feats, labels)
    tr_a = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths,
                            model="gat", activation="none", seed=0)
    tr_b = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths,
                            model="gat", activation="none", seed=7)
    ckpt_b = save_checkpoint(tr_b, str(tmp_path / "b.npz"), step=1)
    exp_b = tr_b.predict(data).astype(np.float32)
    eng = ServeEngine(plan, fin=feats.shape[1], widths=widths, model="gat",
                      activation="none",
                      params=jax.tree.map(np.asarray, tr_a.params),
                      max_batch=8, mode="subgraph")
    eng.set_features(feats)
    sel = np.arange(8)
    eng.query(sel)                      # warm under revision 0
    old_cg = eng._stabilizers.copy()
    eng.swap_weights(ckpt_b)
    assert not np.array_equal(eng._stabilizers, old_cg)
    np.testing.assert_array_equal(eng.query(sel), exp_b[sel])


def test_watch_checkpoint_dir_hot_swaps(tiny, tmp_path):
    """The ``--watch-checkpoint-dir`` machinery: a rotation directory grows
    a newer checkpoint; the next flush window's poll swaps it in; corrupt
    newest falls back to the previous intact one."""
    from sgcn_tpu.resilience.checkpoint import CheckpointManager

    plan, feats, labels = tiny["plan"], tiny["feats"], tiny["labels"]
    widths = tiny["widths"]
    data = make_train_data(plan, feats, labels)
    mgr = CheckpointManager(str(tmp_path / "ckpts"), keep_last=3)
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths, seed=0)
    p0 = mgr.save(tr, 0)
    eng = ServeEngine(plan, fin=feats.shape[1], widths=widths,
                      checkpoint=p0, max_batch=8, mode="subgraph")
    eng.set_features(feats)
    eng.attach_checkpoint_watch(str(tmp_path / "ckpts"))
    sel = np.arange(6)
    eng.query(sel)
    assert eng.weights_rev == 0        # nothing newer than the loaded step

    tr.step(data)
    mgr.save(tr, 1)
    exp1 = tr.predict(data).astype(np.float32)
    got = eng.query(sel)               # poll at this flush window swaps
    assert eng.weights_rev == 1
    assert_logits_match(got, exp1[sel])

    # a corrupt newest checkpoint is skipped with a warning; the engine
    # keeps serving the last intact revision
    tr.step(data)
    p2 = mgr.save(tr, 2)
    with open(p2, "r+b") as fh:
        fh.seek(100)
        fh.write(b"\xff\xff\xff\xff")
    with pytest.warns(RuntimeWarning, match="corrupt"):
        eng.query(sel)
    assert eng.weights_rev == 1


# -------------------------------------------------------------- concurrent
def test_concurrent_submit_matches_sequential(tiny):
    """Double-buffered dispatch returns the sequential path's exact bits,
    in submission order — including with two batches in flight back to
    back."""
    plan, feats = tiny["plan"], tiny["feats"]
    eng = ServeEngine(plan, fin=feats.shape[1], widths=tiny["widths"],
                      max_batch=8, mode="subgraph")
    eng.set_features(feats)
    rng = np.random.default_rng(3)
    batches = [rng.integers(0, plan.n, size=nq) for nq in (3, 8, 1, 5)]
    sequential = [eng.query(b) for b in batches]
    handles = [eng.submit(b) for b in batches]       # all in flight
    for h, exp in zip(handles, sequential):
        np.testing.assert_array_equal(h.result(), exp)


def test_concurrent_loadgen_deterministic_accounting():
    """``run_loadgen(concurrent=True)`` on an injected clock: every query
    served exactly once, in order, with the double-buffer draining its
    tail; a batch's latency ends when ITS result is consumed (after the
    next submit), so the figures are deterministic and slightly larger
    than the sequential path's — the honest accounting."""
    now = [0.0]

    def clock():
        return now[0]

    def sleep(dt):
        now[0] += dt

    class _Handle:
        def __init__(self, eng, batch):
            self._eng, self._batch = eng, batch

        def result(self):
            now[0] += self._eng._service       # the blocking wait
            self._eng.resolved.append([p.qid for p in self._batch])
            return np.zeros((len(self._batch), 2), np.float32)

    class _AsyncFake:
        def __init__(self, batcher, service_s=0.01):
            self.batcher = batcher
            self._service = service_s
            self.submitted, self.resolved = [], []

        def submit(self, qids):
            self.submitted.append(list(qids))
            return _Handle(self, self.batcher._last_flushed)

    b = MicroBatcher(max_batch=4, latency_budget_ms=1000.0, buckets=(4,),
                     clock=clock)
    eng = _AsyncFake(b, service_s=0.01)

    # run_loadgen hands Pending batches to execute(); the fake handle needs
    # them for latency bookkeeping, so remember the last flush
    orig_take = b._take

    def take():
        out = orig_take()
        b._last_flushed = out
        return out

    b._take = take
    res = run_loadgen(eng, np.arange(8), offered_qps=100.0,
                      clock=clock, sleep=sleep, concurrent=True)
    assert res.queries == 8
    assert res.batches == 2 and res.batch_sizes == [4, 4]
    assert eng.submitted == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert eng.resolved == [[0, 1, 2, 3], [4, 5, 6, 7]]
    # batch 1 submitted at t=0.03, resolved only after batch 2 is in
    # flight (t=0.07) + its own 10 ms wait → q0's latency is 80 ms; batch 2
    # drains from the tail at t=0.09 → q4 (arrived 0.04) waited 50 ms
    assert res.latencies_ms[0] == pytest.approx(80.0)
    assert res.latencies_ms[4] == pytest.approx(50.0)


# ------------------------------------------------------------- telemetry
def test_swap_event_schema_roundtrip(tmp_path):
    from sgcn_tpu.obs import RunRecorder, load_run
    from sgcn_tpu.obs.schema import validate_event

    with RunRecorder(str(tmp_path), run_kind="serve") as rec:
        rec.record_swap(path="ckpt_00000002.npz", weights_rev=2,
                        checkpoint_step=2, wall_s=0.5)
        rec.record_serve(queries=10, achieved_qps=5.0, latency_p50_ms=1.0,
                         latency_p95_ms=2.0, latency_p99_ms=3.0,
                         serve_mode="subgraph", weights_rev=2,
                         touched_rows_per_query=6.5,
                         subgraph_flops_per_query=1234.0)
    log = load_run(str(tmp_path))
    (sw,) = [e for e in log.events if e["kind"] == "swap"]
    assert sw["weights_rev"] == 2 and sw["checkpoint_step"] == 2
    (sv,) = log.serves()
    assert sv["serve_mode"] == "subgraph"
    assert sv["touched_rows_per_query"] == 6.5
    # the swap kind is v5-only: an older stream must not carry it
    with pytest.raises(ValueError, match="unknown event kind"):
        validate_event(dict(sw, v=4))
    with pytest.raises(ValueError, match="non-finite/negative"):
        validate_event(dict(sw, weights_rev=-1))
    with pytest.raises(ValueError, match="serve_mode"):
        validate_event(dict(sv, serve_mode="cached"))


def test_serve_window_carries_subgraph_gauges(tiny, tmp_path):
    """record_window on a sub-graph engine emits the v5 serve-event keys
    and the analytic gauges reconcile with the engine's accumulators."""
    from sgcn_tpu.obs import RunRecorder, load_run
    from sgcn_tpu.serve.loadgen import ServeResult

    plan, feats = tiny["plan"], tiny["feats"]
    eng = ServeEngine(plan, fin=feats.shape[1], widths=tiny["widths"],
                      max_batch=8, mode="subgraph")
    eng.set_features(feats)
    with RunRecorder(str(tmp_path), run_kind="serve") as rec:
        eng.attach_recorder(rec)
        eng.query(np.arange(8))
        res = ServeResult(latencies_ms=[1.0] * 8, window_s=1.0, batches=1,
                          batch_sizes=[8])
        eng.record_window(res, mode="open")
    log = load_run(str(tmp_path))
    (sv,) = log.serves()
    g = eng.gauges()
    assert sv["serve_mode"] == "subgraph" and sv["weights_rev"] == 0
    assert sv["touched_rows_per_query"] == g["touched_rows_per_query"]
    assert sv["subgraph_flops_per_query"] == g["subgraph_flops_per_query"]
    assert sv["wire_rows_per_query"] == g["wire_rows_per_query"]
