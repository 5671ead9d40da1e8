"""Hot-halo replication (``--replica-budget B``): persistent per-layer
replicas of the plan's top-B boundary rows on their consumer chips
(``CommPlan.ensure_replicas``, ``ops/pspmm.py::pspmm_replica[_ragged]``,
docs/replication.md) — CaPGNN-style feature caching (ROADMAP item 2).

Contract pinned here:

  * ``sync_every=1`` replica training is f32-BIT-identical to the exact
    no-replica path on the cora fixture under BOTH transports — losses AND
    parameters ``==`` (the refresh program IS the exact program plus the
    replica gathers; the ragged flavor chains the PR-4/PR-6 parity);
  * the replica (non-refresh) step ships the SHRUNKEN exchange: per-pair
    buckets and ring rounds lose exactly the replicated rows' shipments
    (Σλ of the selection), and the approximate run stays finite with the
    fused ``run_epochs`` reproducing per-step ``step()``;
  * the replica carries are per-layer ``(RP, f_ℓ)`` tables at the
    EXCHANGED widths (same lockstep rule as the stale carries);
  * telemetry: the ``replica`` event block (schema ``REPLICA_KEYS``) is
    emitted and schema-valid, drift is measured at each refresh, and the
    cumulative ``CommStats`` byte gauges reconcile EXACTLY with the sum of
    per-step roofline figures (replica steps booked at the shrunken
    volumes);
  * the native cache-aware km1 driver's objective is <= the cache-blind
    partition's objective under an INDEPENDENT numpy evaluator, at equal
    balance;
  * construction-time gates: GAT, staleness composition, compute_dtype,
    and the mini-batch trainer all reject replication with clear errors.
"""

import os

import numpy as np
import pytest

from sgcn_tpu.io.datasets import load_npz_dataset
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.partition.emit import read_partvec
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, make_train_data

# The exact step with its hub tail and halo-source edges as COO lists
# (``pspmm_ell_sym_coo``): the addition order the carried-halo and ragged
# programs reproduce bit for bit.  The exact full-batch step itself folds
# both stores as slot passes since PR 30 — same edges, another order;
# ``tests/test_fold_slots.py`` bounds the difference.
COO_EXACT = {"shared_envelope": True}

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")

WIDTHS = [16, 7]
BUDGET = 24


@pytest.fixture(scope="module")
def cora():
    """The committed cora-format fixture + its 4-way hp partvec."""
    a, feats, labels = load_npz_dataset(os.path.join(FIX, "cora_like.npz"))
    ahat = normalize_adjacency(a)
    pv = read_partvec(os.path.join(FIX, "cora_like.4.hp"))
    plan = build_comm_plan(ahat, pv, 4)
    return plan, feats.astype(np.float32), labels.astype(np.int32)


@pytest.fixture(scope="module")
def exact_run(cora):
    """Exact no-replica reference: 4 losses + trained parameters, shared
    by both transports' bit-identity assertions (one compile)."""
    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=3,
                          **COO_EXACT)
    d = make_train_data(plan, feats, labels)
    losses = [tr.step(d) for _ in range(4)]
    return losses, [np.asarray(w) for w in tr.params]


@pytest.mark.parametrize("schedule", ["a2a", "ragged"])
def test_replica_sync1_bit_identical_to_exact(cora, exact_run, schedule):
    """THE acceptance contract: ``--replica-budget B>0 --sync-every 1``
    trains cora with losses and parameters exactly equal to the exact
    no-replica path's, under both transports — every step runs the refresh
    program, which is the exact program plus the replica-row gathers."""
    plan, feats, labels = cora
    exact_losses, exact_params = exact_run
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=3,
                          comm_schedule=schedule, replica_budget=BUDGET,
                          sync_every=1)
    assert tr.replica_budget == BUDGET
    assert plan.replica_rows == BUDGET
    d = make_train_data(plan, feats, labels)
    lc = [tr.step(d) for _ in range(4)]
    assert lc == exact_losses                        # bitwise, not allclose
    for wa, wb in zip(exact_params, tr.params):
        np.testing.assert_array_equal(wa, np.asarray(wb))


def test_replica_layout_invariants(cora):
    """Selection + shrunken-layout bookkeeping: the shrunken buckets lose
    exactly the replicated rows' Σλ shipments, the replica slots cover the
    same Σλ receive positions, and the shrunken wire never exceeds the
    full one under either transport."""
    plan, _, _ = cora
    plan.ensure_ragged()
    plan.ensure_replicas(BUDGET)
    lam, cons = plan.replica_scores()
    assert int(lam.sum()) == int(plan.send_counts.sum())
    assert plan.replica_rows == BUDGET
    saving = plan.replica_send_saving
    assert saving >= BUDGET            # every boundary row has λ >= 1
    assert (int(plan.nrep_send_counts.sum())
            == int(plan.send_counts.sum()) - saving)
    assert int(plan.rep_counts.sum()) == saving
    for sched in ("a2a", "ragged"):
        assert (plan.wire_rows_per_exchange(sched, replica=True)
                <= plan.wire_rows_per_exchange(sched))
        for shrunk, full in zip(plan.wire_buffer_shapes(sched, replica=True),
                                plan.wire_buffer_shapes(sched)):
            assert np.prod(shrunk) <= np.prod(full)
    # carries ride the exchanged widths, RP rows each (stale-carry lockstep)
    from sgcn_tpu.models.gcn import exchange_widths
    shapes = plan.replica_carry_shapes(1433, WIDTHS)
    fs = exchange_widths(1433, WIDTHS)
    assert shapes["reps"] == [(plan.rp, f) for f in fs]
    assert shapes["greps"] == shapes["reps"]


def test_replica_run_epochs_parity(cora):
    """The fused multi-step path reproduces per-step ``step()`` exactly,
    refresh scheduling included."""
    plan, feats, labels = cora
    d = make_train_data(plan, feats, labels)
    kw = dict(fin=feats.shape[1], widths=WIDTHS, seed=5,
              comm_schedule="ragged", replica_budget=BUDGET, sync_every=3)
    ta = FullBatchTrainer(plan, **kw)
    la = [ta.step(d) for _ in range(5)]
    tb = FullBatchTrainer(plan, **kw)
    lb = tb.run_epochs(d, 5)
    np.testing.assert_array_equal(np.asarray(la, np.float32),
                                  np.asarray(lb, np.float32))
    for wa, wb in zip(ta.params, tb.params):
        np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
    # stats booked identically: refresh steps at the full volumes, replica
    # steps at the shrunken ones
    ra, rb = ta.stats.report(), tb.stats.report()
    assert ra == rb
    assert ra["replica_exchanges"] == 2 * len(WIDTHS) * 3   # steps 1,2,4
    assert ra["halo_bytes_true_total"] < 5 * ra["halo_bytes_true_per_step"]
    # rows shipped over the same five steps, against the no-replica arm's
    # (every exchange at the full ring): replicated rows leave the wire on
    # the three replica steps, so both totals are strictly lower
    nl, full = len(WIDTHS), plan.wire_rows_per_exchange("ragged")
    shrunk = plan.wire_rows_per_exchange("ragged", replica=True)
    assert ra["wire_rows_total"] == 2 * nl * (2 * full + 3 * shrunk)
    assert ra["wire_rows_total"] < 5 * 2 * nl * full
    assert plan.replica_rows == BUDGET and plan.replica_send_saving > 0


def test_replica_telemetry_books_and_reconciles(cora, tmp_path):
    """Recorder path: the ``replica`` block is emitted and schema-valid
    (load_run re-validates), drift is measured at refreshes, the roofline
    prices replica steps at the shrunken volumes, and the cumulative
    CommStats byte gauges equal the event stream's per-step sums EXACTLY
    — the gauge-reconciliation smoke of the satellite."""
    from sgcn_tpu.obs import RunRecorder, load_run

    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=0,
                          replica_budget=BUDGET, sync_every=3)
    d = make_train_data(plan, feats, labels)
    rec = RunRecorder(str(tmp_path / "run"), config={"replica": BUDGET})
    tr.attach_recorder(rec)
    for _ in range(5):
        tr.step(d)
    rec.close()
    log = load_run(str(tmp_path / "run"))          # schema re-validated
    steps = [e for e in log.events if e["kind"] == "step"]
    assert len(steps) == 5
    blocks = [s["replica"] for s in steps]
    assert [b["sync_step"] for b in blocks] == [True, False, False, True,
                                                False]
    assert [b["refresh_age"] for b in blocks] == [0, 1, 2, 3, 1]
    assert all(b["replica_rows"] == BUDGET for b in blocks)
    # drift exists only at refreshes (fresh values only exist on the wire
    # there); step 4's refresh erased 3 steps of drift — nonzero because
    # the exchanged rows move with the weights (cora is project-first).
    # The INITIALIZING refresh (step 1) reports zero: its in-graph gauge
    # compares against the zero-init carry (initialization magnitude, not
    # drift) and must not dominate the operator's max/mean.
    assert blocks[3]["replica_drift_rms"][-1] > 0
    assert blocks[0]["replica_drift_rms"] == [0.0, 0.0]
    assert blocks[1]["replica_drift_rms"] == [0.0, 0.0]
    # replica steps priced at the shrunken wire, refreshes at the full one
    wire = [s["roofline"]["halo_wire_rows_per_exchange"] for s in steps]
    assert wire[0] == wire[3] == plan.wire_rows_per_exchange("a2a")
    assert wire[1] == plan.wire_rows_per_exchange("a2a", replica=True)
    assert wire[1] < wire[0]
    # exact reconciliation, replica-step resolution included
    comm = steps[-1]["comm"]
    assert comm["halo_bytes_true_total"] == sum(
        s["roofline"]["halo_bytes_true_per_step"] for s in steps)
    assert comm["halo_bytes_wire_total"] == sum(
        s["roofline"]["halo_bytes_wire_per_step"] for s in steps)
    # every replica-mode exchange is synchronous — nothing hidden
    assert comm["hidden_exchanges"] == 0
    assert comm["exposed_exchanges"] == comm["exchanges"]
    # the rendered report carries the replica gauge lines
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(FIX), "..",
                                   "scripts", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    text = mod.render(str(tmp_path / "run"))
    assert "replica gauges (hot-halo replication)" in text
    assert f"replica rows: {BUDGET}" in text


def test_native_cache_aware_km1(cora):
    """The partitioner acceptance inequality: the cache-aware RB driver's
    km1_cache is <= the cache-blind partition's cache objective under an
    independent numpy evaluator, at equal balance caps, and the native and
    numpy objective implementations agree bit-for-bit."""
    import scipy.sparse as sp

    from sgcn_tpu.io.datasets import load_npz_dataset as _l  # noqa: F401
    from sgcn_tpu.partition import (partition_hypergraph_colnet,
                                    partition_hypergraph_colnet_cache)
    from sgcn_tpu.partition.native import cache_aware_km1

    a, _, _ = load_npz_dataset(os.path.join(FIX, "cora_like.npz"))
    k, B = 4, 48
    pv_blind, km1_blind = partition_hypergraph_colnet(a, k, seed=0)
    pv_c, km1_c, km1_cache = partition_hypergraph_colnet_cache(
        a, k, B, seed=0)
    assert km1_cache == cache_aware_km1(a, pv_c, B)
    assert km1_cache <= cache_aware_km1(a, pv_blind, B)
    assert km1_cache <= km1_c
    w = np.maximum(np.diff(sp.csr_matrix(a).indptr), 1)
    cap = 1.03 * w.sum() / k
    wc = np.array([w[pv_c == p].sum() for p in range(k)])
    assert wc.max() <= cap + w.max()     # same slack rule as the driver


def test_replica_gating(cora):
    """Construction-time gates: clear errors for every unsupported combo
    (mirrors analysis/modes.py::is_supported and the CLI conflicts)."""
    plan, feats, labels = cora
    fin = feats.shape[1]
    with pytest.raises(ValueError, match="GAT"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS, model="gat",
                         replica_budget=8)
    # replica × staleness COMPOSES since PR-12 (tests/test_replica_stale.py);
    # the remaining deferred composition is the delta cache
    with pytest.raises(ValueError, match="deferred"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS, halo_staleness=1,
                         halo_delta=True, replica_budget=8)
    with pytest.raises(ValueError, match="f32 non-remat"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS,
                         compute_dtype="bfloat16", replica_budget=8)
    with pytest.raises(ValueError, match="replica_budget must be >= 0"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS, replica_budget=-1)
    with pytest.raises(ValueError, match="replication is not supported"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS, model="gat",
                         replica_budget="auto")
    # sync_every now legal with EITHER lever, still not alone
    with pytest.raises(ValueError, match="sync_every"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS, sync_every=2)
    from sgcn_tpu.train.minibatch import MiniBatchTrainer
    a, _, _ = load_npz_dataset(os.path.join(FIX, "cora_like.npz"))
    with pytest.raises(ValueError, match="mini-batch"):
        MiniBatchTrainer(normalize_adjacency(a), np.asarray(plan.owner), 4,
                         fin=fin, widths=WIDTHS, batch_size=64,
                         replica_budget=8)


def test_replica_budget_clamps_to_boundary(cora):
    """A budget above the boundary row count clamps (everything
    replicated — the communication-free limit) and still trains: replica
    steps ship empty buckets, refreshes the full exchange."""
    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS,
                          replica_budget=10**7, sync_every=2)
    assert plan.replica_rows < 10**7
    assert int(plan.nrep_send_counts.sum()) == 0
    d = make_train_data(plan, feats, labels)
    losses = [tr.step(d) for _ in range(3)]
    assert np.all(np.isfinite(losses))
    rep = tr.stats.report()
    assert rep["true_rows_per_exchange_replica"] == 0
