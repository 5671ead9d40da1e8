"""Composed stale × ragged mode (``--comm-schedule ragged --halo-staleness
1``): the round-structured stale carry on the per-round ppermute ring
(``ops/pspmm.py::pspmm_stale_ragged``) — both perf levers at once
(PipeGCN-complete, ROADMAP open item 1).

Contract pinned here (docs/comm_schedule.md, docs/stale_halo.md):

  * ``sync_every=1`` composed training is f32-BIT-identical to the dense
    exact path on the cora fixture — losses AND parameters ``==`` (the
    fresh fold chains the PR-4 ragged parity through the stale carry);
  * the composed stale run is finite, tracks exact training, books its
    exchanges hidden/exposed like the dense stale mode, and the fused
    ``run_epochs`` path reproduces per-step ``step()``;
  * the carry shapes are ROUND-STRUCTURED (``(Σ_d S_d, f)`` ring receive
    buffers, delta baseline on the same envelope — not ``(k, S, f)``);
  * the ``--halo-delta`` sync step re-bases on an f32 wire, so delta +
    ``sync_every=1`` is ALSO exact (drift resets to zero, not to one bf16
    rounding);
  * drift gauges gain the per-round staleness-age vector and the wire
    gauges (rows, lane-weighted bytes, per-step itemsize split) reconcile
    EXACTLY between ``CommStats`` and the obs event stream;
  * ``auto`` under staleness switches to the wire-byte-only rule (the
    hidden exchange makes the latency threshold moot) and the decision log
    lands in the run manifest.
"""

import os

import numpy as np
import pytest

from sgcn_tpu.io.datasets import load_npz_dataset
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.partition.emit import read_partvec
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, make_train_data
from sgcn_tpu.utils.stats import CommStats

# The exact step with its hub tail and halo-source edges as COO lists
# (``pspmm_ell_sym_coo``): the addition order the carried-halo and ragged
# programs reproduce bit for bit.  The exact full-batch step itself folds
# both stores as slot passes since PR 30 — same edges, another order;
# ``tests/test_fold_slots.py`` bounds the difference.
COO_EXACT = {"shared_envelope": True}

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")

WIDTHS = [16, 7]


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
    """Dense exact-path reference: 4 losses + the trained parameters —
    shared by the bit-identity and the delta-rebase assertions (one
    compile for the module)."""
    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=3,
                          **COO_EXACT)
    d = make_train_data(plan, feats, labels)
    losses = [tr.step(d) for _ in range(4)]
    return losses, [np.asarray(w) for w in tr.params]


def test_composed_sync1_bit_identical_to_dense_exact(cora, exact_run):
    """THE acceptance contract: (ragged, staleness=1, sync_every=1) trains
    cora with losses and parameters exactly equal to the dense exact
    path's — every step consumes the fresh ring receives through the same
    round-order fold, so the PR-4 bit-parity chain survives the carry."""
    plan, feats, labels = cora
    exact_losses, exact_params = exact_run
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=3,
                          comm_schedule="ragged", halo_staleness=1,
                          sync_every=1)
    assert tr.comm_schedule == "ragged" and tr.halo_staleness == 1
    d = make_train_data(plan, feats, labels)
    lc = [tr.step(d) for _ in range(4)]
    assert lc == exact_losses                        # bitwise, not allclose
    for wa, wb in zip(exact_params, tr.params):
        np.testing.assert_array_equal(wa, np.asarray(wb))


@pytest.mark.slow
def test_composed_run_epochs_parity(cora):
    """The fused on-device epoch loop threads the ROUND-STRUCTURED carry
    through its fori body exactly like per-step ``step()`` dispatch —
    losses and CommStats booking agree (slow: compiles a second composed
    trainer plus the multi-step program; the per-step contracts run tier-1
    in test_composed_telemetry_tracks_books_and_reconciles)."""
    plan, feats, labels = cora
    d = make_train_data(plan, feats, labels)
    kw = dict(fin=feats.shape[1], widths=WIDTHS, seed=3,
              comm_schedule="ragged", halo_staleness=1, sync_every=3)
    tr_a = FullBatchTrainer(plan, **kw)
    la = [tr_a.step(d) for _ in range(4)]
    tr_b = FullBatchTrainer(plan, **kw)
    lb = tr_b.run_epochs(d, 4)
    np.testing.assert_allclose(lb, la, rtol=2e-4, atol=1e-5)
    assert tr_b.stats.report() == tr_a.stats.report()


def test_round_structured_carry_shapes(cora):
    """The schedule-aware carry contract: ragged carries are round-major
    ring receive buffers at the exchanged widths; the delta baseline rides
    the same (Σ_d S_d, f) envelope instead of the dense (k, S, f) pad; an
    un-built ragged layout fails loudly; the dense branch is unchanged."""
    from sgcn_tpu.models.gcn import exchange_widths

    plan, feats, labels = cora
    plan.ensure_ragged()
    fin, widths = 300, [64, 4]          # wide input → project-first layer 0
    fs = exchange_widths(fin, widths)
    st = max(1, sum(plan.rr_sizes))
    shapes = plan.stale_carry_shapes(fin, widths, delta=True,
                                     comm_schedule="ragged")
    assert shapes["halos"] == [(st, f) for f in fs]
    assert shapes["ghalos"] == shapes["halos"]
    assert shapes["bases"] == [(st, f) for f in fs]
    nd = plan.stale_carry_shapes(fin, widths, delta=False,
                                 comm_schedule="ragged")
    assert nd["bases"] == [(1, 1)] * len(fs)
    # dense branch keeps the PR-2 contract
    dense = plan.stale_carry_shapes(fin, widths, delta=True)
    assert dense["halos"] == [(plan.r, f) for f in fs]
    assert dense["bases"] == [(plan.k, plan.s, f) for f in fs]
    # un-built layout fails loudly (round sizes ARE the carry layout)
    fresh = build_comm_plan(
        normalize_adjacency(load_npz_dataset(
            os.path.join(FIX, "cora_like.npz"))[0]),
        read_partvec(os.path.join(FIX, "cora_like.4.hp")), 4)
    with pytest.raises(ValueError, match="ensure_ragged"):
        fresh.stale_carry_shapes(fin, widths, comm_schedule="ragged")


def test_delta_sync_rebase_is_exact(cora, exact_run):
    """The f32 re-base contract: with --halo-delta, every sync step ships
    the full f32 row and resets BOTH ends exactly — so delta at
    sync_every=1 is bit-identical to the exact path (drift resets to zero,
    not to one bf16 rounding), composed mode included."""
    plan, feats, labels = cora
    exact_losses, _ = exact_run
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=3,
                          comm_schedule="ragged", halo_staleness=1,
                          halo_delta=True, sync_every=1)
    d = make_train_data(plan, feats, labels)
    ld = [tr.step(d) for _ in range(4)]
    assert ld == exact_losses                        # bitwise, not allclose


def test_composed_telemetry_tracks_books_and_reconciles(cora, tmp_path,
                                                        exact_run):
    """Composed staleness-1 with a periodic sync, ONE telemetry trainer
    (tier-1 budget: this single run carries the tracking, booking AND
    reconciliation contracts): training is finite and tracks the exact
    path; CommStats books sync steps exposed / stale steps hidden with the
    RAGGED wire gauges; the report and the obs event stream agree EXACTLY
    on wire accounting — rows, bytes (cumulative totals at per-step
    itemsize resolution), efficiency, schedule; the drift block carries
    the per-round staleness-age vector; scripts/obs_report.py renders it."""
    from sgcn_tpu.obs import RunRecorder, load_run

    plan, feats, labels = cora
    exact_losses, _ = exact_run
    d = make_train_data(plan, feats, labels)
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=3,
                          comm_schedule="ragged", halo_staleness=1,
                          sync_every=3)
    rec = RunRecorder(str(tmp_path), config={"model": "gcn"})
    tr.attach_recorder(rec)
    losses = [tr.step(d) for _ in range(4)]
    rec.close()

    # finite, and tracking the exact trajectory under bounded staleness
    assert np.all(np.isfinite(losses))
    assert abs(losses[-1] - exact_losses[-1]) < 5e-2
    rep = tr.stats.report()
    nl = tr.nlayers
    assert rep["comm_schedule"] == "ragged"
    assert rep["exchanges"] == 4 * 2 * nl
    assert rep["exposed_exchanges"] == 2 * 2 * nl     # sync at steps 0 and 3
    assert rep["hidden_exchanges"] == 2 * 2 * nl
    assert rep["wire_rows_per_exchange"] == \
        plan.wire_rows_per_exchange("ragged")
    assert rep["wire_rows_per_exchange"] < plan.wire_rows_per_exchange("a2a")
    # the composition's acceptance inequality, as counts over the SAME four
    # steps: the composed arm's exposed wire rows lie strictly below both
    # single levers' — below ragged+exact because half its steps are
    # hidden, below a2a+stale because its sync steps ride the smaller ring
    levers = {"ragged_exact": CommStats.from_plan(plan, "ragged"),
              "a2a_stale": CommStats.from_plan(plan, "a2a")}
    for i in range(4):
        levers["ragged_exact"].count_step(nl)
        levers["a2a_stale"].count_step(nl, hidden=i % 3 != 0)
    assert rep["exposed_wire_rows_total"] == \
        2 * 2 * nl * plan.wire_rows_per_exchange("ragged")
    for st in levers.values():
        assert rep["exposed_wire_rows_total"] < \
            st.report()["exposed_wire_rows_total"]

    log = load_run(str(tmp_path))
    # the schedule-selection decision log landed in the manifest
    dec = log.manifest["comm_schedule"]
    assert dec["resolved"] == "ragged" and dec["rule"] == "explicit"

    steps = log.steps()
    assert len(steps) == 4
    tot_true = tot_wire = 0
    for ev in steps:
        comm, roof, drift = ev["comm"], ev["roofline"], ev["drift"]
        assert comm["comm_schedule"] == roof["comm_schedule"] == "ragged"
        assert comm["wire_rows_per_exchange"] == \
            roof["halo_wire_rows_per_exchange"]
        assert comm["padding_efficiency"] == roof["padding_efficiency"]
        assert comm["halo_bytes_true_per_step"] == \
            roof["halo_bytes_true_per_step"]
        assert comm["halo_bytes_wire_per_step"] == \
            roof["halo_bytes_wire_per_step"]
        assert roof["halo_bytes_wire_per_step"] >= \
            roof["halo_bytes_true_per_step"]
        tot_true += roof["halo_bytes_true_per_step"]
        tot_wire += roof["halo_bytes_wire_per_step"]
        # hidden steps report exposed_comm_frac 0, sync steps 1
        assert roof["exposed_comm_frac"] == \
            (1.0 if drift["sync_step"] else 0.0)
        # per-round staleness-age vector: one entry per ring round, age 0
        # on sync steps, the staleness age on stale steps, null for empty
        ra = drift["round_age"]
        assert len(ra) == len(plan.rr_sizes)
        for sd, age in zip(plan.rr_sizes, ra):
            if sd == 0:
                assert age is None
            else:
                assert age == (0 if drift["sync_step"]
                               else drift["staleness_age"])
    # cumulative byte totals reconcile with the event-sum EXACTLY
    last = steps[-1]["comm"]
    rep = tr.stats.report()
    assert last["halo_bytes_true_total"] == tot_true == \
        rep["halo_bytes_true_total"]
    assert last["halo_bytes_wire_total"] == tot_wire == \
        rep["halo_bytes_wire_total"]

    # the report renderer shows the round-age line
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(FIX), "..",
                                   "scripts", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.render(str(tmp_path))
    assert "round ages (ragged ring)" in out


def test_per_step_wire_itemsize_split(cora):
    """The attribution itemsize split (satellite contract), host-side only:
    under --halo-delta the stale-step feature wire is bf16 and the sync
    (re-base) step's is FULL f32 — regardless of --halo-dtype, which
    governs the gradient wire alone.  The cost model per step kind and
    CommStats' count_step override must agree exactly."""
    plan, feats, _ = cora
    lane = None
    for hd in (None, "bfloat16"):
        tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS,
                              seed=3, comm_schedule="ragged",
                              halo_staleness=1, halo_delta=True,
                              halo_dtype=hd, sync_every=2)
        if lane is None:
            lane = sum(tr.stats.lane_widths)
        rows = int(plan.predicted_send_volume.sum())
        bwd = 2 if hd == "bfloat16" else 4
        sync = tr._step_cost_model(sync_step=True)
        stale = tr._step_cost_model(sync_step=False)
        # sync: f32 re-base fwd + halo_dtype bwd; stale: bf16 fwd
        assert sync.halo_bytes_true_per_step == rows * lane * (4 + bwd)
        assert stale.halo_bytes_true_per_step == rows * lane * (2 + bwd)
        # CommStats books the same figures step by step
        tr.stats.count_step(nlayers=2, hidden=False, wire_itemsize=4)
        assert tr.stats.halo_bytes_true_total == rows * lane * (4 + bwd)
        tr.stats.count_step(nlayers=2, hidden=True)
        assert tr.stats.halo_bytes_true_total == \
            rows * lane * (4 + bwd) + rows * lane * (2 + bwd)


def test_auto_under_staleness_uses_wire_rule(cora):
    """'auto' + staleness switches to the wire-byte-only rule: the hidden
    exchange takes the k−1 ring dispatches off the critical path, so
    ragged wins whenever it ships fewer wire rows (which the k−1 < k round
    structure guarantees on any supported plan) — and the decision log
    names the rule."""
    from sgcn_tpu.parallel.plan import resolve_comm_schedule

    plan, feats, _ = cora
    dec = {}
    got = resolve_comm_schedule("auto", [plan], "gcn", halo_staleness=1,
                                decision=dec)
    assert got == "ragged"
    assert "wire-byte rule" in dec["rule"]
    assert dec["wire_rows_ragged"] < dec["wire_rows_a2a"]
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS,
                          halo_staleness=1, comm_schedule="auto")
    assert tr.comm_schedule == "ragged"
    assert tr.halo_staleness == 1


def test_composed_gating(cora):
    """The REAL remaining unsupported combos still fail loudly — the
    staleness gates (GAT, asymmetric, bf16/remat) apply under the ragged
    schedule exactly as under the dense one."""
    import dataclasses

    plan, feats, _ = cora
    with pytest.raises(ValueError, match="GCN hot path"):
        FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS,
                         model="gat", comm_schedule="ragged",
                         halo_staleness=1)
    with pytest.raises(ValueError, match="f32 non-remat"):
        FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS,
                         comm_schedule="ragged", halo_staleness=1,
                         compute_dtype="bfloat16")
    aplan = dataclasses.replace(plan, symmetric=False)
    with pytest.raises(ValueError, match="asymmetric"):
        FullBatchTrainer(aplan, fin=feats.shape[1], widths=WIDTHS,
                         comm_schedule="ragged", halo_staleness=1)
