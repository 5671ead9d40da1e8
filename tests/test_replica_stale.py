"""The adaptive communication controller PR (ISSUE 12): replica ×
staleness composition (``ops/pspmm.py::pspmm_replica_stale[_ragged]``),
drift-driven partial refresh (``--refresh-band``,
``pspmm_replica_partial``) and the runtime controller
(``train/controller.py``) — docs/comm_schedule.md, docs/replication.md.

Contract pinned here:

  * COMPOSED ``--replica-budget B --halo-staleness 1`` trains under BOTH
    transports, f32-BIT-identical to the exact no-replica path at
    ``--sync-every 1`` (losses AND parameters ``==``) — the sync program
    is exactly the stale mode's full-sync program;
  * the composed carry is the STALE carry (no replica_carry exists — the
    halo carry subsumes the replica tables), stale steps are booked
    hidden AND replica (shrunken wire) with the exposed/hidden wire-row
    split reconciling, and the fused ``run_epochs`` reproduces per-step
    ``step()``;
  * PARTIAL refresh ships only drifted rows, booked at the ACTUAL
    shipped counts with exact CommStats ↔ step-event ↔ roofline
    reconciliation; band semantics (0 → every drifted row, huge → none);
  * the controller's band-crossing ``sync_every`` retune is
    DETERMINISTIC in the injected gauge sequence, and the trainer applies
    + logs its decisions into the manifest ``comm_schedule`` block;
  * ``--replica-budget auto`` resolves at the λ·degree knee with the
    scoring inputs in the decision log;
  * MUTATION checks: the new composed audit-matrix modes fail the
    wire-shape rule on a seeded full-width stale-step exchange (both
    transports) — the shrunken-wire contract is not vacuous.
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
    """Exact no-replica no-staleness reference: 4 losses + trained
    parameters, shared by both transports' bit-identity assertions."""
    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=3,
                          **COO_EXACT)
    d = make_train_data(plan, feats, labels)
    losses = [tr.step(d) for _ in range(4)]
    return losses, [np.asarray(w) for w in tr.params]


# ------------------------------------------------------- composed mode
@pytest.mark.parametrize("schedule", ["a2a", "ragged"])
def test_composed_sync1_bit_identical_to_exact(cora, exact_run, schedule):
    """THE acceptance contract: ``--replica-budget B --halo-staleness 1
    --sync-every 1`` trains cora with losses and parameters exactly equal
    to the exact path's under both transports — every step runs the
    full-sync program, which is ``pspmm_stale``'s sync program verbatim."""
    plan, feats, labels = cora
    exact_losses, exact_params = exact_run
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=3,
                          comm_schedule=schedule, halo_staleness=1,
                          replica_budget=BUDGET, sync_every=1)
    assert tr.replica_budget == BUDGET
    assert not hasattr(tr, "replica_carry")     # the stale carry subsumes it
    d = make_train_data(plan, feats, labels)
    lc = [tr.step(d) for _ in range(4)]
    assert lc == exact_losses                   # bitwise, not allclose
    for wa, wb in zip(exact_params, tr.params):
        np.testing.assert_array_equal(wa, np.asarray(wb))


def test_composed_run_epochs_parity_and_booking(cora):
    """The fused multi-step path reproduces per-step ``step()`` exactly,
    and the booking marks stale steps hidden AND replica-shrunken with
    the subset-priced splits reconciling."""
    plan, feats, labels = cora
    d = make_train_data(plan, feats, labels)
    kw = dict(fin=feats.shape[1], widths=WIDTHS, seed=5,
              comm_schedule="ragged", halo_staleness=1,
              replica_budget=BUDGET, sync_every=3)
    ta = FullBatchTrainer(plan, **kw)
    la = [ta.step(d) for _ in range(5)]
    tb = FullBatchTrainer(plan, **kw)
    lb = tb.run_epochs(d, 5)
    np.testing.assert_array_equal(np.asarray(la, np.float32),
                                  np.asarray(lb, np.float32))
    for wa, wb in zip(ta.params, tb.params):
        np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
    ra, rb = ta.stats.report(), tb.stats.report()
    assert ra == rb
    nl = len(WIDTHS)
    # steps 0 and 3 sync; 1, 2, 4 are stale+shrunken: hidden AND replica
    assert ra["hidden_exchanges"] == 2 * nl * 3
    assert ra["replica_exchanges"] == 2 * nl * 3
    assert ra["hidden_replica_exchanges"] == 2 * nl * 3
    assert (ra["exposed_send_volume"] + ra["hidden_send_volume"]
            == ra["total_send_volume"])
    assert (ra["exposed_wire_rows_total"] + ra["hidden_wire_rows_total"]
            == ra["wire_rows_total"])
    # hidden exchanges rode the SHRUNKEN ring; exposed ones the full ring
    full = plan.wire_rows_per_exchange("ragged")
    shrunk = plan.wire_rows_per_exchange("ragged", replica=True)
    assert shrunk < full
    assert ra["hidden_wire_rows_total"] == shrunk * 2 * nl * 3
    assert ra["exposed_wire_rows_total"] == full * 2 * nl * 2


def test_composed_carry_is_stale_shaped(cora):
    """The composed trainer's carry IS the stale carry — ring-envelope
    halos under ragged, dense (R, f) under a2a, and the ragged-composed
    plan ships the carry scatter map ``nrep_ring_dst`` whose kept
    positions cover exactly the non-replica receive slots."""
    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS,
                          comm_schedule="ragged", halo_staleness=1,
                          replica_budget=BUDGET, sync_every=2)
    shapes = plan.stale_carry_shapes(feats.shape[1], WIDTHS,
                                     comm_schedule="ragged")
    st = sum(plan.rr_sizes)
    assert [tuple(h.shape[1:]) for h in tr.halo_carry["halos"]] \
        == shapes["halos"] == [(st, f) for _, f in shapes["halos"]]
    # nrep_ring_dst: every non-pad entry is a valid full-ring position,
    # and the number of pad entries matches the shrunken ring's padding
    nr = np.asarray(plan.nrep_ring_dst)
    valid = nr < st
    assert int(valid.sum()) == int(plan.nrep_send_counts.sum())
    # kept positions are exactly the full-ring positions NOT replicated:
    # together with rep_ring_pos they cover each chip's receive set
    for q in range(plan.k):
        kept = set(nr[q][nr[q] < st].tolist())
        reps = set(np.asarray(plan.rep_ring_pos)[q][
            : int(plan.rep_counts[q])].tolist())
        assert not (kept & reps)


def test_composed_gating(cora):
    """Construction-time gates of the new compositions."""
    plan, feats, labels = cora
    fin = feats.shape[1]
    with pytest.raises(ValueError, match="deferred"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS, halo_staleness=1,
                         halo_delta=True, replica_budget=8)
    with pytest.raises(ValueError, match="refresh_band"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS, refresh_band=0.1)
    with pytest.raises(ValueError, match="deferred"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS, halo_staleness=1,
                         replica_budget=8, sync_every=2, refresh_band=0.1)
    with pytest.raises(ValueError, match="a2a"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS,
                         comm_schedule="ragged", replica_budget=8,
                         sync_every=2, refresh_band=0.1)
    with pytest.raises(ValueError, match="refresh_band must be >= 0"):
        FullBatchTrainer(plan, fin=fin, widths=WIDTHS, replica_budget=8,
                         sync_every=2, refresh_band=-0.5)


# ----------------------------------------------------- partial refresh
def test_partial_refresh_accounting(cora, tmp_path):
    """``--refresh-band 0``: every drifted replica row refreshes; the
    per-step event counts, the CommStats cumulative booking and the
    roofline byte figures reconcile EXACTLY at the actual shipped rows,
    and strictly fewer rows ship than a full refresh would."""
    from sgcn_tpu.obs import RunRecorder, load_run

    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=0,
                          replica_budget=BUDGET, sync_every=2,
                          refresh_band=0.0)
    d = make_train_data(plan, feats, labels)
    rec = RunRecorder(str(tmp_path / "run"), config={"band": 0.0})
    tr.attach_recorder(rec)
    for _ in range(6):
        tr.step(d)
    rec.close()
    log = load_run(str(tmp_path / "run"))          # schema re-validated
    steps = [e for e in log.events if e["kind"] == "step"]
    blocks = [s["replica"] for s in steps]
    # step 0: full (initializing); steps 2, 4: partial; 1, 3, 5: replica
    assert blocks[0].get("refresh_kind") == "full"
    partials = [b for b in blocks if b.get("refresh_kind") == "partial"]
    assert len(partials) == 2
    shipped = [sum(b["refresh_rows"]) for b in partials]
    saving = plan.replica_send_saving            # full refresh = Σλ rows
    assert all(0 < s <= saving for s in shipped), (shipped, saving)
    # exact booking at the actual rows, fwd + bwd
    rep = tr.stats.report()
    assert rep["partial_refresh_steps"] == 2
    assert rep["partial_refresh_rows_total"] == 2 * sum(shipped)
    assert rep["partial_refresh_wire_rows_total"] == (
        2 * len(WIDTHS) * 2 * plan.partial_refresh_wire_rows)
    # roofline ↔ CommStats byte reconciliation, partial steps included
    assert rep["halo_bytes_true_total"] == sum(
        s["roofline"]["halo_bytes_true_per_step"] for s in steps)
    assert rep["halo_bytes_wire_total"] == sum(
        s["roofline"]["halo_bytes_wire_per_step"] for s in steps)
    # the wire totals carry the side channel on top of the base exchanges
    base = (plan.wire_rows_per_exchange("a2a") * 2 * len(WIDTHS) * 1
            + plan.wire_rows_per_exchange("a2a", replica=True)
            * 2 * len(WIDTHS) * 5)
    assert rep["wire_rows_total"] == base + rep[
        "partial_refresh_wire_rows_total"]
    # rendered report carries the partial-refresh line
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(FIX), "..",
                                   "scripts", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "partial refreshes: 2" in mod.render(str(tmp_path / "run"))


def test_partial_refresh_strictly_fewer_rows_on_hp(cora):
    """THE acceptance figure on the skewed-hp fixture: with a meaningful
    band, partial refreshes ship STRICTLY fewer rows than the full
    refreshes would re-ship for the replica set (and more than zero —
    the band is doing selection, not disabling refresh)."""
    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=0,
                          replica_budget=BUDGET, sync_every=2,
                          refresh_band=0.5)
    d = make_train_data(plan, feats, labels)
    for _ in range(6):
        tr.step(d)
    rep = tr.stats.report()
    full_rows = (2 * plan.replica_send_saving
                 * rep["partial_refresh_steps"])   # fwd+bwd per refresh
    assert 0 < rep["partial_refresh_rows_total"] < full_rows


def test_partial_refresh_band_semantics(cora):
    """A band above any possible drift ships ZERO rows (the replica
    tables keep their step-0 values) and the run stays finite; the
    booked count says so."""
    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=0,
                          replica_budget=BUDGET, sync_every=2,
                          refresh_band=1e12)
    d = make_train_data(plan, feats, labels)
    reps0 = None
    losses = []
    for i in range(5):
        losses.append(tr.step(d))
        if i == 0:
            reps0 = [np.asarray(r) for r in tr.replica_carry["reps"]]
    assert np.all(np.isfinite(losses))
    rep = tr.stats.report()
    assert rep["partial_refresh_steps"] == 2
    assert rep["partial_refresh_rows_total"] == 0
    for a, b in zip(reps0, tr.replica_carry["reps"]):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_partial_refresh_bf16_lockstep(cora):
    """Sender/receiver lockstep under the narrow wire: with ``--halo-dtype
    bfloat16`` the full-refresh baseline anchors at the WIRE-QUANTIZED
    value (what consumers actually received), so after any sequence of
    partial refreshes every consumer's replica row equals the owner's
    baseline row BIT-FOR-BIT — the quantization error must not become
    permanent sender/receiver disagreement."""
    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=0,
                          replica_budget=BUDGET, sync_every=2,
                          refresh_band=0.0, halo_dtype="bfloat16")
    d = make_train_data(plan, feats, labels)
    for _ in range(5):
        tr.step(d)
    reps = [np.asarray(r) for r in tr.replica_carry["reps"]]
    bases = [np.asarray(b) for b in tr.replica_carry["rep_base"]]
    s = plan.s
    for q in range(plan.k):
        for i in range(int(plan.rep_counts[q])):
            rank = int(plan.rep_slots[q, i])
            slot = int(plan.halo_src[q, rank])
            o, j = slot // s, slot % s
            row = int(plan.send_idx[o, q, j])
            pos = int(np.searchsorted(
                plan.rep_rows[o, : int(plan.rep_row_counts[o])], row))
            for layer in range(len(WIDTHS)):
                np.testing.assert_array_equal(reps[layer][q, i],
                                              bases[layer][o, pos])


# ---------------------------------------------------------- controller
def test_controller_band_crossing_determinism():
    """The retune rule is a pure function of the injected gauge sequence:
    above-band halves (floored), below-band doubles (capped), inside-band
    holds; identical inputs give identical decision logs."""
    from sgcn_tpu.train.controller import CommController

    drifts = [0.1, 0.9, 0.9, 0.01, 0.001, 0.2, 0.0, 0.0, 0.0]

    def run():
        c = CommController(sync_every=8, upper=0.5, lower=0.02,
                           min_sync=2, max_sync=16)
        return [c.observe(i, x) for i, x in enumerate(drifts)], c

    seq, c = run()
    #        hold halve halve dbl  dbl  hold dbl  dbl(cap) cap
    assert seq == [8, 4, 2, 4, 8, 8, 16, 16, 16]
    assert c.sync_every == 16 and c.initial_sync_every == 8
    rules = [d["rule"] for d in c.decisions]
    assert rules == ["drift above band", "drift above band",
                     "drift below band", "drift below band",
                     "drift below band"]
    seq2, c2 = run()
    assert seq2 == seq and c2.decisions == c.decisions
    # floor clamp: repeated above-band never goes below min_sync
    c3 = CommController(sync_every=4, min_sync=2)
    for i in range(4):
        c3.observe(i, 1e9)
    assert c3.sync_every == 2
    with pytest.raises(ValueError, match="sync_every"):
        CommController(sync_every=0)
    with pytest.raises(ValueError, match="lower < upper"):
        CommController(sync_every=4, lower=0.9, upper=0.5)


def test_controller_retunes_trainer_and_logs_manifest(cora, tmp_path):
    """``--comm-schedule auto`` + a sync schedule activates the
    controller; with the band forced below the measured drift the trainer
    WIDENS its effective sync_every mid-run and the decisions land in the
    run manifest's ``comm_schedule.controller`` block (rendered by
    obs_report)."""
    from sgcn_tpu.obs import RunRecorder, load_run

    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS, seed=0,
                          comm_schedule="auto", halo_staleness=1,
                          replica_budget=BUDGET, sync_every=2)
    assert tr.controller is not None
    assert tr.comm_decision["controller"]["retunes"] == []
    # force every observed drift below the band -> widen on each sync
    tr.controller.lower = 1e30
    tr.controller.upper = 1e31
    d = make_train_data(plan, feats, labels)
    rec = RunRecorder(str(tmp_path / "run"), config={})
    tr.attach_recorder(rec)
    for _ in range(7):
        tr.step(d)
    rec.close()
    assert tr.sync_every > 2
    # the controller's acceptance figure, as a count over the same seven
    # steps: its exposed wire rows lie at or below every static setting's
    # and strictly below the exact arms' — it widens the sync cadence from
    # 2, so it syncs no more often than the static stale arms (steps 0, 2,
    # 4, 6) and ships the full ring of its own schedule when it does
    rep = tr.stats.report()
    nl = len(WIDTHS)
    wire = {s: plan.wire_rows_per_exchange(s) for s in ("a2a", "ragged")}
    assert rep["exposed_wire_rows_total"] == \
        rep["exposed_exchanges"] * wire[tr.comm_schedule]
    assert rep["exposed_exchanges"] <= 4 * 2 * nl
    statics = {"a2a_exact": 7 * 2 * nl * wire["a2a"],
               "ragged_exact": 7 * 2 * nl * wire["ragged"],
               "ragged_stale": 4 * 2 * nl * wire["ragged"],
               "replica_stale": 4 * 2 * nl * wire["ragged"]}
    assert all(rep["exposed_wire_rows_total"] <= v for v in statics.values())
    assert rep["exposed_wire_rows_total"] < statics["ragged_exact"]
    ctl = tr.comm_decision["controller"]
    assert ctl["retunes"] and ctl["retunes"][0]["rule"] == "drift below band"
    m = load_run(str(tmp_path / "run")).manifest
    assert m["comm_schedule"]["controller"]["retunes"] == ctl["retunes"]
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(FIX), "..",
                                   "scripts", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    text = mod.render(str(tmp_path / "run"))
    assert "controller (drift-banded sync_every retune)" in text
    assert "drift below band" in text


def test_controller_inactive_without_auto_or_schedule(cora):
    """An explicit transport keeps the controller off (static settings
    stay static), as does a missing sync schedule under 'auto'."""
    plan, feats, labels = cora
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS,
                          comm_schedule="ragged", halo_staleness=1,
                          sync_every=2)
    assert tr.controller is None
    tr2 = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS,
                           comm_schedule="auto")
    assert tr2.controller is None


def test_replica_auto_budget_and_decision_log(cora):
    """``--replica-budget auto`` resolves at the λ·degree knee (B > 0 on
    the skewed cora boundary), deterministically, with the scoring inputs
    and the replica-aware wire figures in the decision log."""
    from sgcn_tpu.parallel.plan import choose_replica_budget

    plan, feats, labels = cora
    knee = {}
    b1 = choose_replica_budget(plan, decision=knee)
    assert b1 == choose_replica_budget(plan)     # deterministic
    assert 0 < b1 <= knee["boundary_rows"]
    assert 0 < knee["score_covered"] <= 1
    tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=WIDTHS,
                          comm_schedule="auto", replica_budget="auto",
                          sync_every=2)
    assert tr.replica_budget == b1
    dec = tr.comm_decision
    assert dec["replica_auto"]["chosen"] == b1
    assert dec["replica_budget"] == b1
    # replica-aware scoring: the shrunken wire figures are logged and can
    # only be <= the full ones
    assert dec["wire_rows_a2a_replica"] <= dec["wire_rows_a2a"]
    assert dec["wire_rows_ragged_replica"] <= dec["wire_rows_ragged"]
    assert dec["true_rows_replica"] < dec["true_rows"]
    d = make_train_data(plan, feats, labels)
    assert np.isfinite(tr.step(d))


# ------------------------------------------------------ mutation checks
def _audit_composed(schedule):
    from sgcn_tpu.analysis.hlo_audit import audit_mode
    from sgcn_tpu.analysis.modes import Mode

    return audit_mode(Mode("train", "gcn", schedule, staleness=1,
                           replica=True))


def test_mutation_composed_full_width_stale_a2a(monkeypatch):
    """Seeded violation for the composed a2a mode: the stale step ships
    the FULL exchange instead of the shrunken buckets (the carry merge
    keeps the same bits at sync-every-1, so only the compiled wire shape
    betrays it) — the wire-shape rule must fail on the stale program."""
    import importlib

    pspmm = importlib.import_module("sgcn_tpu.ops.pspmm")
    real = pspmm._replica_stale_exchange

    def full_wire(x, halo_in, send_idx, halo_src, nrep_send_idx,
                  nrep_halo_src, rep_slots, axis_name, wire_dtype, fresh):
        return real(x, halo_in, send_idx, halo_src, send_idx, halo_src,
                    rep_slots, axis_name, wire_dtype, fresh)

    monkeypatch.setattr(pspmm, "_replica_stale_exchange", full_wire)
    entry = _audit_composed("a2a")
    assert not entry["programs"]["stale"]["ok"]
    assert any(v["rule"] == "wire-shape"
               for v in entry["programs"]["stale"]["violations"])
    assert entry["programs"]["sync"]["ok"]       # syncs SHOULD ship full


def test_mutation_composed_full_width_stale_ragged(monkeypatch):
    """Same seeded violation on the ring: the stale step ships the full
    per-round sizes instead of ``nrep_rr_sizes`` — wire-shape fails."""
    import importlib

    import jax.numpy as jnp

    pspmm = importlib.import_module("sgcn_tpu.ops.pspmm")
    real = pspmm._replica_stale_ring_exchange

    def full_ring(x, halo_in, rsend_idx, nrep_rsend_idx, nrep_ring_dst,
                  rr_sizes, nrep_rr_sizes, axis_name, wire_dtype, fresh):
        return real(x, halo_in, rsend_idx, rsend_idx,
                    jnp.arange(rsend_idx.shape[0],
                               dtype=nrep_ring_dst.dtype),
                    rr_sizes, rr_sizes, axis_name, wire_dtype, fresh)

    monkeypatch.setattr(pspmm, "_replica_stale_ring_exchange", full_ring)
    entry = _audit_composed("ragged")
    assert not entry["programs"]["stale"]["ok"]
    assert any(v["rule"] == "wire-shape"
               for v in entry["programs"]["stale"]["violations"])
    assert entry["programs"]["sync"]["ok"]
