"""Preemption tolerance (PR-13, docs/resilience.md): durable checkpoints,
bit-identical resume, and the fault-injection harness that proves both.

Two layers of coverage:

  * **unit** — atomic-write crash safety, fault-spec grammar, deterministic
    corruption, the stalled-vs-slow heartbeat classifier, checkpoint
    checksum/truncation detection (the clear error, not a numpy
    deep-failure), keep-last-K rotation, and the fallback ordering of
    ``CheckpointManager.load_latest``;
  * **integration** (the acceptance surface) — for every mode family
    {exact, stale, replica, replica×stale} × {a2a, ragged} on the cora
    fixture: a REAL trainer-CLI run is hard-killed by the injected fault
    right after its step-4 checkpoint commits (``os._exit``, rc 43), a new
    process resumes with ``--resume auto``, and the resumed losses AND
    final params are ``==`` (f32 bit-for-bit) the uninterrupted run's,
    with the cumulative CommStats totals reconciling across the seam.
    The corrupted-latest path is driven by the harness too
    (``corrupt-after-save``): the resume must fall back to the previous
    intact checkpoint with a logged warning and still hit bit-identity.

The CLI children use the committed cora graph fixture with the synthetic
feature harness (``-f 16``) — the graph is the real fixture, the narrow
features keep each child's compile+train cost inside the tier-1 budget
(see tests/test_collection_lint.py SUBPROCESS_BUDGET_ALLOWLIST).
"""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from sgcn_tpu.resilience import faults
from sgcn_tpu.resilience.atomic import atomic_write, atomic_write_json
from sgcn_tpu.resilience.checkpoint import CheckpointManager
from sgcn_tpu.utils.checkpoint import (
    CheckpointCorruptError, load_checkpoint, read_checkpoint_meta,
    save_checkpoint,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures")

# ---------------------------------------------------------------------------
# unit layer
# ---------------------------------------------------------------------------


def test_atomic_write_crash_leaves_original(tmp_path):
    p = str(tmp_path / "f.json")
    atomic_write_json(p, {"v": 1})
    # a writer that dies mid-block must leave the original intact and no
    # temp litter under any name
    with pytest.raises(RuntimeError, match="boom"):
        with atomic_write(p, "w") as fh:
            fh.write('{"v":')
            raise RuntimeError("boom")
    assert json.load(open(p)) == {"v": 1}
    assert os.listdir(tmp_path) == ["f.json"]
    # a completed rewrite replaces atomically
    atomic_write_json(p, {"v": 2})
    assert json.load(open(p)) == {"v": 2}
    with pytest.raises(ValueError, match="write-only"):
        with atomic_write(p, "r+"):
            pass


def test_fault_spec_grammar():
    s = faults.parse_fault("kill-after-save:4")
    assert (s.kind, s.step) == ("kill-after-save", 4)
    s = faults.parse_fault("corrupt-after-save:6:truncate")
    assert (s.step, s.mode) == (6, "truncate")
    assert faults.parse_fault("corrupt-after-save:2").mode == "bitflip"
    for bad in ("kill-after-save", "kill-after-save:x", "nope:1",
                "corrupt-after-save:2:shred", "stall:dryrun:30"):
        with pytest.raises(ValueError, match="grammar"):
            faults.parse_fault(bad)


def test_corrupt_file_deterministic(tmp_path):
    p = str(tmp_path / "blob")
    open(p, "wb").write(bytes(range(256)) * 4)
    faults.corrupt_file(p, mode="bitflip")
    data = open(p, "rb").read()
    assert len(data) == 1024
    ref = bytes(range(256)) * 4
    assert sum(a != b for a, b in zip(data, ref)) == 1   # exactly one byte
    faults.corrupt_file(p, mode="truncate")
    assert os.path.getsize(p) == int(1024 * 0.6)


# --------------------------------------------------- tiny in-process trainer
@pytest.fixture(scope="module")
def tiny():
    """One small symmetric plan + data, shared by the in-process
    checkpoint unit tests (er_graph — the subprocess layer below owns the
    cora-fixture acceptance runs)."""
    from conftest import er_graph
    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.partition import balanced_random_partition
    from sgcn_tpu.prep import normalize_adjacency
    from sgcn_tpu.train import make_train_data

    a = normalize_adjacency(er_graph(48))
    pv = balanced_random_partition(48, 4, seed=0)
    plan = build_comm_plan(a, pv, 4)
    feats = np.random.default_rng(0).standard_normal((48, 6)).astype(
        np.float32)
    labels = (np.arange(48) % 3).astype(np.int32)
    return plan, make_train_data(plan, feats, labels)


def _trainer(plan, **kw):
    from sgcn_tpu.train import FullBatchTrainer

    return FullBatchTrainer(plan, fin=6, widths=[8, 3], seed=1, **kw)


def test_corruption_raises_clear_error_not_numpy_failure(tiny, tmp_path):
    """The checksum loader's contract: a truncated or bit-flipped .npz
    fails with CheckpointCorruptError naming the damage — never a numpy/
    zipfile deep-failure leaking out of the loader."""
    plan, data = tiny
    tr = _trainer(plan, halo_staleness=1, sync_every=2)
    for _ in range(3):
        tr.step(data)
    good = save_checkpoint(tr, str(tmp_path / "ck.npz"), step=3)

    trunc = str(tmp_path / "trunc.npz")
    open(trunc, "wb").write(open(good, "rb").read())
    faults.corrupt_file(trunc, mode="truncate")
    with pytest.raises(CheckpointCorruptError,
                       match="truncated|damaged|unreadable"):
        read_checkpoint_meta(trunc)
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(_trainer(plan, halo_staleness=1, sync_every=2),
                        trunc)

    flip = str(tmp_path / "flip.npz")
    open(flip, "wb").write(open(good, "rb").read())
    faults.corrupt_file(flip, mode="bitflip")
    with pytest.raises(CheckpointCorruptError,
                       match="checksum|unreadable|corrupt"):
        load_checkpoint(_trainer(plan, halo_staleness=1, sync_every=2),
                        flip)
    # the intact file still loads cleanly after all that — and as a FULL
    # restore (the partial flag telemetry reads is false)
    tr_ok = _trainer(plan, halo_staleness=1, sync_every=2)
    assert load_checkpoint(tr_ok, good) == 3
    assert tr_ok.last_restore_partial is False

    # metadata is covered too: a tampered __step__ whose recorded CRC no
    # longer matches fails as loudly as a damaged leaf (a silent
    # wrong-step resume is exactly what the checksums exist to prevent)
    with np.load(good) as d:
        arrs = {k: d[k] for k in d.files}
    arrs["__step__"] = np.asarray(999, dtype=np.int64)
    tampered = str(tmp_path / "tampered.npz")
    np.savez(tampered, **arrs)
    with pytest.raises(CheckpointCorruptError, match="metadata|__step__"):
        read_checkpoint_meta(tampered)

    # the standalone integrity probe (no trainer needed): intact passes
    # and returns the meta block, every damage flavor raises
    from sgcn_tpu.utils.checkpoint import verify_checkpoint_file
    assert verify_checkpoint_file(good)["step"] == 3
    for bad in (trunc, flip, tampered):
        with pytest.raises(CheckpointCorruptError):
            verify_checkpoint_file(bad)


def test_rotation_and_fallback_ordering(tiny, tmp_path):
    """keep-last-K rotation; load_latest walks newest-first, falls back
    past corrupt files with a warning, raises only when NOTHING is
    intact."""
    plan, data = tiny
    mgr = CheckpointManager(str(tmp_path / "ck"), keep_last=2)
    tr = _trainer(plan, halo_staleness=1, sync_every=2)
    for i in range(1, 7):
        tr.step(data)
        if i % 2 == 0:
            mgr.save(tr, step=i)
    assert [s for s, _ in mgr.checkpoints()] == [4, 6]   # 2 rotated away

    faults.corrupt_file(mgr.path_for(6), mode="bitflip")
    tr2 = _trainer(plan, halo_staleness=1, sync_every=2)
    with pytest.warns(RuntimeWarning, match="corrupt"):
        step, path, skipped = mgr.load_latest(tr2)
    assert step == 4 and path.endswith("ckpt_00000004.npz")
    assert [os.path.basename(s) for s in skipped] == ["ckpt_00000006.npz"]

    faults.corrupt_file(mgr.path_for(4), mode="truncate")
    with pytest.raises(CheckpointCorruptError, match="all 2 checkpoint"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            mgr.load_latest(_trainer(plan, halo_staleness=1, sync_every=2))

    with pytest.raises(FileNotFoundError, match="nothing to resume"):
        CheckpointManager(str(tmp_path / "empty")).load_latest(tr2)
    with pytest.raises(ValueError, match="keep_last"):
        CheckpointManager(str(tmp_path / "x"), keep_last=0)


def test_rotation_never_deletes_the_fresh_save(tiny, tmp_path):
    """A reused directory holding HIGHER-stamped checkpoints from a
    previous run must not make step-ordered rotation delete the file this
    run just wrote — and the shadowing hazard is warned about loudly."""
    plan, data = tiny
    mgr = CheckpointManager(str(tmp_path / "ck"), keep_last=3)
    tr = _trainer(plan)
    tr.step(data)
    for s in (10, 15, 20):              # stale files from a "previous run"
        mgr.save(tr, step=s)
    tr2 = _trainer(plan)
    tr2.step(data)
    with pytest.warns(RuntimeWarning, match="PAST this run"):
        path = mgr.save(tr2, step=5)
    assert os.path.exists(path)          # the fresh save survived rotation
    assert 5 in [s for s, _ in mgr.checkpoints()]


def test_manager_sweeps_stale_temp_litter(tiny, tmp_path):
    """A kill mid-save strands an atomic-write temp file; the FIRST save
    of a new run sweeps it (save(), not __init__: every rank constructs a
    manager, only the coordinator writes — a non-writer rank sweeping a
    shared filesystem could unlink a live coordinator's in-flight temp),
    so repeated preemptions cannot grow the directory past the
    keep-last-K disk bound."""
    plan, data = tiny
    d = tmp_path / "ck"
    d.mkdir()
    stray = d / "ckpt_00000004.npz.tmp.12345"
    stray.write_bytes(b"half-written")
    keepme = d / "unrelated.txt"
    keepme.write_text("not ours")
    mgr = CheckpointManager(str(d))
    assert stray.exists()               # construction alone must NOT sweep
    tr = _trainer(plan)
    tr.step(data)
    mgr.save(tr, step=1)
    assert not stray.exists()
    assert keepme.exists()


def test_partial_state_and_mode_mismatch_warn_loudly(tiny, tmp_path):
    """Old (v1) checkpoints load params-only with the loud PARTIAL STATE
    warning; a carry-mode mismatch between file and trainer is named, not
    silently dropped."""
    import jax

    plan, data = tiny
    tr = _trainer(plan, halo_staleness=1, sync_every=2)
    for _ in range(2):
        tr.step(data)
    # v1-format file: leaves + step only (what pre-PR-13 writers produced)
    leaves = jax.tree.leaves((tr.params, tr.opt_state))
    old = {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}
    old["__step__"] = np.asarray(2, dtype=np.int64)
    oldpath = str(tmp_path / "old.npz")
    np.savez(oldpath, **old)
    with pytest.warns(RuntimeWarning, match="PARTIAL STATE"):
        assert load_checkpoint(
            _trainer(plan, halo_staleness=1, sync_every=2), oldpath) == 2
    # stale-mode checkpoint into an exact trainer: carry ignored, loudly
    ck = save_checkpoint(tr, str(tmp_path / "stale.npz"), step=2)
    with pytest.warns(RuntimeWarning, match="IGNORED"):
        assert load_checkpoint(_trainer(plan), ck) == 2
    meta = read_checkpoint_meta(ck)
    assert meta["version"] >= 2 and meta["n_carry"] > 0
    assert meta["state"]["carry"] == "halo_carry"


def test_controller_state_survives_resume(tiny, tmp_path):
    """The PR-12 controller's mid-run retune is algorithmic state: the
    EFFECTIVE sync_every and the retune log must cross the seam."""
    plan, data = tiny
    tr = _trainer(plan, halo_staleness=1, sync_every=4,
                  auto_tune_sync=True)
    assert tr.controller is not None
    for _ in range(2):
        tr.step(data)
    # inject a retune as the drift band would
    tr.sync_every = tr.controller.observe(2, 0.001)   # below band: widen
    assert tr.sync_every == 8 and len(tr.controller.decisions) == 1
    ck = save_checkpoint(tr, str(tmp_path / "ctl.npz"), step=2)
    tr2 = _trainer(plan, halo_staleness=1, sync_every=4,
                   auto_tune_sync=True)
    load_checkpoint(tr2, ck)
    assert tr2.sync_every == 8
    assert tr2.controller.sync_every == 8
    assert tr2.controller.decisions == tr.controller.decisions
    assert tr2.comm_decision["controller"]["retunes"]


def test_obs_checkpoint_resume_events_render(tiny, tmp_path):
    """run_resumable emits schema-v4 checkpoint events under a recorder;
    resume events land via record_resume; obs_report renders both."""
    from sgcn_tpu.obs import RunRecorder, load_run
    from sgcn_tpu.resilience.runner import run_resumable

    plan, data = tiny
    d = str(tmp_path / "run")
    mgr = CheckpointManager(str(tmp_path / "ck"))
    tr = _trainer(plan)
    rec = RunRecorder(d, config={}, run_kind="train")
    tr.attach_recorder(rec)
    report = run_resumable(tr, data, 4, manager=mgr, checkpoint_every=2,
                           verbose=False)
    rec.record_resume(step=2, path=mgr.path_for(2), fallback=True,
                      skipped=[mgr.path_for(4)])
    rec.close()
    assert len(report["losses"]) == 4
    log = load_run(d)                    # re-validates every event
    assert len(log.checkpoints()) == 2
    assert log.checkpoints()[0]["step"] == 2
    assert log.resumes()[0]["fallback"] is True
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"), d],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "resilience:" in r.stdout and "FELL BACK" in r.stdout
    assert "last checkpoint: step 4" in r.stdout


# ---------------------------------------------------------------------------
# integration layer: the fault-injection harness on the cora fixture
# ---------------------------------------------------------------------------

# the acceptance matrix: {exact, stale, replica, replica×stale} × {a2a,
# ragged}.  sync_every=2 keeps a sync/refresh step INSIDE the resumed
# stretch, so the restored schedule counters are actually load-bearing.
MODES = {
    "exact-a2a": [],
    "exact-ragged": ["--comm-schedule", "ragged"],
    "stale-a2a": ["--halo-staleness", "1", "--sync-every", "2"],
    "stale-ragged": ["--halo-staleness", "1", "--sync-every", "2",
                     "--comm-schedule", "ragged"],
    "replica-a2a": ["--replica-budget", "8", "--sync-every", "2"],
    "replica-ragged": ["--replica-budget", "8", "--sync-every", "2",
                       "--comm-schedule", "ragged"],
    "repstale-a2a": ["--replica-budget", "8", "--halo-staleness", "1",
                     "--sync-every", "2"],
    "repstale-ragged": ["--replica-budget", "8", "--halo-staleness", "1",
                        "--sync-every", "2", "--comm-schedule", "ragged"],
}
TOTAL_STEPS = 6          # --warmup 0 --epochs 6
KILL_STEP = 4            # fault fires after the step-4 save commits


def _run_cli(mode_flags, ckdir, extra=(), env_extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)      # let -b cpu set its own device count
    env["PYTHONPATH"] = REPO
    env.pop(faults.FAULT_ENV, None)
    if env_extra:
        env.update(env_extra)
    cmd = [sys.executable, "-m", "sgcn_tpu.train",
           "-a", os.path.join(FIX, "cora_like.A.mtx"),
           "-p", os.path.join(FIX, "cora_like.4.hp"),
           "-b", "cpu", "-s", "4", "-l", "2", "-f", "16",
           "--warmup", "0", "--epochs", str(TOTAL_STEPS),
           "--checkpoint-dir", str(ckdir), "--checkpoint-every", "2",
           *mode_flags, *extra]
    return subprocess.run(cmd, capture_output=True, text=True, cwd=REPO,
                          env=env, timeout=420)


def _leaves(path):
    with np.load(path) as d:
        n = sum(1 for f in d.files if f.startswith("leaf_"))
        return [d[f"leaf_{i}"] for i in range(n)]


def _assert_crash_resume_parity(mode, tmp_path, fault, expect_resume_step,
                                expect_fallback):
    flags = MODES[mode]
    # uninterrupted baseline (own checkpoint dir; identical schedule)
    r = _run_cli(flags, tmp_path / "a",
                 extra=["--save-checkpoint", str(tmp_path / "final_a.npz")])
    assert r.returncode == 0, r.stderr[-3000:]
    base = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(base["losses"]) == TOTAL_STEPS

    # kill a REAL run mid-flight via the injected fault (hard os._exit
    # right after the step-KILL_STEP checkpoint commits)
    r = _run_cli(flags, tmp_path / "b",
                 env_extra={faults.FAULT_ENV: fault})
    assert r.returncode == faults.FAULT_EXIT_CODE, (
        f"fault did not fire (rc={r.returncode}):\n{r.stderr[-2000:]}")

    # new process, --resume auto: completes the remainder of the schedule
    r = _run_cli(flags, tmp_path / "b",
                 extra=["--resume", "auto",
                        "--save-checkpoint", str(tmp_path / "final_b.npz")])
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["resumed"]["step"] == expect_resume_step
    assert res["resumed"]["fallback"] is expect_fallback

    # THE contract: losses == (f32 bit-for-bit via exact float repr) and
    # final params ==, with comm totals reconciling across the seam
    assert res["losses"] == base["losses"][expect_resume_step:], (
        f"{mode}: resumed losses diverge from the uninterrupted tail")
    fa = _leaves(str(tmp_path / "final_a.npz"))
    fb = _leaves(str(tmp_path / "final_b.npz"))
    assert len(fa) == len(fb)
    for i, (x, y) in enumerate(zip(fa, fb)):
        assert x.dtype == y.dtype and (x == y).all(), (
            f"{mode}: param leaf {i} not bit-identical after resume")
    for key in ("exchanges", "hidden_exchanges", "total_send_volume",
                "wire_rows_total", "exposed_send_volume",
                "hidden_send_volume"):
        assert base[key] == res[key], (
            f"{mode}: cumulative {key} does not reconcile across the "
            f"seam ({base[key]} vs {res[key]})")
    return r


@pytest.mark.parametrize("mode", list(MODES))
def test_crash_resume_bit_identity(mode, tmp_path):
    """Kill-at-step + resume == uninterrupted, per mode family × transport
    (the PR-13 acceptance matrix), driven end to end by the fault
    harness."""
    _assert_crash_resume_parity(
        mode, tmp_path, fault=f"kill-after-save:{KILL_STEP}",
        expect_resume_step=KILL_STEP, expect_fallback=False)


def test_minibatch_durable_resume(tmp_path):
    """The mini-batch flavor of the durable path: checkpoint-every counts
    EPOCHS (saved through the inner trainer), kill-after-save fires at the
    epoch-2 save, and --resume auto completes the remaining epochs without
    repeating the warm-up (durability + resumability, no bit-identity
    claim — docs/resilience.md)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO

    def run(extra, fault=None):
        e = dict(env)
        e.pop(faults.FAULT_ENV, None)
        if fault:
            e[faults.FAULT_ENV] = fault
        return subprocess.run(
            [sys.executable, "-m", "sgcn_tpu.train",
             "-a", os.path.join(FIX, "cora_like.A.mtx"),
             "-p", os.path.join(FIX, "cora_like.4.hp"),
             "-b", "cpu", "-s", "4", "-l", "2", "-f", "16", "-n", "200",
             "--warmup", "1", "--epochs", "4",
             "--checkpoint-dir", str(tmp_path / "ck"),
             "--checkpoint-every", "2", *extra],
            capture_output=True, text=True, cwd=REPO, env=e, timeout=420)

    r = run([], fault="kill-after-save:2")
    assert r.returncode == faults.FAULT_EXIT_CODE, r.stderr[-2000:]
    assert [os.path.basename(p) for _, p in
            CheckpointManager(str(tmp_path / "ck")).checkpoints()] \
        == ["ckpt_00000002.npz"]
    r = run(["--resume", "auto"])
    assert r.returncode == 0, r.stderr[-3000:]
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["resumed"]["step"] == 2
    assert rep["epochs"] == 4 and rep["start_epoch"] == 2


def test_corrupted_latest_falls_back_and_stays_bit_identical(tmp_path):
    """The corrupt-after-save fault damages the step-4 checkpoint and THEN
    kills: --resume auto must detect the corruption, warn, fall back to
    the intact step-2 checkpoint, and STILL reach bit-identity — proven by
    the harness, not hand-staged files."""
    r = _assert_crash_resume_parity(
        "stale-a2a", tmp_path,
        fault=f"corrupt-after-save:{KILL_STEP}:bitflip",
        expect_resume_step=KILL_STEP - 2, expect_fallback=True)
    assert "corrupt" in r.stderr and "falling back" in r.stderr