"""Trainer-CLI telemetry smoke on the cora fixture (tier-1).

One child run covers the whole acceptance surface of the run-telemetry
subsystem: ``--profile DIR`` (profiler trace directory created, non-empty)
plus ``--metrics-out DIR`` (manifest + per-step JSONL) in stale-halo mode,
so the events must carry

  * comm fields that EXACTLY reconcile with the final ``CommStats.report()``
    line the CLI prints (hidden + exposed == total, volumes included);
  * roofline utilization populated from the analytic cost model;
  * drift-gauge fields, present and finite, with the full-sync schedule
    visible in ``sync_step``/``staleness_age``;
  * the measured-time layer (PR-7): span events for every step/epoch
    phase, a per-step ``measured_vs_model`` block whose measured
    phase-time total reconciles with ``PhaseTimer.report()`` to <1%, and
    a manifest ``profile`` block pointing at a parseable profiler trace;

and ``scripts/obs_report.py`` must render the directory.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures")


@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory):
    """ONE CLI child shared by every assertion below (the child pays the
    jax-import + compile cost once; tier-1 budget discipline)."""
    d = tmp_path_factory.mktemp("obs")
    prof, metrics = str(d / "prof"), str(d / "run")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # let -b cpu set its own device count
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [sys.executable, "-m", "sgcn_tpu.train",
         "--npz", os.path.join(FIX, "cora_like.npz"),
         "-p", os.path.join(FIX, "cora_like.4.hp"),
         "-b", "cpu", "-s", "4", "-l", "2", "--normalize",
         "--epochs", "3", "--warmup", "1",
         "--halo-staleness", "1", "--sync-every", "2",
         "--profile", prof, "--metrics-out", metrics],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    return prof, metrics, report


def test_profile_trace_written(telemetry_run):
    prof, _, _ = telemetry_run
    traces = []
    for root, _dirs, files in os.walk(prof):
        traces += [f for f in files
                   if f.endswith((".xplane.pb", ".trace.json.gz"))]
    assert traces, f"no profiler trace files under {prof}"


def test_manifest_and_events_validate(telemetry_run):
    _, metrics, _ = telemetry_run
    from sgcn_tpu.obs import load_run
    log = load_run(metrics)             # load_run re-validates every record
    m = log.manifest
    assert m["run_kind"] == "train"
    assert m["plan"]["k"] == 4 and m["plan"]["symmetric"] is True
    assert len(m["plan"]["digest"]) == 16
    assert m["partitioner"]["partvec"].endswith("cora_like.4.hp")
    assert m["backend"]["device_count"] == 4
    assert len(log.steps()) == 4        # 1 warmup + 3 timed epochs
    assert len(log.summaries()) == 1


def test_step_comm_reconciles_with_commstats_report(telemetry_run):
    """hidden + exposed == total, and the LAST step's cumulative snapshot
    equals the end-of-run CommStats.report() line the CLI printed."""
    _, metrics, report = telemetry_run
    from sgcn_tpu.obs import load_run
    steps = load_run(metrics).steps()
    for ev in steps:
        c = ev["comm"]
        assert (c["exposed_exchanges"] + c["hidden_exchanges"]
                == c["exchanges"])
        assert (c["exposed_send_volume"] + c["hidden_send_volume"]
                == c["total_send_volume"])
    last = steps[-1]["comm"]
    for key in ("exchanges", "exposed_exchanges", "hidden_exchanges",
                "total_send_volume", "exposed_send_volume",
                "hidden_send_volume", "max_send_volume", "total_send_msgs"):
        assert last[key] == report[key], (key, last[key], report[key])


def test_roofline_populated_from_cost_model(telemetry_run):
    _, metrics, _ = telemetry_run
    from sgcn_tpu.obs import load_run
    steps = load_run(metrics).steps()
    for ev in steps:
        r = ev["roofline"]
        assert r["gather_GB"] > 0
        assert r["achieved_gather_GBs"] > 0
        # a CPU run: no stream ceiling was ever stated for this device
        assert "stream_ceiling_frac" not in r
        assert r["exposed_comm_frac"] in (0.0, 1.0)  # stale A/B per step
    # the full-sync schedule shows up as exposed steps: step 1 (carry init)
    # and every sync-every-th step
    fracs = [ev["roofline"]["exposed_comm_frac"] for ev in steps]
    assert fracs[0] == 1.0 and 0.0 in fracs


def test_drift_gauges_present_and_finite(telemetry_run):
    _, metrics, _ = telemetry_run
    from sgcn_tpu.obs import load_run
    steps = load_run(metrics).steps()
    for ev in steps:
        d = ev["drift"]
        assert isinstance(d["sync_step"], bool)
        assert d["staleness_age"] >= 0
        for fld in ("halo_drift_rms", "halo_drift_rel",
                    "halo_quant_err_rms"):
            assert len(d[fld]) == 2          # one gauge per layer
            assert np.all(np.isfinite(d[fld])), (fld, d)
    assert steps[0]["drift"]["sync_step"] is True     # carry init
    ages = [ev["drift"]["staleness_age"] for ev in steps]
    assert max(ages) <= 2                   # --sync-every 2 bounds the age


def test_span_events_thread_the_step_and_epoch_paths(telemetry_run):
    """Every optimizer step emits a nested 'step' span under its epoch's
    'train_step' span (warmup steps under 'warmup') — measured phase times
    in the SAME stream as the analytic gauges."""
    _, metrics, _ = telemetry_run
    from sgcn_tpu.obs import load_run
    log = load_run(metrics)
    spans = [e for e in log.events if e["kind"] == "span"]
    steps = [s for s in spans if s["name"] == "step"]
    assert len(steps) == 4              # 1 warmup + 3 timed epochs
    assert {s["parent"] for s in steps} == {"warmup", "train_step"}
    assert all(s["depth"] == 1 for s in steps)
    assert [s["step"] for s in steps] == [1, 2, 3, 4]
    epochs = [s for s in spans if s["name"] == "train_step"]
    assert len(epochs) == 3 and all(s["depth"] == 0 for s in epochs)
    # span durations ARE the step wall times the step events carry
    walls = [e["wall_s"] for e in log.steps()]
    for sp, w in zip(steps, walls):
        assert abs(sp["dur_s"] - w) < 1e-6


def test_measured_vs_model_reconciles_with_phase_timer(telemetry_run):
    """The acceptance inequality: the measured phase-time total across the
    per-step measured_vs_model blocks reconciles with PhaseTimer.report()
    (the 'step' phase the spans feed) to <1%."""
    _, metrics, _ = telemetry_run
    from sgcn_tpu.obs import load_run
    steps = load_run(metrics).steps()
    mvms = [ev["measured_vs_model"] for ev in steps]
    assert all(isinstance(m, dict) for m in mvms)
    measured_total = sum(m["phase_total_s"] for m in mvms)
    # the LAST step's phases snapshot is taken after its span exits, so it
    # covers every step span of the run
    ph = steps[-1]["phases"]["step"]
    assert ph["count"] == len(steps)
    assert abs(measured_total - ph["total_s"]) < 0.01 * ph["total_s"]
    for ev in steps:
        gs = ev["measured_vs_model"]["components"]["gather_stream"]
        assert gs["measured_s"] > 0 and gs["model_s"] > 0
        assert abs(gs["ratio"] * gs["model_s"] / gs["measured_s"] - 1.0) \
            < 0.01


def test_profile_trace_recorded_in_manifest_and_parses(telemetry_run):
    """--profile and --metrics-out compose: the manifest records the trace
    path + gzip'd size, and the trace parses into classified op time from
    the run directory alone."""
    _, metrics, _ = telemetry_run
    from sgcn_tpu.obs import load_run, summarize_trace, trace_path_for_run
    log = load_run(metrics)
    prof = log.manifest["profile"]
    assert prof["trace_files"], "no trace files recorded in the manifest"
    entry = prof["trace_files"][0]
    assert os.path.exists(entry["path"])
    assert entry["bytes"] == os.path.getsize(entry["path"])
    tpath = trace_path_for_run(log.manifest, metrics)
    assert tpath == entry["path"]
    ts = summarize_trace(tpath)
    assert ts.n_events > 0
    assert sum(ts.classes.values()) > 0
    assert 0 <= ts.exposed_comm_s <= ts.comm_s + 1e-9


@pytest.fixture(scope="module")
def ragged_run(tmp_path_factory):
    """A second CLI child on the cora fixture under the RAGGED schedule
    (exact mode) — with the module's stale/a2a child above, --metrics-out
    has run under both transports, the gauge-reconciliation smoke of the
    comm-schedule work (docs/comm_schedule.md)."""
    d = tmp_path_factory.mktemp("obs_ragged")
    metrics = str(d / "run")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [sys.executable, "-m", "sgcn_tpu.train",
         "--npz", os.path.join(FIX, "cora_like.npz"),
         "-p", os.path.join(FIX, "cora_like.4.hp"),
         "-b", "cpu", "-s", "4", "-l", "2", "--normalize",
         "--epochs", "2", "--warmup", "1",
         "--comm-schedule", "ragged", "--metrics-out", metrics],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    return metrics, report


def _assert_wire_reconciles(metrics, report):
    """CommStats' printed report and the obs events must agree on wire
    accounting EXACTLY — rows, bytes, efficiency, schedule."""
    from sgcn_tpu.obs import load_run

    log = load_run(metrics)
    steps = log.steps()
    for ev in steps:
        comm, roof = ev["comm"], ev["roofline"]
        assert comm["comm_schedule"] == roof["comm_schedule"]
        assert comm["wire_rows_per_exchange"] == \
            roof["halo_wire_rows_per_exchange"]
        assert comm["padding_efficiency"] == roof["padding_efficiency"]
        # bytes are rows × Σ layer widths × itemsize × 2 on BOTH sides of
        # the split, so the true/wire byte ratio must equal the true/wire
        # ROW ratio the CommStats side reports — byte-for-byte, no slack
        assert (roof["halo_bytes_wire_per_step"]
                * comm["true_rows_per_exchange"]
                == roof["halo_bytes_true_per_step"]
                * comm["wire_rows_per_exchange"])
        assert roof["halo_bytes_wire_per_step"] >= \
            roof["halo_bytes_true_per_step"]
    last = steps[-1]["comm"]
    for key in ("comm_schedule", "wire_rows_per_exchange", "wire_rows_total",
                "true_rows_per_exchange", "padding_efficiency"):
        assert last[key] == report[key], (key, last[key], report[key])


def test_wire_gauges_reconcile_under_both_schedules(telemetry_run,
                                                    ragged_run):
    """The satellite contract: --metrics-out under BOTH schedules, CommStats
    report and obs events agreeing on wire bytes exactly; the ragged run's
    wire strictly below the dense run's at equal true volume."""
    _, metrics_a2a, report_a2a = telemetry_run
    metrics_rag, report_rag = ragged_run
    _assert_wire_reconciles(metrics_a2a, report_a2a)
    _assert_wire_reconciles(metrics_rag, report_rag)
    assert report_a2a["comm_schedule"] == "a2a"
    assert report_rag["comm_schedule"] == "ragged"
    assert report_a2a["true_rows_per_exchange"] == \
        report_rag["true_rows_per_exchange"]
    assert report_rag["wire_rows_per_exchange"] < \
        report_a2a["wire_rows_per_exchange"]


def test_obs_report_renders(telemetry_run):
    _, metrics, _ = telemetry_run
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         metrics],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "drift gauges" in out
    assert "exposed" in out and "hidden" in out
    assert "roofline:  gather" in out
    assert "stream-ceiling" not in out      # a CPU run: no ceiling stated
    # the measured-time layer renders too: spans, the per-step
    # measured-vs-model reconciliation, and the trace-derived attribution
    assert "spans:" in out
    assert "measured vs model" in out
    assert "trace (" in out and "measured op classes" in out
