"""Unit tests for the measured-time profiling layer (sgcn_tpu.obs.tracing)
and its schema/recorder integration:

  * PhaseTimer nesting — child time attributed to the child only, reentrant
    same-name entry no longer double-counts (the pre-fix corruption), and
    the inclusive side keeps the whole-region semantics ``fit()`` times with;
  * SpanTimer — nested spans over the shared timer, span events through the
    recorder;
  * trace parser — op classification into the attribution vocabulary, the
    overlap/exposed/straggler math on a synthetic trace, and a real parse
    of the checked-in 8-vdev trace artifact;
  * measured_vs_model — block construction, schema validation of the
    ratio/abs-err join, rejection of inconsistent joins;
  * schema v2 back-compat — the frozen v1 fixture run dir loads clean, a
    v1 stream may not carry the v2-only span kind.
"""

import gzip
import json
import os
import time

import numpy as np
import pytest

from sgcn_tpu.utils.timers import PhaseTimer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures")


# ------------------------------------------------------- PhaseTimer nesting

def test_phase_timer_nested_child_only_attribution():
    t = PhaseTimer()
    # wide sleep gap: a loaded host can overshoot the short sleep, and the
    # ordering assertion below must not flake on scheduler jitter
    with t.phase("outer"):
        time.sleep(0.01)
        with t.phase("inner"):
            time.sleep(0.08)
    rep = t.report()
    # self time: the child's 0.08 s belongs to the child ONLY
    assert rep["inner"]["total_s"] >= 0.08
    assert rep["outer"]["total_s"] < rep["inner"]["total_s"]
    # inclusive keeps the whole-region meaning
    assert rep["outer"]["inclusive_s"] >= 0.09
    assert abs(rep["outer"]["inclusive_s"]
               - (rep["outer"]["total_s"] + rep["inner"]["total_s"])) < 0.01
    # Σ self times == elapsed wall: nothing counted twice
    assert t.inclusive_total("outer") == rep["outer"]["inclusive_s"]


def test_phase_timer_reentrant_same_name_no_double_count():
    """The satellite fix: re-entering a phase under itself used to add BOTH
    frames' full durations (totals ~2x wall)."""
    t = PhaseTimer()
    with t.phase("a"):
        time.sleep(0.02)
        with t.phase("a"):
            time.sleep(0.02)
    # self-time halves sum to the single wall duration
    assert 0.035 < t.totals["a"] < 0.08
    # inclusive is reentrancy-guarded: the outermost frame counts once
    assert 0.035 < t.inclusive["a"] < 0.08
    assert t.counts["a"] == 2


def test_phase_timer_sync_callable_still_runs():
    t = PhaseTimer()
    hit = []
    with t.phase("p", sync=lambda: (hit.append(1), np.zeros(1))[1]):
        pass
    assert hit == [1]
    assert t.counts["p"] == 1


def test_phase_timer_raising_sync_unwinds_the_stack():
    """Async dispatch errors surface exactly at the block_until_ready sync
    point; a raising sync must still pop/account its frame — a dead frame
    would silently poison every later phase's attribution."""
    t = PhaseTimer()

    def boom():
        raise RuntimeError("dispatch error")

    with pytest.raises(RuntimeError):
        with t.phase("bad", sync=boom):
            pass
    assert t._stack == []
    assert t.counts["bad"] == 1
    # subsequent accounting is uncorrupted: a fresh phase attributes its
    # own time (not to a leftover frame) and reentrancy still works
    with t.phase("good"):
        time.sleep(0.02)
    assert t.totals["good"] >= 0.02
    assert t.inclusive["good"] >= 0.02


# ---------------------------------------------------------------- span API

def test_span_timer_nesting_and_events(tmp_path):
    from sgcn_tpu.obs import RunRecorder, load_run
    from sgcn_tpu.obs.tracing import SpanTimer

    d = str(tmp_path / "run")
    with RunRecorder(d, config={}) as rec:
        st = SpanTimer(recorder=rec)
        with st.span("train_step", step=1) as outer:
            time.sleep(0.01)
            with st.span("step", step=1) as inner:
                time.sleep(0.01)
        assert outer.dur_s > inner.dur_s > 0
    log = load_run(d)
    spans = [e for e in log.events if e["kind"] == "span"]
    # exit order: the inner span closes (and is emitted) first
    assert [s["name"] for s in spans] == ["step", "train_step"]
    assert spans[0]["parent"] == "train_step" and spans[0]["depth"] == 1
    assert "parent" not in spans[1] and spans[1]["depth"] == 0
    assert spans[0]["step"] == 1
    # the span generalizes PhaseTimer: both names landed in the timer too
    assert st.timer.counts["step"] == st.timer.counts["train_step"] == 1


# ------------------------------------------------------------- trace parser

def test_classify_op_vocabulary():
    from sgcn_tpu.obs.tracing import classify_op

    assert classify_op("all-to-all.6") == "exchange"
    assert classify_op("collective-permute-start.1") == "exchange"
    assert classify_op("Rendezvous") == "collective_wait"
    assert classify_op("Wait for rendezvous callback") == "collective_wait"
    assert classify_op("all-to-all-done.2") == "collective_wait"
    # point-to-point transfer pairs: start = exchange, completion = wait
    assert classify_op("send.3") == "exchange"
    assert classify_op("recv.3") == "exchange"
    assert classify_op("recv-done.2") == "collective_wait"
    assert classify_op("copy_gather_fusion.2") == "spmm"
    assert classify_op("wrapped_scatter.4") == "spmm"
    assert classify_op("select_slice_fusion.7") == "spmm"
    assert classify_op("dot_general.3") == "dense"
    assert classify_op("wrapped_broadcast") == "other"
    # async COPY completion is not comm wait (only collective -done ops are)
    assert classify_op("copy-done.1") == "other"
    # dtype casts are not dense math (`convolution` yes, `convert` no)
    assert classify_op("convert.5") == "other"
    assert classify_op("convolution.1") == "dense"
    # host/runtime scaffolding is not device op time
    assert classify_op("$profiler.py:246 trace") is None
    assert classify_op("end: copy.17") is None
    assert classify_op("ThunkExecutor::Execute") is None
    assert classify_op("PjitFunction(per_chip)") is None


def _synthetic_trace(tmp_path, events):
    path = str(tmp_path / "t.trace.json.gz")
    with gzip.open(path, "wt") as fh:
        json.dump({"traceEvents": events}, fh)
    return path


def test_summarize_trace_overlap_and_skew(tmp_path):
    """Hand-built two-device trace: device A's collective is half covered by
    concurrent compute, device B is a straggler with 2x busy time."""
    from sgcn_tpu.obs.tracing import summarize_trace

    ev = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "/device:TPU:1"}},
        # device 0: 100 µs compute, then a 100 µs all-to-all whose first
        # 50 µs overlaps a second compute op on another thread
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 100,
         "name": "copy_gather_fusion.1"},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 100, "dur": 100,
         "name": "all-to-all.1"},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 100, "dur": 50,
         "name": "dot_general.1"},
        # device 1: pure compute, twice device 0's busy window
        {"ph": "X", "pid": 2, "tid": 1, "ts": 0, "dur": 500,
         "name": "copy_gather_fusion.2"},
    ]
    ts = summarize_trace(_synthetic_trace(tmp_path, ev))
    assert ts.n_events == 4
    us = 1e-6
    assert abs(ts.classes["spmm"] - 600 * us) < 1e-12
    assert abs(ts.classes["exchange"] - 100 * us) < 1e-12
    assert abs(ts.comm_s - 100 * us) < 1e-12
    # 50 of the 100 µs collective ran under concurrent compute
    assert abs(ts.exposed_comm_s - 50 * us) < 1e-12
    assert abs(ts.measured_overlap_frac - 0.5) < 1e-9
    assert ts.skew is not None
    assert ts.skew["straggler"] == "/device:TPU:1"
    # busy: dev0 200 µs (0..200 union), dev1 500 µs -> max/mean = 500/350
    assert abs(ts.skew["busy_max_over_mean"] - 500 / 350) < 1e-9
    per = ts.per_step(2)
    assert abs(per["exchange_s"] - 50 * us) < 1e-12


def test_summarize_trace_duplicate_process_names(tmp_path):
    """Distinct pids sharing process_name metadata (merged multi-host
    captures) must stay distinct devices — collapsing them would shrink the
    straggler denominator and overwrite per-class seconds."""
    from sgcn_tpu.obs.tracing import summarize_trace

    ev = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 100,
         "name": "copy_gather_fusion.1"},
        {"ph": "X", "pid": 2, "tid": 1, "ts": 0, "dur": 300,
         "name": "copy_gather_fusion.2"},
    ]
    ts = summarize_trace(_synthetic_trace(tmp_path, ev))
    us = 1e-6
    assert len(ts.devices) == 2
    assert abs(ts.classes["spmm"] - 400 * us) < 1e-12
    assert ts.skew is not None               # two devices, 2x skew visible
    assert abs(ts.skew["busy_max_over_mean"] - 300 / 200) < 1e-9
    assert ts.skew["straggler"].startswith("/device:TPU:0")


def test_summarize_trace_drops_host_pids_when_devices_exist(tmp_path):
    """A real TPU profile carries host/runtime pids next to the device
    pids; their wall time is not device op time — the host must not
    inflate class totals or be elected straggler.  (A CPU-backend trace
    has no /device: pid, so its /host:CPU stays in — pinned by
    test_summarize_trace_checked_in_artifact.)"""
    from sgcn_tpu.obs.tracing import summarize_trace

    ev = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "/device:TPU:1"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 100,
         "name": "copy_gather_fusion.1"},
        {"ph": "X", "pid": 2, "tid": 1, "ts": 0, "dur": 200,
         "name": "copy_gather_fusion.2"},
        # classifiable host activity, much longer than any device op
        {"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 9000,
         "name": "wrapped_broadcast"},
    ]
    ts = summarize_trace(_synthetic_trace(tmp_path, ev))
    us = 1e-6
    assert set(ts.devices) == {"/device:TPU:0", "/device:TPU:1"}
    assert ts.n_events == 2                           # host op not counted
    assert ts.classes.get("other", 0.0) == 0.0        # host op dropped
    assert abs(ts.classes["spmm"] - 300 * us) < 1e-12
    assert ts.skew is not None
    assert ts.skew["straggler"] == "/device:TPU:1"    # never the host


def test_summarize_trace_checked_in_artifact():
    """The committed 8-vdev CPU trace parses and classifies: the overlap
    evidence run shipped all-to-alls and gather fusions, so both classes
    must be non-empty and exposure bounded by total comm."""
    from sgcn_tpu.obs.tracing import summarize_trace

    ts = summarize_trace(os.path.join(
        REPO, "bench_artifacts", "overlap_8dev_cpu.trace.json.gz"))
    assert ts.n_events > 100
    assert ts.classes["exchange"] > 0
    assert ts.classes["spmm"] > 0
    assert 0 <= ts.exposed_comm_s <= ts.comm_s + 1e-9
    assert ts.measured_overlap_frac is not None
    assert 0 <= ts.measured_overlap_frac <= 1
    # one /host:CPU process -> no per-device skew on the CPU backend
    assert ts.skew is None


def test_find_trace_files_and_manifest_profile(tmp_path):
    from sgcn_tpu.obs import RunRecorder, load_run
    from sgcn_tpu.obs.tracing import find_trace_files, trace_path_for_run

    prof = tmp_path / "prof" / "plugins" / "profile" / "run1"
    prof.mkdir(parents=True)
    tpath = prof / "host.trace.json.gz"
    with gzip.open(str(tpath), "wt") as fh:
        json.dump({"traceEvents": []}, fh)
    hits = find_trace_files(str(tmp_path / "prof"))
    assert len(hits) == 1
    assert hits[0]["path"] == str(tpath)
    assert hits[0]["bytes"] == os.path.getsize(str(tpath))

    d = str(tmp_path / "run")
    with RunRecorder(d, config={}) as rec:
        rec.set_profile(str(tmp_path / "prof"))
    log = load_run(d)
    pb = log.manifest["profile"]
    assert pb["dir"] == str(tmp_path / "prof")
    assert pb["trace_files"][0]["path"] == str(tpath)
    assert trace_path_for_run(log.manifest, d) == str(tpath)

    # relocated run dir: the manifest's absolute paths are stale, but a
    # trace copied under the run dir itself still resolves (last-resort
    # rundir glob — 'from the run directory alone' holds anywhere)
    moved = tmp_path / "moved_run"
    moved.mkdir()
    inner = moved / "host.trace.json.gz"
    with gzip.open(str(inner), "wt") as fh:
        json.dump({"traceEvents": []}, fh)
    stale = {"profile": {"dir": "/nonexistent/prof",
                         "trace_files": [{"path": "/nonexistent/t.gz",
                                          "bytes": 1}]}}
    assert trace_path_for_run(stale, str(moved)) == os.path.abspath(str(inner))
    assert trace_path_for_run(stale, str(tmp_path / "nowhere")) is None


# -------------------------------------------------------- measured vs model

def test_measured_vs_model_block_and_validation():
    from sgcn_tpu.obs import validate_event
    from sgcn_tpu.obs.attribution import STREAM_CEILING_GBS
    from sgcn_tpu.obs.tracing import measured_vs_model_block

    class Cost:
        gather_bytes = 655_000_000      # exactly 1 ms at the stream ceiling

    blk = measured_vs_model_block(Cost(), wall_s=0.004)
    gs = blk["components"]["gather_stream"]
    assert abs(gs["model_s"] - 655e6 / (STREAM_CEILING_GBS * 1e9)) < 1e-12
    assert gs["measured_s"] == 0.004
    assert abs(gs["ratio"] - 4.0) < 1e-6
    assert abs(gs["abs_err_s"] - 0.003) < 1e-9
    assert blk["phase_total_s"] == 0.004
    ev = {"v": 2, "ts": 1.0, "kind": "step", "step": 1, "loss": 1.0,
          "wall_s": 0.004, "measured_vs_model": blk}
    validate_event(ev)                  # the block round-trips the schema

    # an inconsistent join (ratio not measured/model) is a writer bug
    bad = {"phase_total_s": 0.004,
           "components": {"gather_stream": dict(gs, ratio=1.0)}}
    with pytest.raises(ValueError, match="inconsistent"):
        validate_event(dict(ev, measured_vs_model=bad))
    # a missing analytic side is a writer bug (model_s must be computable)
    with pytest.raises(ValueError, match="model_s"):
        validate_event(dict(ev, measured_vs_model={
            "phase_total_s": 0.004, "components": {"x": {"measured_s": 1.0}}}))
    with pytest.raises(ValueError, match="phase_total_s"):
        validate_event(dict(ev, measured_vs_model={"components": {
            "x": {"model_s": 1.0, "measured_s": None}}}))


def test_measured_vs_model_trace_join():
    from sgcn_tpu.obs.attribution import ICI_CEILING_GBS
    from sgcn_tpu.obs.tracing import measured_vs_model_block

    class Cost:
        gather_bytes = 1_000_000

    # exposed vs exposed: measured exposed_comm_s (NOT total collective
    # seconds — hidden comm is overlap, not model error) against the
    # analytic exposed wire bytes serialized at the nominal ICI rate.  The
    # model side must NOT scale with the step wall: exposed_comm_frac is a
    # fraction of the step's exchanges, so a frac x wall model would read
    # every exact run's compute share as cost-model error.
    ehb = 0.004 * ICI_CEILING_GBS * 1e9     # 4 ms of wire at the ceiling
    blk = measured_vs_model_block(
        Cost(), wall_s=0.01,
        trace_per_step={"exchange_s": 0.005, "collective_wait_s": 0.001,
                        "exposed_comm_s": 0.003},
        exposed_halo_bytes=ehb)
    ex = blk["components"]["exchange"]
    assert ex["measured_s"] == 0.003   # exposed only, 3ms of 6ms total
    assert ex["model_s"] == 0.004      # ehb / ICI ceiling, wall-independent
    assert abs(ex["ratio"] - 0.75) < 1e-6
    # no exposed_halo_bytes -> no exchange join (TraceSummary.per_step
    # alone carries no analytic side)
    blk = measured_vs_model_block(
        Cost(), wall_s=0.01, trace_per_step={"exposed_comm_s": 0.002})
    assert "exchange" not in blk["components"]


# ------------------------------------------------------- schema back-compat

def test_v1_fixture_run_loads_clean():
    """The frozen v1 run dir (pre-span, pre-measured_vs_model) must load
    through the CURRENT loader without modification — the one-release
    back-compat contract of schema.py."""
    from sgcn_tpu.obs import load_run

    log = load_run(os.path.join(FIX, "v1_run"))
    assert log.manifest["v"] == 1
    assert [e["kind"] for e in log.events] == ["step", "step", "eval",
                                               "summary"]
    steps = log.steps()
    assert steps[0]["roofline"]["comm_schedule"] == "a2a"
    assert steps[1]["drift"]["sync_step"] is False
    assert len(log.heartbeats) == 2
    # and the v1 stream round-trips the validator directly
    from sgcn_tpu.obs import validate_event
    for ev in log.events + log.heartbeats:
        validate_event(ev)


def test_v1_stream_may_not_carry_v2_kinds():
    from sgcn_tpu.obs import validate_event

    with pytest.raises(ValueError, match="kind"):
        validate_event({"v": 1, "ts": 1.0, "kind": "span",
                        "name": "x", "dur_s": 0.1})
    # a v2 stream may not carry the v3-only serve kind either
    with pytest.raises(ValueError, match="kind"):
        validate_event({"v": 2, "ts": 1.0, "kind": "serve", "queries": 1,
                        "achieved_qps": 1.0, "latency_p50_ms": 1.0,
                        "latency_p95_ms": 1.0, "latency_p99_ms": 1.0})
    # unknown version is rejected outright
    with pytest.raises(ValueError, match="version"):
        validate_event({"v": 99, "ts": 1.0, "kind": "step", "step": 1,
                        "loss": 1.0, "wall_s": 0.1})


def test_v2_span_event_validates():
    from sgcn_tpu.obs import validate_event

    validate_event({"v": 2, "ts": 1.0, "kind": "span", "name": "step",
                    "dur_s": 0.25, "parent": "train_step", "depth": 1,
                    "step": 4, "pid": 123})
    with pytest.raises(ValueError, match="dur_s"):
        validate_event({"v": 2, "ts": 1.0, "kind": "span", "name": "x",
                        "dur_s": -0.1})
    with pytest.raises(ValueError, match="non-finite"):
        validate_event({"v": 2, "ts": 1.0, "kind": "span", "name": "x",
                        "dur_s": float("nan")})


def test_v5_fixture_run_loads_clean():
    """The frozen v5 run dir (pre-memory: no ``memory`` event kind, no
    manifest ``memory`` block) must load through the CURRENT loader without
    modification — the one-release back-compat contract, re-pinned at the
    v5 -> v6 bump (ISSUE 18)."""
    from sgcn_tpu.obs import load_run, validate_event

    log = load_run(os.path.join(FIX, "v5_run"))
    assert log.manifest["v"] == 5
    assert "memory" not in log.manifest
    assert [e["kind"] for e in log.events] == [
        "span", "step", "span", "span", "step", "span", "span", "step",
        "span", "summary", "summary"]
    assert len(log.heartbeats) == 2
    assert all(e["v"] == 5 for e in log.events + log.heartbeats)
    for ev in log.events + log.heartbeats:
        validate_event(ev)


def test_v5_stream_may_not_carry_v6_kinds():
    from sgcn_tpu.obs import validate_event

    with pytest.raises(ValueError, match="kind"):
        validate_event({"v": 5, "ts": 1.0, "kind": "memory",
                        "program": "train_step", "model_bytes": 1024})


def test_v6_memory_event_validates():
    from sgcn_tpu.obs import validate_event

    # model-only (plan-time) and with the XLA measured join + ratio
    validate_event({"v": 6, "ts": 1.0, "kind": "memory",
                    "program": "train_step", "workload": "train",
                    "model_bytes": 2048})
    validate_event({"v": 6, "ts": 1.0, "kind": "memory",
                    "program": "bucket0", "workload": "serve",
                    "model_bytes": 2048, "measured_peak_bytes": 1024,
                    "argument_bytes": 512, "output_bytes": 256,
                    "temp_bytes": 256, "alias_bytes": 0,
                    "generated_code_bytes": 4096, "ratio": 0.5,
                    "budget_bytes": 1 << 30})
    with pytest.raises(ValueError, match="workload"):
        validate_event({"v": 6, "ts": 1.0, "kind": "memory",
                        "program": "x", "workload": "infer",
                        "model_bytes": 1})
    with pytest.raises(ValueError, match="non-finite/negative"):
        validate_event({"v": 6, "ts": 1.0, "kind": "memory",
                        "program": "x", "model_bytes": -1})
    # the ratio must agree with its own endpoints
    with pytest.raises(ValueError, match="ratio"):
        validate_event({"v": 6, "ts": 1.0, "kind": "memory",
                        "program": "x", "model_bytes": 1000,
                        "measured_peak_bytes": 500, "ratio": 2.0})
