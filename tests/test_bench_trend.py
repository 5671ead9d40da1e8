"""Tier-1 gate: the bench trend contract (``scripts/bench_trend.py``).

Two halves:

  * the tree passes ``--check`` (a landed regression fails the suite the
    commit it lands).  The tree holds no ``BENCH_r*.json`` round at present
    — the old rounds were taken on a set-up that is gone and were deleted
    with it (PERF.md) — so today this pins that an empty history is clean;
  * the gate's own semantics — tolerance bands per metric kind,
    degradation-marker awareness (a degraded round is a gap, never a
    comparison point), deterministic-counter strictness — pinned on
    synthetic histories, including the synthetic REGRESSED artifact the
    acceptance criteria require to fail.

Plus the measured-provenance rule ``scripts/validate_bench.py`` grew with
the trend gate: an epoch-time claim from round 6 on must say it was
measured live (``measured: true``) or carry a degradation marker.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

from bench_trend import (DEFAULT_TIME_BAND, check_series, check_tree,  # noqa: E402
                         extract_series, load_history)
from validate_bench import check_measured_provenance  # noqa: E402


def _rec(value, metric="fullbatch_gcn_epoch_time", rc=0, **parsed_extra):
    parsed = {"metric": metric, "value": value, "unit": "s",
              "measured": True, **parsed_extra}
    return {"n": 1, "cmd": "python bench.py", "rc": rc, "tail": "x",
            "parsed": parsed}


def _write_history(tmp_path, records):
    for rnd, rec in records:
        with open(tmp_path / f"BENCH_r{rnd:02d}.json", "w") as fh:
            json.dump(rec, fh)
    return str(tmp_path)


def test_checked_in_tree_passes_the_gate():
    problems, report = check_tree(REPO)
    assert not problems, "\n".join(problems)
    assert "gate: clean" in report


def test_gate_fails_on_synthetic_regressed_artifact(tmp_path):
    """The acceptance shape: append one regressed round to a healthy
    history and --check must fail naming the series."""
    # band anchor = median of previous points (0.30, 0.10) = 0.20
    root = _write_history(tmp_path, [
        (1, _rec(0.30)), (2, _rec(0.10)),
        (3, _rec(0.20 * DEFAULT_TIME_BAND * 2)),   # 2x outside the band
    ])
    problems, report = check_tree(root)
    assert len(problems) == 1
    assert "fullbatch_gcn_epoch_time" in problems[0]
    assert "regression" in problems[0]
    assert "VIOLATIONS" in report
    # the same history minus the bad round is clean
    os.remove(os.path.join(root, "BENCH_r03.json"))
    problems, _ = check_tree(root)
    assert not problems


def test_gate_anchor_is_median_not_best(tmp_path):
    """One lucky fast outlier must not permanently tighten the gate: the
    band anchors on the MEDIAN previous point, and the default band sits
    above the 1.665x cross-session drift recorded in rounds 3-5 (identical
    code 2.18 s vs 3.63 s)."""
    assert DEFAULT_TIME_BAND > 1.665
    root = _write_history(tmp_path, [
        (1, _rec(0.30)), (2, _rec(0.02)),          # r02 is a lucky outlier
        (3, _rec(0.30)),   # normal again — a best-anchored 2x band (0.04)
    ])                     # would flag it; median anchor 0.16 clears it
    problems, _ = check_tree(root)
    assert not problems


def test_gate_is_degradation_marker_aware(tmp_path):
    """A degraded/skipped/rc!=0 round is a GAP: reported, never compared —
    so it can neither fake a regression nor hide one by becoming the
    'best previous' point."""
    root = _write_history(tmp_path, [
        (1, _rec(0.30)),
        # marked null — and its partial 8-dev diagnostic counters must NOT
        # enter the zero-band series either
        (2, _rec(None, degraded="flagship deadline", km1_8dev=99999,
                 n_8dev=40000, graph_8dev="ba", partitioner_8dev="hp")),
        (3, {"n": 1, "cmd": "x", "rc": 124, "tail": "timeout"}),  # hard fail
        (4, _rec(0.25)),
    ])
    series, gaps = extract_series(load_history(root))
    key = ("time", "fullbatch_gcn_epoch_time", "er", "s",
           None, None, None, None, None, None)
    assert [r for r, _ in series[key]] == [1, 4]
    assert [r for r, _ in gaps] == [2, 3]
    assert "deadline" in gaps[0][1]
    assert not any(k[0] == "counter" for k in series)
    assert not check_series(series)


def test_gate_only_bands_wall_clock_units(tmp_path):
    """Only unit == "s" series are gate-able (lower-is-better by
    construction); a throughput-style metric improving UPWARD forms a
    report-only series and must not trip the band."""
    root = _write_history(tmp_path, [
        (1, _rec(10.0, metric="minibatch_throughput", unit="it/s")),
        (2, _rec(20.0, metric="minibatch_throughput", unit="it/s")),
    ])
    series, _ = extract_series(load_history(root))
    key = ("metric", "minibatch_throughput", "er", "it/s",
           None, None, None, None, None, None)
    assert [v for _, v in series[key]] == [10.0, 20.0]
    assert not check_series(series)
    # ...and the report labels the trend neutrally (an upward throughput
    # series is not a "regression")
    problems, report = check_tree(root)
    assert not problems
    assert "net change: 10 -> 20" in report
    assert "regression" not in report


def test_gate_scopes_series_by_config(tmp_path):
    """A config change (different graph family) starts a NEW series — a
    slower number on a different workload is not a regression."""
    root = _write_history(tmp_path, [
        (1, _rec(0.05, graph="er")),
        (2, _rec(0.50, graph="ba")),       # 10x slower, different graph
    ])
    series, _ = extract_series(load_history(root))
    assert not check_series(series)
    # scalar bench-config fields scope a wall-clock series too: a bigger
    # problem size is a different measurement, not a regression — and
    # partitioner "none" normalizes to absent (the r01/r02 history shape)
    (tmp_path / "cfg").mkdir()
    root2 = _write_history(tmp_path / "cfg", [
        (1, _rec(0.05)),
        (2, _rec(0.05, partitioner="none")),
        (3, _rec(5.00, n=200000)),         # 100x slower at a bigger n
    ])
    series, _ = extract_series(load_history(root2))
    assert not check_series(series)
    key = ("time", "fullbatch_gcn_epoch_time", "er", "s",
           None, None, None, None, None, None)
    assert [r for r, _ in series[key]] == [1, 2]   # 'none' == absent
    # render survives the mixed None/int cfg slots in series keys
    problems, report = check_tree(root2)
    assert not problems
    assert "n=200000" in report


def test_gate_rejects_non_finite_values(tmp_path):
    """A NaN/Infinity value must not enter a series: every NaN comparison
    is False, so one poisoned point (or median anchor) would make the gate
    read clean forever."""
    root = _write_history(tmp_path, [(1, _rec(0.10)), (2, _rec(0.10))])
    with open(tmp_path / "BENCH_r03.json", "w") as fh:
        fh.write('{"n": 3, "cmd": "x", "rc": 0, "tail": "x", "parsed": '
                 '{"metric": "fullbatch_gcn_epoch_time", "value": NaN, '
                 '"unit": "s", "measured": true}}')
    series, _ = extract_series(load_history(root))
    key = ("time", "fullbatch_gcn_epoch_time", "er", "s",
           None, None, None, None, None, None)
    assert [r for r, _ in series[key]] == [1, 2]   # NaN round excluded
    assert not check_series(series)


def test_gate_zero_band_for_deterministic_counters(tmp_path):
    """Plan-derived counters (km1, comm rows) are reproducible bit-for-bit:
    within one diagnostic config they may never increase."""
    base = dict(n_8dev=40000, graph_8dev="ba", partitioner_8dev="hp")
    root = _write_history(tmp_path, [
        (1, _rec(0.05, km1_8dev=1000, **base)),
        (2, _rec(0.05, km1_8dev=1001, **base)),      # +1 row regression
    ])
    problems = check_series(extract_series(load_history(root))[0])
    assert any("km1_8dev" in p and "never regress" in p for p in problems)
    # a DIFFERENT config's larger km1 is a new series, not a violation
    (tmp_path / "o").mkdir()
    root2 = _write_history(tmp_path / "o", [
        (1, _rec(0.05, km1_8dev=1000, **base)),
        (2, _rec(0.05, km1_8dev=9999, **dict(base, n_8dev=120000))),
    ])
    assert not check_series(extract_series(load_history(root2))[0])


def test_pallas_ragged_counters_registered_zero_band(tmp_path):
    """The kernel × schedule A/B counters (ISSUE 15) register as zero-band
    series scoped on (n, graph, k); the zero-halo-table contract of the
    pallas ragged arm is literally a zero that may never move."""
    def _prab(halo_bytes):
        return {"pallas_ragged_ab_8dev": {
            "n": 12000, "graph": "ba", "k": 8,
            "ell_ragged": {"epoch_s": 0.1, "measured": True,
                           "wire_rows_per_exchange": 24096,
                           "halo_table_bytes_per_step": 0},
            "pallas_ragged": {"epoch_s": 0.2, "measured": True,
                              "wire_rows_per_exchange": 24096,
                              "halo_table_bytes_per_step": halo_bytes},
            "pallas_a2a": {"epoch_s": 0.2, "measured": True,
                           "wire_rows_per_exchange": 28736,
                           "halo_table_bytes_per_step": 1000}}}

    root = _write_history(tmp_path, [
        (1, _rec(0.05, **_prab(0))), (2, _rec(0.05, **_prab(4096)))])
    series, _ = extract_series(load_history(root))
    key = [k for k in series
           if k[1] == "pallas_ragged_pallas_ragged_halo_table_bytes_per_step"]
    assert key and series[key[0]] == [(1, 0.0), (2, 4096.0)]
    problems = check_series(series)
    assert any("halo_table_bytes_per_step" in p and "never regress" in p
               for p in problems)
    # emulate-mode epoch times are NOT tracked series (never a CPU claim)
    assert not any("pallas" in k[1] and "epoch" in k[1] for k in series)


def test_cli_check_mode_exit_codes(tmp_path):
    """--check is the gate (rc 1 on violation); report mode always rc 0."""
    root = _write_history(tmp_path, [(1, _rec(0.10)), (2, _rec(0.90))])
    script = os.path.join(REPO, "scripts", "bench_trend.py")
    r = subprocess.run([sys.executable, script, root, "--check"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "VIOLATIONS" in r.stdout
    r = subprocess.run([sys.executable, script, root],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    r = subprocess.run([sys.executable, script, root, "--check",
                        "--time-band", "20"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0               # per-metric band is a dial


# ------------------------------------------------- measured provenance rule

def test_epoch_time_claims_need_measured_provenance():
    """From round 6 on, a numeric epoch-time value must carry
    measured:true or a degradation marker; earlier rounds are
    grandfathered (retro-stamping provenance onto history would itself be
    a hand-edit)."""
    naked = {"n": 7, "cmd": "x", "rc": 0, "tail": "",
             "parsed": {"metric": "fullbatch_gcn_epoch_time", "value": 0.1,
                        "unit": "s"}}
    errs = check_measured_provenance(naked, 7)
    assert any("measured:true" in e for e in errs)
    # round 6 is the FIRST enforced round: the checked-in history ends at
    # r05, so the next generated record must not slip through the gate
    assert check_measured_provenance(naked, 6)
    assert not check_measured_provenance(naked, 5)       # grandfathered
    assert not check_measured_provenance(naked, 4)       # grandfathered
    ok = json.loads(json.dumps(naked))
    ok["parsed"]["measured"] = True
    assert not check_measured_provenance(ok, 7)
    degraded = json.loads(json.dumps(naked))
    degraded["parsed"]["value"] = None
    degraded["parsed"]["degraded"] = "deadline"
    assert not check_measured_provenance(degraded, 9)
    # a present-but-untrue flag is a violation at ANY round
    lying = json.loads(json.dumps(naked))
    lying["parsed"]["measured"] = "yes"
    assert any("live measurement" in e
               for e in check_measured_provenance(lying, 3))
    # ...including on a FAILED round (rc != 0) — exactly the hand-edit
    # shape the rule exists to catch; only the numeric-claim rule is
    # rc-gated
    failed_lying = json.loads(json.dumps(lying))
    failed_lying["rc"] = 1
    assert any("live measurement" in e
               for e in check_measured_provenance(failed_lying, 7))
    failed_clean = json.loads(json.dumps(naked))
    failed_clean["rc"] = 1
    assert not check_measured_provenance(failed_clean, 7)


def test_bench_emits_the_measured_flag():
    """bench.py's flagship and minibatch emissions carry measured: True
    next to the live differential value (string-level pin: the flag's
    emission site sits right where the value is rounded in)."""
    with open(os.path.join(REPO, "bench.py")) as fh:
        src = fh.read()
    assert src.count('"measured": True') >= 2


def _serve_rec(p50, wire_q, nnz=160000):
    arms = {"a2a": {"achieved_qps": 40.0, "latency_p50_ms": p50,
                    "latency_p99_ms": p50 * 3,
                    "wire_rows_per_exchange": 1000,
                    "wire_rows_per_query": 187.5},
            "ragged": {"achieved_qps": 42.0, "latency_p50_ms": p50,
                       "latency_p99_ms": p50 * 3,
                       "wire_rows_per_exchange": 600,
                       "wire_rows_per_query": wire_q}}
    return _rec(0.1, serve_qps_8dev={
        "n": 20000, "graph": "ba", "nnz": nnz, "nlayers": 2, "k": 8,
        "offered_qps": 50.0, "max_batch": 16, "measured": True,
        "arms": arms})


def test_serve_series_registration(tmp_path):
    """The serving series after ISSUE 18: measured QPS stays REPORT-ONLY
    (no universal better-direction once arms saturate differently), the
    latency quantiles register under the GATED "latency" kind, and the
    plan-derived wire-row gauges stay zero-band counters scoped to the
    serve config; a wire-row increase within one config trips the gate."""
    from bench_trend import _SERVE_CFG_KEYS

    root = _write_history(tmp_path, [
        (1, _serve_rec(4.0, 112.5)), (2, _serve_rec(5.0, 112.5)),
    ])
    block = _serve_rec(0, 0)["parsed"]["serve_qps_8dev"]
    cfg = tuple(block[k] for k in _SERVE_CFG_KEYS)
    series, _ = extract_series(load_history(root))
    lat_key = ("latency", "serve_ragged_latency_p50_ms", "serve", "ms") + cfg
    assert [v for _, v in series[lat_key]] == [4.0, 5.0]
    qps_key = ("metric", "serve_ragged_achieved_qps", "serve", "qps") + cfg
    assert qps_key in series            # QPS: still report-only
    ctr_key = ("counter", "serve_ragged_wire_rows_per_query") + cfg
    assert [v for _, v in series[ctr_key]] == [112.5, 112.5]
    assert not check_series(series)     # +25% p50: inside the 2x band
    # a denser graph (different nnz) is a NEW series, not a regression
    with open(os.path.join(root, "BENCH_r03.json"), "w") as fh:
        json.dump(_serve_rec(4.0, 300.0, nnz=640000), fh)
    series, _ = extract_series(load_history(root))
    assert not check_series(series)
    # but a wire-row regression within ONE config DOES trip the zero band
    with open(os.path.join(root, "BENCH_r04.json"), "w") as fh:
        json.dump(_serve_rec(4.0, 150.0), fh)
    series, _ = extract_series(load_history(root))
    problems = check_series(series)
    assert any("serve_ragged_wire_rows_per_query" in p for p in problems)


def test_serve_latency_gate_trips_on_regression(tmp_path):
    """ISSUE 18 satellite: serve latency is no longer report-only — a
    quantile beyond the 2x median-anchored band fails --check with the
    serve-latency message (the same synthetic-regressed-artifact shape the
    wall-clock gate is pinned with)."""
    root = _write_history(tmp_path, [
        (1, _serve_rec(4.0, 112.5)), (2, _serve_rec(5.0, 112.5)),
        (3, _serve_rec(4.5, 112.5)),
        (4, _serve_rec(4.5 * DEFAULT_TIME_BAND * 2, 112.5)),
    ])
    problems = check_series(extract_series(load_history(root))[0])
    lat_hits = [p for p in problems if "latency" in p]
    assert lat_hits, problems
    assert any("serve-latency regression" in p for p in lat_hits)
    # both quantiles of both arms regressed in the synthetic record
    assert any("serve_ragged_latency_p99_ms" in p for p in lat_hits)


def test_memory_footprint_counters_zero_band(tmp_path):
    """ISSUE 18 satellite: the analytic per-chip footprint gauges register
    as zero-band counters scoped by (n, nnz, k) — a byte of growth in any
    family within one config trips the gate; a different graph size is a
    new series."""
    from bench_trend import _MEMORY_CFG_KEYS

    def mem_rec(ws, nnz=160000):
        return _rec(0.1, memory_footprint_8dev={
            "n": 20000, "nnz": nnz, "k": 8, "graph": "ba", "fin": 32,
            "nlayers": 2, "analytic": True, "modes": {
                "train_gcn_a2a": {"analytic": True, "model_bytes": 1000 + ws,
                                  "params_bytes": 400,
                                  "workspace_bytes": ws},
            }})

    root = _write_history(tmp_path, [(1, mem_rec(600)), (2, mem_rec(600))])
    series, _ = extract_series(load_history(root))
    cfg = tuple(mem_rec(0)["parsed"]["memory_footprint_8dev"][k]
                for k in _MEMORY_CFG_KEYS)
    key = ("counter", "memory_train_gcn_a2a_workspace_bytes") + cfg
    assert [v for _, v in series[key]] == [600.0, 600.0]
    assert ("counter", "memory_train_gcn_a2a_model_bytes") + cfg in series
    assert not check_series(series)
    # a different nnz scopes a fresh series — no cross-config comparison
    with open(os.path.join(root, "BENCH_r03.json"), "w") as fh:
        json.dump(mem_rec(9000, nnz=640000), fh)
    series, _ = extract_series(load_history(root))
    assert not check_series(series)
    # one byte of growth within the SAME config is a regression
    with open(os.path.join(root, "BENCH_r04.json"), "w") as fh:
        json.dump(mem_rec(601), fh)
    problems = check_series(extract_series(load_history(root))[0])
    assert any("memory_train_gcn_a2a_workspace_bytes" in p
               for p in problems), problems
    assert any("may never regress" in p for p in problems)
