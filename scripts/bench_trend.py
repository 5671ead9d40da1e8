"""Trend report + regression gate over the ``BENCH_r*.json`` history.

Usage::

    python scripts/bench_trend.py [ROOT] [--check] [--time-band X]

Every round's bench driver record is already schema-checked individually
(``scripts/validate_bench.py``); this script is the TREND contract on top:
the per-round numbers form series, and ``--check`` fails when the newest
point of a series regresses outside its tolerance band.  Run in tier-1 by
``tests/test_bench_trend.py``, so a landed bench regression fails CI
instead of silently becoming the new baseline.

Rules:

  * **Series identity** — points are only compared when they measure the
    same thing: the flagship/minibatch epoch time keys on
    ``(metric, graph, unit)`` plus any scalar bench-config fields the
    record carries (``_TIME_CFG_KEYS``: problem size, model, dtype, …;
    a ``partitioner`` of ``"none"`` normalizes to absent); the 8-dev
    diagnostic gauges additionally key on their own config (``n_8dev``,
    ``graph_8dev``, ``partitioner_8dev``).  A config change starts a new
    series rather than faking a regression.
  * **Tolerance bands, per metric kind** — measured wall-clock values
    (``unit == "s"``; other units form report-only series, since a
    throughput-style metric improves UPWARD and must not trip a
    lower-is-better band) get a MULTIPLICATIVE band (default ``--time-band
    2.0``: the newest point must be ≤ 2× the MEDIAN previous point).  The
    anchor is the median, not the historical best — one lucky fast outlier
    must not permanently tighten the gate — and the band sits above the
    cross-session drift recorded on the rounds-3-5 development chip
    (identical code 2.18 s vs 3.63 s across sessions = 1.665×; spread on
    the current chip: not measured), so only a regression on top of
    normal drift trips it.  Deterministic counters
    (``COUNTER_KEYS``: ``km1_8dev``, ``comm_volume_rows_8dev``) get a ZERO
    band: they are plan-derived, reproducible bit-for-bit, and may never
    increase within a series.
  * **Serving series** (PR-8, gate since ISSUE 18) — the
    ``serve_qps_8dev``/``serve_subgraph_ab_8dev`` arms' measured latency
    quantiles are GATED with the same median-anchored multiplicative band
    as the epoch times (latency is lower-is-better by construction; rounds
    r01–r05 established the anchor per ROADMAP item 3c); achieved QPS
    stays REPORT-ONLY (it improves upward), and the plan-derived
    per-query/per-exchange wire-row gauges are zero-band counters like
    ``km1_8dev``.
  * **Memory-footprint series** (ISSUE 18) — the ``memory_footprint_8dev``
    block's analytic per-chip byte counts (per mode, per array family —
    ``sgcn_tpu.obs.memory``, no clock or allocator anywhere) are ZERO-band
    counters scoped on the block's (n, nnz, k): a byte that grows at fixed
    config is a new resident array, not noise.
  * **Degradation-marker aware** — a record with ``rc != 0``, or a null
    ``value`` carrying a ``skipped``/``degraded`` marker, is a GAP in the
    series (reported, never compared): the graceful-degradation contract
    says a missing number explains itself, and a gap must not poison the
    trend either way.

Exit status: 0 clean (or report-only mode), 1 with violations listed.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import numbers
import os
import re
import sys
from collections import defaultdict

ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")

# deterministic (plan-derived) gauges: zero tolerance, may never increase
COUNTER_KEYS = ("km1_8dev", "comm_volume_rows_8dev")
# flagship keys that scope a counter series to one diagnostic config
_DIAG_CFG_KEYS = ("n_8dev", "graph_8dev", "partitioner_8dev")
# serving-bench series (PR-8, the serve_qps_8dev block): achieved QPS is
# REPORT-ONLY (it improves UPWARD, so the lower-is-better band never
# applies — the PR-7 unit rule), while the measured latency quantiles are
# GATED since ISSUE 18 (ROADMAP item 3c): rounds r01–r05 established the
# band, and latency is lower-is-better by construction, so the newest
# point must stay within the median-anchored multiplicative band exactly
# like the epoch-time series (degraded/skipped rounds stay gaps).  The
# plan-derived per-query wire-row gauge remains a zero-band counter.
SERVE_REPORT_KEYS = ("achieved_qps",)
SERVE_LATENCY_KEYS = ("latency_p50_ms", "latency_p99_ms")
SERVE_COUNTER_KEYS = ("wire_rows_per_query", "wire_rows_per_exchange")
# serve config fields that scope a serving series (a different graph size /
# density / depth / rate / batch shape is a different measurement, not a
# regression — nnz/nlayers matter because the zero-band wire-row counters
# are plan- and depth-derived)
_SERVE_CFG_KEYS = ("n", "graph", "nnz", "nlayers", "k", "offered_qps",
                   "max_batch")
# sub-graph serving A/B series (PR-14, the serve_subgraph_ab_8dev block):
# the block's `analytic` gauges are computed over a FIXED chunking of the
# seeded query trace (plan-derived, no clock anywhere) — ZERO-band
# counters scoped on (n, nnz, nlayers, k, schedule, max_batch) per
# ROADMAP item 3(d).  The ARMS' per-query figures are NOT counters: they
# ride the open loop's real-clock batch composition (deadline flushes
# vary with host load), so only latency/QPS report-only series come from
# the arms (SERVE_REPORT_KEYS, the PR-7 unit rule).
SUBGRAPH_COUNTER_KEYS = ("full_rows_per_query", "full_flops_per_query",
                         "subgraph_rows_per_query",
                         "subgraph_flops_per_query", "wire_rows_per_query")
_SUBGRAPH_CFG_KEYS = ("n", "nnz", "nlayers", "k", "schedule", "max_batch")
# hot-halo replication A/B series (PR-10 block, registered PR-12): every
# one of these is plan-derived and bit-reproducible at fixed config, so
# they are ZERO-band counters — the measured −11.2% true-rows win is
# regression-gated per round, not asserted once.  Scoped per partition arm
# (random/hp — the partitioner axis lives in the series name) and on the
# block's (n, graph, k, B, sync_every) config.
REPLICA_COUNTER_KEYS = (
    "true_rows_per_exchange", "true_rows_per_exchange_replica",
    "wire_rows_per_exchange", "wire_rows_per_exchange_replica",
    "wire_rows_per_step_noreplica", "wire_rows_per_step_replica",
    "km1", "km1_cache_aware", "replica_rows")
# ONE cfg-key tuple for both replica-family blocks (replica_ab +
# controller_ab share the scoping axes by construction — the controller
# child runs the same fixture shape)
_REPLICA_CFG_KEYS = ("n", "graph", "k", "replica_budget", "sync_every")
# controller A/B series (PR-12 block): the STATIC arms' exposed wire rows
# per step are schedule-derived zero-band counters; the controller arm's
# figure depends on its drift-driven retunes, so it registers REPORT-ONLY
# (a retune threshold flip across jax versions must not read as a counter
# regression) — the per-round winner check lives in validate_bench.
CONTROLLER_COUNTER_KEYS = ("exposed_wire_rows_per_step",)
# kernel × schedule A/B series (ISSUE 15, the pallas_ragged_ab_8dev
# block): per-arm wire rows and analytic halo-table bytes are plan-derived
# and bit-reproducible at fixed config — ZERO-band counters (the
# zero-halo-table contract of the pallas ragged arm is literally a zero
# that may never move); the emulate-mode epoch times stay out entirely
# (CPU kernel-emulation speed is not a tracked claim, unlike the real
# trainers' epoch series).
PALLAS_RAGGED_COUNTER_KEYS = ("wire_rows_per_exchange",
                              "halo_table_bytes_per_step")
_PALLAS_RAGGED_CFG_KEYS = ("n", "graph", "k")
# analytic per-chip HBM footprint series (ISSUE 18, the
# memory_footprint_8dev block): every figure is derived from the CommPlan
# + model config alone (sgcn_tpu.obs.memory — no clock, no compile, no
# allocator anywhere), so the per-mode per-family byte counts are ZERO-band
# counters scoped on the block's (n, nnz, k) — the mode flags live in the
# series name.  A byte that grows at fixed config is a real residency
# regression (a new resident array family), never noise.
_MEMORY_CFG_KEYS = ("n", "nnz", "k")
# scalar bench-config fields that scope a wall-clock series: a round run at
# a different problem size / model / dtype is a DIFFERENT measurement, not
# a regression (graph already keys separately)
_TIME_CFG_KEYS = ("n", "model", "dtype", "layers", "epochs", "partitioner")

DEFAULT_TIME_BAND = 2.0


def _median(vals: list) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def _is_num(x) -> bool:
    # non-finite floats must not enter a series: every NaN comparison is
    # False, so one NaN value (or a NaN-poisoned median anchor) would make
    # the gate read clean forever (validate_bench rejects NaN at the file
    # level; this guards the gate when run standalone)
    return (isinstance(x, numbers.Real) and not isinstance(x, bool)
            and math.isfinite(x))


def load_history(root: str) -> list:
    """``[(round, filename, record)]`` sorted by round number."""
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = ROUND_RE.search(os.path.basename(path))
        if not m:
            continue
        with open(path) as fh:
            out.append((int(m.group(1)), os.path.basename(path),
                        json.load(fh)))
    return sorted(out)


def extract_series(history) -> tuple[dict, list]:
    """Split the history into comparable series and gaps.

    Returns ``(series, gaps)``: ``series`` maps a key tuple to
    ``[(round, value)]`` in round order; ``gaps`` is ``[(round, reason)]``
    for rounds that measured nothing (degradation-marker aware)."""
    series: dict = defaultdict(list)
    gaps: list = []
    for rnd, fname, rec in history:
        if rec.get("rc") != 0:
            gaps.append((rnd, f"rc={rec.get('rc')} "
                              f"(tail: {str(rec.get('tail'))[-60:].strip()})"))
            continue
        parsed = rec.get("parsed")
        if not isinstance(parsed, dict):
            gaps.append((rnd, "no parsed result"))
            continue
        v = parsed.get("value")
        metric = parsed.get("metric", "?")
        if v is None:
            reason = (parsed.get("degraded") or parsed.get("skipped")
                      or "value null")
            gaps.append((rnd, f"{metric}: {reason}"))
            continue    # a degraded round is a GAP for its counters too —
            #             a partial diagnostic must not enter the zero-band
            #             series either
        elif _is_num(v):
            # only wall-clock values (unit "s", lower-is-better by
            # construction) are gate-able; other units form report-only
            # series — a throughput metric improving upward must not trip
            # the band
            unit = parsed.get("unit", "s")
            kind = "time" if unit == "s" else "metric"
            cfg = tuple(None if (c := parsed.get(k)) == "none" else c
                        for k in _TIME_CFG_KEYS)
            key = (kind, metric, parsed.get("graph", "er"), unit) + cfg
            series[key].append((rnd, float(v)))
        # deterministic 8-dev diagnostic counters, scoped to their config
        cfg = tuple(parsed.get(k) for k in _DIAG_CFG_KEYS)
        if any(c is not None for c in cfg):
            for ck in COUNTER_KEYS:
                if _is_num(parsed.get(ck)):
                    series[("counter", ck) + cfg].append(
                        (rnd, float(parsed[ck])))
        # hot-halo replication A/B: zero-band plan-derived counters per
        # partition arm (see REPLICA_COUNTER_KEYS)
        rb = parsed.get("replica_ab_8dev")
        if isinstance(rb, dict):
            rcfg = tuple(rb.get(k) for k in _REPLICA_CFG_KEYS)
            for part in ("random", "hp"):
                e = rb.get(part)
                if not isinstance(e, dict):
                    continue
                for ck in REPLICA_COUNTER_KEYS:
                    if _is_num(e.get(ck)):
                        series[("counter", f"replica_{part}_{ck}")
                               + rcfg].append((rnd, float(e[ck])))
        # controller A/B: static arms zero-band, controller arm report-only
        cb = parsed.get("controller_ab_8dev")
        if isinstance(cb, dict) and isinstance(cb.get("arms"), dict):
            ccfg = tuple(cb.get(k) for k in _REPLICA_CFG_KEYS)
            for arm, e in cb["arms"].items():
                if not isinstance(e, dict):
                    continue
                for ck in CONTROLLER_COUNTER_KEYS:
                    if not _is_num(e.get(ck)):
                        continue
                    kind = ("metric" if arm == "controller" else "counter")
                    key = ((kind, f"controller_{arm}_{ck}", "controller",
                            "rows") + ccfg if kind == "metric"
                           else (kind, f"controller_{arm}_{ck}") + ccfg)
                    series[key].append((rnd, float(e[ck])))
        # kernel × schedule A/B: zero-band plan-derived counters per arm
        # (see PALLAS_RAGGED_COUNTER_KEYS — the zero-halo-table contract)
        pb = parsed.get("pallas_ragged_ab_8dev")
        if isinstance(pb, dict):
            pcfg = tuple(pb.get(k) for k in _PALLAS_RAGGED_CFG_KEYS)
            for arm in ("ell_ragged", "pallas_ragged", "pallas_a2a"):
                e = pb.get(arm)
                if not isinstance(e, dict):
                    continue
                for ck in PALLAS_RAGGED_COUNTER_KEYS:
                    if _is_num(e.get(ck)):
                        series[("counter", f"pallas_ragged_{arm}_{ck}")
                               + pcfg].append((rnd, float(e[ck])))
        # serving-bench series (see SERVE_* docstrings above): per transport
        # arm, report-only QPS + GATED latency + zero-band wire-row counters
        sv = parsed.get("serve_qps_8dev")
        if isinstance(sv, dict) and isinstance(sv.get("arms"), dict):
            scfg = tuple(sv.get(k) for k in _SERVE_CFG_KEYS)
            for arm, e in sv["arms"].items():
                if not isinstance(e, dict):
                    continue
                for rk in SERVE_REPORT_KEYS:
                    if _is_num(e.get(rk)):
                        series[("metric", f"serve_{arm}_{rk}", "serve",
                                rk.rsplit("_", 1)[-1]) + scfg].append(
                            (rnd, float(e[rk])))
                for rk in SERVE_LATENCY_KEYS:
                    if _is_num(e.get(rk)):
                        series[("latency", f"serve_{arm}_{rk}", "serve",
                                "ms") + scfg].append((rnd, float(e[rk])))
                for ck in SERVE_COUNTER_KEYS:
                    if _is_num(e.get(ck)):
                        series[("counter", f"serve_{arm}_{ck}")
                               + scfg].append((rnd, float(e[ck])))
        # sub-graph serving A/B: zero-band DETERMINISTIC analytic counters
        # from the fixed-chunking block + report-only latency/QPS from the
        # measured arms (see SUBGRAPH_COUNTER_KEYS)
        sg = parsed.get("serve_subgraph_ab_8dev")
        if isinstance(sg, dict):
            gcfg = tuple(sg.get(k) for k in _SUBGRAPH_CFG_KEYS)
            for arm, e in (sg.get("arms") or {}).items():
                if not isinstance(e, dict):
                    continue
                for rk in SERVE_REPORT_KEYS:
                    if _is_num(e.get(rk)):
                        series[("metric", f"serve_subgraph_{arm}_{rk}",
                                "serve", rk.rsplit("_", 1)[-1])
                               + gcfg].append((rnd, float(e[rk])))
                for rk in SERVE_LATENCY_KEYS:
                    if _is_num(e.get(rk)):
                        series[("latency", f"serve_subgraph_{arm}_{rk}",
                                "serve", "ms") + gcfg].append(
                            (rnd, float(e[rk])))
            det = sg.get("analytic")
            if isinstance(det, dict):
                for ck in SUBGRAPH_COUNTER_KEYS:
                    if _is_num(det.get(ck)):
                        series[("counter", f"serve_subgraph_{ck}")
                               + gcfg].append((rnd, float(det[ck])))
        # analytic per-chip HBM footprint gauges (see _MEMORY_CFG_KEYS):
        # zero-band counters — plan-derived bytes per (mode, array family)
        mf = parsed.get("memory_footprint_8dev")
        if isinstance(mf, dict) and isinstance(mf.get("modes"), dict):
            mcfg = tuple(mf.get(k) for k in _MEMORY_CFG_KEYS)
            for mid, e in mf["modes"].items():
                if not isinstance(e, dict):
                    continue
                for ck, v in sorted(e.items()):
                    if ck.endswith("_bytes") and _is_num(v):
                        series[("counter", f"memory_{mid}_{ck}")
                               + mcfg].append((rnd, float(v)))
    return dict(series), gaps


def check_series(series: dict, time_band: float = DEFAULT_TIME_BAND) -> list:
    """Gate the newest point of every multi-point series against its band;
    returns violation strings (empty = clean)."""
    problems = []
    # cfg slots mix None/str/int — sort on the stringified key
    for key, pts in sorted(series.items(),
                           key=lambda kv: tuple(map(str, kv[0]))):
        if len(pts) < 2:
            continue
        prev, (last_rnd, last) = pts[:-1], pts[-1]
        best = min(v for _, v in prev)
        kind = key[0]
        if kind == "metric":
            continue        # non-"s" units: reported, never gated (no
            #                 universal better-direction for them)
        if kind in ("time", "latency"):
            # median anchor: a single lucky fast point must not tighten
            # the gate forever, and the band must clear the 1.665x
            # cross-session drift recorded in rounds 3-5.
            # "latency" is the serve-quantile flavor (ms, lower-is-better
            # like "s" — gated since ISSUE 18 once r01–r05 set the anchor)
            anchor = _median([v for _, v in prev])
            limit = anchor * time_band
            if last > limit:
                what = ("a serve-latency regression"
                        if kind == "latency"
                        else "a measured-time regression")
                problems.append(
                    f"{_key_name(key)}: r{last_rnd:02d} value {last:g} "
                    f"exceeds the {time_band}x band over the median "
                    f"previous point {anchor:g} (limit {limit:g}) — "
                    f"{what} landed in the bench history")
        else:
            if last > best:
                problems.append(
                    f"{_key_name(key)}: r{last_rnd:02d} value {last:g} "
                    f"above the best previous {best:g} — deterministic "
                    "plan-derived counters may never regress within one "
                    "config")
    return problems


def _key_name(key: tuple) -> str:
    if key[0] in ("metric", "latency") and len(key) > 2 and key[2] == "serve":
        names = (_SUBGRAPH_CFG_KEYS
                 if key[1].startswith("serve_subgraph_")
                 else _SERVE_CFG_KEYS)
        cfg = [f"{k}={c}" for k, c in zip(names, key[4:])
               if c is not None]
        return f"{key[1]} ({key[3]}" \
               + (", " + ", ".join(cfg) if cfg else "") + ")"
    if key[0] == "metric" and len(key) > 2 and key[2] == "controller":
        cfg = [f"{k}={c}" for k, c in zip(_REPLICA_CFG_KEYS, key[4:])
               if c is not None]
        return f"{key[1]} (report-only" \
               + (", " + ", ".join(cfg) if cfg else "") + ")"
    if key[0] == "counter" and key[1].startswith("serve_subgraph_"):
        cfg = [f"{k}={c}" for k, c in zip(_SUBGRAPH_CFG_KEYS, key[2:])
               if c is not None]
        return f"{key[1]} ({', '.join(cfg)})"
    if key[0] == "counter" and key[1].startswith("serve_"):
        cfg = [f"{k}={c}" for k, c in zip(_SERVE_CFG_KEYS, key[2:])
               if c is not None]
        return f"{key[1]} ({', '.join(cfg)})"
    if key[0] == "counter" and key[1].startswith("memory_"):
        cfg = [f"{k}={c}" for k, c in zip(_MEMORY_CFG_KEYS, key[2:])
               if c is not None]
        return f"{key[1]} ({', '.join(cfg)})"
    if key[0] == "counter" and key[1].startswith(("replica_",
                                                   "controller_")):
        cfg = [f"{k}={c}" for k, c in zip(_REPLICA_CFG_KEYS, key[2:])
               if c is not None]
        return f"{key[1]} ({', '.join(cfg)})"
    if key[0] in ("time", "metric"):
        cfg = [f"{k}={c}" for k, c in zip(_TIME_CFG_KEYS, key[4:])
               if c is not None]
        return f"{key[1]} (graph={key[2]}, {key[3]}" \
               + (", " + ", ".join(cfg) if cfg else "") + ")"
    return f"{key[1]} ({', '.join(str(c) for c in key[2:] if c is not None)})"


def render(series: dict, gaps: list, problems: list) -> str:
    lines = ["bench trend:"]
    for key, pts in sorted(series.items(),
                           key=lambda kv: tuple(map(str, kv[0]))):
        trail = "  ".join(f"r{r:02d}={v:g}" for r, v in pts)
        lines.append(f"  {_key_name(key)}: {trail}")
        if len(pts) >= 2:
            first, last = pts[0][1], pts[-1][1]
            if first > 0:
                # report-only series (kind "metric") have no universal
                # better-direction — label the trend neutrally
                word = ("change" if key[0] == "metric"
                        else "improvement" if last <= first
                        else "regression")
                lines.append(f"    net {word}: "
                             f"{first:g} -> {last:g} ({last / first:.3g}x)")
    if gaps:
        lines.append("  gaps (degraded/skipped rounds, never compared):")
        for rnd, reason in gaps:
            lines.append(f"    r{rnd:02d}: {reason}")
    if problems:
        lines.append(f"  VIOLATIONS ({len(problems)}):")
        for p in problems:
            lines.append(f"    {p}")
    else:
        lines.append("  gate: clean")
    return "\n".join(lines)


def check_tree(root: str, time_band: float = DEFAULT_TIME_BAND):
    """Full pipeline for one root: ``(problems, report_text)``."""
    series, gaps = extract_series(load_history(root))
    problems = check_series(series, time_band=time_band)
    return problems, render(series, gaps, problems)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("root", nargs="?",
                    default=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                    help="directory holding the BENCH_r*.json history")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero on tolerance-band violations "
                         "(the tier-1 gate mode)")
    ap.add_argument("--time-band", type=float, default=DEFAULT_TIME_BAND,
                    help="multiplicative band for measured wall-clock "
                         "series (newest <= band x median previous); "
                         f"default {DEFAULT_TIME_BAND}")
    args = ap.parse_args()
    problems, report = check_tree(args.root, time_band=args.time_band)
    print(report)
    if args.check and problems:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
