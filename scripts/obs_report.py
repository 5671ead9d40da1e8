"""Render a run-telemetry directory into a human-readable summary.

Usage::

    python scripts/obs_report.py RUNDIR [--steps N]

Loads (and schema-validates) the directory written by ``--metrics-out``
(trainer and serve CLIs) and prints:

  * the manifest header (run kind, config highlights, git rev, backend,
    plan digest + partitioner provenance);
  * the step table: loss / grad-norm / wall-time statistics, roofline
    utilization, the hidden-vs-exposed comm split, and — for stale-halo
    runs — the drift-gauge columns (staleness age, per-layer drift,
    quantization error);
  * the measured-time layer (schema v2, ``sgcn_tpu/obs/tracing.py``):
    span breakdown (per-name count/total, nesting), the per-step
    ``measured_vs_model`` reconciliation (ratio + absolute error per
    component), and — when the manifest records a ``--profile`` trace —
    the trace-derived attribution: per-class op seconds, measured overlap
    fraction / exposed-comm time, per-device straggler skew, joined
    against the analytic exposed-comm fraction;
  * eval records, summary report, and the heartbeat timeline (the
    "slow vs stalled" signal of the launch/dryrun layers).

Read-only; a run directory that fails validation prints the schema error
and exits non-zero — this script is also the quickest way to check one.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fmt(x, nd=4):
    if x is None:
        return "-"
    if isinstance(x, float):
        return f"{x:.{nd}g}"
    return str(x)


def _stats(vals):
    if not vals:
        return "-"
    lo, hi = min(vals), max(vals)
    mean = sum(vals) / len(vals)
    return f"{_fmt(mean)} (min {_fmt(lo)}, max {_fmt(hi)})"


def render(path: str, max_steps: int = 12) -> str:
    from sgcn_tpu.obs import load_run

    log = load_run(path)
    m = log.manifest
    lines = [f"run: {path}"]
    if m:
        lines.append(f"  kind={m['run_kind']}  schema=v{m['v']}  "
                     f"git={(m.get('git_rev') or '?')[:10]}")
    else:
        lines.append("  (no manifest — heartbeats/spans written through "
                     "$SGCN_METRICS_OUT without a RunRecorder, e.g. the "
                     "launch/dryrun layers or a killed bench)")
    be = m.get("backend")
    if be:
        lines.append(f"  backend: {be.get('platform')} × "
                     f"{be.get('device_count')} devices, "
                     f"{be.get('process_count')} process(es)")
    pl = m.get("plan")
    if pl:
        lines.append(
            f"  plan: n={pl['n']} k={pl['k']} b={pl['b']} r={pl['r']} "
            f"symmetric={pl['symmetric']} digest={pl['digest']}")
        lines.append(
            f"        send rows/exchange={pl['send_rows_per_exchange']} "
            f"messages/exchange={pl['messages_per_exchange']}")
    pt = m.get("partitioner")
    if pt:
        lines.append("  partitioner: "
                     + " ".join(f"{k}={v}" for k, v in pt.items()))
    cfg = m.get("config", {})
    knobs = {k: cfg[k] for k in ("model", "loss", "halo_staleness",
                                 "halo_delta", "sync_every", "dtype",
                                 "halo_dtype", "epochs", "batch_size")
             if cfg.get(k)}
    if knobs:
        lines.append("  config: "
                     + " ".join(f"{k}={v}" for k, v in knobs.items()))
    cs = m.get("comm_schedule")
    if cs:
        # the transport-selection decision log (resolve_comm_schedule) —
        # how an 'auto' pick is reconstructible from the run dir alone
        lines.append(f"  comm schedule: {cs.get('asked')} -> "
                     f"{cs.get('resolved')} ({cs.get('rule')})")
        if cs.get("wire_rows_a2a") is not None:
            lines.append(
                f"    scored wire rows/exchange: a2a "
                f"{cs['wire_rows_a2a']}, ragged "
                f"{cs.get('wire_rows_ragged')} (true {cs.get('true_rows')})")
        if cs.get("replica_budget"):
            lines.append(
                f"    replica-aware (B={cs['replica_budget']}, "
                f"{cs.get('replica_rows', '?')} rows): shrunken wire "
                f"a2a {cs.get('wire_rows_a2a_replica', '?')}, ragged "
                f"{cs.get('wire_rows_ragged_replica', '?')} (true "
                f"{cs.get('true_rows_replica', '?')})")
        pd = cs.get("pallas_dispatch")
        if pd:
            # per-degree-bucket kernel choice of the Pallas family
            # (ISSUE 15; docs/comm_schedule.md)
            fams = [(k, pd[k]) for k in ("local", "halo", "combined")
                    if pd.get(k)]
            lines.append(
                f"    pallas dispatch ({pd.get('model')}, tb="
                f"{pd.get('tb')}, emax cap {pd.get('emax_cap')}): "
                + "; ".join(
                    f"{name} [" + " ".join(
                        f"{c.get('tiles')}x{c.get('emax')}:"
                        f"{c.get('kernel')}" for c in classes) + "]"
                    for name, classes in fams))
        ra = cs.get("replica_auto")
        if ra:
            lines.append(
                f"    replica budget auto ({ra.get('rule')}): B="
                f"{ra.get('chosen')} of {ra.get('boundary_rows')} boundary "
                f"rows, λ·degree score covered "
                f"{_fmt(ra.get('score_covered'))}")
        ctl = cs.get("controller")
        if ctl:
            lines.append(
                f"    controller ({ctl.get('kind')}): band "
                f"{ctl.get('band')}, sync_every "
                f"{ctl.get('initial_sync_every')} -> "
                f"{ctl.get('sync_every')}, {len(ctl.get('retunes', []))} "
                "retune(s)")
            for d in ctl.get("retunes", []):
                old, new = (d.get("sync_every") or ["?", "?"])[:2]
                lines.append(
                    f"      step {d.get('step')}: drift_rel_max "
                    f"{_fmt(d.get('drift_rel_max'))} {d.get('rule')} — "
                    f"sync_every {old} -> {new}")

    mem = m.get("memory") if m else None
    if mem:
        # the per-chip HBM footprint reconciliation (schema v6,
        # docs/observability.md): analytic model per array family, joined
        # against XLA's memory_analysis() when a compile was measured
        tot = mem.get("total", {})
        lines.append(
            "  memory (per-chip analytic model"
            + (", measured join" if tot.get("measured_bytes") is not None
               else "") + "):")
        fams = sorted((mem.get("families") or {}).items(),
                      key=lambda kv: -(kv[1].get("model_bytes") or 0))
        for name, row in fams:
            mb = row.get("model_bytes")
            if not mb:
                continue
            lines.append(f"    {name:<16s} {mb:>12,} B")
        for label, row in (("TOTAL", tot),
                           ("arguments", mem.get("arguments", {})),
                           ("donated", mem.get("donated", {}))):
            if row.get("model_bytes") is None:
                continue
            joined = (f"  measured {row['measured_bytes']:,} B "
                      f"(ratio {_fmt(row.get('ratio'), 2)})"
                      if row.get("measured_bytes") is not None else "")
            lines.append(f"    {label:<16s} {row['model_bytes']:>12,} B"
                         + joined)

    steps = log.steps()
    if steps:
        lines.append(f"\nsteps: {len(steps)}")
        lines.append("  loss:      first " + _fmt(steps[0]["loss"])
                     + " → last " + _fmt(steps[-1]["loss"]))
        gn = [s["grad_norm"] for s in steps if s.get("grad_norm") is not None]
        if gn:
            lines.append("  grad_norm: " + _stats(gn))
        lines.append("  wall_s:    "
                     + _stats([s["wall_s"] for s in steps]))
        roofs = [s["roofline"] for s in steps if s.get("roofline")]
        if roofs:
            line = ("  roofline:  gather "
                    + _stats([r["achieved_gather_GBs"] for r in roofs])
                    + " GB/s")
            # emitted only on the device kind the ceiling was stated for
            fracs = [r["stream_ceiling_frac"] for r in roofs
                     if "stream_ceiling_frac" in r]
            if fracs:
                line += ", stream-ceiling frac " + _stats(fracs)
            lines.append(line)
            ef = [r["exposed_comm_frac"] for r in roofs
                  if "exposed_comm_frac" in r]
            if ef:
                lines.append("  exposed-comm frac: " + _stats(ef))
        comm = steps[-1].get("comm")
        if comm:
            lines.append(
                f"  comm (cumulative): {comm['exchanges']} exchanges = "
                f"{comm['exposed_exchanges']} exposed + "
                f"{comm['hidden_exchanges']} hidden; send rows "
                f"{comm['total_send_volume']} = "
                f"{comm['exposed_send_volume']} + "
                f"{comm['hidden_send_volume']}")
            if "wire_rows_per_exchange" in comm:
                # padded-vs-true split of the selected exchange schedule
                # (docs/comm_schedule.md)
                lines.append(
                    f"  wire ({comm.get('comm_schedule', 'a2a')} schedule): "
                    f"{comm['wire_rows_per_exchange']} padded rows/exchange "
                    f"for {comm.get('true_rows_per_exchange', '?')} true — "
                    f"padding efficiency "
                    f"{_fmt(comm.get('padding_efficiency'), 3)}")
        drifts = [s["drift"] for s in steps if s.get("drift")]
        if drifts:
            lines.append("\ndrift gauges (stale-halo mode):")
            nl = len(drifts[-1]["halo_drift_rms"])
            lines.append("  staleness age: last "
                         + str(drifts[-1]["staleness_age"]) + ", max "
                         + str(max(d["staleness_age"] for d in drifts)))
            ages = [d["round_age"] for d in drifts
                    if d.get("round_age") is not None]
            if ages:
                # composed stale × ragged mode: per-round consumed-buffer
                # age ("-" = empty round, ships nothing)
                live = sum(1 for x in ages[-1] if x is not None)
                max_age = max((x for ra in ages for x in ra
                               if x is not None), default=0)
                lines.append(
                    "  round ages (ragged ring): last ["
                    + " ".join("-" if x is None else str(x)
                               for x in ages[-1])
                    + f"]  ({live}/{len(ages[-1])} live rounds, "
                    + f"max age {max_age})")
            for layer in range(nl):
                dr = [d["halo_drift_rms"][layer] for d in drifts]
                rel = [d["halo_drift_rel"][layer] for d in drifts]
                qe = [d["halo_quant_err_rms"][layer] for d in drifts]
                lines.append(f"  layer {layer}: ‖stale−fresh‖ " + _stats(dr)
                             + f", relative {_fmt(rel[-1])} (last)"
                             + (f", quant-err {_stats(qe)}"
                                if any(qe) else ""))
        reps = [s["replica"] for s in steps if s.get("replica")]
        if reps:
            # hot-halo replication (--replica-budget, docs/replication.md):
            # drift is measured AT each refresh (the drift the refresh
            # erased) — between refreshes no fresh value exists to compare
            lines.append("\nreplica gauges (hot-halo replication):")
            last = reps[-1]
            lines.append(
                f"  replica rows: {last['replica_rows']}; refresh age: "
                f"last {last['refresh_age']}, max "
                + str(max(r["refresh_age"] for r in reps)))
            syncs = [r for r in reps if r.get("sync_step")]
            if syncs:
                for layer in range(len(last["replica_drift_rms"])):
                    dr = [r["replica_drift_rms"][layer] for r in syncs]
                    rel = [r["replica_drift_rel"][layer] for r in syncs]
                    lines.append(
                        f"  layer {layer}: ‖replica−fresh‖ at refresh "
                        + _stats(dr) + f", relative {_fmt(rel[-1])} (last)")
            partials = [r for r in reps if r.get("refresh_kind") == "partial"]
            if partials:
                # drift-banded partial refresh (--refresh-band): the
                # actually-shipped side-channel rows per refresh — the
                # per-step face of CommStats' partial_refresh_* totals
                shipped = [sum(r["refresh_rows"]) for r in partials]
                lines.append(
                    f"  partial refreshes: {len(partials)}, shipped "
                    f"rows/refresh " + _stats(shipped)
                    + f" (side-channel wire rows "
                    f"{partials[-1].get('refresh_wire_rows')})")
        hdr = (" step      loss  grad_norm    wall_s  exposed  age"
               "  drift_rms(last layer)")
        lines.append("\n" + hdr)
        show = steps if len(steps) <= max_steps else (
            steps[: max_steps // 2] + [None] + steps[-max_steps // 2:])
        for s in show:
            if s is None:
                lines.append("  ...")
                continue
            d = s.get("drift") or {}
            r = s.get("roofline") or {}
            lines.append(
                f" {s['step']:>4} {_fmt(s['loss'], 6):>9} "
                f"{_fmt(s.get('grad_norm'), 4):>10} "
                f"{_fmt(s['wall_s'], 4):>9} "
                f"{_fmt(r.get('exposed_comm_frac'), 3):>8} "
                f"{_fmt(d.get('staleness_age')):>4} "
                f"{_fmt((d.get('halo_drift_rms') or [None])[-1], 4):>10}")

    # ------------------------------------------- memory reconciliation (v6)
    mems = [e for e in log.events if e["kind"] == "memory"]
    if mems:
        lines.append(f"\nmemory events (per compiled program): {len(mems)}")
        for ev in mems:
            joined = ""
            if ev.get("measured_peak_bytes") is not None:
                joined = (f"  measured peak {ev['measured_peak_bytes']:,} B"
                          f" (ratio {_fmt(ev.get('ratio'), 2)})")
            lines.append(
                f"  {ev.get('workload', '?')}/{ev['program']}: model "
                f"{ev['model_bytes']:,} B" + joined)

    # ---------------------------------------------- measured-time layer (v2)
    spans = [e for e in log.events if e["kind"] == "span"]
    if spans:
        lines.append(f"\nspans: {len(spans)}")
        by_name: dict = {}
        for sp in spans:
            agg = by_name.setdefault(sp["name"], [0, 0.0, 0])
            agg[0] += 1
            agg[1] += sp["dur_s"]
            agg[2] = max(agg[2], int(sp.get("depth", 0)))
        for name, (cnt, tot, depth) in sorted(by_name.items(),
                                              key=lambda kv: -kv[1][1]):
            lines.append(f"  {name}: n={cnt} total {_fmt(tot)}s "
                         f"avg {_fmt(tot / cnt)}s"
                         + (f" (max depth {depth})" if depth else ""))
    if steps:
        mvms = [s["measured_vs_model"] for s in steps
                if isinstance(s.get("measured_vs_model"), dict)]
        if mvms:
            lines.append("\nmeasured vs model (per-step reconciliation):")
            lines.append("  phase total: "
                         + _stats([m["phase_total_s"] for m in mvms]) + " s")
            for comp in mvms[-1]["components"]:
                ratios = [m["components"][comp]["ratio"] for m in mvms
                          if m["components"].get(comp, {}).get("ratio")
                          is not None]
                last = mvms[-1]["components"][comp]
                lines.append(
                    f"  {comp}: model {_fmt(last.get('model_s'))}s, "
                    f"measured {_fmt(last.get('measured_s'))}s (last)"
                    + (f"; ratio {_stats(ratios)}" if ratios else ""))
    # even a manifest-less dir (killed bench) resolves a trace copied under
    # the run dir — trace_path_for_run's last-resort rundir glob
    from sgcn_tpu.obs.tracing import summarize_trace, trace_path_for_run
    tpath = trace_path_for_run(m or {}, path)
    if tpath:
        try:
            ts = summarize_trace(tpath)
        except (OSError, ValueError, KeyError) as e:
            lines.append(f"\ntrace: {tpath} failed to parse: {e}")
            ts = None
        if ts is not None:
            lines.append(f"\ntrace ({os.path.basename(tpath)}, "
                         f"{ts.n_events} classified ops):")
            lines.append("  measured op classes: " + "  ".join(
                f"{c}={_fmt(ts.classes.get(c, 0.0))}s"
                for c in ("spmm", "dense", "exchange", "collective_wait",
                          "other")))
            roofs = [s["roofline"] for s in steps if s.get("roofline")]
            ef = [r["exposed_comm_frac"] for r in roofs
                  if "exposed_comm_frac" in r]
            if ts.measured_overlap_frac is not None:
                lines.append(
                    f"  comm: {_fmt(ts.comm_s)}s wall, "
                    f"{_fmt(ts.exposed_comm_s)}s exposed — measured "
                    f"overlap frac {_fmt(ts.measured_overlap_frac, 3)}")
                if ef:
                    lines.append(
                        "  vs analytic exposed-comm frac "
                        f"{_fmt(sum(ef) / len(ef), 3)} (event-stream mean) "
                        "— the measured-vs-model overlap join")
            if steps:
                per = ts.per_step(len(steps))
                lines.append("  per step (/" + str(len(steps)) + "): "
                             + "  ".join(
                                 f"{k}={_fmt(v)}s"
                                 for k, v in per.items() if v))
                # the exchange component of measured_vs_model, joined
                # post-hoc (the trace only exists after the run): the ONE
                # join implementation lives in tracing.exchange_join
                from sgcn_tpu.obs.tracing import exchange_join
                ehb = [r["exposed_halo_bytes"] for r in roofs
                       if "exposed_halo_bytes" in r]
                if ehb:
                    j = exchange_join(per, sum(ehb) / len(ehb))
                    line = (f"  exchange join: model {_fmt(j['model_s'])}s "
                            f"vs measured {_fmt(j['measured_s'])}s per step")
                    if "ratio" in j:
                        line += f" (ratio {_fmt(j['ratio'], 3)})"
                    nevals = len(log.evals())
                    if nevals:
                        # eval forward passes share the profiled region but
                        # are not steps — their collectives inflate the
                        # measured side, so it is an upper bound here
                        line += (f" [{nevals} evals in trace — measured is "
                                 "an upper bound]")
                    lines.append(line)
            if ts.skew:
                lines.append(
                    f"  straggler: {ts.skew['straggler']} at "
                    f"{_fmt(ts.skew['busy_max_over_mean'], 4)}x mean busy "
                    "(per-device skew gauge)")

    # ------------------------------------------------ resilience layer (v4)
    ckpts, resumes = log.checkpoints(), log.resumes()
    if ckpts or resumes:
        lines.append(f"\nresilience: {len(ckpts)} checkpoint(s), "
                     f"{len(resumes)} resume(s) (docs/resilience.md)")
        for rv in resumes:
            tag = []
            if rv.get("fallback"):
                tag.append("FELL BACK past corrupt newest: "
                           + ", ".join(os.path.basename(s)
                                       for s in rv.get("skipped", [])))
            if rv.get("partial_state"):
                tag.append("PARTIAL STATE (params-only)")
            lines.append(
                f"  resume @ step {int(rv['step'])} from "
                f"{os.path.basename(rv['path'])}"
                + (f"  [{'; '.join(tag)}]" if tag else ""))
        if ckpts:
            last = ckpts[-1]
            saves = [c["wall_s"] for c in ckpts if c.get("wall_s")
                     is not None]
            lines.append(
                f"  last checkpoint: step {int(last['step'])} → "
                f"{os.path.basename(last['path'])}"
                + (f" ({int(last['bytes'])} bytes)"
                   if last.get("bytes") is not None else "")
                + (f"; save wall_s " + _stats(saves) if saves else ""))

    serves = log.serves()
    if serves:
        lines.append(f"\nserve windows: {len(serves)} "
                     "(sgcn_tpu/serve latency gauges, schema v3)")
        for sv in serves:
            line = (f"  {int(sv['queries'])} queries @ "
                    f"{_fmt(sv['achieved_qps'])} QPS achieved"
                    + (f" (offered {_fmt(sv['offered_qps'])}, "
                       f"{sv.get('mode', '?')} loop)"
                       if sv.get("offered_qps") is not None else
                       f" ({sv.get('mode', '?')} loop)"))
            lines.append(line)
            lines.append(
                f"    latency ms: p50 {_fmt(sv['latency_p50_ms'])}  "
                f"p95 {_fmt(sv['latency_p95_ms'])}  "
                f"p99 {_fmt(sv['latency_p99_ms'])}"
                + (f"  (budget {_fmt(sv['latency_budget_ms'])})"
                   if sv.get("latency_budget_ms") is not None else ""))
            if sv.get("batches") is not None:
                lines.append(
                    f"    batches {int(sv['batches'])} "
                    f"(mean {_fmt(sv.get('mean_batch'))} queries; "
                    f"{int(sv.get('full_flushes', 0))} full / "
                    f"{int(sv.get('deadline_flushes', 0))} deadline "
                    "flushes)")
            if sv.get("compiles") is not None:
                lines.append(
                    f"    compiles {int(sv['compiles'])} over buckets "
                    f"{sv.get('buckets')} — steady-state windows must "
                    "show 0 (the no-recompile contract)")
            if sv.get("shed") is not None:
                # deadline shedding (docs/resilience.md): overdue queries
                # returned as explicit shed markers instead of silently
                # blowing the published p99
                lines.append(
                    f"    shed {int(sv['shed'])} quer"
                    f"{'y' if sv['shed'] == 1 else 'ies'} past "
                    f"{_fmt(sv.get('shed_factor'))}× the latency budget "
                    "before dispatch (explicit markers, not p99 outliers)")
            if sv.get("wire_rows_per_query") is not None:
                lines.append(
                    f"    wire ({sv.get('comm_schedule', '?')} schedule): "
                    f"{_fmt(sv['wire_rows_per_query'])} rows/query "
                    "(analytic, plan-derived)")
            if sv.get("serve_mode") is not None:
                # v5: engine mode + weight revision + the sub-graph
                # engine's accumulated per-query analytic gauges
                extra = ""
                if sv.get("touched_rows_per_query") is not None:
                    extra = (f"; {_fmt(sv['touched_rows_per_query'])} "
                             "touched rows/query, "
                             f"{_fmt(sv.get('subgraph_flops_per_query'))} "
                             "FLOPs/query (analytic)")
                lines.append(
                    f"    engine: {sv['serve_mode']} mode, weights rev "
                    f"{int(sv.get('weights_rev', 0))}{extra}")

    swaps = [e for e in log.events if e.get("kind") == "swap"]
    if swaps:
        lines.append(f"\nweight hot-swaps: {len(swaps)} (zero-recompile, "
                     "schema v5)")
        for sw in swaps:
            lines.append(
                f"  rev {int(sw['weights_rev'])} ← "
                f"{os.path.basename(sw['path'])}"
                + (f" (ckpt step {int(sw['checkpoint_step'])})"
                   if sw.get("checkpoint_step") is not None else "")
                + (f", {_fmt(sw['wall_s'])} s"
                   if sw.get("wall_s") is not None else ""))

    for ev in log.evals():
        lines.append(f"\neval @ step {ev['step']}: loss {_fmt(ev['loss'])}"
                     + (f", acc {_fmt(ev['acc'])}" if "acc" in ev else ""))
    for sm in log.summaries():
        rep = sm["report"]
        keys = [k for k in ("metric", "value", "unit", "epochs", "epoch_s",
                            "err", "total_send_volume") if k in rep]
        lines.append("\nsummary: "
                     + " ".join(f"{k}={_fmt(rep[k])}" for k in keys))
    if log.heartbeats:
        lines.append(f"\nheartbeats: {len(log.heartbeats)}")
        t0 = log.heartbeats[0]["ts"]
        for hb in log.heartbeats[-20:]:
            lines.append(f"  +{hb['ts'] - t0:8.2f}s  pid {hb.get('pid')}  "
                         f"{hb['event']}"
                         + (f" — {hb['detail']}" if hb.get("detail") else ""))
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("rundir", help="directory written by --metrics-out")
    ap.add_argument("--steps", type=int, default=12,
                    help="max rows in the per-step table (head+tail)")
    args = ap.parse_args()
    try:
        print(render(args.rundir, max_steps=args.steps))
    except (OSError, ValueError) as e:
        print(f"obs_report: {args.rundir} failed to load: {e}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
