"""Schema-validate the checked-in bench evidence files.

Usage::

    python scripts/validate_bench.py [ROOT]      # default: repo root

Validates every ``BENCH_*.json`` / ``MULTICHIP_*.json`` at the root and
every ``bench_artifacts/*.json``, and exits non-zero listing each
violation.  Run by the tier-1 suite (``tests/test_validate_bench.py``), so
a hand-edited or wrongly-shaped artifact fails CI instead of silently
poisoning the evidence chain.

What counts as a violation:

  * **driver records** (``BENCH_*``): missing ``n/cmd/rc/tail``; an rc=0
    record without a parseable one-line result (``parsed``); a result with
    ``value: null`` but NO ``skipped``/``degraded`` marker — the graceful-
    degradation contract says a missing number must explain itself;
  * **measurement quality**: a ``measurement`` block claiming more clean
    differential estimates than were targeted (impossible by construction
    — a hand-edit tell);
  * **dryrun records** (``MULTICHIP_*``): ``ok: true`` with a non-zero rc,
    or ``ok: false`` with no ``skipped``/``degraded`` explanation;
  * **non-standard JSON**: ``NaN``/``Infinity`` tokens — ``json.dumps``
    emits them for non-finite floats, but they are not valid JSON and no
    checked-in artifact may carry them;
  * **ragged-schedule accounting** (PR-4; GAT flavor PR-5): a flagship
    result carrying ``comm_schedule`` must name a resolved schedule (never
    ``auto``); a ``ragged_ab_8dev`` / ``gat_ragged_ab_8dev`` A/B block must
    either be a per-partition dict whose configs carry positive timings,
    ``padding_efficiency`` in (0, 1], a padded/true ratio ≥ 1, and
    ``wire_rows_ragged ≤ wire_rows_a2a`` (per-round pads can never exceed
    the global pad — a violation is a hand-edit tell; the GAT block's hp
    config must win STRICTLY — the satellite's acceptance figure, asserted
    on wire rows, never epoch speed), or be ``null`` WITH a matching
    ``*_degraded`` marker;
  * **composed-mode accounting** (PR-6): a ``ragged_stale_ab_8dev`` block
    must carry all three arms (a2a+stale, ragged+exact, ragged+stale) with
    positive timings and an exposed-comm accounting in which the composed
    arm is ≤ both single levers on the exposed fraction and STRICTLY below
    both on exposed wire rows per step, plus the honest-measurement note
    (CPU-mesh epoch speed is never the asserted figure), or be ``null``
    with a degradation marker;
  * **measured-time provenance** (PR-7): an epoch-time claim (a numeric
    ``value`` on a ``*_epoch_time`` metric) must carry ``measured: true``
    — the flag ``bench.py`` sets only when the number came out of a live
    differential measurement in that process — or a ``skipped``/
    ``degraded`` marker.  Enforced from round ``BENCH_r06`` on (the first round generated after the flag landed; earlier
    records predate the flag and retro-stamping provenance onto history
    would itself be a hand-edit); a ``measured`` flag that is present but
    not literally ``true`` is a violation at ANY round;
  * **memory provenance** (ISSUE 18): any numeric ``*_bytes`` residency
    claim in a bench block must sit under ``analytic: true`` (plan-derived,
    ``sgcn_tpu.obs.memory``) or ``measured: true`` (XLA
    ``memory_analysis()``) provenance — itself or via an enclosing block;
    enforced from round ``BENCH_r06`` on like the measured-time rule, and
    a present-but-untrue ``analytic`` flag is a violation at ANY round;
  * **serving-bench accounting** (PR-8): a ``serve_qps_8dev`` block must
    carry both transport arms with positive achieved QPS, ordered positive
    latency quantiles under ``measured: true`` provenance, compile counters
    within the pre-compiled bucket count (a runtime recompile violates the
    bucket contract), a STRICT ragged-vs-a2a wire-row win on the skewed hp
    partition (the forward-only carry-over of the training schedules'
    acceptance figure — never CPU-mesh latency; the ``note`` says so), or
    be ``null`` with a ``serve_qps_degraded`` marker;
  * **replication accounting** (PR-10): a ``replica_ab_8dev`` block must
    carry ``replica_budget > 0`` and per-partition configs whose shrunken
    figures (replica true/wire rows, cumulative true bytes) never exceed
    the full ones, with the hp config winning STRICTLY on
    ``halo_bytes_true_total`` and wire rows/step (the CaPGNN before/after
    metric — never CPU-mesh epoch speed; the ``note`` says so) and the
    cache-aware km1 ≤ the cache-blind partition's cache objective
    (``check_replica_ab``), or be ``null`` with a ``replica_ab_degraded``
    marker;
  * **static-analysis report** (``bench_artifacts/analysis_report.json``,
    PR-9): a committed report must be a FULL-matrix run (``fast: false``)
    with ``ok: true`` and internally consistent — an ``ok`` flag
    contradicting its own violation lists, a red report committed as
    evidence, or a matrix shrunk below the supported floor are all
    hand-edit tells (``check_analysis_report``);
  * **resume provenance** (PR-13, ``docs/resilience.md``): a parsed result
    claiming a resume must name the checkpoint that seeded it — either the
    trainer CLI's ``resumed: {step, path, fallback}`` block (its identity
    fields validated), or a bare ``resumed: true`` flag WITH a
    ``checkpoint_meta`` ``{step, version}`` block; any other ``resumed``
    value is a violation anywhere, same rule as the ``measured`` flag
    (the provenance flag may only assert a real resume);
  * **the pow2-k RB constraint** (``products_ksweep.json``): ``hp_rb``
    entries at non-power-of-two k, or k < 32.  The PR-2 review incident:
    ``partition_hypergraph_rb`` recurses on k/2 and the auto-select
    (``native/sgcnpart.cpp``) only fires for pow2 k >= 32, so RB results
    at k ∈ {9, 15, 21, 27} were unreproducible with the code at HEAD and
    had to be reverted.  This check makes that class of edit impossible to
    land quietly; if non-pow2 RB support ever lands, regenerate the sweep
    with ``scripts/products_ksweep.py`` and update this rule WITH it.
"""

from __future__ import annotations

import glob
import json
import numbers
import os
import re
import sys

_BENCH_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def _load_strict(path: str):
    """Parse refusing the NaN/Infinity extensions (hand-edit / bad-generator
    tell — not valid JSON, and every reader downstream would choke)."""
    def bad_constant(name):
        raise ValueError(f"non-standard JSON constant {name!r}")

    with open(path) as fh:
        return json.load(fh, parse_constant=bad_constant)


def _is_num(x) -> bool:
    return isinstance(x, numbers.Real) and not isinstance(x, bool)


# first bench round whose driver record must carry epoch-time provenance
# (bench.py emits ``measured: true`` since PR-7; earlier history predates
# the flag, and stamping it onto old records would itself be a hand-edit)
MEASURED_PROVENANCE_SINCE = 6


def check_measured_provenance(rec: dict, round_no: int | None) -> list[str]:
    """The epoch-time provenance rule (module docstring): numeric
    ``*_epoch_time`` values need ``measured: true`` from round
    ``MEASURED_PROVENANCE_SINCE`` on; a present-but-untrue flag is always
    a violation (asserting anything but a live measurement is a lie)."""
    if not isinstance(rec.get("parsed"), dict):
        return []
    parsed = rec["parsed"]
    errs = []
    # flag integrity applies to ANY record carrying the flag — including a
    # failed round (rc != 0): a hand-edited false/yes flag is a lie there
    # too, so only the numeric-claim rule below is rc-gated
    if "measured" in parsed and parsed["measured"] is not True:
        errs.append(f"measured={parsed['measured']!r}: the provenance flag "
                    "may only assert a live measurement (true) — drop it "
                    "or fix the generator")
    if rec.get("rc") != 0:
        return errs
    metric = parsed.get("metric")
    if (isinstance(metric, str) and metric.endswith("_epoch_time")
            and _is_num(parsed.get("value"))
            and parsed.get("measured") is not True
            and not (isinstance(parsed.get("skipped"), str)
                     or isinstance(parsed.get("degraded"), str))
            and (round_no is None
                 or round_no >= MEASURED_PROVENANCE_SINCE)):
        errs.append(f"numeric {metric} value without measured:true "
                    "provenance (or a skipped/degraded marker) — an "
                    "epoch-time claim must say it was measured live "
                    "(bench.py sets the flag; rounds < "
                    f"r{MEASURED_PROVENANCE_SINCE:02d} are grandfathered)")
    return errs


# first bench round whose residency-byte claims must carry provenance
# (bench.py stamps ``analytic: true`` on the memory_footprint_8dev block
# since ISSUE 18; earlier history predates the vocabulary)
MEMORY_PROVENANCE_SINCE = 6


def check_memory_provenance(rec: dict, round_no: int | None) -> list[str]:
    """The memory-provenance rule (ISSUE 18, the residency flavor of the
    epoch-time rule above): any numeric ``*_bytes`` claim in a bench block
    must sit in a dict that — itself or via an enclosing block — declares
    how the number was obtained: ``analytic: true`` (derived purely from
    the CommPlan + model config, ``sgcn_tpu.obs.memory``) or ``measured:
    true`` (XLA's own ``compiled.memory_analysis()``).  A residency byte
    with neither provenance is unfalsifiable.  Flag integrity — a
    present-but-untrue ``analytic`` flag — is a violation in ANY round
    (asserting plan-derivation falsely is a lie); the claim rule is
    rc- and round-gated like the measured-time rule."""
    if not isinstance(rec.get("parsed"), dict):
        return []
    errs: list[str] = []
    claim_gated = (rec.get("rc") == 0
                   and (round_no is None
                        or round_no >= MEMORY_PROVENANCE_SINCE))

    def walk(node, path: str, flagged: bool, root: bool = False) -> None:
        if isinstance(node, list):
            for i, v in enumerate(node):
                walk(v, f"{path}[{i}]", flagged)
            return
        if not isinstance(node, dict):
            return
        if "analytic" in node and node["analytic"] is not True:
            errs.append(
                f"{path or 'parsed'}: analytic={node['analytic']!r} — the "
                "provenance flag may only assert a plan-derived figure "
                "(true); drop it or fix the generator")
        # the ROOT parsed dict's flags do not count as byte provenance:
        # its `measured: true` asserts the headline TIME value was timed
        # live (check_measured_provenance) — letting it inherit downward
        # would make this rule vacuous on every bench record
        here = (not root) and (flagged or node.get("analytic") is True
                               or node.get("measured") is True)
        for k, v in node.items():
            if (claim_gated and isinstance(k, str) and k.endswith("_bytes")
                    and _is_num(v) and not here):
                errs.append(
                    f"{path or 'parsed'}: numeric residency claim {k!r} "
                    "without analytic:true or measured:true provenance in "
                    "its block — a byte count must say whether it is "
                    "plan-derived (sgcn_tpu.obs.memory) or from XLA "
                    "memory_analysis() (rounds < "
                    f"r{MEMORY_PROVENANCE_SINCE:02d} are grandfathered)")
            walk(v, f"{path}/{k}" if path else k, here)

    walk(rec["parsed"], "", False, root=True)
    return errs


def check_bench_record(rec: dict) -> list[str]:
    errs = []
    for key, typ in (("n", numbers.Integral), ("cmd", str),
                     ("rc", numbers.Integral), ("tail", str)):
        if not isinstance(rec.get(key), typ):
            errs.append(f"missing/badly-typed driver key {key!r}")
    if errs:
        return errs
    if rec["rc"] == 0:
        parsed = rec.get("parsed")
        if not isinstance(parsed, dict):
            errs.append("rc=0 but no parsed one-line JSON result")
            return errs
        if not isinstance(parsed.get("metric"), str):
            errs.append("parsed result missing string 'metric'")
        if "value" not in parsed:
            errs.append("parsed result missing 'value'")
        elif parsed["value"] is None:
            if not (isinstance(parsed.get("skipped"), str)
                    or isinstance(parsed.get("degraded"), str)):
                errs.append("value=null without a skipped/degraded marker "
                            "(graceful-degradation contract)")
        elif not _is_num(parsed["value"]):
            errs.append(f"value is {type(parsed['value']).__name__}, "
                        "expected number or null")
        meas = parsed.get("measurement")
        if isinstance(meas, dict) and meas:
            ce, te = meas.get("clean_estimates"), meas.get("target_estimates")
            if not (isinstance(ce, numbers.Integral)
                    and isinstance(te, numbers.Integral)
                    and 1 <= ce <= te):
                errs.append(f"measurement block inconsistent: "
                            f"clean={ce} target={te}")
        if "comm_schedule" in parsed and parsed["comm_schedule"] not in (
                "a2a", "ragged"):
            errs.append(f"comm_schedule={parsed['comm_schedule']!r} is not "
                        "a resolved schedule (a2a|ragged; 'auto' must "
                        "resolve before emission)")
        if "ragged_ab_8dev" in parsed:
            errs += check_ragged_ab(parsed)
        if "gat_ragged_ab_8dev" in parsed:
            errs += check_ragged_ab(parsed, prefix="gat_ragged_ab")
        if "ragged_stale_ab_8dev" in parsed:
            errs += check_ragged_stale_ab(parsed)
        if "pallas_ragged_ab_8dev" in parsed:
            errs += check_pallas_ragged_ab(parsed)
        if "replica_ab_8dev" in parsed:
            errs += check_replica_ab(parsed)
        if "controller_ab_8dev" in parsed:
            errs += check_controller_ab(parsed)
        if "serve_qps_8dev" in parsed:
            errs += check_serve_qps(parsed)
        if "serve_subgraph_ab_8dev" in parsed:
            errs += check_serve_subgraph_ab(parsed)
    if isinstance(rec.get("parsed"), dict):
        # flag integrity applies even to failed rounds (cf. `measured`)
        errs += check_resume_provenance(rec["parsed"])
    return errs


def check_resume_provenance(parsed: dict) -> list[str]:
    """The resume-provenance rule (module docstring): a resume claim must
    name the checkpoint that seeded it, in one of the two shapes the repo
    produces — the trainer CLI's ``resumed: {step, path, fallback}``
    block (the report ``--resume auto`` emits, which IS the identity), or
    a bare ``resumed: true`` flag accompanied by a ``checkpoint_meta``
    ``{step, version}`` block.  Anything else is unverifiable."""
    if "resumed" not in parsed:
        return []
    errs = []
    res = parsed["resumed"]
    if isinstance(res, dict):
        # the trainer CLI's shape: the block itself names the checkpoint
        if not (isinstance(res.get("step"), numbers.Integral)
                and res["step"] >= 0
                and isinstance(res.get("path"), str) and res["path"]):
            errs.append(f"resumed block {res!r} missing its checkpoint "
                        "identity ({step >= 0, path} — the trainer CLI's "
                        "--resume auto shape, docs/resilience.md)")
        return errs
    if res is not True:
        errs.append(f"resumed={res!r}: the provenance flag may only "
                    "assert a real resume (true, or the trainer's "
                    "{step, path, ...} block) — drop it or fix the "
                    "generator")
        return errs
    meta = parsed.get("checkpoint_meta")
    if not (isinstance(meta, dict)
            and isinstance(meta.get("step"), numbers.Integral)
            and meta["step"] >= 0
            and isinstance(meta.get("version"), numbers.Integral)
            and meta["version"] >= 1):
        errs.append("resumed:true without a matching checkpoint_meta "
                    "block ({step >= 0, version >= 1} at minimum) — a "
                    "resume claim must name the checkpoint that seeded it "
                    "(docs/resilience.md)")
    return errs


def check_controller_ab(parsed: dict) -> list[str]:
    """The adaptive-controller A/B contract (PR-12,
    docs/comm_schedule.md): a ``controller_ab_8dev`` block must carry the
    controller arm plus all four static arms with positive paired epoch
    times and a consistent exposed-wire accounting in which the
    controller's exposed wire rows per step are <= EVERY static arm and
    STRICTLY below at least one — the controller's acceptance figure
    (never CPU-mesh epoch time; the honest-measurement ``note`` must say
    so).  ``null`` needs a ``controller_ab_degraded`` marker."""
    errs = []
    block = parsed["controller_ab_8dev"]
    if block is None:
        if not isinstance(parsed.get("controller_ab_degraded"), str):
            errs.append("controller_ab_8dev null without a "
                        "controller_ab_degraded marker "
                        "(graceful-degradation contract)")
        return errs
    if not isinstance(block, dict):
        return [f"controller_ab_8dev is {type(block).__name__}, expected "
                "dict or null"]
    arms = block.get("arms")
    if not isinstance(arms, dict):
        return ["controller_ab_8dev carries no arms dict"]
    required = ("controller", "a2a_exact", "ragged_exact", "ragged_stale",
                "replica_stale")
    missing = [a for a in required if not isinstance(arms.get(a), dict)]
    if missing:
        return [f"controller_ab_8dev missing arm(s) {missing}"]
    for nm in required:
        e = arms[nm]
        if not (_is_num(e.get("epoch_s")) and e["epoch_s"] > 0):
            errs.append(f"controller_ab_8dev.arms.{nm}.epoch_s="
                        f"{e.get('epoch_s')!r}")
        if not (_is_num(e.get("exposed_wire_rows_per_step"))
                and e["exposed_wire_rows_per_step"] >= 0):
            errs.append(f"controller_ab_8dev.arms.{nm}."
                        "exposed_wire_rows_per_step="
                        f"{e.get('exposed_wire_rows_per_step')!r}")
    if errs:
        return errs
    ce = arms["controller"]["exposed_wire_rows_per_step"]
    statics = [nm for nm in required if nm != "controller"]
    worse = [nm for nm in statics
             if ce > arms[nm]["exposed_wire_rows_per_step"]]
    if worse:
        errs.append(
            f"controller_ab_8dev: controller exposed wire rows/step {ce} "
            f"above static arm(s) {worse} — the controller's acceptance "
            "inequality")
    if not any(ce < arms[nm]["exposed_wire_rows_per_step"]
               for nm in statics):
        errs.append(
            f"controller_ab_8dev: controller exposed wire rows/step {ce} "
            "not STRICTLY below any static arm — a universal tie is not "
            "a win")
    cp = block.get("clean_pairs")
    if not (_is_num(cp) and cp >= 1):
        errs.append(f"controller_ab_8dev: clean_pairs={cp!r}")
    note = block.get("note")
    if not (isinstance(note, str) and "exposed" in note):
        errs.append("controller_ab_8dev: missing the honest-measurement "
                    "note naming exposed wire rows as the asserted figure "
                    "(CPU-mesh epoch speed is not the claim)")
    return errs


def check_serve_qps(parsed: dict) -> list[str]:
    """The serving-bench block contract (PR-8): a ``serve_qps_8dev`` block
    must carry both transport arms (a2a, ragged) with positive achieved QPS,
    ordered positive latency quantiles UNDER ``measured: true`` provenance
    (latency claims are live host-clock measurements, same rule as the
    epoch-time flag), zero steady-state recompiles implied by consistent
    bucket/compile counters, and the wire-row accounting in which the
    ragged arm ships STRICTLY fewer wire rows than a2a on the skewed hp
    partition — the forward-only carry-over of the training schedules' win
    (never CPU-mesh latency; the block's ``note`` must say so).  ``null``
    needs a ``serve_qps_degraded`` marker."""
    errs = []
    block = parsed["serve_qps_8dev"]
    if block is None:
        if not isinstance(parsed.get("serve_qps_degraded"), str):
            errs.append("serve_qps_8dev null without a serve_qps_degraded "
                        "marker (graceful-degradation contract)")
        return errs
    if not isinstance(block, dict):
        return [f"serve_qps_8dev is {type(block).__name__}, expected "
                "dict or null"]
    if block.get("measured") is not True:
        errs.append("serve_qps_8dev: latency claims without measured:true "
                    "provenance — quantiles must come from a live "
                    "measurement in the emitting process")
    if not (_is_num(block.get("offered_qps")) and block["offered_qps"] > 0):
        errs.append(f"serve_qps_8dev: offered_qps="
                    f"{block.get('offered_qps')!r}")
    arms = block.get("arms")
    if not isinstance(arms, dict):
        return errs + ["serve_qps_8dev carries no arms dict"]
    missing = [a for a in ("a2a", "ragged") if not isinstance(arms.get(a),
                                                             dict)]
    if missing:
        return errs + [f"serve_qps_8dev missing arm(s) {missing}"]
    for nm in ("a2a", "ragged"):
        e = arms[nm]
        if not (_is_num(e.get("achieved_qps")) and e["achieved_qps"] > 0):
            errs.append(f"serve_qps_8dev.arms.{nm}.achieved_qps="
                        f"{e.get('achieved_qps')!r}")
        p50, p99 = e.get("latency_p50_ms"), e.get("latency_p99_ms")
        if not (_is_num(p50) and _is_num(p99) and 0 < p50 <= p99):
            errs.append(f"serve_qps_8dev.arms.{nm}: latency quantiles "
                        f"p50={p50!r} p99={p99!r} (need 0 < p50 <= p99)")
        for key in ("wire_rows_per_exchange", "wire_rows_per_query"):
            if not (_is_num(e.get(key)) and e[key] >= 0):
                errs.append(f"serve_qps_8dev.arms.{nm}.{key}="
                            f"{e.get(key)!r}")
        comp = e.get("compiles")
        bkts = e.get("buckets")
        if comp is not None and isinstance(bkts, list):
            if not (_is_num(comp) and comp <= len(bkts)):
                errs.append(
                    f"serve_qps_8dev.arms.{nm}: compiles={comp!r} exceeds "
                    f"the {len(bkts)} pre-compiled buckets — a runtime "
                    "recompile violates the bucket contract")
    if errs:
        return errs
    wa = arms["a2a"]["wire_rows_per_exchange"]
    wr = arms["ragged"]["wire_rows_per_exchange"]
    if not wr < wa:
        errs.append(f"serve_qps_8dev: wire_rows_ragged={wr!r} not STRICTLY "
                    f"below wire_rows_a2a={wa!r} on the skewed partition — "
                    "the forward-only carry-over of the schedule's "
                    "acceptance figure")
    tr_, wq = (arms["ragged"].get("true_rows_per_exchange"),
               arms["ragged"]["wire_rows_per_exchange"])
    if _is_num(tr_) and tr_ > wq:
        errs.append(f"serve_qps_8dev: true_rows={tr_!r} above "
                    f"wire_rows_ragged={wq!r}")
    note = block.get("note")
    if not (isinstance(note, str) and "wire" in note):
        errs.append("serve_qps_8dev: missing the honest-measurement note "
                    "naming the wire-row accounting as the asserted figure "
                    "(CPU-mesh latency is not the cross-transport claim)")
    return errs


def check_serve_subgraph_ab(parsed: dict) -> list[str]:
    """The sub-graph serving A/B contract (PR-14, docs/serving.md phase 2):
    a ``serve_subgraph_ab_8dev`` block must carry both engine arms (full,
    subgraph) with positive achieved QPS and ordered positive latency
    quantiles UNDER ``measured: true`` provenance, positive analytic
    per-query figures, and the acceptance inequality: the sub-graph arm's
    analytic rows/query AND FLOPs/query must both sit ≥10× below the full
    arm's (the ``*_cut`` fields must agree with the arms they summarize —
    never CPU-mesh latency; the ``note`` must say so).  ``null`` needs a
    ``serve_subgraph_degraded`` marker."""
    errs = []
    block = parsed["serve_subgraph_ab_8dev"]
    if block is None:
        if not isinstance(parsed.get("serve_subgraph_degraded"), str):
            errs.append("serve_subgraph_ab_8dev null without a "
                        "serve_subgraph_degraded marker "
                        "(graceful-degradation contract)")
        return errs
    if not isinstance(block, dict):
        return [f"serve_subgraph_ab_8dev is {type(block).__name__}, "
                "expected dict or null"]
    if block.get("measured") is not True:
        errs.append("serve_subgraph_ab_8dev: latency claims without "
                    "measured:true provenance")
    arms = block.get("arms")
    if not isinstance(arms, dict):
        return errs + ["serve_subgraph_ab_8dev carries no arms dict"]
    missing = [a for a in ("full", "subgraph")
               if not isinstance(arms.get(a), dict)]
    if missing:
        return errs + [f"serve_subgraph_ab_8dev missing arm(s) {missing}"]
    for nm in ("full", "subgraph"):
        e = arms[nm]
        if not (_is_num(e.get("achieved_qps")) and e["achieved_qps"] > 0):
            errs.append(f"serve_subgraph_ab_8dev.arms.{nm}.achieved_qps="
                        f"{e.get('achieved_qps')!r}")
        p50, p99 = e.get("latency_p50_ms"), e.get("latency_p99_ms")
        if not (_is_num(p50) and _is_num(p99) and 0 < p50 <= p99):
            errs.append(f"serve_subgraph_ab_8dev.arms.{nm}: latency "
                        f"quantiles p50={p50!r} p99={p99!r} "
                        "(need 0 < p50 <= p99)")
        for key in ("rows_per_query", "flops_per_query"):
            if not (_is_num(e.get(key)) and e[key] > 0):
                errs.append(f"serve_subgraph_ab_8dev.arms.{nm}.{key}="
                            f"{e.get(key)!r}")
    det = block.get("analytic")
    if not isinstance(det, dict):
        errs.append("serve_subgraph_ab_8dev carries no analytic block — "
                    "the asserted cuts must come from the DETERMINISTIC "
                    "fixed-chunking gauges, not the real-clock arms")
    if errs:
        return errs
    for fk, sk, cut_key in (
            ("full_rows_per_query", "subgraph_rows_per_query",
             "rows_per_query_cut"),
            ("full_flops_per_query", "subgraph_flops_per_query",
             "flops_per_query_cut")):
        full_v, sub_v = det.get(fk), det.get(sk)
        if not (_is_num(full_v) and _is_num(sub_v) and full_v > 0
                and sub_v > 0):
            errs.append(f"serve_subgraph_ab_8dev.analytic: {fk}={full_v!r} "
                        f"/ {sk}={sub_v!r}")
            continue
        cut = block.get(cut_key)
        if not (_is_num(cut) and cut >= 10.0):
            errs.append(f"serve_subgraph_ab_8dev: {cut_key}={cut!r} below "
                        "the >=10x acceptance cut (the query-proportional "
                        "claim)")
        elif abs(cut - full_v / max(sub_v, 1e-9)) > 0.01 * max(cut, 1.0):
            errs.append(f"serve_subgraph_ab_8dev: {cut_key}={cut!r} "
                        f"inconsistent with its own analytic block "
                        f"({full_v}/{sub_v}) — the summary must be "
                        "derivable from its record")
    note = block.get("note")
    if not (isinstance(note, str) and "ANALYTIC" in note):
        errs.append("serve_subgraph_ab_8dev: missing the honest-"
                    "measurement note naming the ANALYTIC per-query gauges "
                    "as the asserted figures (CPU-mesh latency is not the "
                    "cross-arm claim)")
    return errs


def check_ragged_stale_ab(parsed: dict) -> list[str]:
    """The composed-mode three-way A/B contract (PR-6): the
    ``ragged_stale_ab_8dev`` block must carry all three arms (a2a+stale,
    ragged+exact, ragged+stale) with positive paired-differential timings
    and a consistent exposed-comm accounting in which the composed arm's
    exposed fraction is <= both single levers and its exposed wire rows
    per step are STRICTLY below both — the acceptance figure of the
    composition (never CPU-mesh epoch speed; the block must say so in its
    honest-measurement ``note``).  ``null`` needs a degradation marker."""
    errs = []
    block = parsed["ragged_stale_ab_8dev"]
    if block is None:
        if not isinstance(parsed.get("ragged_stale_ab_degraded"), str):
            errs.append("ragged_stale_ab_8dev null without a "
                        "ragged_stale_ab_degraded marker "
                        "(graceful-degradation contract)")
        return errs
    if not isinstance(block, dict):
        return [f"ragged_stale_ab_8dev is {type(block).__name__}, expected "
                "dict or null"]
    arms = block.get("arms")
    if not isinstance(arms, dict):
        return ["ragged_stale_ab_8dev carries no arms dict"]
    required = ("a2a_stale", "ragged_exact", "ragged_stale")
    missing = [a for a in required if not isinstance(arms.get(a), dict)]
    if missing:
        return [f"ragged_stale_ab_8dev missing arm(s) {missing}"]
    for nm in required:
        e = arms[nm]
        if not (_is_num(e.get("epoch_s")) and e["epoch_s"] > 0):
            errs.append(f"ragged_stale_ab_8dev.arms.{nm}.epoch_s="
                        f"{e.get('epoch_s')!r}")
        frac = e.get("exposed_comm_frac")
        if not (_is_num(frac) and 0 <= frac <= 1):
            errs.append(f"ragged_stale_ab_8dev.arms.{nm}: "
                        f"exposed_comm_frac={frac!r} outside [0, 1]")
        for key in ("wire_rows_per_exchange", "exposed_wire_rows_per_step"):
            if not (_is_num(e.get(key)) and e[key] >= 0):
                errs.append(f"ragged_stale_ab_8dev.arms.{nm}.{key}="
                            f"{e.get(key)!r}")
    if errs:
        return errs
    comp, a2s, rex = (arms["ragged_stale"], arms["a2a_stale"],
                      arms["ragged_exact"])
    if not (comp["exposed_comm_frac"] <= a2s["exposed_comm_frac"]
            and comp["exposed_comm_frac"] <= rex["exposed_comm_frac"]):
        errs.append("ragged_stale_ab_8dev: composed exposed_comm_frac "
                    f"{comp['exposed_comm_frac']} exceeds a single lever's "
                    "— the composition's acceptance inequality")
    if not (comp["exposed_wire_rows_per_step"]
            < a2s["exposed_wire_rows_per_step"]
            and comp["exposed_wire_rows_per_step"]
            < rex["exposed_wire_rows_per_step"]):
        errs.append("ragged_stale_ab_8dev: composed exposed wire rows "
                    f"{comp['exposed_wire_rows_per_step']} not STRICTLY "
                    "below both single levers "
                    f"({a2s['exposed_wire_rows_per_step']}, "
                    f"{rex['exposed_wire_rows_per_step']})")
    cp = block.get("clean_pairs")
    if not (_is_num(cp) and cp >= 1):
        errs.append(f"ragged_stale_ab_8dev: clean_pairs={cp!r}")
    note = block.get("note")
    if not (isinstance(note, str) and "exposed" in note):
        errs.append("ragged_stale_ab_8dev: missing the honest-measurement "
                    "note naming exposed-comm accounting as the asserted "
                    "figure (CPU-mesh epoch speed is not the claim)")
    return errs


def check_ragged_ab(parsed: dict, prefix: str = "ragged_ab") -> list[str]:
    """The a2a-vs-ragged A/B block contract (see module docstring); the
    same rules validate the GCN block (``ragged_ab_8dev``) and the GAT one
    (``gat_ragged_ab_8dev``, PR-5).  The GAT block additionally requires a
    STRICT wire-row win on the skewed hp partition — the satellite's
    acceptance figure (never epoch speed: the virtual mesh has no ICI)."""
    errs = []
    name = f"{prefix}_8dev"
    block = parsed[name]
    if block is None:
        if not isinstance(parsed.get(f"{prefix}_degraded"), str):
            errs.append(f"{name} null without a {prefix}_degraded "
                        "marker (graceful-degradation contract)")
        return errs
    if not isinstance(block, dict):
        return [f"{name} is {type(block).__name__}, expected "
                "dict or null"]
    configs = [c for c in ("random", "hp") if c in block]
    if not configs:
        return [f"{name} carries no random/hp partition config"]
    for cfg in configs:
        e = block[cfg]
        if not isinstance(e, dict):
            errs.append(f"{name}.{cfg} is not a dict")
            continue
        for key in ("epoch_s_a2a", "epoch_s_ragged"):
            if not (_is_num(e.get(key)) and e[key] > 0):
                errs.append(f"{name}.{cfg}.{key}={e.get(key)!r}")
        pe = e.get("padding_efficiency")
        if not (_is_num(pe) and 0 < pe <= 1):
            errs.append(f"{name}.{cfg}: padding_efficiency={pe!r} "
                        "outside (0, 1]")
        ratio = e.get("padded_true_ratio_a2a")
        if ratio is not None and not (_is_num(ratio) and ratio >= 1):
            errs.append(f"{name}.{cfg}: padded_true_ratio_a2a="
                        f"{ratio!r} below 1 (padding cannot shrink the "
                        "true volume)")
        wa, wr = e.get("wire_rows_a2a"), e.get("wire_rows_ragged")
        if not (_is_num(wa) and _is_num(wr) and wr <= wa):
            errs.append(f"{name}.{cfg}: wire_rows_ragged={wr!r} "
                        f"exceeds wire_rows_a2a={wa!r} — per-round pads "
                        "can never exceed the global pad")
        if (prefix == "gat_ragged_ab" and cfg == "hp"
                and _is_num(wa) and _is_num(wr) and not wr < wa):
            errs.append(f"{name}.hp: wire_rows_ragged={wr!r} not STRICTLY "
                        f"below wire_rows_a2a={wa!r} on the skewed "
                        "partition — the schedule's acceptance figure")
        tr = e.get("true_rows")
        if _is_num(tr) and _is_num(wr) and tr > wr:
            errs.append(f"{name}.{cfg}: true_rows={tr!r} above "
                        f"wire_rows_ragged={wr!r}")
    return errs


def check_pallas_ragged_ab(parsed: dict) -> list[str]:
    """The kernel × schedule A/B block contract (ISSUE 15,
    ``pallas_ragged_ab_8dev``): three arms (``ell_ragged`` /
    ``pallas_ragged`` / ``pallas_a2a``) with positive MEASURED epoch times
    (emulate-mode — the honest-measurement note must say CPU epoch speed
    is never the claim), and the DETERMINISTIC acceptance counters: the
    pallas ragged arm's wire rows EQUAL the ELL ragged arm's (the kernel
    must not touch the transport), strictly below the pallas a2a arm's on
    the skewed hp partition, and ZERO analytic HBM halo-table bytes in
    both ragged arms while the a2a arm books a positive figure.  ``null``
    needs a ``pallas_ragged_ab_degraded`` marker."""
    errs = []
    block = parsed["pallas_ragged_ab_8dev"]
    if block is None:
        if not isinstance(parsed.get("pallas_ragged_ab_degraded"), str):
            errs.append("pallas_ragged_ab_8dev null without a "
                        "pallas_ragged_ab_degraded marker "
                        "(graceful-degradation contract)")
        return errs
    if not isinstance(block, dict):
        return [f"pallas_ragged_ab_8dev is {type(block).__name__}, "
                "expected dict or null"]
    note = str(block.get("timing", ""))
    if "never" not in note or "claim" not in note:
        errs.append("pallas_ragged_ab_8dev.timing missing the "
                    "honest-measurement note (CPU epoch speed is never "
                    "the claim)")
    arms = ("ell_ragged", "pallas_ragged", "pallas_a2a")
    for arm in arms:
        e = block.get(arm)
        if not isinstance(e, dict):
            errs.append(f"pallas_ragged_ab_8dev.{arm} missing")
            continue
        if not (_is_num(e.get("epoch_s")) and e["epoch_s"] > 0):
            errs.append(f"pallas_ragged_ab_8dev.{arm}.epoch_s="
                        f"{e.get('epoch_s')!r}")
        if e.get("measured") is not True:
            errs.append(f"pallas_ragged_ab_8dev.{arm}: epoch_s claim "
                        "without measured: true provenance")
    if all(isinstance(block.get(a), dict) for a in arms):
        wr = block["pallas_ragged"].get("wire_rows_per_exchange")
        we = block["ell_ragged"].get("wire_rows_per_exchange")
        wa = block["pallas_a2a"].get("wire_rows_per_exchange")
        if not (_is_num(wr) and _is_num(we) and wr == we):
            errs.append(f"pallas_ragged_ab_8dev: pallas ragged wire "
                        f"{wr!r} != ELL ragged wire {we!r} — the kernel "
                        "must not touch the transport")
        if not (_is_num(wr) and _is_num(wa) and wr < wa):
            errs.append(f"pallas_ragged_ab_8dev: pallas ragged wire "
                        f"{wr!r} not STRICTLY below the a2a pad {wa!r} "
                        "on the skewed partition")
        for arm in ("ell_ragged", "pallas_ragged"):
            hb = block[arm].get("halo_table_bytes_per_step")
            if hb != 0:
                errs.append(f"pallas_ragged_ab_8dev.{arm}: "
                            f"halo_table_bytes_per_step={hb!r} — the "
                            "ragged arms must book ZERO HBM halo-table "
                            "bytes (in-kernel fold)")
        ha = block["pallas_a2a"].get("halo_table_bytes_per_step")
        if not (_is_num(ha) and ha > 0):
            errs.append(f"pallas_ragged_ab_8dev.pallas_a2a: "
                        f"halo_table_bytes_per_step={ha!r} (the dense "
                        "exchange assembles halo tables — a zero here "
                        "means the analytic model broke)")
    return errs


def check_replica_ab(parsed: dict) -> list[str]:
    """The hot-halo-replication A/B block contract (PR-10,
    docs/replication.md): a ``replica_ab_8dev`` block must carry B > 0,
    per-partition configs with positive paired epoch times and equal step
    counts implied by the cumulative gauges, shrunken figures never above
    the full ones, and — STRICTLY, on the skewed hp partition — the
    acceptance inequalities: ``halo_bytes_true_total`` and wire rows/step
    lower with B>0 than the no-replica arm, plus the cache-aware km1 <=
    the cache-blind partition's cache objective.  ``null`` needs a
    ``replica_ab_degraded`` marker.  Never epoch speed: the virtual mesh
    has no ICI."""
    errs = []
    block = parsed["replica_ab_8dev"]
    if block is None:
        if not isinstance(parsed.get("replica_ab_degraded"), str):
            errs.append("replica_ab_8dev null without a replica_ab_degraded "
                        "marker (graceful-degradation contract)")
        return errs
    if not isinstance(block, dict):
        return [f"replica_ab_8dev is {type(block).__name__}, expected "
                "dict or null"]
    if not (_is_num(block.get("replica_budget"))
            and block["replica_budget"] > 0):
        errs.append(f"replica_ab_8dev: replica_budget="
                    f"{block.get('replica_budget')!r} (need B > 0)")
    configs = [c for c in ("random", "hp") if c in block]
    if not configs:
        return errs + ["replica_ab_8dev carries no random/hp partition "
                       "config"]
    for cfg in configs:
        e = block[cfg]
        if not isinstance(e, dict):
            errs.append(f"replica_ab_8dev.{cfg} is not a dict")
            continue
        for key in ("epoch_s_noreplica", "epoch_s_replica"):
            if not (_is_num(e.get(key)) and e[key] > 0):
                errs.append(f"replica_ab_8dev.{cfg}.{key}={e.get(key)!r}")
        if not (_is_num(e.get("replica_rows")) and e["replica_rows"] > 0):
            errs.append(f"replica_ab_8dev.{cfg}.replica_rows="
                        f"{e.get('replica_rows')!r} (B>0 must replicate "
                        "at least one boundary row)")
        for shrunk, full in (
                ("true_rows_per_exchange_replica", "true_rows_per_exchange"),
                ("wire_rows_per_exchange_replica", "wire_rows_per_exchange"),
                ("halo_bytes_true_total_replica",
                 "halo_bytes_true_total_noreplica"),
                ("wire_rows_per_step_replica", "wire_rows_per_step_"
                                               "noreplica")):
            s, f = e.get(shrunk), e.get(full)
            if not (_is_num(s) and _is_num(f) and s <= f):
                errs.append(f"replica_ab_8dev.{cfg}: {shrunk}={s!r} "
                            f"exceeds {full}={f!r} — deleting rows can "
                            "never grow the exchange")
    hp = block.get("hp")
    if isinstance(hp, dict):
        for shrunk, full in (
                ("halo_bytes_true_total_replica",
                 "halo_bytes_true_total_noreplica"),
                ("wire_rows_per_step_replica",
                 "wire_rows_per_step_noreplica")):
            s, f = hp.get(shrunk), hp.get(full)
            if _is_num(s) and _is_num(f) and not s < f:
                errs.append(f"replica_ab_8dev.hp: {shrunk}={s!r} not "
                            f"STRICTLY below {full}={f!r} on the skewed "
                            "partition — the feature's acceptance figure")
        kc, kb = (hp.get("km1_cache_aware"),
                  hp.get("km1_cache_blind_partition"))
        if not (_is_num(kc) and _is_num(kb) and kc <= kb):
            errs.append(f"replica_ab_8dev.hp: km1_cache_aware={kc!r} not "
                        f"<= the cache-blind partition's objective {kb!r} "
                        "— the co-optimizer's acceptance inequality")
    note = block.get("note")
    if not (isinstance(note, str) and "wire" in note):
        errs.append("replica_ab_8dev: missing the honest-measurement note "
                    "naming the byte accounting as the asserted figure "
                    "(CPU-mesh epoch speed is not the claim)")
    return errs


# the supported-matrix floor a committed analysis report may not shrink
# below (48 mode entries at PR-15 HEAD: PR-14's 39 + the eight Pallas
# kernel-family modes — {a2a,ragged} × (GCN × {f32,bf16 wire} ∪ GAT ×
# {fused,split}) — + the banded-fixture ragged-pallas elision entry; the
# matrix only grows)
ANALYSIS_MIN_MODES = 48


def check_analysis_report(rec: dict) -> list[str]:
    """The committed-analysis-report contract (module docstring): schema'd,
    full-matrix, green, and self-consistent — every ``ok`` flag must agree
    with the violation lists under it."""
    errs = []
    if rec.get("schema") != "sgcn_analysis_report":
        return [f"schema={rec.get('schema')!r}, expected "
                "'sgcn_analysis_report'"]
    if not isinstance(rec.get("v"), numbers.Integral):
        errs.append("missing integer schema version 'v'")
    if rec.get("fast") is not False:
        errs.append("committed report must be a FULL-matrix run "
                    "(fast: false) — the --fast subset is a smoke, not "
                    "evidence")
    if rec.get("ok") is not True:
        errs.append("ok is not true — fix the violations (or the rules) "
                    "instead of committing a red report as evidence")
    hlo = rec.get("hlo")
    if not isinstance(hlo, dict) or not isinstance(hlo.get("modes"), dict):
        errs.append("missing hlo.modes block")
        return errs
    modes = hlo["modes"]
    if hlo.get("n_modes") != len(modes):
        errs.append(f"hlo.n_modes={hlo.get('n_modes')!r} != "
                    f"{len(modes)} mode entries — inconsistent")
    if len(modes) < ANALYSIS_MIN_MODES:
        errs.append(f"{len(modes)} mode entries below the supported-"
                    f"matrix floor {ANALYSIS_MIN_MODES} — the matrix "
                    "only grows; a shrunk report is a silently narrowed "
                    "audit")
    for mid, entry in modes.items():
        progs = entry.get("programs")
        if not isinstance(progs, dict) or not progs:
            errs.append(f"hlo.modes[{mid}]: no programs block")
            continue
        viols = [v for p in progs.values()
                 for v in p.get("violations", [])]
        if bool(entry.get("ok")) == bool(viols):
            errs.append(f"hlo.modes[{mid}]: ok={entry.get('ok')!r} "
                        f"contradicts {len(viols)} recorded violation(s)")
        for label, p in progs.items():
            if bool(p.get("ok")) == bool(p.get("violations")):
                errs.append(f"hlo.modes[{mid}].programs[{label}]: "
                            f"ok={p.get('ok')!r} contradicts its "
                            "violation list")
            if p.get("ok") is not True:
                errs.append(f"hlo.modes[{mid}].programs[{label}]: "
                            f"ok={p.get('ok')!r} — a committed report "
                            "must be green in every program")
        if entry.get("ok") is not True:
            # green-only must hold per ENTRY, not just at the top — else
            # the one-line hand-edit (flip the top-level booleans) passes
            errs.append(f"hlo.modes[{mid}]: ok={entry.get('ok')!r} — a "
                        "committed report must be green in every mode")
    if hlo.get("ok") is not True:
        errs.append("hlo.ok is not true")
    ast_block = rec.get("ast")
    if not isinstance(ast_block, dict) or not isinstance(
            ast_block.get("rules"), dict):
        errs.append("missing ast.rules block")
        return errs
    for name, entry in ast_block["rules"].items():
        if bool(entry.get("ok")) == bool(entry.get("violations")):
            errs.append(f"ast.rules[{name}]: ok={entry.get('ok')!r} "
                        "contradicts its violation list")
        if entry.get("ok") is not True:
            errs.append(f"ast.rules[{name}]: ok={entry.get('ok')!r} — a "
                        "committed report must be green in every rule")
    if ast_block.get("ok") is not True:
        errs.append("ast.ok is not true")
    return errs


def check_multichip_record(rec: dict) -> list[str]:
    errs = []
    if not isinstance(rec.get("n_devices"), numbers.Integral):
        errs.append("missing/badly-typed n_devices")
    if not isinstance(rec.get("ok"), bool):
        errs.append("missing/badly-typed ok")
        return errs
    if rec["ok"]:
        if rec.get("rc", 0) != 0:
            errs.append(f"ok=true with rc={rec.get('rc')}")
    elif rec.get("rc", 0) == 0 and not (rec.get("skipped")
                                        or rec.get("degraded")):
        # a clean exit claiming failure must say why; a non-zero rc is its
        # own explanation (historical pre-contract records: rc=1 round 1,
        # rc=124 round 5)
        errs.append("ok=false, rc=0, and no skipped/degraded explanation")
    return errs


def _pow2(k: int) -> bool:
    return k >= 1 and (k & (k - 1)) == 0


def check_products_ksweep(rec: dict) -> list[str]:
    errs = []
    sweep = rec.get("sweep")
    if not isinstance(sweep, dict):
        return ["missing 'sweep' block"]
    for fam, by_k in sweep.items():
        for kstr, entry in by_k.items():
            try:
                k = int(kstr)
            except ValueError:
                errs.append(f"{fam}: non-integer k key {kstr!r}")
                continue
            for method, block in entry.items():
                if not isinstance(block, dict):
                    continue
                km1 = block.get("km1")
                if not (_is_num(km1) and km1 > 0):
                    errs.append(f"{fam}/k={k}/{method}: km1={km1!r}")
                ts = block.get("time_s")
                if ts is not None and not (_is_num(ts) and ts > 0):
                    errs.append(f"{fam}/k={k}/{method}: time_s={ts!r}")
            if "hp_rb" in entry and not (_pow2(k) and k >= 32):
                errs.append(
                    f"{fam}/k={k}: hp_rb entry at non-pow2 or <32 k — "
                    "partition_hypergraph_rb recurses on k/2 and the "
                    "auto-select fires only for pow2 k>=32; this shape is "
                    "unreproducible with the code at HEAD (the reverted "
                    "PR-2 hand-edit)")
    return errs


def check_products_partition(rec: dict) -> list[str]:
    errs = []
    g = rec.get("graph")
    if not (isinstance(g, dict) and _is_num(g.get("n"))
            and _is_num(g.get("nnz"))):
        errs.append("missing graph{n, nnz}")
    if not _is_num(rec.get("k")):
        errs.append("missing k")
    for method in ("hp", "rp", "gp"):
        block = rec.get(method)
        if not (isinstance(block, dict) and _is_num(block.get("km1"))):
            errs.append(f"missing {method}.km1")
    return errs


def check_shard_epoch_model(rec: dict) -> list[str]:
    errs = []
    cfg = rec.get("config")
    if not (isinstance(cfg, dict) and _is_num(cfg.get("k"))
            and _is_num(cfg.get("n"))):
        errs.append("missing config{k, n}")
    models = [m for m in ("gcn", "gat")
              if isinstance(rec.get(m), dict) and "error" not in rec[m]]
    if not models:
        errs.append("no usable gcn/gat model block")
    for m in models:
        v = rec[m].get("epoch_s_8chip_model")
        if not (_is_num(v) and v > 0):
            errs.append(f"{m}.epoch_s_8chip_model={v!r}")
    return errs


# artifact filename -> dedicated checker (everything else: strict-parse only)
_ARTIFACT_CHECKS = {
    "analysis_report.json": check_analysis_report,
    "products_ksweep.json": check_products_ksweep,
    "products_partition.json": check_products_partition,
    "products_partition_dcsbm.json": check_products_partition,
    "shard_epoch_model.json": check_shard_epoch_model,
    "shard_epoch_model_dcsbm.json": check_shard_epoch_model,
}


def validate_tree(root: str) -> list[str]:
    """Validate every bench evidence file under ``root``; return violations
    as ``path: message`` strings (empty = clean)."""
    problems: list[str] = []

    def run(path, checker):
        try:
            rec = _load_strict(path)
        except (ValueError, json.JSONDecodeError) as e:
            problems.append(f"{os.path.relpath(path, root)}: unparseable "
                            f"({e})")
            return
        for msg in (checker(rec) if checker else []):
            problems.append(f"{os.path.relpath(path, root)}: {msg}")

    for path in sorted(glob.glob(os.path.join(root, "BENCH_*.json"))):
        m = _BENCH_ROUND_RE.search(os.path.basename(path))
        rnd = int(m.group(1)) if m else None
        run(path, lambda rec, rnd=rnd: (check_bench_record(rec)
                                        + check_measured_provenance(rec, rnd)
                                        + check_memory_provenance(rec, rnd)))
    for path in sorted(glob.glob(os.path.join(root, "MULTICHIP_*.json"))):
        run(path, check_multichip_record)
    for path in sorted(glob.glob(os.path.join(root, "bench_artifacts",
                                              "*.json"))):
        run(path, _ARTIFACT_CHECKS.get(os.path.basename(path)))
    return problems


def main() -> int:
    root = sys.argv[1] if len(sys.argv) > 1 else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    problems = validate_tree(root)
    if problems:
        print(f"validate_bench: {len(problems)} violation(s):")
        for p in problems:
            print(f"  {p}")
        return 1
    n = (len(glob.glob(os.path.join(root, "BENCH_*.json")))
         + len(glob.glob(os.path.join(root, "MULTICHIP_*.json")))
         + len(glob.glob(os.path.join(root, "bench_artifacts", "*.json"))))
    print(f"validate_bench: {n} file(s) clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
