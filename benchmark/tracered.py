"""Reduction of a profiler trace to device busy time, idle gaps, collective
time and a per-op breakdown.

A trace is read into a neutral form — ``[{"name": plane, "lines": {line:
[[name, start_ns, dur_ns, stats], ...]}}]`` — from one of three sources: the
``.xplane.pb`` the JAX profiler writes (``jax.profiler.ProfileData``), a
trace-event JSON (the recorded ``bench_artifacts/tpu_epoch.trace.json.gz``),
or a fixture this module dumped.  Everything below works on that form.

A TPU device plane carries nested lines: ``XLA Modules`` (one event per
program run) and ``XLA Ops``, on which a ``while`` spans the ops of its body.
Summing durations therefore counts the same time more than once.  Busy time
here is the union of the LEAF events of the op line — events that contain no
other event — and nothing else.

The interval arithmetic (``union``, ``overlap_len``) is the benchmark's copy
of ``sgcn_tpu/obs/tracing.py::_interval_union`` / ``_overlap_len``.
"""

from __future__ import annotations

import gzip
import json
import os
import re

OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."              # the benchmark's own TraceAnnotations
KEPT_STATS = ("hlo_category", "tf_op")
EPS_NS = 1.0                        # ProfileData cuts times to whole ns
# by HLO opcode; an instruction may be named after the JAX primitive instead
# (``all_to_all.5``), so underscores count as hyphens
COLLECTIVE = re.compile(
    r"^(all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"collective-broadcast|ppermute|psum)")


def is_collective(ev: list) -> bool:
    return bool(COLLECTIVE.match(ev[0].replace("_", "-"))
                or COLLECTIVE.match(ev[3].get("hlo_category", "")))
MIN_GAP_NS = 20_000                 # shorter gaps are launch cadence


# ------------------------------------------------------------------- loading
def load_xplane(path: str) -> list:
    """Device op/module lines and the benchmark's host spans of one
    ``.xplane.pb``.  An op event is named by its whole HLO line there
    (``%fusion.839 = f32[...] fusion(...)``); the instruction's name is kept.
    Where the profiler wrote its trace-event JSON beside the file, each op's
    HLO category and JAX primitive (event metadata that ``ProfileData`` does
    not show) are joined on by instruction name."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = {}
        for line in plane.lines:
            if device and line.name not in (OP_LINE, ASYNC_LINE, MODULE_LINE):
                continue
            events = []
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(SPAN_PREFIX):
                    continue
                if device and name.startswith("%"):
                    name = name[1:].split(" ", 1)[0]
                events.append([name, float(ev.start_ns),
                               float(ev.duration_ns), {}])
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    side = path[:-len(".xplane.pb")] + ".trace.json.gz"
    if os.path.exists(side):
        meta = {}
        for p in load_trace_json(side):
            for ev in p["lines"].get(OP_LINE, []):
                meta.setdefault(ev[0], ev[3])
        for p in planes:
            for ln in (OP_LINE, ASYNC_LINE):
                for ev in p["lines"].get(ln, []):
                    ev[3] = meta.get(ev[0], ev[3])
    return planes


def load_trace_json(path: str) -> list:
    """The same form from a trace-event JSON (``ts``/``dur`` in µs)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    pname, tname = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname[e["pid"]] = e["args"]["name"]
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tname[(e["pid"], e["tid"])] = e["args"]["name"]
    planes = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        plane = planes.setdefault(e["pid"], {
            "name": pname.get(e["pid"], str(e["pid"])), "lines": {}})
        line = tname.get((e["pid"], e["tid"]), str(e["tid"]))
        args = e.get("args") or {}
        plane["lines"].setdefault(line, []).append(
            [e["name"], e["ts"] * 1e3, e["dur"] * 1e3,
             {k: str(args[k]) for k in KEPT_STATS if k in args}])
    return list(planes.values())


def dump_fixture(planes: list, path: str, t0: float, t1: float) -> None:
    """Write the events of ``planes`` that start in ``[t0, t1)`` (ns)."""
    cut = [{"name": p["name"],
            "lines": {ln: [e for e in evs if t0 <= e[1] < t1]
                      for ln, evs in p["lines"].items()}}
           for p in planes]
    with gzip.open(path, "wt") as fh:
        json.dump(cut, fh, separators=(",", ":"))


def load_fixture(path: str) -> list:
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


# ---------------------------------------------------------------- intervals
def union(intervals) -> list:
    """Merge ``[start, end)`` intervals into a disjoint sorted union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap_len(a: list, b: list) -> float:
    """Total intersection length of two disjoint sorted unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def length(u: list) -> float:
    return sum(e - s for s, e in u)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def leaf_events(events: list) -> list:
    """Events of one line that contain no other event of that line.  An event
    is inside the one before it only if it also ENDS inside it: neighbours
    whose times were cut to whole nanoseconds may overlap by one, and an op
    cut to no time at all is nobody's child."""
    order = sorted((e for e in events if e[2] >= EPS_NS),
                   key=lambda e: (e[1], -e[2]))
    leaves, stack = [], []           # stack of [end, has_child, event]
    for ev in order:
        end = ev[1] + ev[2]
        while stack and (stack[-1][0] <= ev[1] + EPS_NS
                         or end > stack[-1][0] + EPS_NS):
            _, parent, top = stack.pop()
            if not parent:
                leaves.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([end, False, ev])
    leaves.extend(top for _, parent, top in stack if not parent)
    return leaves


# ----------------------------------------------------------------- reduction
def device_planes(planes: list) -> list:
    return sorted((p for p in planes if p["name"].startswith(DEVICE_PREFIX)
                   and OP_LINE in p["lines"]), key=lambda p: p["name"])


def host_spans(planes: list) -> list:
    """``[name, start, end]`` of the benchmark's TraceAnnotations."""
    return sorted(
        [e[0], e[1], e[1] + e[2]]
        for p in planes if not p["name"].startswith(DEVICE_PREFIX)
        for evs in p["lines"].values() for e in evs
        if e[0].startswith(SPAN_PREFIX))


def window_of(plane: dict, runs: int) -> tuple:
    """The measured window on one device's clock.  The main program is the
    module with most time on the module line; with more than ``runs`` runs of
    it in the trace the window is ``runs`` whole periods, start of the first
    to start of run ``runs + 1``; with exactly ``runs`` it ends with the last."""
    mods = plane["lines"].get(MODULE_LINE, [])
    if not mods:                     # no module line: span of the op line
        ops = plane["lines"][OP_LINE]
        return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)
    total = {}
    for e in mods:
        total[e[0]] = total.get(e[0], 0.0) + e[2]
    main = max(total, key=total.get)
    starts = sorted((e[1], e[1] + e[2]) for e in mods if e[0] == main)
    if len(starts) < runs:
        raise ValueError(f"{plane['name']}: {len(starts)} runs of {main} in "
                         f"the trace, window needs {runs}")
    if len(starts) > runs:
        return starts[0][0], starts[runs][0]
    return starts[0][0], starts[runs - 1][1]


def primitive_of(ev: list) -> str:
    """The JAX primitive an op's HLO metadata names (``jit(f)/jvp()/gather:``
    → ``gather``); ``""`` where it names none.  It is the finest attribution
    the trace allows until the program names its scopes: HLO source lines
    now point at the jitted call, not at the op."""
    return ev[3].get("tf_op", "").rstrip(":").rsplit("/", 1)[-1]


def op_label(ev: list) -> str:
    """Breakdown name: the op without its instance number, its HLO category,
    its primitive, and whether it belongs to the backward pass."""
    stats = ev[3]
    label = re.sub(r"[.\d]+$", "", ev[0])
    if stats.get("hlo_category") and stats["hlo_category"] != label:
        label += f" [{stats['hlo_category']}]"
    if primitive_of(ev):
        label += " " + primitive_of(ev)
        if "transpose(" in stats["tf_op"]:
            label += " (bwd)"
    return label


def reduce_plane(plane: dict, runs: int, spans: list) -> dict:
    """Busy, collective and idle figures of one device inside its window
    (all in seconds)."""
    lo, hi = window_of(plane, runs)
    leaves = [e for e in leaf_events(plane["lines"][OP_LINE])
              if e[1] + e[2] > lo and e[1] < hi]
    ivals = lambda evs: clip([(e[1], e[1] + e[2]) for e in evs], lo, hi)
    sync = [e for e in leaves if is_collective(e)]
    compute = union(ivals([e for e in leaves if not is_collective(e)]))
    coll = union(ivals(sync + [e for e in plane["lines"].get(ASYNC_LINE, [])
                               if is_collective(e)]))
    busy = union(compute + coll)
    # every synchronous collective of the window by (name, occurrence): the
    # chips run one program, so the same key is the same exchange on each
    coll_runs, seen = {}, {}
    for e in sorted(sync, key=lambda e: e[1]):
        nth = seen[e[0]] = seen.get(e[0], -1) + 1
        coll_runs[e[0], nth] = min(e[1] + e[2], hi) - max(e[1], lo)

    ops, prims = {}, {}
    for e in leaves:
        dur = min(e[1] + e[2], hi) - max(e[1], lo)
        label, prim = op_label(e), primitive_of(e)
        ops[label] = ops.get(label, 0.0) + dur
        prims[prim] = prims.get(prim, 0.0) + dur

    gaps = {}
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < MIN_GAP_NS:
            continue
        best, name = 0.0, "unattributed"
        for sname, s0, s1 in spans:
            ov = min(b, s1) - max(a, s0)
            if ov > best:
                best, name = ov, sname
        gaps[name] = gaps.get(name, 0.0) + (b - a)

    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": length(busy) * ns,
        "compute_s": length(compute) * ns,
        "collective_s": length(coll) * ns,
        "coll_runs": {k: v * ns for k, v in coll_runs.items()},
        "exposed_collective_s": (length(coll)
                                 - overlap_len(coll, compute)) * ns,
        "ops": {k: v * ns for k, v in ops.items()},
        "primitives": {k: v * ns for k, v in prims.items()},
        "gaps": {k: v * ns for k, v in gaps.items()},
        "n_leaf": len(leaves),
    }


def reduce_trace(planes: list, runs: int) -> dict | None:
    """Mean over the device planes of ``reduce_plane``, with each chip's own
    figures under ``per_chip``.  ``None`` where no device op line was traced
    (a CPU run).

    ``collective_wait_s``: a synchronous collective ends on every chip
    together, so the chip that entered it last spends in it only what the
    exchange itself takes; what any other chip spends beyond that, it waits.
    Per chip, the sum over its collectives of its own seconds less the least
    any chip spent in the same one.  Those seconds count as busy."""
    devs = device_planes(planes)
    if not devs:
        return None
    spans = host_spans(planes)
    per = [reduce_plane(p, runs, spans) for p in devs]
    for d in per:
        d["collective_wait_s"] = sum(
            secs - min(o["coll_runs"].get(key, secs) for o in per)
            for key, secs in d["coll_runs"].items())
    n = len(per)
    mean = lambda key: sum(d[key] for d in per) / n
    merged = lambda key: {
        name: sum(d[key].get(name, 0.0) for d in per) / n
        for name in {k for d in per for k in d[key]}}
    scalars = ("window_s", "busy_s", "compute_s", "collective_s",
               "exposed_collective_s", "collective_wait_s")
    return {
        "chips": n, **{key: mean(key) for key in scalars},
        "ops": merged("ops"), "primitives": merged("primitives"),
        "gaps": per[0]["gaps"],      # idle gaps: chip 0 against the host
        "per_chip": [{k: d[k] for k in scalars + ("n_leaf",)} for d in per],
    }


def primitive_share(red: dict, primitive: str) -> float | None:
    """Percent of the leaf-op seconds of a reduced window in ops whose HLO
    metadata names ``primitive``; nothing where the trace names none."""
    prims = red.get("primitives", {})
    if not any(prims):
        return None
    return 100.0 * prims.get(primitive, 0.0) / sum(prims.values())


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
