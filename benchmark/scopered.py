"""Reduction of a traced window by the scopes the program names itself.

``sgcn_tpu`` wraps the compiled step in ``jax.named_scope``s of a fixed
vocabulary (``scopes.json`` is the benchmark's copy).  A scope reaches the
device trace through each op's ``tf_op``
(``jit(per_chip)/shard_map/transpose(jvp(sgcn.layer1))/sgcn.agg_slots/jit(_take)/gather:``),
wherever the transforms put it, so the reduction reads tokens:

* the LEAF scope of an op is the last ``sgcn.<name>`` token that is not a
  layer; an op with none is ``unscoped``;
* its layer is the ``sgcn.layer<i>`` token (``-`` where there is none);
* it is backward where ``transpose(`` is in the ``tf_op`` (the custom
  backward of the aggregation re-runs the forward form, so the aggregation
  scopes name backward ops too);
* a collective outside the two scopes made for one (``xchg_a2a``,
  ``grad_psum``) is booked as ``<leaf>:collective``: the transposition puts
  the weight gradients' ``psum`` under ``dense``, and a chip waiting there
  is not a dense product.

Seconds are those of the leaf ops of the device's op line, clipped to the
window ``tracered.window_of`` gives — what ``tracered.reduce_plane`` sums
as busy time, so all rows together are the device's busy seconds.

The runner's ``run`` dict carries the reduced trace, not the events, so
``table`` loads the trace this process just wrote under
``inputs.CACHE_DIR/trace/`` (the newest ``*.xplane.pb`` there, refused if it
is older than this process) and memoises the result.  A program that names
no scope (a parent commit) gives ``None``, as does a run without a trace.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time

import inputs
import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "scopes.json")) as _fh:
    _VOCAB = json.load(_fh)
PREFIX = _VOCAB["prefix"]
LAYER, *LEAVES = _VOCAB["scopes"]
COLLECTIVE_SCOPES = ("xchg_a2a", "grad_psum")
UNSCOPED, NO_LAYER = "unscoped", "-"
TOKEN = re.compile(re.escape(PREFIX) + r"([A-Za-z0-9_]+)")
LAYER_TOKEN = re.compile(LAYER + r"(\d+)")
LOADED_AT = time.time()             # the readers import this before set-up

_memo: dict = {}


def scope_of(tf_op: str) -> tuple:
    """``(layer, leaf scope, direction)`` of one op's ``tf_op``."""
    layer, leaf = NO_LAYER, UNSCOPED
    for token in TOKEN.findall(tf_op):
        m = LAYER_TOKEN.fullmatch(token)
        if m:
            layer = LAYER + m.group(1)
        elif token in LEAVES:
            leaf = token
    return layer, leaf, "bwd" if "transpose(" in tf_op else "fwd"


def reduce_plane(plane: dict, runs: int) -> dict:
    """``{(layer, scope, direction): seconds}`` of one device's window."""
    lo, hi = tracered.window_of(plane, runs)
    rows: dict = {}
    for ev in tracered.leaf_events(plane["lines"][tracered.OP_LINE]):
        for a, b in tracered.clip([(ev[1], ev[1] + ev[2])], lo, hi):
            layer, leaf, way = scope_of(ev[3].get("tf_op", ""))
            if tracered.is_collective(ev) and leaf not in COLLECTIVE_SCOPES:
                leaf += ":collective"
            key = (layer, leaf, way)
            rows[key] = rows.get(key, 0.0) + (b - a) * 1e-9
    return rows


def reduce_scopes(planes: list, runs: int, epochs: int) -> dict | None:
    """Seconds per epoch by ``(layer, scope, direction)``: each chip's own
    table under ``per_chip`` and their mean under ``mean``.  ``None`` where
    no device plane was traced or no op carries a scope token."""
    devs = tracered.device_planes(planes)
    per = [{k: v / epochs for k, v in reduce_plane(p, runs).items()}
           for p in devs]
    if not any(leaf.split(":")[0] != UNSCOPED
               for rows in per for _, leaf, _ in rows):
        return None
    keys = sorted({k for rows in per for k in rows})
    return {"chips": len(per), "per_chip": per,
            "mean": {k: sum(rows.get(k, 0.0) for rows in per) / len(per)
                     for k in keys}}


def seconds(rows: dict, *scopes: str) -> float:
    """Sum over layers and directions of the rows of ``scopes``."""
    return sum(v for (_, leaf, _), v in rows.items() if leaf in scopes)


def by_scope(rows: dict) -> dict:
    """``{"layer1/agg_slots/bwd": seconds}``: JSON has no tuple keys."""
    return {"/".join(k): round(v, 6) for k, v in sorted(rows.items())}


# ------------------------------------------------------- this process's trace
def newest_trace() -> str | None:
    """The ``.xplane.pb`` this process wrote, or nothing."""
    files = glob.glob(os.path.join(inputs.CACHE_DIR, "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    files = [f for f in files if os.path.getmtime(f) >= LOADED_AT - 1.0]
    return max(files, key=os.path.getmtime) if files else None


def program_spans(path: str) -> list:
    """``[name, start, end]`` (ns) of the program's own host spans in one
    ``.xplane.pb`` (``tracered.load_xplane`` keeps only the benchmark's)."""
    from jax.profiler import ProfileData

    return [[ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith(tracered.DEVICE_PREFIX)
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


def innermost(spans: list) -> list:
    """Nested spans cut into disjoint pieces, each named after the shortest
    span that covers it, so that ``tracered.reduce_plane``, which gives a gap
    to the span overlapping it most, gives it to the innermost one."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    out: list = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [sp for sp in spans if sp[1] <= a and b <= sp[2]]
        if not cover:
            continue
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0]
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1][2] = b
        else:
            out.append([name, a, b])
    return out


def table(run: dict) -> dict | None:
    """The reduction of this run's trace, once per process; ``None`` without
    a trace (a CPU rehearsal names no device metric) or without scopes."""
    if not run.get("trace"):
        return None
    if "table" not in _memo:
        path = newest_trace()
        runs = epochs = run["trace"]["epochs"]
        planes = tracered.load_xplane(path) if path else []
        red = reduce_scopes(planes, runs, epochs)
        _memo["table"] = red
        if red is not None:
            print("bench: " + json.dumps({
                "scopes_per_chip": [by_scope(r) for r in red["per_chip"]]}),
                flush=True)
            gaps = tracered.reduce_plane(tracered.device_planes(planes)[0],
                                         runs, innermost(program_spans(path)))["gaps"]
            print("bench: " + json.dumps({"idle_gaps_by_program_span": {
                k: v / epochs for k, v in gaps.items()}}), flush=True)
    return _memo["table"]


def scope_seconds(run: dict, *scopes: str) -> float | None:
    """Mean over chips of the seconds per epoch under ``scopes``; nothing
    where the trace holds no op of them."""
    red = table(run)
    if red is None:
        return None
    return seconds(red["mean"], *scopes) or None


# ------------------------------------- the program's host spans and counters
def program_table(name: str) -> dict:
    """``span_totals()`` or ``counters()`` of ``sgcn_tpu.obs.tracing`` in
    this process; empty where the program has no such table (a parent
    commit)."""
    from sgcn_tpu.obs import tracing

    return getattr(tracing, name, dict)()


def span_durations(name: str) -> list:
    """Durations (s, oldest first) of the program's host span ``name``."""
    return program_table("span_totals").get(name, {}).get("durations", [])
