"""Seconds of a traced window by the typed attention model's sub-scopes.

``sgcn_tpu/models/rgat.py`` names its row-wise work by ``jax.named_scope``s
opened INSIDE the leaf scope ``sgcn.dense`` of ``scopes.json``
(``.../sgcn.dense/sgcn.ratt_project/dot_general:``,
``.../sgcn.dense/sgcn.ratt_norm/...``): ``scopered`` skips the token and
books the op to its leaf, and this module reads the last token of
``scopes_ratt.json`` in the same ``tf_op`` (``scopered_rel``'s rule, over its
own vocabulary).  Seconds are those of the leaf ops of the device's op line,
clipped to the window, per epoch, mean over chips; a fusion carries the
``tf_op`` of its root instruction, so a sub-scope's seconds are those of the
fusions ROOTED in it.  A collective there is booked apart, as
``<sub-scope>:collective``.

A program without the sub-scopes (a parent commit), a run without a trace
and a CPU rehearsal give ``None``.
"""

from __future__ import annotations

import json
import os

import scopered
import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "scopes_ratt.json")) as _fh:
    _VOCAB = json.load(_fh)
SUBSCOPES = tuple(_VOCAB["subscopes"])

_memo: dict = {}


def sub_of(tf_op: str) -> str | None:
    """The last sub-scope token of one op's ``tf_op``, if any."""
    found = [t for t in scopered.TOKEN.findall(tf_op) if t in SUBSCOPES]
    return found[-1] if found else None


def reduce_plane(plane: dict, runs: int) -> dict:
    """``{sub-scope | sub-scope:collective: seconds}`` of one device's
    window."""
    lo, hi = tracered.window_of(plane, runs)
    rows: dict = {}
    for ev in tracered.leaf_events(plane["lines"][tracered.OP_LINE]):
        sub = sub_of(ev[3].get("tf_op", ""))
        if sub is None:
            continue
        if tracered.is_collective(ev):
            sub += ":collective"
        for a, b in tracered.clip([(ev[1], ev[1] + ev[2])], lo, hi):
            rows[sub] = rows.get(sub, 0.0) + (b - a) * 1e-9
    return rows


def reduce_ratt(planes: list, runs: int, epochs: int) -> dict | None:
    """Seconds per epoch by key, mean over chips; ``None`` where no device
    plane was traced or no op carries a sub-scope token."""
    per = [reduce_plane(p, runs) for p in tracered.device_planes(planes)]
    if not any(per):
        return None
    keys = sorted({k for rows in per for k in rows})
    return {k: sum(rows.get(k, 0.0) for rows in per) / len(per) / epochs
            for k in keys}


def table(run: dict) -> dict | None:
    """The reduction of this run's trace (the one ``scopered`` reads), once
    per process."""
    if not run.get("trace"):
        return None
    if "table" not in _memo:
        path = scopered.newest_trace()
        runs = epochs = run["trace"]["epochs"]
        planes = tracered.load_xplane(path) if path else []
        _memo["table"] = reduce_ratt(planes, runs, epochs)
        if _memo["table"] is not None:
            print("bench: " + json.dumps({"ratt_subscopes": {
                k: round(v, 6) for k, v in _memo["table"].items()}}),
                flush=True)
    return _memo["table"]


def seconds(run: dict, key: str) -> float | None:
    red = table(run)
    return (red.get(key) or None) if red else None
