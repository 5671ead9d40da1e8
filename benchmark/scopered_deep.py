"""Seconds of a traced window by the deep residual stack's sub-scopes, and
the seconds its checkpoints re-ran.

``sgcn_tpu/models/deepergcn.py`` names the layer's row-wise work by
``jax.named_scope``s opened INSIDE ``sgcn.dense``
(``.../sgcn.dense/sgcn.norm/...``): ``scopered`` skips the token and books
the op to ``dense``, and this module reads the last token of
``scopes_deep.json`` in the same ``tf_op`` (``scopered_att``'s rule).  A
collective there (BatchNorm's ``psum``s, the stabiliser's ``pmax``) is booked
apart, as ``<sub-scope>:collective``: a chip waiting in one is not doing
row-wise arithmetic.

Every layer of the stack runs under ``jax.checkpoint``, and an op the
backward re-runs carries ``rematted_computation`` in its ``tf_op``: the
seconds of those ops, over all busy seconds, are the price of what the
checkpoints do not keep.  Seconds are those of the leaf ops of the device's
op line, clipped to the window, per epoch, mean over chips — ``scopered``'s
rule; a fusion carries the ``tf_op`` of its root instruction, so both
readings are of fusions ROOTED in a sub-scope or in the recomputation.

A program without the sub-scopes (a parent commit), a run without a trace
and a CPU rehearsal give ``None``.
"""

from __future__ import annotations

import json
import os

import scopered
import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "scopes_deep.json")) as _fh:
    _VOCAB = json.load(_fh)
SUBSCOPES = tuple(_VOCAB["subscopes"])
RECOMPUTE_TOKEN = _VOCAB["recompute_token"]
RECOMPUTED, BUSY = "recomputed", "busy"

_memo: dict = {}


def sub_of(tf_op: str) -> str | None:
    """The last sub-scope token of one op's ``tf_op``, if any."""
    found = [t for t in scopered.TOKEN.findall(tf_op) if t in SUBSCOPES]
    return found[-1] if found else None


def reduce_plane(plane: dict, runs: int) -> dict:
    """``{sub-scope | sub-scope:collective | "recomputed" | "busy":
    seconds}`` of one device's window."""
    lo, hi = tracered.window_of(plane, runs)
    rows = {BUSY: 0.0}
    for ev in tracered.leaf_events(plane["lines"][tracered.OP_LINE]):
        tf_op = ev[3].get("tf_op", "")
        took = sum(b - a for a, b in tracered.clip(
            [(ev[1], ev[1] + ev[2])], lo, hi)) * 1e-9
        if not took:
            continue
        keys = [BUSY]
        sub = sub_of(tf_op)
        if sub is not None:
            keys.append(sub + (":collective" if tracered.is_collective(ev)
                               else ""))
        if RECOMPUTE_TOKEN in tf_op:
            keys.append(RECOMPUTED)
        for key in keys:
            rows[key] = rows.get(key, 0.0) + took
    return rows


def reduce_deep(planes: list, runs: int, epochs: int) -> dict | None:
    """Seconds per epoch by key, mean over chips; ``None`` where no device
    plane was traced or no op carries a sub-scope token."""
    per = [reduce_plane(p, runs) for p in tracered.device_planes(planes)]
    if not any(k.split(":")[0] in SUBSCOPES for rows in per for k in rows):
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
        _memo["table"] = reduce_deep(planes, runs, epochs)
        if _memo["table"] is not None:
            print("bench: " + json.dumps({"deep_subscopes": {
                k: round(v, 6) for k, v in _memo["table"].items()}}),
                flush=True)
    return _memo["table"]


def seconds(run: dict, key: str) -> float | None:
    red = table(run)
    return (red.get(key) or None) if red else None


def recompute_share(run: dict) -> float | None:
    """Busy seconds of the ops the checkpoints re-ran over all busy
    seconds, in percent."""
    red = table(run)
    if not red or not red.get(BUSY):
        return None
    return 100.0 * red.get(RECOMPUTED, 0.0) / red[BUSY]
