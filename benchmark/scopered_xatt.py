"""Seconds of a traced window by the graph-transformer model's sub-scopes and
slot passes.

``sgcn_tpu/models/unimp.py`` names its row-wise work by ``jax.named_scope``s
opened INSIDE the leaf scope ``sgcn.dense`` of ``scopes.json``
(``.../sgcn.dense/sgcn.xatt_project/dot_general:``) and each slot pass inside
its ``agg_*`` leaf (``.../sgcn.agg_slots/sgcn.xatt_max/...``): ``scopered``
skips the token and books the op to its leaf.  This module is
``scopered_ratt``'s reduction — the last token of a vocabulary in an op's
``tf_op``, the leaf ops of the device's op line clipped to the window, per
epoch, mean over chips, a collective booked apart as
``<token>:collective`` — loaded as an instance of its own over the
vocabulary of ``scopes_xatt.json``.  A fusion carries the ``tf_op`` of its
root instruction, so a token's seconds are those of the fusions ROOTED in it.

A program without the tokens (a parent commit), a run without a trace and a
CPU rehearsal give ``None``.
"""

from __future__ import annotations

import json
import os

import manifest
import scopered
import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
_VOCAB = manifest.read_json(os.path.join(HERE, "scopes_xatt.json"))
_ratt = manifest.load_module(os.path.join(HERE, "scopered_ratt.py"))
SUBSCOPES = _ratt.SUBSCOPES = tuple(_VOCAB["subscopes"] + _VOCAB["passes"])
sub_of, reduce_xatt = _ratt.sub_of, _ratt.reduce_ratt

_memo: dict = {}


def table(run: dict) -> dict | None:
    """The reduction of this run's trace (the one ``scopered`` reads), once
    per process."""
    if not run.get("trace"):
        return None
    if "table" not in _memo:
        path = scopered.newest_trace()
        runs = epochs = run["trace"]["epochs"]
        planes = tracered.load_xplane(path) if path else []
        _memo["table"] = reduce_xatt(planes, runs, epochs)
        if _memo["table"] is not None:
            print("bench: " + json.dumps({"xatt_subscopes": {
                k: round(v, 6) for k, v in _memo["table"].items()}}),
                flush=True)
    return _memo["table"]


def seconds(run: dict, key: str) -> float | None:
    red = table(run)
    return (red.get(key) or None) if red else None
