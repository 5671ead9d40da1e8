"""The price of a slot, from inside: seconds of a traced window by bucket,
width class and relation of the slot passes, joined to the program's own
count of what each executes.

``sgcn_tpu/ops/pspmm.py`` names every bucket of its slot reduce in the
compiled step (``.../sgcn.agg_slots/sgcn.bkt_534x64_u/...``: rows × width and
the form that ran), the sorted row scatter of every class of virtual rows
(``sgcn.fold_rows``) and, in the typed aggregation, the relation a pass walks
(``sgcn.pair_0_1``) — three token families outside ``scopes.json``'s
vocabulary (``scopes_slots.json`` is the benchmark's copy), so ``scopered``
still books each op to its leaf.  The program counter ``slots.work`` lists
the same passes: per pass its layer, direction, tags, runs an epoch, and per
store the buckets with their form.

The reduction follows ``scopered_rel``'s rules — leaf ops of the device's op
line, clipped to the window, per epoch, mean over chips, each chip's own
column kept; a collective is booked apart by ``scopered`` and is no slot
work — and groups the seconds under the three aggregation leaf scopes by
``(layer, leaf scope, direction, tags, bucket token | fold_rows | nothing)``.
A group joins the passes of ``slots.work`` with its layer, direction and
tags (several share them where one token covers several runs) and gets the
slots (or rows) they execute an epoch under that bucket; seconds ÷ count is
the price.  What carries neither a bucket nor ``fold_rows`` is
``unbucketed``: concatenations, ``out + ell``, reshapes' copies, the
attention's row-wise epilogues.

A program without the tokens (a parent commit), a run without a trace and a
CPU rehearsal give ``None``.
"""

from __future__ import annotations

import json
import os
import re

import scopered
import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "scopes_slots.json")) as _fh:
    _VOCAB = json.load(_fh)
BUCKET = re.compile(_VOCAB["bucket"])
PAIR = re.compile(_VOCAB["pair"])
FOLD_ROWS = _VOCAB["fold_rows"]
TAGS = tuple(_VOCAB["tags"])
STORE_OF = dict(_VOCAB["stores"])       # leaf scope -> store of slots.work
COUNTER = "slots.work"
# kind of a priced group -> the count of ``per_epoch`` it divides by
COUNT_OF = {"ell": "ell_slots", "fold": "fold_slots", "rows": "virtual_rows"}
COLUMNS = ("layer", "scope", "way", "tags", "bucket", "count", "seconds",
           "ns", "seconds_per_chip")

_memo: dict = {}


def key_of(tf_op: str) -> tuple | None:
    """``(layer, leaf scope, direction, tags, bucket token | "fold_rows" |
    None)`` of one op under an aggregation leaf scope; ``None`` for an op
    under any other."""
    layer, leaf, way = scopered.scope_of(tf_op)
    if leaf not in STORE_OF:
        return None
    tokens = scopered.TOKEN.findall(tf_op)
    what = [t for t in tokens if BUCKET.fullmatch(t) or t == FOLD_ROWS]
    tags = tuple(sorted({t for t in tokens
                         if t in TAGS or PAIR.fullmatch(t)}))
    return layer, leaf, way, tags, what[-1] if what else None


def reduce_plane(plane: dict, runs: int) -> dict:
    """``{key_of(op): seconds}`` of one device's window."""
    lo, hi = tracered.window_of(plane, runs)
    rows: dict = {}
    for ev in tracered.leaf_events(plane["lines"][tracered.OP_LINE]):
        key = key_of(ev[3].get("tf_op", ""))
        if key is None or tracered.is_collective(ev):
            continue
        for a, b in tracered.clip([(ev[1], ev[1] + ev[2])], lo, hi):
            rows[key] = rows.get(key, 0.0) + (b - a) * 1e-9
    return rows


def reduce_slots(planes: list, runs: int, epochs: int) -> dict | None:
    """Seconds per epoch by group: each chip's own table under ``per_chip``
    and their mean under ``mean``.  ``None`` where no device plane was traced
    or no op carries a bucket or ``fold_rows`` token."""
    per = [{k: v / epochs for k, v in reduce_plane(p, runs).items()}
           for p in tracered.device_planes(planes)]
    if not any(k[4] is not None for rows in per for k in rows):
        return None
    keys = sorted({k for rows in per for k in rows},
                  key=lambda k: (k[:4], k[4] or ""))
    return {"chips": len(per), "per_chip": per,
            "mean": {k: sum(rows.get(k, 0.0) for rows in per) / len(per)
                     for k in keys}}


def executed(work: dict, key: tuple) -> int | None:
    """Slots (for ``fold_rows``: virtual rows) an epoch that the passes of
    ``work`` with the group's layer, direction and tags execute under its
    bucket; ``None`` where no pass lists it."""
    layer, leaf, way, tags, what = key
    store, total = STORE_OF[leaf], 0
    shape = BUCKET.fullmatch(what).groups() if what != FOLD_ROWS else None
    for p in work["passes"]:
        if (f"{scopered.LAYER}{p['layer']}", p["way"],
                tuple(sorted(p["tags"]))) != (layer, way, tags):
            continue
        for e in p["stores"].get(store, []):
            if shape is None:
                total += e["rows"] * p["times_per_epoch"]
            elif (str(e["rows"]), str(e["width"]), e["form"]) == shape:
                total += e["rows"] * e["width"] * p["times_per_epoch"]
    return total or None


def prices(red: dict, work: dict) -> dict:
    """The ``slot_prices`` table and the sums the five metrics read.

    ``rows``: one list a group, in ``COLUMNS``' order (count and ns ``None``
    for an unbucketed group or one no pass lists).  ``sums``: seconds an
    epoch under bucket tokens in ``agg_slots`` (``ell_s``) and in the two
    fold scopes (``fold_s``), under ``fold_rows`` (``rows_s``), under
    neither (``unbucketed_s``), all of them (``agg_s``), and
    ``priced_s`` = Σ ns × count + unbucketed — equal to ``agg_s`` where
    every group found its count.  ``joined``: the counts the groups found,
    by kind — equal to ``per_epoch``'s where every listed bucket left ops
    of its own in the trace."""
    rows, sums = [], {k: 0.0 for k in ("ell_s", "fold_s", "rows_s",
                                       "unbucketed_s", "priced_s")}
    joined = dict.fromkeys(COUNT_OF.values(), 0)
    for key, secs in red["mean"].items():
        layer, leaf, way, tags, what = key
        count = None if what is None else executed(work, key)
        ns = None if count is None else secs * 1e9 / count
        rows.append([layer, leaf, way, list(tags), what, count,
                     round(secs, 7), None if ns is None else round(ns, 4),
                     [round(chip.get(key, 0.0), 7)
                      for chip in red["per_chip"]]])
        kind = ("unbucketed" if what is None else "rows"
                if what == FOLD_ROWS else "ell"
                if STORE_OF[leaf] == "ell" else "fold")
        sums[kind + "_s"] += secs
        sums["priced_s"] += secs if (what is None or count) else 0.0
        if count:
            joined[COUNT_OF[kind]] += count
    sums["agg_s"] = sum(red["mean"].values())
    return {"columns": list(COLUMNS), "rows": rows, "chips": red["chips"],
            "sums": {k: round(v, 7) for k, v in sums.items()},
            "joined": joined, "per_epoch": work["per_epoch"],
            "relations": work.get("relations", {})}


def counter() -> dict | None:
    """The program's ``slots.work``; nothing where it leaves none."""
    return scopered.program_table("counters").get(COUNTER) or None


def table(run: dict) -> dict | None:
    """``prices`` of this run's trace (the one ``scopered`` reads) against
    the program's counter, once per process, printed as ONE ``bench:`` line;
    ``None`` without a trace, without the tokens or without the counter."""
    if not run.get("trace"):
        return None
    if "table" not in _memo:
        work, out = counter(), None
        if work is not None:
            path = scopered.newest_trace()
            runs = epochs = run["trace"]["epochs"]
            planes = tracered.load_xplane(path) if path else []
            red = reduce_slots(planes, runs, epochs)
            if red is not None:
                out = prices(red, work)
                print("bench: " + json.dumps({"slot_prices": out}),
                      flush=True)
        _memo["table"] = out
    return _memo["table"]


def price_ns(run: dict, seconds: str, count: str) -> float | None:
    """ns each: ``sums[seconds]`` ÷ ``per_epoch[count]``; nothing where
    either is nothing."""
    tab = table(run)
    if tab is None or not tab["sums"][seconds] or not tab["per_epoch"][count]:
        return None
    return tab["sums"][seconds] * 1e9 / tab["per_epoch"][count]
