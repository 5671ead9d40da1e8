"""Resolution of a cell of ``BENCHMARK.json`` to the files that define it.

A cell names a configuration and a traffic mix; a configuration entry names
its file; a traffic mix is ``traffic/<name>.json``, whose ``kind`` names the
runner ``runners/<kind>.py``; a metric is the reader ``end_to_end/<name>.py``
or ``layer_metrics/<name>.py``.  A later PR adds a configuration, a mix, a
runner kind or a metric by adding files and manifest entries, editing none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(path: str):
    """Import one file by path (readers, runners, the reference)."""
    name = "bench_" + os.path.relpath(path, HERE)[:-3].replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, nested dicts merged key by key."""
    out = dict(base)
    for key, val in over.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], val)
        else:
            out[key] = val
    return out


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    runner: object        # module runners/<kind>.py
    end_to_end: list      # [(name, unit, reader module)]
    per_layer: list


def _metrics(entries: list, cell: str, folder: str) -> list:
    out = []
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        path = os.path.join(HERE, folder, m["name"] + ".py")
        out.append((m["name"], m["unit"], load_module(path)))
    return out


def resolve(workload: str, rehearse: bool = False) -> Cell:
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    entry = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = read_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = read_json(os.path.join(HERE, "traffic",
                                     entry["traffic"] + ".json"))
    if rehearse:
        config = merged(config, config.get("rehearse", {}))
    runner = load_module(os.path.join(HERE, "runners",
                                      traffic["kind"] + ".py"))
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, runner=runner,
                end_to_end=_metrics(bench["end_to_end"], workload,
                                    "end_to_end"),
                per_layer=_metrics(bench["per_layer"], workload,
                                   "layer_metrics"))
