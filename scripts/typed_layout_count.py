"""What the typed-graph cell's slot passes execute, counted in the sandbox.

Builds a configuration's typed layout (``models/rgcn.py::build_typed_layout``)
from the benchmark's cached graph at k = 1 and prints, per pass and relation,
the edges that carry a value, the executed slots, the virtual rows and the
buckets + classes of the layout walked, and the step's executed ÷ live — the
``rel.work`` counter's table without a device (PERF.md §5 quotes it).  No
chip, no cell runs it; the graph is generated on first use (~30 s) and the
plan takes ~35 s.

    python scripts/typed_layout_count.py [benchmark/configs/rgcn-mag-2x64.json]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]


def main(path: str) -> None:
    import inputs                                   # the benchmark's own
    from sgcn_tpu.models import rgcn
    from sgcn_tpu.parallel import build_comm_plan

    with open(path) as fh:
        cfg = json.load(fh)
    ahat, _ = inputs.load_graph(cfg["n"], cfg["graph"])
    plan = build_comm_plan(ahat, np.zeros(cfg["n"], np.int64), 1)
    model = {k: v for k, v in cfg["model"].items() if k != "name"}
    args = rgcn.resolve_args(cfg["f_in"], cfg["widths"], model)
    t0 = time.time()
    layout = rgcn.build_typed_layout(plan, args)
    print(f"build_typed_layout: {time.time() - t0:.1f} s; heights "
          f"{layout['heights']}")
    for (s, d), (buckets, tail, halo) in layout["layouts"]:
        print(f"layout {args['types'][s][0]} -> {args['types'][d][0]}: "
              f"buckets {buckets} tail {tail} halo {halo}")
    specs = rgcn.layer_specs(args, layout)
    lanes = [cfg["f_in"]] + list(cfg["widths"][:-1])
    passes = rgcn.pass_counts(args, layout, specs, lanes)
    print("| pass | into | relation | edges | slots | rows | classes |")
    print("|---|---|---|---|---|---|---|")
    for p in passes:
        head = f"layer {p['layer']} {p['direction']} ({p['lanes']} lanes)"
        for run in p["run"]:
            print(f"| {head} | {', '.join(p['into'])} | {run['relation']} | "
                  f"{run['edges']:,} | {run['slots']:,} | {run['rows']:,} | "
                  f"{run['classes']} |")
        print(f"| {head} | all | run {len(p['run'])}, left out "
              f"{p['left_out'] or 'none'} | {p['edges']:,} | {p['slots']:,} "
              f"| {sum(r['rows'] for r in p['run']):,} | "
              f"{sum(r['classes'] for r in p['run'])} |")
    live = sum(p["edges"] for p in passes)
    executed = sum(p["slots"] for p in passes)
    rows = sum(r["rows"] for p in passes for r in p["run"])
    print(f"step: executed {executed:,} slots + {rows:,} virtual rows for "
          f"{live:,} live edge visits: executed / live = "
          f"{executed / live:.4f}")
    est = rgcn.estimate_rgcn_hbm_bytes(plan, cfg["f_in"], cfg["widths"], args,
                                       layout)
    print("memory estimate (GB):",
          {k: round(v / 1e9, 3) for k, v in est.items()})


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "benchmark", "configs", "rgcn-mag-2x64.json"))
