"""Bisect the GAT runtime worker-crash blind spot.

Round 4's record (`models/gat.py` "KNOWN BLIND SPOT"): the 2-layer
BA-products f32 GAT step passed compile AND the calibrated HBM capacity
model, then killed the TPU worker at runtime.  The guard since fences tail
sizes > 20M edges — calibrated on two points, fragile.  This script makes
the fence principled: it sweeps the hub-tail length at fixed everything-else
(synthetic plans with a controlled COO tail; bucket cells held constant)
and records, for each point, compile-ok / run-ok / crash — narrowing the
edge to a measured boundary.

DANGER: a positive hit KILLS the TPU worker and resets chip state (the
round-4 drift event) — run this LAST in a session, never before
measurements you care about.  Each point runs in a SUBPROCESS so a dead
worker fails the point, not the sweep.  The children run ONE AT A TIME
from this parent, which never touches JAX: a chip belongs to one process,
and a parent that held it would starve every child.

Writes ``bench_artifacts/gat_crash_bisect.json`` incrementally.

Run: PYTHONPATH=/root/repo python -u scripts/gat_crash_bisect.py
     [--tails 8,12,16,20,24,29] [--n 2450000]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "bench_artifacts")

# child payload: build a products-shape BA graph, truncate the built
# combined tail to the requested length post-build (bucket cells stay
# untouched — the control the bisect needs), and run ONE 2-layer GAT step
# with the capacity guard bypassed
CHILD = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["SGCN_GAT_UNSAFE"] = "1"           # bypass the fence ON PURPOSE
import numpy as np
from sgcn_tpu.io.datasets import ba_graph
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, make_train_data

n, tail_target = {n}, {tail}
ahat = normalize_adjacency(ba_graph(n, 25, seed=0))
pv = np.zeros(n, dtype=np.int64)
plan = build_comm_plan(ahat, pv, 1)
plan.ensure_cell()
true_tail = int(plan.ctail_nnz[0])
print(f"TAILINFO true_tail={{true_tail}} target={{tail_target}}", flush=True)
if true_tail < tail_target:
    print("SKIP tail smaller than target", flush=True)
    sys.exit(3)
# truncate the combined tail to the target length (keeps dst-sorted order;
# the dropped edges simply don't contribute — numerics irrelevant here)
import dataclasses
plan = dataclasses.replace(
    plan,
    ctail_dst=plan.ctail_dst[:, :tail_target],
    ctail_src=plan.ctail_src[:, :tail_target],
    ctail_w=plan.ctail_w[:, :tail_target],
    ctail_nnz=np.minimum(plan.ctail_nnz, tail_target),
)
rng = np.random.default_rng(0)
feats = rng.standard_normal((n, 128)).astype(np.float32)
labels = rng.integers(0, 40, n).astype(np.int32)
tr = FullBatchTrainer(plan, fin=128, widths=[128, 40], model="gat",
                      activation="none", seed=2)
data = make_train_data(plan, feats, labels)
# explicit AOT compile so the parent can tell compile-OOM from runtime
# crash (jax.jit compiles lazily inside the first call otherwise)
from sgcn_tpu.parallel.mesh import shard_stacked
sdata = type(data)(**shard_stacked(tr.mesh, vars(data)))
compiled = tr._step.lower(tr.params, tr.opt_state, tr.pa, sdata.h0,
                          sdata.labels, sdata.train_valid).compile()
print("COMPILED", flush=True)
loss = tr.step(data)
print(f"RAN loss={{loss}}", flush=True)
"""


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--tails", default="8,12,16,20,24,29",
                   help="tail lengths to probe, in MILLIONS of edges")
    p.add_argument("--n", type=int, default=2_450_000)
    p.add_argument("--timeout", type=int, default=2400)
    args = p.parse_args()

    path = os.path.join(ART, "gat_crash_bisect.json")
    rec = {"n": args.n, "points": {}}
    if os.path.exists(path):
        with open(path) as fh:
            prev = json.load(fh)
        if prev.get("n") == args.n:     # cache is per-n; stale n restarts
            rec = prev

    def tpu_alive() -> bool:
        try:
            pr = subprocess.run(
                [sys.executable, "-c",
                 "import jax; jax.devices()"],
                capture_output=True, timeout=120)
            return pr.returncode == 0
        except subprocess.TimeoutExpired:
            return False

    for tm in (float(x) for x in args.tails.split(",")):
        tail = int(tm * 1e6)
        key = f"{tm:g}M"
        if key in rec["points"]:
            print(f"{key}: cached {rec['points'][key]['status']}", flush=True)
            continue
        # a dead worker would misclassify this point as compile-fail and
        # poison the cache — verify the chip is reachable first
        alive = False
        for _ in range(5):
            if tpu_alive():
                alive = True
                break
            print("TPU unreachable; waiting 120s", flush=True)
            time.sleep(120)
        if not alive:
            print(f"{key}: TPU down, NOT cached — rerun later", flush=True)
            continue
        t0 = time.time()
        try:
            proc = subprocess.run(
                [sys.executable, "-u", "-c",
                 CHILD.format(repo=REPO, n=args.n, tail=tail)],
                capture_output=True, text=True, timeout=args.timeout)
            out = proc.stdout
            if "RAN loss=" in out:
                status = "ran"
            elif proc.returncode == 3:
                status = "tail-too-small"
            elif "COMPILED" in out:
                status = "runtime-crash"      # compiled, then died
            else:
                status = "compile-fail"
            detail = (out.strip().splitlines()[-1:] or [""])[0] \
                + (" | " + proc.stderr.strip().splitlines()[-1]
                   if proc.returncode not in (0, 3) and proc.stderr else "")
        except subprocess.TimeoutExpired:
            status, detail = "timeout", f"> {args.timeout}s"
        rec["points"][key] = {"status": status, "detail": detail[:400],
                              "elapsed_s": round(time.time() - t0, 1)}
        print(f"{key}: {json.dumps(rec['points'][key])}", flush=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(rec, fh, indent=1)
        os.replace(tmp, path)
        if rec["points"][key]["status"] in ("runtime-crash", "timeout"):
            print("worker likely dead; pausing 180s before the next point",
                  flush=True)
            time.sleep(180)
    print("wrote", path, flush=True)


if __name__ == "__main__":
    main()
