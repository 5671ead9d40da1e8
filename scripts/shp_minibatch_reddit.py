"""SHP → mini-batch trainer composition at Reddit shape.

The reference pipeline: ``GPU/SHP/main.py`` pickles a baseline full-graph HP
partvec and a stochastic-HP partvec (``:131-140``), and
``GPU/PGCN-Mini-batch.py:217-218`` consumes one of them for distributed
mini-batch training.  The paper's SHP claim is that the stochastic partition
lowers EXPECTED mini-batch communication; round 3 only simulated that at toy
size.  This script measures it IN THE TRAINER at Reddit's vertex count:

  1. generate a power-law graph at Reddit's n (232 965 vertices; zero egress
     forbids the real 114M-edge Reddit, so degree is the products-like 50 —
     the vertex count and batch geometry are what SHP cares about),
  2. run the SHP pipeline (k=8, batch 4096 — the BASELINE.json Reddit
     config) producing pv_hp and pv_stchp,
  3. build the mini-batch trainer under EACH partvec on the virtual-8 CPU
     mesh, run the fused one-program epoch sweep, and report the
     TRAINER-side comm volumes (CommStats counters — the same numbers the
     reference prints at end of run, ``GPU/PGCN.py:230-238``) plus the
     fused-epoch wall-clock,
  4. write ``bench_artifacts/shp_reddit.json``.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     PYTHONPATH=/root/repo python scripts/shp_minibatch_reddit.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import argparse

    import jax
    jax.config.update("jax_platforms", "cpu")

    from sgcn_tpu.io.datasets import ba_graph, dcsbm_graph
    from sgcn_tpu.prep import normalize_adjacency
    from sgcn_tpu.shp.model import run_shp
    from sgcn_tpu.train.minibatch import MiniBatchTrainer

    ap = argparse.ArgumentParser()
    # dcsbm: the real Reddit is community-structured
    # (41 subreddit classes) like dcsbm, NOT an expander like ba — ba is
    # where partitioning cannot win, so it under-sells the SHP margin
    ap.add_argument("--graph", default="ba", choices=["ba", "dcsbm"])
    args = ap.parse_args()

    n, k, batch = 232_965, 8, 4096
    t0 = time.time()
    if args.graph == "ba":
        a = ba_graph(n, 25, seed=0)
        gnote = ("Reddit vertex count; synthetic power-law (zero egress), "
                 "deg ~50")
    else:
        a = dcsbm_graph(n, ncomm=50, avg_deg=50, seed=0)
        gnote = ("Reddit vertex count; dcsbm power-law+communities "
                 "(the real Reddit's structure profile), deg ~50")
    ahat = normalize_adjacency(a)
    del a
    print(f"graph n={n} nnz={ahat.nnz} {time.time()-t0:.0f}s", flush=True)

    # 100 sampled batches: each 4096-vertex batch touches ~1.8% of the
    # vertices, so the stochastic hypergraph needs enough samples to SEE the
    # batch distribution (an under-sampled one measurably LOSES to plain hp
    # — observed at toy scale with 6 batches); 100 keeps the stacked
    # hypergraph ~6M pins, well inside the partitioner's budget
    t0 = time.time()
    shp = run_shp(ahat, k, nsampled_batches=100, batch_size=batch,
                  sim_iters=20, seed=1)
    t_shp = time.time() - t0
    print(f"shp: km1_hp={shp['km1_hp']} km1_stchp={shp['km1_stchp']} "
          f"sim hp={shp['sim_comm_volume_hp']} "
          f"stchp={shp['sim_comm_volume_stchp']} ({t_shp:.0f}s)", flush=True)

    rng = np.random.default_rng(0)
    feats = rng.standard_normal((n, 64)).astype(np.float32)
    labels = rng.integers(0, 16, size=n).astype(np.int32)

    out = {
        "graph": {"family": args.graph, "n": n, "nnz": int(ahat.nnz),
                  "note": gnote},
        "k": k, "batch_size": batch,
        "shp_pipeline_s": round(t_shp, 1),
        "km1_fullgraph": {"hp": int(shp["km1_hp"]),
                          "stchp": int(shp["km1_stchp"])},
        "simulated_batch_volume": {
            "hp": int(shp["sim_comm_volume_hp"]),
            "stchp": int(shp["sim_comm_volume_stchp"])},
    }

    for name in ("hp", "stchp"):
        pv = shp[f"partvec_{name}"]
        t0 = time.time()
        tr = MiniBatchTrainer(ahat, pv, k, fin=64, widths=[64, 16],
                              batch_size=batch, seed=0)
        t_build = time.time() - t0
        # warm-up (compile) then timed fused sweeps
        losses = tr.run_epochs_fused(feats, labels, epochs=1)
        t0 = time.time()
        losses = tr.run_epochs_fused(feats, labels, epochs=3)
        epoch_s = (time.time() - t0) / 3
        rep = tr.fused_stats_report()
        # per-epoch deterministic plan volume (counters accumulate over the
        # warm-up too, so report the per-epoch plan prediction alongside)
        plan_vol = sum(int(p.predicted_send_volume.sum()) for p in tr.plans)
        out[name] = {
            "nbatches": len(tr.plans),
            "build_s": round(t_build, 1),
            "epoch_s_8dev_cpu": round(epoch_s, 4),
            "final_loss": float(np.asarray(losses)[-1]),
            "plan_send_rows_per_layer_pass": plan_vol,
            "trainer_total_send_volume": int(rep["total_send_volume"]),
            "trainer_total_send_msgs": int(rep["total_send_msgs"]),
        }
        print(name, json.dumps(out[name]), flush=True)

    out["volume_ratio_stchp_vs_hp"] = round(
        out["stchp"]["plan_send_rows_per_layer_pass"]
        / max(out["hp"]["plan_send_rows_per_layer_pass"], 1), 4)
    dst = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench_artifacts", "shp_reddit.json")
    # per-family blocks: the ba and dcsbm runs coexist in one artifact
    rec = {}
    if os.path.exists(dst):
        with open(dst) as f:
            rec = json.load(f)
        if "graph" in rec:           # migrate the old single-run layout
            rec = {rec["graph"]["family"]: rec}
    rec[args.graph] = out
    tmp = dst + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
    os.replace(tmp, dst)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
