"""Products-scale partitioner k-sweep.

The reference sweeps k over its large graphs offline
(``GPU/hypergraph/run.sh:1-13`` drives whole dataset directories through the
part-vector generators).  This sweep runs the native hp (colnet km1) and gp
(edge-cut) partitioners at k ∈ {8, 16, 32, 64} on both products-shape bench
graphs (BA power-law and dcsbm power-law+communities, n=2.45M, ~125M nnz),
recording km1 / wall-clock / balance per point.

km1 of the column-net model EQUALS the comm plan's send rows per layer pass
(the plan-volume invariant ``tests/test_plan.py`` pins), so the
sweep IS the comm-volume-vs-k curve without 8 more ~2-minute plan builds.

Writes ``bench_artifacts/products_ksweep.json``.  Single-core job, ~1-2 h;
run it nohup'd:  PYTHONPATH=/root/repo python -u scripts/products_ksweep.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))
ART = os.path.join(REPO, "bench_artifacts")


def balance(pv: np.ndarray, k: int) -> float:
    cnt = np.bincount(pv, minlength=k)
    return float(cnt.max() / cnt.mean())


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--graphs", default="dcsbm,ba")
    p.add_argument("--ks", default="8,16,32,64")
    p.add_argument("-n", type=int, default=2_450_000)
    args = p.parse_args()

    from products_partition import km1_of
    from sgcn_tpu.io.datasets import ba_graph, dcsbm_graph
    from sgcn_tpu.partition import (partition_graph,
                                    partition_hypergraph_colnet)
    from sgcn_tpu.prep import normalize_adjacency

    ks = [int(x) for x in args.ks.split(",")]
    path = os.path.join(ART, "products_ksweep.json")
    out: dict = {"n": args.n, "ks": ks, "host": "single core",
                 "rp_method": "balanced_random_partition seed 314159",
                 "note": "km1 == plan send rows per layer pass "
                         "(plan-volume invariant)", "sweep": {}}
    if os.path.exists(path):
        with open(path) as fh:
            prev = json.load(fh)
        # resume only the SAME sweep: cached points under a different n
        # would be silently relabeled
        if prev.get("n") == args.n:
            prev["ks"] = sorted(set(prev.get("ks", [])) | set(ks))
            out = prev
    for gname in args.graphs.split(","):
        t0 = time.time()
        if gname == "ba":
            a = ba_graph(args.n, 25, seed=0)
        else:
            a = dcsbm_graph(args.n, ncomm=200, avg_deg=50, seed=0)
        ahat = normalize_adjacency(a)
        del a
        csr = ahat.tocsr()
        print(f"{gname}: graph {time.time()-t0:.0f}s nnz={ahat.nnz}",
              flush=True)
        block = out["sweep"].setdefault(gname, {})
        for k in ks:
            kk = str(k)
            if kk in block:
                print(f"{gname} k={k}: cached", flush=True)
                continue
            t0 = time.time()
            pv_hp, km1_hp = partition_hypergraph_colnet(ahat, k, seed=0)
            t_hp = time.time() - t0
            t0 = time.time()
            pv_gp, _cut = partition_graph(ahat, k, seed=0)
            t_gp = time.time() - t0
            km1_gp = km1_of(csr, np.asarray(pv_gp), k)
            # permutation-based random, seed decorrelated from the graph
            # generator: iid integers(0,k) from default_rng(0) share the
            # uniform stream dcsbm_graph(seed=0) used for community
            # assignment and partially ALIGN with the communities
            # (measured: km1 404k vs a true-random 694k at 100k cells)
            from sgcn_tpu.partition import balanced_random_partition
            pv_rp = np.asarray(balanced_random_partition(
                args.n, k, seed=314159))
            km1_rp = km1_of(csr, pv_rp, k)
            block[kk] = {
                "hp": {"km1": int(km1_hp), "time_s": round(t_hp, 1),
                       "balance": balance(np.asarray(pv_hp), k)},
                "gp": {"km1": int(km1_gp), "time_s": round(t_gp, 1),
                       "balance": balance(np.asarray(pv_gp), k)},
                "rp_km1": int(km1_rp),
            }
            print(f"{gname} k={k}: {json.dumps(block[kk])}", flush=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(out, fh, indent=1)
            os.replace(tmp, path)
        del ahat, csr
    print("wrote", path, flush=True)


if __name__ == "__main__":
    main()
