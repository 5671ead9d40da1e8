"""Products-scale partitioner proof.

Runs the native partitioners on a products-shape power-law graph
(``ba_graph(n=2450000, 25, seed=0)``, avg degree ~50, normalized; ``--family
dcsbm`` gives the family of the benchmark's stand-in graph) at k=8, and
records the evidence the reference produces offline for its benchmark matrices (``GCN-HP/main.cpp:284-356`` partitions the real
ogbn-scale mtx and self-reports cut/conn + chrono time;
``GPU/hypergraph/run.sh:1-13`` sweeps whole dataset dirs):

  * wall-clock of each partitioner (hp colnet km1, gp edge-cut, random),
  * balance (nnz-weighted and vertex-count max/mean),
  * km1 = sum over columns (lambda - 1) — equal to the halo send volume in
    feature rows per layer per direction (every column has its diagonal
    nonzero after normalization, so the owner is always among the pins),

then writes

  * ``bench_artifacts/products_partition.npz``   (hp + gp part vectors;
    written on demand, git-ignored)
  * ``bench_artifacts/products_partition.json``  (all metrics + provenance)

A ~20-minute single-core job; nothing here was measured on the chip
(``PERF.md`` §4 has the benchmark's own partition figures).

Usage: PYTHONPATH=/root/repo python scripts/products_partition.py [-n N] [-k K]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np
import scipy.sparse as sp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sgcn_tpu.io.datasets import ba_graph                      # noqa: E402
from sgcn_tpu.partition import (                               # noqa: E402
    balanced_random_partition, partition_graph, partition_hypergraph_colnet,
)
from sgcn_tpu.prep import normalize_adjacency                  # noqa: E402


def km1_of(a: sp.csr_matrix, pv: np.ndarray, k: int) -> int:
    """Connectivity-1 of a part vector over the column-net model, vectorized:
    dedup (column, part-of-row) pairs, then km1 = #pairs - #nonempty columns."""
    coo = a.tocoo()
    pairs = np.unique(coo.col.astype(np.int64) * k + pv[coo.row])
    ncols = len(np.unique(pairs // k))
    return int(len(pairs) - ncols)


def balance_of(pv: np.ndarray, w: np.ndarray, k: int) -> dict:
    pwn = np.bincount(pv, weights=w, minlength=k)
    pwc = np.bincount(pv, minlength=k)
    return {"nnz_max_over_mean": round(float(pwn.max() / pwn.mean()), 4),
            "count_max_over_mean": round(float(pwc.max() / pwc.mean()), 4)}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("-n", type=int, default=2_450_000)
    p.add_argument("--attach", type=int, default=25)   # avg deg ~= 2*attach
    p.add_argument("--family", default="ba", choices=["ba", "dcsbm"],
                   help="ba = power-law expander (partitioners beat "
                        "random only marginally, an honest property of "
                        "preferential attachment); dcsbm = power-law + "
                        "planted communities (the real-ogbn structure "
                        "profile, where partition quality is measurable)")
    p.add_argument("-k", type=int, default=8)
    p.add_argument("-o", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_artifacts"))
    args = p.parse_args()

    t0 = time.time()
    if args.family == "ba":
        a = ba_graph(args.n, args.attach, seed=0)
        graph_meta = {
            "family": "ba", "n": int(args.n), "attach": args.attach,
            "seed": 0,
            "matches_bench": "ba_graph(n=%d, attach=%d, seed=0), avg deg %d"
                             % (args.n, args.attach, 2 * args.attach)}
    else:
        from sgcn_tpu.io.datasets import dcsbm_graph
        a = dcsbm_graph(args.n, ncomm=200, avg_deg=2 * args.attach, seed=0)
        graph_meta = {
            "family": "dcsbm", "n": int(args.n), "ncomm": 200,
            "avg_deg": 2 * args.attach, "seed": 0,
            "why": "power-law + communities: the structure profile of the "
                   "real ogbn-products, where partition quality is "
                   "measurable (BA is an expander)"}
    ahat = normalize_adjacency(a)
    w = np.diff(ahat.indptr).astype(np.float64)
    print(f"graph: n={args.n} nnz={ahat.nnz} gen+norm {time.time()-t0:.1f}s",
          flush=True)

    k = args.k
    graph_meta["nnz"] = int(ahat.nnz)
    out: dict = {
        "graph": graph_meta,
        "k": k,
        "host": "single CPU core",
    }

    t0 = time.time()
    pv_rp = balanced_random_partition(args.n, k, seed=1)
    t_rp = time.time() - t0
    t0 = time.time()
    km1_rp = km1_of(ahat, pv_rp, k)
    print(f"rp: km1={km1_rp} part {t_rp:.1f}s score {time.time()-t0:.1f}s",
          flush=True)
    out["rp"] = {"km1": km1_rp, "time_s": round(t_rp, 2),
                 **balance_of(pv_rp, w, k)}

    t0 = time.time()
    pv_hp, km1_hp = partition_hypergraph_colnet(ahat, k, seed=0)
    t_hp = time.time() - t0
    assert km1_hp == km1_of(ahat, pv_hp, k)   # self-reported metric is honest
    print(f"hp: km1={km1_hp} time {t_hp:.1f}s", flush=True)
    out["hp"] = {"km1": int(km1_hp), "time_s": round(t_hp, 2),
                 **balance_of(pv_hp, w, k),
                 "vs_random": round(km1_rp / max(km1_hp, 1), 2)}

    t0 = time.time()
    pv_gp, cut_gp = partition_graph(ahat, k, seed=0)
    t_gp = time.time() - t0
    km1_gp = km1_of(ahat, pv_gp, k)
    print(f"gp: cut={cut_gp} km1={km1_gp} time {t_gp:.1f}s", flush=True)
    out["gp"] = {"edge_cut": int(cut_gp), "km1": km1_gp,
                 "time_s": round(t_gp, 2), **balance_of(pv_gp, w, k),
                 "vs_random": round(km1_rp / max(km1_gp, 1), 2)}

    os.makedirs(args.o, exist_ok=True)
    stem = ("products_partition" if args.family == "ba"
            else f"products_partition_{args.family}")
    np.savez_compressed(os.path.join(args.o, stem + ".npz"),
                        pv_hp=pv_hp.astype(np.int32),
                        pv_gp=pv_gp.astype(np.int32))
    with open(os.path.join(args.o, stem + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
