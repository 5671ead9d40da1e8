"""8-chip products epoch model from REAL per-chip shard measurements (r5 #1).

The north star (`BASELINE.json:5`) is an 8-chip ogbn-products 2-layer/128
full-batch GCN epoch; the chip tool offers one chip or one four-chip host.
The plan pads every
per-chip array to identical shapes, so chip c's compiled program — send-side
gather, halo gather, bucketed local+halo SpMM, dense matmuls, loss, symmetric
backward, Adam — is the same program every chip runs (MAX over ranks = any
rank).  This script:

  1. rebuilds the products-shape bench graph and the saved hp partition
     (``bench_artifacts/products_partition*.npz``, from
     ``scripts/products_partition.py``),
  2. builds the REAL k=8 comm plan and extracts one chip's shard
     (``sgcn_tpu.parallel.proxy``),
  3. measures that per-chip program on the real TPU with the round-3
     differential protocol (the per-call dispatch constant cancels),
  4. models the collectives the single chip cannot time from the plan's
     exact padded exchange bytes over a bidirectional-ring ICI model
     (v5e: 45 GB/s one-way per link — the conservative 1D-ring reading of
     the 2x4 slice; the 2D torus routes all_to_all faster), and
  5. writes ``bench_artifacts/shard_epoch_model[_dcsbm][_bf16wire|_abwire]
     .json`` (dtype-suffixed so --halo-dtype runs never overwrite the f32
     baseline artifact) with the composed 8-chip epoch-time model:
        lower bound  max(compute, comm)   (XLA overlaps the a2a with the
                                           local slot passes — proven on the
                                           compiled v5e 8-chip schedule,
                                           tests/test_overlap_hlo.py)
        upper bound  compute + comm       (zero overlap)

Reference protocol being matched: per-epoch wall-clock, MAX over ranks,
after warm-up (``GPU/PGCN.py:202-228``, ``Parallel-GCN/main.c:441-445``).

Usage:
  PYTHONPATH=/root/repo python scripts/shard_epoch_model.py
      [--graph ba|dcsbm] [--chip 0] [--models gcn,gat] [--epochs 4]
      [--halo-dtype float32|bfloat16|ab]
  ('ab' measures the f32 AND bf16 wire back to back under ONE plan — the
  drift-proof same-session comparison; GCN only)
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
ART = os.path.join(REPO, "bench_artifacts")

# v5e ICI: one-way per-link bandwidth (scaling-book spec value).  The 8-chip
# slice is a 2x4 torus; the model uses the 1D bidirectional ring its mesh
# axis maps to — conservative (2D routing can only be faster).
W_LINK = 45e9


def ring_a2a_seconds(per_chip_bytes: float, k: int) -> float:
    """All-to-all time on a bidirectional ring: every chip ships
    ``per_chip_bytes`` split uniformly over k-1 peers; balanced shortest-path
    routing loads each directed link with ``bytes * avg_hops / 2``."""
    d = np.arange(1, k)
    avg_hops = np.minimum(d, k - d).mean()
    return per_chip_bytes * avg_hops / 2 / W_LINK


def ring_allreduce_seconds(grad_bytes: float, k: int) -> float:
    """Ring allreduce (reduce-scatter + all-gather): 2(k-1)/k passes."""
    return 2 * (k - 1) / k * grad_bytes / W_LINK




def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--graph", default="ba", choices=["ba", "dcsbm"])
    p.add_argument("--chip", type=int, default=0)
    p.add_argument("--models", default="gcn,gat",
                   help="comma list drawn from {gcn, gat}")
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--halo-dtype", default="float32",
                   choices=["float32", "bfloat16", "ab"],
                   help="dtype of the a2a halo buffer (exchange-only bf16 "
                        "halves ICI bytes; tables/activations stay f32). "
                        "'ab' measures BOTH under one plan in one session "
                        "— the only drift-proof comparison at GB-table "
                        "scale (rates drifted 1.665x across sessions on "
                        "the rounds-3-5 development chip)")
    p.add_argument("--fin", type=int, default=128)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--classes", type=int, default=40)
    p.add_argument("--layers", type=int, default=2)
    args = p.parse_args()
    models = [m for m in args.models.split(",") if m]
    bad = set(models) - {"gcn", "gat"}
    if bad or not models:
        p.error(f"--models must be a comma list from {{gcn,gat}}, got "
                f"{args.models!r}")   # fail BEFORE minutes of graph/plan build
    if args.halo_dtype == "ab" and models != ["gcn"] \
            and args.models != "gcn,gat":   # explicit non-gcn request
        p.error("--halo-dtype ab measures the GCN wire A/B only; "
                "drop --models or pass --models gcn")

    from bench import diff_time_q
    from sgcn_tpu.models.gcn import exchange_widths
    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.parallel.proxy import shard_proxy_data, shard_proxy_plan
    from sgcn_tpu.prep import normalize_adjacency
    from sgcn_tpu.train import FullBatchTrainer

    suffix = "" if args.graph == "ba" else f"_{args.graph}"
    with open(os.path.join(ART, f"products_partition{suffix}.json")) as fh:
        rec = json.load(fh)
    g = rec["graph"]
    k = rec["k"]
    t0 = time.time()
    if args.graph == "ba":
        from sgcn_tpu.io.datasets import ba_graph
        a = ba_graph(g["n"], g["attach"], seed=g["seed"])
    else:
        from sgcn_tpu.io.datasets import dcsbm_graph
        a = dcsbm_graph(g["n"], ncomm=g["ncomm"], avg_deg=g["avg_deg"],
                        seed=g["seed"])
    ahat = normalize_adjacency(a)
    del a
    print(f"graph regen {time.time()-t0:.0f}s nnz={ahat.nnz}", flush=True)

    pv = np.load(os.path.join(ART, f"products_partition{suffix}.npz"))
    t0 = time.time()
    plan = build_comm_plan(ahat, pv["pv_hp"].astype(np.int64), k)
    print(f"plan build {time.time()-t0:.0f}s b={plan.b} s={plan.s} "
          f"r={plan.r} e={plan.e}", flush=True)
    del ahat

    widths = [args.hidden] * (args.layers - 1) + [args.classes]
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((plan.n, args.fin)).astype(np.float32)
    labels = rng.integers(0, args.classes, size=plan.n).astype(np.int32)
    proxy = shard_proxy_plan(plan, chip=args.chip)
    data = shard_proxy_data(plan, args.chip, feats, labels)
    del feats, labels

    # ---------------------------------------------------------- comm model
    ew = exchange_widths(args.fin, widths)
    true_rows = int(plan.predicted_send_volume[args.chip])
    grad_bytes = 4 * sum(
        i * o for i, o in zip([args.fin] + widths[:-1], widths))
    psum_s = ring_allreduce_seconds(grad_bytes, k)   # one grad psum per step

    def comm_model(halo_dtype: str, wire_widths) -> dict:
        halo_itemsize = 2 if halo_dtype == "bfloat16" else 4
        # padded bytes actually crossing ICI per chip per pass: (k-1) peer
        # buckets of S rows (the self-bucket stays on chip)
        pass_bytes = [(k - 1) * plan.s * w * halo_itemsize
                      for w in wire_widths]
        # fwd + bwd exchange per layer (symmetric VJP reuses the fwd form)
        a2a_s = sum(2 * ring_a2a_seconds(b, k) for b in pass_bytes)
        return {
            "model": "bidirectional ring over the 1D mesh axis; 2D-torus "
                     "routing of the 2x4 v5e slice can only be faster",
            "w_link_GBs": W_LINK / 1e9,
            "exchange_widths": list(wire_widths),
            "halo_dtype": halo_dtype,
            "padded_a2a_bytes_per_chip_per_pass": pass_bytes,
            "true_send_rows_chip": true_rows,
            "padded_send_rows_chip": int((k - 1) * plan.s),
            "a2a_s_per_epoch": a2a_s,
            "grad_bytes": grad_bytes,
            "psum_s_per_epoch": psum_s,
            "comm_s_per_epoch": a2a_s + psum_s,
        }

    # the GAT trainer rejects halo_dtype (its exchange narrows via the
    # packed compute_dtype path) — its wire is modeled f32 regardless; it
    # ships the POST-projection [p ‖ u] rows (fout + 1 lanes per layer),
    # not the GCN's project-first-rule widths
    if args.halo_dtype == "ab":
        # same-session wire A/B: one plan, one device data placement, both
        # wire dtypes measured back to back — the drift-proof form
        jobs = [("gcn", "gcn", "float32"),
                ("gcn_bf16wire", "gcn", "bfloat16")]
    else:
        jobs = [(m, m, args.halo_dtype if m == "gcn" else "float32")
                for m in models]
    comm_by_entry = {
        entry: comm_model(dt, ew if model == "gcn"
                          else [w + 1 for w in widths])
        for entry, model, dt in jobs}
    print("comm model:", json.dumps(comm_by_entry[jobs[0][0]]), flush=True)

    # ------------------------------------------------- measured compute leg
    out = {
        "config": {
            "graph": args.graph, "n": g["n"], "nnz": g["nnz"], "k": k,
            "fin": args.fin, "widths": widths, "chip": args.chip,
            "partitioner": "hp",
            "plan": {"b": plan.b, "s": plan.s, "r": plan.r, "e": plan.e},
        },
        "comm": comm_by_entry,
        "protocol": "per-chip shard program measured on the real v5e chip "
                    "(differential, median of 3); collectives modeled from "
                    "the plan's padded exchange bytes",
    }
    for entry, model, wire_dt in jobs:
        comm = comm_by_entry[entry]
        t0 = time.time()
        try:
            kw = ({"activation": "none"} if model == "gat" else
                  ({"halo_dtype": wire_dt}
                   if wire_dt != "float32" else {}))
            tr = FullBatchTrainer(proxy, fin=args.fin, widths=widths,
                                  seed=2, model=model, **kw)
        except MemoryError as e:
            out[entry] = {"error": f"capacity guard: {e}"}
            print(f"{entry}: {out[entry]}", flush=True)
            continue

        def make_run(nep):
            def run():
                losses = tr.run_epochs(data, nep, sync=False)
                return float(losses[-1])
            return run

        try:
            compute_s, n_clean = diff_time_q(make_run, 1,
                                             max(3, args.epochs))
        except RuntimeError as e:
            out[entry] = {"error": f"measurement failed: {e}"}
            print(f"{entry}: {out[entry]}", flush=True)
            continue
        comm_s = comm["comm_s_per_epoch"]
        out[entry] = {
            "per_chip_compute_s": compute_s,
            "clean_estimates": n_clean,
            "setup_plus_measure_s": round(time.time() - t0, 1),
            "epoch_s_8chip_model": compute_s + comm_s,
            "epoch_s_8chip_model_overlapped": max(compute_s, comm_s),
        }
        print(f"{entry}: {json.dumps(out[entry])}", flush=True)
        del tr

    dt = {"float32": "", "bfloat16": "_bf16wire",
          "ab": "_abwire"}[args.halo_dtype]
    path = os.path.join(ART, f"shard_epoch_model{suffix}{dt}.json")
    if os.path.exists(path):
        # merge: a partial re-run (e.g. after a lost worker killed one
        # model's measurement) must not discard the other model's entry —
        # but ONLY under the identical config; a changed config would
        # mislabel the kept measurement
        with open(path) as fh:
            prev = json.load(fh)
        if prev.get("config") == out["config"]:
            for key, val in out.items():
                # any measurement entry (gcn / gat / gcn_bf16wire / ...):
                # never overwrite a previous GOOD number with a new error
                if isinstance(val, dict) and "error" in val and \
                        isinstance(prev.get(key), dict) and \
                        "error" not in prev[key] and \
                        "per_chip_compute_s" in prev[key]:
                    continue
                prev[key] = val
            out = prev
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmp, path)
    print("wrote", path, flush=True)


if __name__ == "__main__":
    main()
