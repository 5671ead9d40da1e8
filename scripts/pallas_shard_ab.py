"""Pallas VMEM-kernel A/B in its selection regime, on the real chip (the
kernel is wired + parity-tested and runs on the chip — chip_smoke.py — but
its speed against the ELL path has never been measured on the current
code; ROADMAP A7).

The kernel's window is per-chip tables small enough to pin in VMEM — what
k-way sharding produces as k grows (`ops/pallas_spmm.py::use_pallas_spmm`).
One physical chip can measure exactly that via the shard proxy: build a
k-way plan whose per-chip [local] and [halo] tables fit the budget, take
chip 0's shard, and run the SAME per-chip program with the Pallas
aggregator on and off (SGCN_PALLAS_SPMM=1/0), back-to-back in one session:
wall seconds per ``step()`` with the loss read back, after one warm-up step,
median of ``--epochs`` steps (as the benchmark's runner times an epoch).

Writes ``bench_artifacts/pallas_shard_ab.json``.

Run (TPU): PYTHONPATH=/root/repo python -u scripts/pallas_shard_ab.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
ART = os.path.join(REPO, "bench_artifacts")


def median_step_s(tr, data, steps: int) -> float:
    """Median wall seconds of one ``step()`` + loss readback, after a
    warm-up step that pays the compile."""
    float(tr.step(data))
    samples = []
    for _ in range(steps):
        t0 = time.perf_counter()
        float(tr.step(data))
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples))


def main() -> None:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("-n", type=int, default=40_000)
    p.add_argument("--avg-deg", type=int, default=14)
    p.add_argument("-k", type=int, default=32)
    p.add_argument("-f", type=int, default=64)
    p.add_argument("--epochs", type=int, default=8)
    args = p.parse_args()

    from sgcn_tpu.io.datasets import er_graph
    from sgcn_tpu.ops.pallas_spmm import use_pallas_spmm
    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.parallel.proxy import shard_proxy_data, shard_proxy_plan
    from sgcn_tpu.partition import partition_hypergraph_colnet
    from sgcn_tpu.prep import normalize_adjacency
    from sgcn_tpu.train import FullBatchTrainer

    widths = [args.f, 16]
    ahat = normalize_adjacency(er_graph(args.n, args.avg_deg, seed=0))
    pv, km1 = partition_hypergraph_colnet(ahat, args.k, seed=0)
    plan = build_comm_plan(ahat, np.asarray(pv, np.int64), args.k)
    proxy = shard_proxy_plan(plan, chip=0)
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((args.n, args.f)).astype(np.float32)
    labels = rng.integers(0, 16, args.n).astype(np.int32)
    data = shard_proxy_data(plan, 0, feats, labels)

    out = {
        "config": {"n": args.n, "avg_deg": args.avg_deg, "k": args.k,
                   "fin": args.f, "widths": widths, "km1": int(km1),
                   "plan": {"b": plan.b, "r": plan.r, "e": plan.e}},
        "protocol": "chip-0 shard program on the real chip, pallas vs ELL "
                    "aggregator, median wall s per step() after a warm-up "
                    "step, same session",
    }
    for name, env in (("pallas", "1"), ("ell", "0")):
        os.environ["SGCN_PALLAS_SPMM"] = env
        fired = use_pallas_spmm(proxy, args.f, widths)
        if name == "pallas" and not fired:
            out["error"] = (f"selector did not fire: b={plan.b} r={plan.r} "
                            f"fmax={max([args.f] + widths)}")
            print(out["error"], flush=True)
            break
        t0 = time.time()
        tr = FullBatchTrainer(proxy, fin=args.f, widths=widths, seed=2)
        assert (tr._fwd_static.get("pallas_tb") is not None) == \
            (name == "pallas")
        steps = max(3, args.epochs)
        out[name] = {"epoch_s": median_step_s(tr, data, steps),
                     "steps": steps,
                     "setup_plus_measure_s": round(time.time() - t0, 1)}
        print(name, json.dumps(out[name]), flush=True)
        del tr
    os.environ.pop("SGCN_PALLAS_SPMM", None)
    if "pallas" in out and "ell" in out:
        out["pallas_vs_ell"] = round(
            out["ell"]["epoch_s"] / out["pallas"]["epoch_s"], 3)
    path = os.path.join(ART, "pallas_shard_ab.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmp, path)
    print("wrote", path, flush=True)


if __name__ == "__main__":
    main()
