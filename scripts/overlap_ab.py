"""Comm/compute-overlap evidence: wall-clock A/B of the split-edge-list form
(``pspmm_overlap`` — local SpMM has no data dependence on the halo
all_to_all, so the scheduler may run them concurrently) against the combined
form (``pspmm_exchange`` — every gather waits for the exchange).

This is the scheduler-level counterpart of the structural jaxpr test
(``tests/test_pspmm.py``: collective-independence of the local scatter-add)
and of the reference's Irecv/compute/Waitany loop
(``Parallel-GCN/main.c:238-299``).

Runs on whatever devices are visible; use the virtual 8-device CPU mesh via
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``
when only one real chip is reachable.  A RANDOM partition maximizes halo
traffic (every part's boundary ≈ its whole vertex set), making the exchange
as expensive as possible relative to local compute.

Prints one JSON line; optionally archives a profiler trace with --trace.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", type=int, default=40_000)
    ap.add_argument("--deg", type=int, default=14)
    ap.add_argument("-f", type=int, default=128)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--trace", default=None,
                    help="directory for a jax.profiler trace of the overlap form")
    args = ap.parse_args()

    import jax
    from jax.sharding import PartitionSpec as P

    from sgcn_tpu.io.datasets import er_graph
    from sgcn_tpu.ops import pspmm_exchange, pspmm_overlap
    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
    from sgcn_tpu.partition import balanced_random_partition
    from sgcn_tpu.prep import normalize_adjacency

    k = len(jax.devices())
    ahat = normalize_adjacency(er_graph(args.n, args.deg, seed=0))
    pv = balanced_random_partition(args.n, k, seed=0)   # comm-heavy on purpose
    plan = build_comm_plan(ahat, pv, k)
    mesh = make_mesh_1d(k)
    rng = np.random.default_rng(0)
    h = rng.standard_normal((args.n, args.f)).astype(np.float32)
    hb = shard_stacked(mesh, plan.scatter_rows(h))

    fields = ("send_idx", "halo_src", "edge_dst", "edge_src", "edge_w",
              "ledge_dst", "ledge_src", "ledge_w",
              "hedge_dst", "hedge_src", "hedge_w")
    pa = shard_stacked(mesh, {f: getattr(plan, f) for f in fields})

    def compiled(form, iters):
        def per_chip(pa, h):
            pa = jax.tree.map(lambda x: x[0], pa)

            def body(i, x):
                for _ in range(args.layers):
                    if form == "overlap":
                        x = pspmm_overlap(
                            x, pa["send_idx"], pa["halo_src"],
                            pa["ledge_dst"], pa["ledge_src"], pa["ledge_w"],
                            pa["hedge_dst"], pa["hedge_src"], pa["hedge_w"])
                    else:
                        x = pspmm_exchange(
                            x, pa["send_idx"], pa["halo_src"],
                            pa["edge_dst"], pa["edge_src"], pa["edge_w"])
                    x = x * 0.2     # keep values bounded across iterations
                return x

            return jax.lax.fori_loop(0, iters, body, h[0])[None]

        return jax.jit(jax.shard_map(per_chip, mesh=mesh,
                                     in_specs=(P("v"), P("v")),
                                     out_specs=P("v")))

    def measure(form, lo=2, hi=10, reps=5):
        def once(iters):
            fn = compiled(form, iters)
            float(np.asarray(fn(pa, hb)).ravel()[0])    # compile + warm
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                out = fn(pa, hb)
                float(np.asarray(out).ravel()[0])       # sync
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        return max((once(hi) - once(lo)) / (hi - lo), 1e-9)

    t_overlap = measure("overlap")
    t_exchange = measure("exchange")

    if args.trace:
        fn = compiled("overlap", 4)
        float(np.asarray(fn(pa, hb)).ravel()[0])
        with jax.profiler.trace(args.trace):
            float(np.asarray(fn(pa, hb)).ravel()[0])

    print(json.dumps({
        "metric": "pspmm_overlap_ab",
        "devices": k,
        "n": args.n,
        "layers": args.layers,
        "comm_volume_rows": int(plan.predicted_send_volume.sum()),
        "t_overlap_s": round(t_overlap, 6),
        "t_exchange_s": round(t_exchange, 6),
        "overlap_speedup": round(t_exchange / t_overlap, 4),
    }))


if __name__ == "__main__":
    main()
