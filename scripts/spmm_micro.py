"""SpMM microbenchmarks on the real chip — the data behind the kernel design.

MEASUREMENT PROTOCOL: all timings here are **differential**: run the same
jitted fori_loop at two iteration counts and report
``(t(hi) - t(lo)) / (hi - lo)``, which cancels the fixed cost of a jitted
call (dispatch + readback; ~5 ms per trainer step() on a v5e, PERF.md
bring-up).  Blocking is via scalar readback (``float()``).

Times each candidate strategy for the hot op (Â·H row-gather + reduce,
Parallel-GCN/main.c:269-272 role).

Run: python scripts/spmm_micro.py [--n 169343] [--deg 14] [--f 128]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _run_loop(body, init, iters, reps=5):
    jfn = jax.jit(lambda c: jax.lax.fori_loop(0, iters, body, c),
                  static_argnums=())
    def run():
        out = jfn(init)
        leaf = jax.tree.leaves(out)[-1]
        return float(jnp.asarray(leaf).ravel()[0])   # scalar readback = sync
    run()                                            # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def timed(body, init, lo=4, hi=24):
    """Differential per-iteration seconds of `body` inside lax.fori_loop."""
    tlo = _run_loop(body, init, lo)
    thi = _run_loop(body, init, hi)
    return max((thi - tlo) / (hi - lo), 1e-9)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=169_343)
    p.add_argument("--deg", type=int, default=14)
    p.add_argument("--f", type=int, default=128)
    p.add_argument("--ellk", type=int, default=24)
    args = p.parse_args()
    n, f, ellk = args.n, args.f, args.ellk
    rng = np.random.default_rng(0)

    table = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    nrows = n * ellk
    # +8 slack so a loop-varying window offset defeats loop hoisting
    idx_full = jnp.asarray(rng.integers(0, n, size=nrows + 8), jnp.int32)
    w = jnp.asarray(rng.standard_normal((n, ellk)), jnp.float32)
    gb = nrows * f * 4 / 1e9

    # 0) streaming ceiling: elementwise over the gathered volume
    big = jnp.asarray(rng.standard_normal((nrows // 8 * 8, f)), jnp.float32)

    def ew(i, c):
        x, s = c
        y = x * 1.000001 + 0.5
        return y, s + y[0, 0]

    t = timed(ew, (big, jnp.float32(0)))
    print(f"stream r+w {2*big.size*4/1e9:.2f}GB    {t*1e3:8.2f} ms   "
          f"{2*big.size*4/t/1e9:7.1f} GB/s")

    # 1) full ELL spmm: take + weighted reduce (the shipped hot path)
    def ell_spmm(i, c):
        table, s = c
        idx = jax.lax.dynamic_slice(idx_full, (i % 8,), (nrows,))
        g = jnp.take(table, idx, axis=0).reshape(n, ellk, f)
        out = jnp.einsum("nkf,nk->nf", g, w)
        return table, s + out[0, 0]

    t = timed(ell_spmm, (table, jnp.float32(0)))
    print(f"ell_spmm take+reduce  {t*1e3:8.2f} ms   {gb/t:7.1f} GB/s gathered "
          f"({nrows/t/1e6:.0f} Mrows/s)")

    # 1b) sorted indices (locality probe)
    idx_sorted = jnp.sort(idx_full)

    def ell_spmm_sorted(i, c):
        table, s = c
        idx = jax.lax.dynamic_slice(idx_sorted, (i % 8,), (nrows,))
        g = jnp.take(table, idx, axis=0).reshape(n, ellk, f)
        out = jnp.einsum("nkf,nk->nf", g, w)
        return table, s + out[0, 0]

    t = timed(ell_spmm_sorted, (table, jnp.float32(0)))
    print(f"ell_spmm sorted idx   {t*1e3:8.2f} ms   {gb/t:7.1f} GB/s gathered")

    # 1c) gather only (sum consumes all rows, no einsum)
    def take_only(i, c):
        table, s = c
        idx = jax.lax.dynamic_slice(idx_full, (i % 8,), (nrows,))
        g = jnp.take(table, idx, axis=0)
        return table, s + g.sum()

    t = timed(take_only, (table, jnp.float32(0)))
    print(f"take+sum              {t*1e3:8.2f} ms   {gb/t:7.1f} GB/s gathered")

    # 1d) bf16 table gather
    tb16 = table.astype(jnp.bfloat16)

    def ell_bf16(i, c):
        tb, s = c
        idx = jax.lax.dynamic_slice(idx_full, (i % 8,), (nrows,))
        g = jnp.take(tb, idx, axis=0).reshape(n, ellk, f).astype(jnp.float32)
        out = jnp.einsum("nkf,nk->nf", g, w)
        return tb, s + out[0, 0]

    t = timed(ell_bf16, (tb16, jnp.float32(0)))
    print(f"ell_spmm bf16 table   {t*1e3:8.2f} ms   {gb/2/t:7.1f} GB/s gathered")

    # 2) dense matmul rooflines
    wdense = jnp.asarray(rng.standard_normal((f, f)), jnp.float32)

    def dense(i, c):
        x, s = c
        y = x @ wdense
        return x, s + y[0, 0]

    t = timed(dense, (table, jnp.float32(0)))
    print(f"dense (n,{f})@({f},{f})  {t*1e3:8.2f} ms   "
          f"{2*n*f*f/t/1e12:7.2f} TFLOP/s  ({(2*n*f*4)/t/1e9:.0f} GB/s)")

    m = 4096
    a4 = jnp.full((m, m), 0.001, jnp.bfloat16)

    def mm4k(i, c):
        a, s = c
        y = ((a @ a) * 1e-3).astype(jnp.bfloat16)
        return y, s + y[0, 0].astype(jnp.float32)

    t = timed(mm4k, (a4, jnp.float32(0)))
    print(f"matmul 4096^3 bf16    {t*1e3:8.2f} ms   {2*m**3/t/1e12:7.1f} TFLOP/s")

    # 3) dynamic_gather (take_along_axis) in-VMEM shuffle throughput
    from jax.experimental import pallas as pl

    S = 2048
    chunk = jnp.asarray(rng.standard_normal((S, f)), jnp.float32)
    gidx = jnp.asarray(rng.integers(0, S, size=(S, 1)), jnp.int32)

    def tga_kernel(idx_ref, x_ref, o_ref):
        ii = jnp.broadcast_to(idx_ref[:], (S, f))
        o_ref[:] = jnp.take_along_axis(x_ref[:], ii, axis=0)

    def vmem_gather(i, c):
        chunk, s = c
        y = pl.pallas_call(
            tga_kernel,
            out_shape=jax.ShapeDtypeStruct((S, f), jnp.float32),
        )((gidx + i) % S, chunk)
        return chunk, s + y[0, 0]

    try:
        t = timed(vmem_gather, (chunk, jnp.float32(0)))
        print(f"pallas take_along S={S} {t*1e3:8.3f} ms   "
              f"{S*f*4/t/1e9:7.1f} GB/s shuffled ({S/t/1e6:.1f} Mrows/s)")
    except Exception as e:
        print(f"pallas take_along_axis: FAILED {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
