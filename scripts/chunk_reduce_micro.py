"""Microbenchmark: scan-over-slots vs scan-over-row-chunks for the bucketed
slot reduce (`sgcn_tpu.ops.pspmm.bucketed_slot_reduce` scan branch).

Hypothesis tested (round-3 continuation): the scan-over-slots form carries
the full (nb, f) accumulator through every scan step — at ogbn-products
scale ~1.2 GB of carry READ + WRITE per slot on top of the gather — so
scanning over ROW CHUNKS instead (slots fully unrolled inside the body,
per-chunk output emitted through scan `ys`, no carry) should recover the
unrolled path's rate.

MEASURED RESULT (v5e, nb=2.4M, wb=16, f=128): the hypothesis is WRONG.
  scan-over-slots  (unroll=2):      0.219 s   176 Mrows/s
  scan-over-chunks (nc=196608, 12): 0.403 s    95 Mrows/s   (0.54x)
Chunking LOSES: ~196k-row gathers inside a scan run at roughly half the
per-gather rate of 2.4M-row gathers — per-gather overhead dominates before
any carry-traffic saving shows up.  Note the big-table rate itself (176
Mrows/s on a 1.2 GB table) sits well below the 350–460 Mrows/s measured on
a 169k-row table (`spmm_micro.py`), i.e. the gather rate degrades with
table size; that part is a hardware/XLA property no re-blocking of the
reduction fixed.  The shipped `bucketed_slot_reduce` therefore keeps the
scan-over-slots form.

Run on the real chip:  python scripts/chunk_reduce_micro.py
Differential protocol: per-iteration time from two on-device fori_loop
iteration counts, cancelling the per-call dispatch constant.
CAVEAT: the timing sink reads one output element; XLA's DCE can narrow a
concatenated-output variant (negative/zero differential reveals it — see
the variant-c result printed last; treat it as a lower bound only if its
differential is sane).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from sgcn_tpu.ops.pspmm import (_CONCURRENT_TEMP_LIMIT as _LIMIT,
                                  _SCHED_OVERLAP_SLOTS as _OVERLAP,
                                  _SCAN_LIVE_LIMIT)


def reduce_scan_slots(flat_idx, flat_w, nb, wb, h, unroll):
    seg_i = flat_idx.reshape(wb, nb)
    seg_w = flat_w.reshape(wb, nb)

    def body(carry, iw):
        i_t, w_t = iw
        return carry + jnp.take(h, i_t, axis=0) * w_t[:, None], None

    acc0 = jnp.zeros((nb, h.shape[1]), h.dtype)
    acc, _ = lax.scan(body, acc0, (seg_i, seg_w), unroll=unroll)
    return acc


def reduce_scan_chunks(flat_idx, flat_w, nb, wb, h, nc):
    f = h.shape[1]
    nchunks = nb // nc
    main = nchunks * nc

    def body(carry, c):
        acc = None
        for t in range(wb):
            idx = lax.dynamic_slice(flat_idx, (t * nb + c * nc,), (nc,))
            w = lax.dynamic_slice(flat_w, (t * nb + c * nc,), (nc,))
            contrib = jnp.take(h, idx, axis=0) * w[:, None]
            acc = contrib if acc is None else acc + contrib
        return carry, acc

    _, ys = lax.scan(body, jnp.int32(0), jnp.arange(nchunks))
    out_main = ys.reshape(main, f)
    if main == nb:
        return out_main
    rem = nb - main
    acc = None
    for t in range(wb):
        idx = lax.dynamic_slice(flat_idx, (t * nb + main,), (rem,))
        w = lax.dynamic_slice(flat_w, (t * nb + main,), (rem,))
        contrib = jnp.take(h, idx, axis=0) * w[:, None]
        acc = contrib if acc is None else acc + contrib
    return jnp.concatenate([out_main, acc], axis=0)


def reduce_chunks_unrolled(flat_idx, flat_w, nb, wb, h, nc):
    """Variant c: Python-unrolled chunk loop, no scan at all."""
    f = h.shape[1]
    outs = []
    off = 0
    while off < nb:
        c = min(nc, nb - off)
        acc = None
        for t in range(wb):
            idx = flat_idx[t * nb + off: t * nb + off + c]
            w = flat_w[t * nb + off: t * nb + off + c]
            contrib = jnp.take(h, idx, axis=0) * w[:, None]
            acc = contrib if acc is None else acc + contrib
        outs.append(acc)
        off += c
    return jnp.concatenate(outs, axis=0)


def diff_time(fn, args, lo=2, hi=6, reps=3):
    """Differential fori_loop timing with the spmm_micro safeguards
    (ADVICE r3): the gather TABLE (last arg) is extended by 8 slack rows
    and dynamic-sliced at ``i % 8`` inside the loop, so every iteration's
    gathers are loop-VARYING and while-loop invariant code motion cannot
    hoist the body; the slice feeds only the gather source, NOT the scan
    xs (a varying-offset slice reshaped into scan xs is the known
    pathological-compile shape on this stack — see the measurement-protocol
    notes).  The sink sums the WHOLE output so DCE cannot narrow the
    gathers to the first chunk; that sum adds an identical ~2 ms to every
    strategy's iteration, well under the ~200 ms bodies being compared."""
    *rest, h = args
    h_ext = jnp.concatenate([h, h[:8]], axis=0)

    def prog(nit):
        @jax.jit
        def run(h_ext, *a):
            def body(i, acc):
                h_i = lax.dynamic_slice(h_ext, (i % 8, 0), h.shape)
                return acc + fn(*a, h_i).sum()
            return lax.fori_loop(0, nit, body, jnp.float32(0))
        return run

    def once(nit):
        run = prog(nit)
        float(run(h_ext, *rest))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(run(h_ext, *rest))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t_lo, t_hi = once(lo), once(hi)
    return (t_hi - t_lo) / (hi - lo)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--nb", type=int, default=2_400_000)
    p.add_argument("--wb", type=int, default=16)
    p.add_argument("-f", type=int, default=128)
    p.add_argument("--dtype", default="float32")
    args = p.parse_args()

    nb, wb, f = args.nb, args.wb, args.f
    dt = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((nb, f)), dt)
    flat_idx = jnp.asarray(rng.integers(0, nb, size=nb * wb), jnp.int32)
    flat_w = jnp.asarray(rng.standard_normal(nb * wb), dt)

    slot_bytes = nb * f * dt.itemsize
    unroll = max(1, min(4, _SCAN_LIVE_LIMIT // max(slot_bytes, 1)))
    per_row = f * dt.itemsize
    nc = max(1, _LIMIT // (min(wb, _OVERLAP) * per_row))
    nc = min(nc, nb)
    rows = nb * wb

    t = diff_time(lambda i, w, hh: reduce_scan_slots(i, w, nb, wb, hh, unroll),
                  (flat_idx, flat_w, h))
    print(f"scan-over-slots  (unroll={unroll}): {t:.4f}s  "
          f"{rows / t / 1e6:.0f} Mrows/s")

    t2 = diff_time(lambda i, w, hh: reduce_scan_chunks(i, w, nb, wb, hh, nc),
                   (flat_idx, flat_w, h))
    nchunks = nb // nc
    print(f"scan-over-chunks (nc={nc}, {nchunks} chunks): {t2:.4f}s  "
          f"{rows / t2 / 1e6:.0f} Mrows/s")
    print(f"speedup: {t / t2:.2f}x")

    t3 = diff_time(lambda i, w, hh: reduce_chunks_unrolled(i, w, nb, wb, hh, nc),
                   (flat_idx, flat_w, h))
    print(f"unrolled-chunks  (nc={nc}): {t3:.4f}s  "
          f"{rows / t3 / 1e6:.0f} Mrows/s")


if __name__ == "__main__":
    main()
