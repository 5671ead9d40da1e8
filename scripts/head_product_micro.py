"""Microbenchmark behind the forms of ``models/mhgat.py``'s products by the
0/1 head matrix (``head_products``; PERF.md §6, PR 32, step 1): the forward
and the backward slot body of the multi-head attention layer with those
products in several forms, each timed per executed slot over the attention
cell's own shapes and compared with the parent's.

The shapes are ``products8-gat.fullbatch``'s: 306,129 rows, the plan's six
ELL buckets and the hub tail's one class of virtual rows (counted in the
sandbox from ``build_comm_plan`` on the cell's graph, seed 0), K = 4 heads of
C = 128 (layers 0 and 1) and C = 47 (layer 2), f32.  Sources are random rows
of the table and one slot in twenty is masked, both drawn ON THE DEVICE (the
v5e's gather rate does not depend on the pattern: PERF.md §6, PR 28; numpy
on the chip's host cost PR 30 forty chip-minutes).  Every store runs through
the program's own ``_store_reduce``, so a bucket scans at the unroll the
cell takes.

Forms (``a`` is the parent's, the program runs ``s`` forward and ``a``
backward; every other is exact too; ``floor`` is not a form
but the slot with its products taken out, which costs MORE than some forms:
a lane broadcast without the MXU is dear):

  forward  ``a``  two ``Precision.HIGHEST`` spreads (p, q)
           ``b``  each spread as three default-precision passes of ``split3``
           ``c``  each spread as ONE pass of the stacked pieces
           ``d``  p as ``c``; q's rows by ``where`` on a spread 0/1 indicator
           ``e``  p and q in one stacked product, 2·K·C lanes wide
           ``s``  ONE stacked spread of the signed coefficient ±p (the sign
                  is [x > 0]): p's rows by its magnitude, q's by its
                  positive part
           ``sh`` as ``s``, the one spread at ``HIGHEST``
  backward ``a``  ``HIGHEST`` sum over lanes + ``HIGHEST`` spread
           ``b``  three-pass sum + three-pass spread
           ``c``  three-pass sum + one-pass stacked spread
           ``c2`` sum as one pass over a 3·K·C contraction + stacked spread
           ``m1`` ``HIGHEST`` sum + stacked spread
           ``m2`` three-pass sum + ``HIGHEST`` spread
           ``r``  NOT the program's backward: no sum in the slot at all —
                  ``Σ_i φ'α (g_i·z_j)`` taken as ``z_j·(Σ_i φ'α g_i)``, a
                  second wide accumulator by a signed spread as ``s``, and
                  one sum a row after the pass (what ROADMAP A13 leaves)

Run on the chip:  python scripts/head_product_micro.py
Writes ``chiprun_out/head_product_micro.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

HERE = os.path.join(os.path.dirname(__file__), "..")
ROWS = 306129
ELL_BUCKETS = ((75614, 64), (32984, 54), (44638, 47), (51110, 41),
               (52670, 36), (49113, 31))            # 14,232,551 slots
TAIL_CLASS = (92344, 32)                            # 2,955,008 slots
HEADS, SLOPE = 4, 0.2
FWD_FORMS = ("a", "b", "c", "d", "e", "s", "sh", "floor")
BWD_FORMS = ("a", "b", "c", "c2", "m1", "m2", "r", "floor")


def timed(fn, args, reps: int = 3, inner: int = 2):
    """Best seconds a call over ``reps`` runs of ``inner`` calls, and the
    last output."""
    import jax

    out = jax.block_until_ready(fn(*args))          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / inner)
    return best, out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--channels", nargs="*", type=int, default=[128, 47])
    ap.add_argument("--stores", nargs="*", default=["ell", "tail"])
    ap.add_argument("--fwd", nargs="*", default=list(FWD_FORMS))
    ap.add_argument("--bwd", nargs="*", default=list(BWD_FORMS))
    ap.add_argument("--tiny", action="store_true",
                    help="sandbox rehearsal: the shapes cut to a few rows")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "head_product_micro.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from sgcn_tpu.models import mhgat
    from sgcn_tpu.models.mhgat import (_head_lanes, _leaky, _spread_heads,
                                       _store_reduce, split3)

    HIGHEST = jax.lax.Precision.HIGHEST
    k = HEADS
    b, buckets, tail = ROWS, ELL_BUCKETS, TAIL_CLASS
    if args.tiny:
        b = 1024
        buckets, tail = ((512, 5), (512, 3)), (64, 4)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    results = {"device": [dev.platform, dev.device_kind], "rows": b,
               "ell_buckets": buckets, "tail_class": tail, "heads": k,
               "helpers": {}, "passes": []}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def save():
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=1)

    # ----------------------------------------------------- the forms' pieces
    def bf(x):
        return x.astype(jnp.bfloat16)

    def dot32(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    def spread_highest(p, f):
        return jnp.dot(p, _head_lanes(k, f), precision=HIGHEST)

    def spread_three(p, f):
        lanes = bf(_head_lanes(k, f))
        hi, mid, lo = (dot32(x, lanes) for x in split3(p))
        return hi + (mid + lo)

    def spread_stacked(p, f):
        return _spread_heads(p, f)

    def sum_highest(x):
        return jnp.dot(x, _head_lanes(k, x.shape[1]).T, precision=HIGHEST)

    def sum_three(x):
        lanes = bf(_head_lanes(k, x.shape[1])).T
        hi, mid, lo = (dot32(piece, lanes) for piece in split3(x))
        return hi + (mid + lo)

    def sum_stacked(x):
        lanes = bf(_head_lanes(k, x.shape[1])).T
        return dot32(jnp.concatenate(split3(x), axis=1),
                     jnp.concatenate([lanes] * 3, axis=0))

    spreads = {"a": spread_highest, "b": spread_three, "c": spread_stacked,
               "c2": spread_stacked, "s": spread_stacked,
               "sh": spread_highest, "m1": spread_stacked,
               "m2": spread_highest}
    sums = {"a": sum_highest, "b": sum_three, "c": sum_three,
            "c2": sum_stacked, "m1": sum_highest, "m2": sum_three}

    # ------------------------------------------------ the helpers on a block
    def check_helpers(c):
        f, n = k * c, 1024 if args.tiny else 65536
        ks = jax.random.split(jax.random.PRNGKey(c), 4)
        # coefficients 1 … 2^-100, exact zeros (masked slots)
        p = (jax.random.uniform(ks[0], (n, k), minval=0.5, maxval=1.0)
             * jnp.exp2(-jax.random.randint(ks[1], (n, k), 0, 101)
                        .astype(jnp.float32)))
        p = jnp.where(jax.random.uniform(ks[2], (n, k)) < 0.1, 0.0, p)
        rows = jax.random.normal(ks[3], (n, f), jnp.float32)
        want = jax.jit(lambda p, r: r * jnp.repeat(p, c, axis=1))(p, rows)
        row = {"channels": c, "rows": n}
        for name, fn in (("highest", spread_highest), ("three", spread_three),
                         ("stacked", spread_stacked)):
            got = jax.jit(lambda p, r, fn=fn: r * fn(p, f))(p, rows)
            row[f"spread_{name}_bits_differ"] = int(
                jnp.sum(got.view(jnp.int32) != want.view(jnp.int32)))
        # positive terms, so an ulp of the sum is an ulp of its terms' size
        x = jnp.abs(rows * jax.random.normal(ks[1], (n, f), jnp.float32))
        ref = np.asarray(x, np.float64).reshape(n, k, c).sum(-1)
        ulp = np.spacing(ref.astype(np.float32)).astype(np.float64)
        for name, fn in (("highest", sum_highest), ("three", sum_three),
                         ("stacked", sum_stacked),
                         ("default", lambda x: jnp.dot(
                             x, _head_lanes(k, x.shape[1]).T))):
            got = np.asarray(jax.jit(fn)(x), np.float64)
            row[f"sum_{name}_max_ulp"] = float(np.max(np.abs(got - ref) / ulp))
            row[f"sum_{name}_max_rel"] = float(
                np.max(np.abs(got - ref) / ref))
        results["helpers"][str(c)] = row
        print("helpers", json.dumps(row), flush=True)
        save()

    # ------------------------------------------------------- the slot bodies
    def fwd_edge(form, tabs, src, mask, dst_side):
        """``_aggregate_fwd``'s ``edge``: (num, den, pnum, pden) of a slot."""
        (tab_z, tab_t), (s_i, m_i) = tabs, dst_side
        x = s_i + jnp.take(tab_t, src, axis=0)
        p = jnp.where((mask != 0)[:, None],
                      jnp.exp(_leaky(x, SLOPE) - m_i), 0.0)
        q = jnp.where(x > 0, p, 0.0)
        rows = jnp.take(tab_z, src, axis=0)
        f = rows.shape[1]
        if form == "floor":         # no spread at all: what a slot costs
            return rows * p[:, :1], p, rows * q[:, :1], q       # without it
        if form in ("s", "sh"):
            signed = spreads[form](jnp.where(x > 0, p, -p), f)
            return (rows * jnp.abs(signed), p,
                    rows * jnp.maximum(signed, 0.0), q)
        if form == "d":
            num = rows * spread_stacked(p, f)
            pos = dot32(bf(x > 0), bf(_head_lanes(k, f)))
            return num, p, jnp.where(pos > 0, num, 0.0), q
        if form == "e":
            lanes = bf(_head_lanes(k, f))
            zero = jnp.zeros_like(lanes)
            rhs = jnp.concatenate(
                [jnp.concatenate([lanes, zero], axis=1)] * 3
                + [jnp.concatenate([zero, lanes], axis=1)] * 3, axis=0)
            both = dot32(jnp.concatenate(split3(p) + split3(q), axis=1), rhs)
            return rows * both[:, :f], p, rows * both[:, f:], q
        sp = spreads[form]
        return rows * sp(p, f), p, rows * sp(q, f), q

    def bwd_edge(form, tabs, src, mask, dst_side):
        """``_aggregate_bwd``'s ``edge``: (dz, dt) of a slot."""
        (tab_g, tab_scal), (t_j, z_j) = tabs, dst_side
        si = jnp.take(tab_scal, src, axis=0)
        x = si[:, :k] + t_j
        alpha = jnp.where(
            (mask != 0)[:, None],
            jnp.exp(_leaky(x, SLOPE) - si[:, k:2 * k]) * si[:, 2 * k:3 * k],
            0.0)
        gi = jnp.take(tab_g, src, axis=0)
        if form == "floor":
            de = alpha * ((gi * z_j)[:, :k] - si[:, 3 * k:])
            return gi * alpha[:, :1], jnp.where(x > 0, de, SLOPE * de)
        if form == "r":
            signed = spread_stacked(jnp.where(x > 0, alpha, -alpha),
                                    gi.shape[1])
            ac = alpha * si[:, 3 * k:]
            return (gi * jnp.abs(signed), gi * jnp.maximum(signed, 0.0),
                    ac, jnp.where(x > 0, ac, 0.0))
        de = alpha * (sums[form](gi * z_j) - si[:, 3 * k:])
        return (gi * spreads[form](alpha, gi.shape[1]),
                jnp.where(x > 0, de, SLOPE * de))

    def one_pass(direction, form, store, idx, mask, vrow, tabs, dst_side):
        f = tabs[0].shape[1]
        edge = partial(fwd_edge if direction == "fwd" else bwd_edge, form)
        widths = ((f, k, f, k) if direction == "fwd"
                  else (f, f, k, k) if form == "r" else (f, k))
        acc = _store_reduce(
            idx, mask, buckets if store == "ell" else (tail,), vrow,
            dst_side, partial(edge, tabs),
            lambda nb: tuple(jnp.zeros((nb, w), jnp.float32)
                             for w in widths),
            lambda nb: 3 * nb * f * 4)
        if vrow is not None:
            # the tail's virtual rows add to their destinations, as
            # _all_stores
            acc = tuple(jnp.zeros((b, w), jnp.float32).at[vrow].add(
                v, indices_are_sorted=True) for w, v in zip(widths, acc))
        if form == "r":
            # the sums a row that the slots no longer make: ∂L/∂t
            dz, dzpos, ac, acpos = acc
            z_j = dst_side[1]
            return dz, (SLOPE * (sum_three(z_j * dz) - ac) + (1.0 - SLOPE)
                        * (sum_three(z_j * dzpos) - acpos))
        return acc

    # ------------------------------------------------------------- the runs
    for c in args.channels:
        check_helpers(c)
        f = k * c
        ks = jax.random.split(jax.random.PRNGKey(1000 + c), 12)
        z = jax.random.normal(ks[0], (b, f), jnp.float32)
        g = jax.random.normal(ks[1], (b, f), jnp.float32)
        t = jax.random.normal(ks[2], (b, k), jnp.float32)
        s = jax.random.normal(ks[3], (b, k), jnp.float32)
        m = _leaky(s + 3.0, SLOPE)          # near the max pass's result
        dinv = jax.random.uniform(ks[4], (b, k), minval=0.01, maxval=1.0)
        cc = jax.random.normal(ks[5], (b, k), jnp.float32)
        scal = jnp.concatenate([s, m, dinv, cc], axis=1)
        for store in args.stores:
            shapes = buckets if store == "ell" else (tail,)
            slots = sum(nb * wb for nb, wb in shapes)
            idx = jax.random.randint(ks[6], (slots,), 0, b, jnp.int32)
            mask = (jax.random.uniform(ks[7], (slots,)) > 0.05
                    ).astype(jnp.int8)
            vrow = (None if store == "ell" else jnp.sort(
                jax.random.randint(ks[8], (tail[0],), 0, b, jnp.int32)))
            for direction, forms, tabs, dst_side in (
                    ("fwd", args.fwd, (z, t), (s, m)),
                    ("bwd", args.bwd, (g, scal), (t, z))):
                base = None
                for form in forms:
                    fn = jax.jit(partial(one_pass, direction, form, store))
                    a = (idx, mask, vrow, tabs, dst_side)
                    t0 = time.perf_counter()
                    comp = fn.lower(*a).compile()
                    compile_s = time.perf_counter() - t0
                    secs, out = timed(fn, a)
                    row = {"channels": c, "store": store,
                           "direction": direction, "form": form,
                           "slots": slots, "seconds": secs,
                           "ns_per_slot": 1e9 * secs / slots,
                           "compile_s": compile_s,
                           "temp_bytes": int(comp.memory_analysis()
                                             .temp_size_in_bytes)}
                    if form == "a":
                        base = out
                    elif form != "floor" and base is not None:
                        # wide outputs to the bit; narrow ones by distance
                        for name, x, y in zip(
                                ("num", "den", "pnum", "pden")
                                if direction == "fwd" else ("dz", "dt"),
                                out, base):
                            if x.shape[1] == f:
                                row[f"{name}_bits_differ"] = int(jnp.sum(
                                    x.view(jnp.int32) != y.view(jnp.int32)))
                                # (a −0 against a +0 is a bit, not a value)
                                row[f"{name}_values_differ"] = int(
                                    jnp.sum(x != y))
                            else:
                                row[f"{name}_max_rel"] = float(
                                    jnp.max(jnp.abs(x - y))
                                    / jnp.max(jnp.abs(y)))
                    results["passes"].append(row)
                    print(json.dumps(row), flush=True)
                    save()
                    del out, fn, comp
                del base
            del idx, mask, vrow
    print("the program runs", json.dumps(mhgat.head_products()))


if __name__ == "__main__":
    main()
