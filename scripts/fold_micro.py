"""Microbenchmark behind ``parallel/plan.py::FOLD_ROW_COST`` (PERF.md §6,
PR 30, step 1): one COO edge store folded into ``(b, 128)`` f32 rows as the
parent did (sorted ``segment_sum`` per edge) and as slot passes over virtual
rows (``ops.pspmm.fold_slots``) at one width, at width classes and at several
scan-liveness limits, plus the sorted row scatter alone.

The stores are the benchmark cells' own: per-destination run lengths counted
in the sandbox from the cells' plans (``build/fold_step1/*.npy``, made by a
scratch script from ``build_comm_plan`` on the products stand-in at k = 1 and
at gp's k = 4, seed 0); sources are random rows of the table (the v5e's
gather rate does not depend on the pattern: PERF.md §6, PR 28), weights
random.  Layouts are built by the program's own ``_build_virtual_rows`` at
the shapes every chip executes (the maximum over chips).

Run on the chip:  python scripts/fold_micro.py [--store k4_halo ...]
Writes ``chiprun_out/fold_micro.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

HERE = os.path.join(os.path.dirname(__file__), "..")
HIST = os.path.join(HERE, "build", "fold_step1")
LANES = 128
CLASS_SETS = ((4, 8), (8, 16), (4, 8, 16), (8, 16, 32), (4, 8, 16, 32))
LIVE_LIMITS = (3 * 1024**3 // 4, 3 * 1024**3 // 2, 3 * 1024**3)


def load_store(name: str, rng):
    """``(dst, src, w, counts, b, height)`` stacked over chips, as a plan
    holds a COO store: dst-sorted, padded to the fullest chip by the plan's
    own rule (``padding_rows``, destination ``b − 1``, weight 0)."""
    from sgcn_tpu.parallel.plan import padding_rows

    tag, kind = name.split("_")
    meta = json.load(open(os.path.join(HIST, f"{tag}_meta.json")))
    b, k = meta["b"], meta["k"]
    height = b if kind == "tail" else meta["r"]
    degs = [np.load(os.path.join(HIST, f"{tag}_{kind}_deg_{p}.npy"))
            for p in range(k)]
    counts = np.array([int(d.sum()) for d in degs])
    e = int(counts.max())
    dst = np.full((k, e), b - 1, np.int32)
    src = np.empty((k, e), np.int32)
    w = np.zeros((k, e), np.float32)
    for p, dg in enumerate(degs):
        c = int(counts[p])
        dst[p, :c] = np.repeat(np.arange(b, dtype=np.int32), dg)
        src[p, :c] = rng.integers(0, height, c, dtype=np.int32)
        src[p, c:] = padding_rows(e - c, height)
        w[p, :c] = rng.uniform(0.01, 1.0, c).astype(np.float32)
    return dst, src, w, counts, b, height


def timed(fn, out, args, reps: int = 5, inner: int = 3):
    """Best seconds a call of ``reps`` runs of ``inner`` calls, the output
    fed back as the donated accumulator."""
    import jax

    out = fn(out, *args)            # compile + warm
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(inner):
            out = fn(out, *args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / inner)
    return best, out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--store", nargs="*",
                    default=["k4_tail", "k4_halo", "k1_tail"])
    ap.add_argument("--widths", nargs="*", default=None,
                    help="class sets to time, e.g. 16 4-8-16 (default: the "
                         "whole sweep, COO fold and row scatter included); "
                         "'rule' is the plan's own choice")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "fold_micro.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from sgcn_tpu.ops.pspmm import fold_slots
    from sgcn_tpu.parallel.plan import (FOLD_WIDTHS, _build_virtual_rows,
                                        choose_fold_widths, _run_lengths)

    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    rng = np.random.default_rng(0)
    results = {"device": [dev.platform, dev.device_kind], "stores": {}}

    def mem():
        s = dev.memory_stats() or {}
        return {k: s.get(k) for k in ("peak_bytes_in_use",
                                      "peak_bytes_reserved")}

    for name in args.store:
        dst, src, w, counts, b, height = load_store(name, rng)
        full = int(np.argmax(counts))           # the chip without padding
        rows = []
        results["stores"][name] = {
            "b": b, "table_rows": height, "coo_entries": int(dst.shape[1]),
            "true_edges": counts.tolist(), "variants": rows}
        table = jax.device_put(
            rng.standard_normal((height, LANES)).astype(np.float32))
        out = jnp.zeros((b, LANES), jnp.float32)

        def record(label, seconds, entries, extra=None, compiled=None):
            row = {"variant": label, "seconds": seconds, "entries": entries,
                   "ns_per_entry": 1e9 * seconds / max(entries, 1),
                   **(extra or {}), **mem()}
            if compiled is not None:
                row["temp_bytes"] = int(
                    compiled.memory_analysis().temp_size_in_bytes)
            rows.append(row)
            print(name, json.dumps(row), flush=True)
            with open(args.out, "w") as fh:
                json.dump(results, fh, indent=1)

        # ---- the parent's form: gather · w, sorted segment_sum, add
        def coo(acc, tab, d, s, wt):
            g = jnp.take(tab, s, axis=0) * wt[:, None]
            return acc + jax.ops.segment_sum(
                g, d, num_segments=acc.shape[0], indices_are_sorted=True)

        if args.widths is None:
            fn = jax.jit(coo, donate_argnums=0)
            a = tuple(jax.device_put(x[full]) for x in (dst, src, w))
            comp = fn.lower(out, table, *a).compile()
            secs, out = timed(fn, out, (table, *a))
            record("coo", secs, int(dst.shape[1]), compiled=comp)
            del a

        # ---- slot form: one width, width classes, liveness limits
        degs = _run_lengths(dst, w, counts, b)
        rule = choose_fold_widths(degs)
        sets = [(wd,) for wd in FOLD_WIDTHS] + [
            c for c in CLASS_SETS if c != rule] + [rule]
        if args.widths is not None:
            sets = [rule if x == "rule" else tuple(map(int, x.split("-")))
                    for x in args.widths]
        layouts = {}
        for widths in sets:
            lay = _build_virtual_rows(dst, src, w, counts, b, height,
                                      widths=widths)
            classes = lay["classes"]
            slots = sum(nv * wd for nv, wd in classes)
            nrows = sum(nv for nv, _ in classes)
            a = tuple(jax.device_put(lay[x][full])
                      for x in ("idx", "w", "row"))
            limits = (LIVE_LIMITS if widths == rule and args.widths is None
                      else LIVE_LIMITS[:1])     # the program's own limit
            for limit in limits:
                fn = jax.jit(
                    lambda acc, tab, i, wt, r, classes=classes, limit=limit:
                    fold_slots(acc, tab, i, wt, r, classes,
                               scan_live_limit=limit), donate_argnums=0)
                comp = fn.lower(out, table, *a).compile()
                secs, out = timed(fn, out, (table, *a))
                record("slots" + "".join(f"-{wd}" for wd in widths)
                       + ("*" if widths == rule else ""), secs, slots,
                       {"classes": [list(c) for c in classes],
                        "virtual_rows": nrows, "live_limit": limit,
                        "ns_per_coo_entry": 1e9 * secs / dst.shape[1]},
                       compiled=comp)
            if len(widths) == 1:
                layouts[widths[0]] = (classes[0][0], a[2])
            del a, lay

        # ---- the sorted row scatter alone
        for wd, (nv, row) in (layouts if args.widths is None else {}).items():
            part = jax.device_put(
                rng.standard_normal((nv, LANES)).astype(np.float32))
            fn = jax.jit(lambda acc, p, r: acc.at[r].add(
                p, indices_are_sorted=True), donate_argnums=0)
            secs, out = timed(fn, out, (part, row))
            record(f"row_scatter-{wd}", secs, nv)
            del part
        del table, out, layouts


if __name__ == "__main__":
    main()
