"""Microbenchmark behind ``parallel/plan.py::ROW_WINDOW`` (PERF.md §6, PR 36,
step 1): what ONE bucket of ``ops.pspmm.bucketed_slot_reduce`` costs a slot
as a function of its row count modulo 1,024.

The traced cells (PERF.md §5, PR 35) price a slot at ~5 ns in most buckets
and ~11 ns in those whose row count modulo 1,024 is ≥ 902; nothing was
measured between 889 and 901, below 133 or at an exact multiple.  This
script walks the residue: one bucket ``(rows, 32)`` with the GCN's
``contrib`` (gather · weight, accumulate: ``ops.pspmm._ell_slots``) over a
table of 1 M × 128 f32 (beyond VMEM), random sources and weights made on the
device, ``rows = m · 1,024 + r``, in the scanned form (unroll 4, what a
bucket of products scale and every fold class runs) and the unrolled one.
The exact symmetric step's backward is the same pass over the cotangent
table (``_pspmm_ell_sym_bwd``), so ``bwd`` times the SAME executable on a
second table: it shows the price is the shape's, not the operand's.  A few
plain 2-D-free row gathers at the exchange's row counts follow (no code
reads them; PERF.md §7).

Run on the chip:  python scripts/row_residue_micro.py
Writes ``chiprun_out/row_residue_micro.json`` (kept as
``bench_artifacts/row_residue_micro.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

HERE = os.path.join(os.path.dirname(__file__), "..")
LANES = 128
WIDTH = 32
TABLE_ROWS = 1 << 20
MULTIPLES = (100, 600)
RESIDUES = (0, 8, 24, 64, 128, 256, 512, 640, 704, 768, 832, 864, 880, 896,
            897, 912, 960, 1023)
# the exchange's gathers at gp4's shapes (rows mod 1,024: 973, 850) beside
# the same counts moved to residue 128
PLAIN_GATHERS = (609_229, 609_408, 1_817_426, 1_817_728)


def timed(fn, args, reps: int = 4, inner: int = 3) -> float:
    """Best seconds a call over ``reps`` runs of ``inner`` calls."""
    import jax

    jax.block_until_ready(fn(*args))            # compile + warm
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / inner)
    return best


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multiples", nargs="*", type=int, default=MULTIPLES)
    ap.add_argument("--residues", nargs="*", type=int, default=RESIDUES)
    ap.add_argument("--table-rows", type=int, default=TABLE_ROWS)
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "row_residue_micro.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from sgcn_tpu.ops.pspmm import bucket_forms, bucketed_slot_reduce

    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    results = {"device": [dev.platform, dev.device_kind], "lanes": LANES,
               "width": WIDTH, "table_rows": args.table_rows,
               "buckets": [], "plain_gathers": []}

    def save():
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=1)

    keys = jax.random.split(jax.random.key(0), 4)
    tables = {way: jax.random.normal(key, (args.table_rows, LANES),
                                     jnp.float32)
              for way, key in zip(("fwd", "bwd"), keys)}

    # the two forms, through the reduce's own decision (``bucket_forms``):
    # a bucket whose slot temporaries "weigh nothing" unrolls, a ``scanned``
    # one of real weight scans at the largest unroll that fits (4)
    forms = {"s": dict(slot_bytes=lambda nb: nb * LANES * 4, scanned=True),
             "u": dict(slot_bytes=lambda nb: 0)}

    for m in args.multiples:
        for r in args.residues:
            rows = m * 1024 + r
            idx = jax.random.randint(keys[2], (rows * WIDTH,), 0,
                                     args.table_rows, jnp.int32)
            w = jax.random.uniform(keys[3], (rows * WIDTH,), jnp.float32,
                                   0.01, 1.0)
            for form, policy in forms.items():
                unroll = bucket_forms(((rows, WIDTH),), **policy)[0]

                def one(table, i, wt, policy=policy, rows=rows):
                    return bucketed_slot_reduce(
                        i, wt, ((rows, WIDTH),),
                        contrib=lambda ix, wx: (jnp.take(table, ix, axis=0)
                                                * wx[:, None]),
                        init=lambda nb: jnp.zeros((nb, LANES), jnp.float32),
                        **policy)[0]

                fn = jax.jit(one)
                row = {"rows": rows, "multiple": m, "residue": r,
                       "form": form + ("" if unroll is None else str(unroll))}
                try:
                    for way, table in tables.items():
                        secs = timed(fn, (table, idx, w))
                        row[f"{way}_seconds"] = secs
                        row[f"{way}_ns_per_slot"] = (
                            1e9 * secs / (rows * WIDTH))
                except Exception as e:          # an unrolled form too large
                    row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
                results["buckets"].append(row)
                print(json.dumps(row), flush=True)
                save()
            del idx, w

    for rows in PLAIN_GATHERS:
        idx = jax.random.randint(keys[2], (rows,), 0, args.table_rows,
                                 jnp.int32)
        secs = timed(jax.jit(lambda t, i: jnp.take(t, i, axis=0)),
                     (tables["fwd"], idx), inner=10)
        row = {"rows": rows, "residue": rows % 1024, "seconds": secs,
               "ns_per_row": 1e9 * secs / rows}
        results["plain_gathers"].append(row)
        print(json.dumps(row), flush=True)
        save()


if __name__ == "__main__":
    main()
