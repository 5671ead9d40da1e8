"""What the runners share: the state a run accumulates, the timed loop, and
the part vector of a cell with more than one chip."""

from __future__ import annotations

import math
import os
import subprocess
import time
from dataclasses import dataclass, field

import numpy as np

import inputs
import manifest


@dataclass
class State:
    """One run's trainer-side objects and what the windows recorded."""

    trainer: object = None
    data: object = None
    params0: list = None            # seeded initial weights, on the host
    halo_counts: list = field(default_factory=list)   # plan: rows per chip
    losses: list = field(default_factory=list)    # every loss, in step order
    samples: dict = field(default_factory=dict)   # series -> [s per epoch]
    attempted: int = 0              # measured epochs
    failed: int = 0                 # of those: raised, or loss not finite
    extra: dict = field(default_factory=dict)     # runner's own


def record(state: State, loss) -> None:
    """Book one finished epoch and its loss."""
    loss = float(loss)
    state.losses.append(loss)
    state.attempted += 1
    state.failed += not math.isfinite(loss)


def timed_window(state: State, series: str, seconds: float, once) -> None:
    """Call ``once()`` (one epoch; returns its loss) until ``seconds`` have
    passed, at least once; record wall seconds per epoch."""
    out = state.samples.setdefault(series, [])
    end = time.perf_counter() + seconds
    while True:
        t = time.perf_counter()
        loss = once()
        out.append(time.perf_counter() - t)
        record(state, loss)
        if time.perf_counter() >= end:
            return


def partition(ctx, k: int, spec: dict) -> np.ndarray:
    """Part vector for ``k`` chips.  ``k == 1`` needs none.  Else
    ``spec["function"]`` names the partitioner of ``sgcn_tpu.partition`` to
    call (the native library is rebuilt from source first, so a stale one is
    never what runs); the vector is kept with its quality and its seconds
    under ``.cache``, and a hit re-reports both."""
    n = ctx.ahat.shape[0]
    if k == 1:
        return np.zeros(n, np.int64)

    def make():
        import sgcn_tpu.partition as program

        subprocess.run(["make", "-B", "-C",
                        os.path.join(manifest.ROOT, "native"),
                        "libsgcnpart.so"],
                       check=True, capture_output=True, text=True)
        t = time.perf_counter()
        pv, quality = getattr(program, spec["function"])(
            ctx.ahat, k, seed=spec["seed"])
        return pv, np.int64(quality), np.float64(time.perf_counter() - t)

    (pv, quality, secs), hit = inputs.cached_arrays(
        "part", {"n": n, "graph": ctx.cell.config["graph"], "k": k,
                 "partition": spec},
        make, ("partvec", "quality", "seconds"))
    ctx.notes["partition"] = {
        **spec, "cache_hit": hit, "seconds": float(secs),
        "quality": int(quality), "sizes": np.bincount(pv, minlength=k).tolist()}
    return np.asarray(pv, np.int64)
