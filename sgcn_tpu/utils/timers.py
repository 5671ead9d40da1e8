"""Phase timers — the CAGNET baseline's phase-time breakdown, generalized.

The reference accumulates ``data_comm / local_spmm / all_reduce / local_update``
wall-clock per phase (``Cagnet/main.c:35-38,148-151,171-175,395-413``).  Under
jit whole steps fuse into one program, so phase timing is host-side around
block_until_ready boundaries; for intra-step attribution take a
``jax.profiler`` trace (``python -m sgcn_tpu.train --profile DIR``) and read it
by the scopes and spans of ``sgcn_tpu.obs.tracing``.

Nesting contract: phases may nest (the span API in ``obs/tracing.py`` wraps
this timer, and a step-level span runs inside ``fit()``'s epoch phase).
``totals`` holds SELF time — a child phase's time is attributed to the child
only, so Σ totals over all names equals elapsed wall and nothing is counted
twice.  ``inclusive`` holds wall time per name with a reentrancy guard (a
phase re-entered under itself adds nothing — the outermost frame already
covers it), which is what callers timing a whole region want
(``FullBatchTrainer.fit``).  The pre-nesting behavior — every frame adds its
full duration to ``totals`` — double-counted any nested or reentrant entry.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict

import jax


class PhaseTimer:
    def __init__(self):
        self.totals: dict[str, float] = defaultdict(float)   # SELF time
        self.counts: dict[str, int] = defaultdict(int)
        self.inclusive: dict[str, float] = defaultdict(float)  # wall time,
        #   reentrancy-guarded (outermost frame of a name counts once)
        self._stack: list[list] = []     # [name, accumulated child seconds]

    @contextlib.contextmanager
    def phase(self, name: str, sync=None):
        """Time a phase. ``sync`` is a zero-arg callable returning the arrays to
        block on (evaluated after the body, so it sees post-body values —
        passing a value directly would capture stale pre-body buffers)."""
        frame = [name, 0.0]
        self._stack.append(frame)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            # the pop/accounting must survive a raising sync (async dispatch
            # errors surface exactly at block_until_ready): a dead frame
            # left on the stack would poison every later phase's totals
            try:
                if sync is not None:
                    jax.block_until_ready(sync())
            finally:
                dt = time.perf_counter() - t0
                self._stack.pop()
                # self time: children already claimed frame[1] of this window
                self.totals[name] += dt - frame[1]
                self.counts[name] += 1
                if all(f[0] != name for f in self._stack):
                    self.inclusive[name] += dt
                if self._stack:
                    self._stack[-1][1] += dt

    def inclusive_total(self, name: str) -> float:
        """Wall time spent under ``name`` (reentrancy-guarded) — equals
        ``totals[name]`` when the phase never had children."""
        return self.inclusive[name]

    def report(self) -> dict:
        return {
            name: {"total_s": self.totals[name], "count": self.counts[name],
                   "avg_s": self.totals[name] / max(self.counts[name], 1),
                   "inclusive_s": self.inclusive[name]}
            for name in self.totals
        }
