"""Median host seconds of the program's own ``step.dispatch`` span (around
the call of the jitted step inside ``FullBatchTrainer.step``).  The warm-up
step holds the compile, which a mean would carry.  Reported beside the device
metrics only: a CPU rehearsal has no trace."""

import statistics

import scopered


def read(run):
    spans = scopered.span_durations("step.dispatch")
    return statistics.median(spans) if spans and run.get("trace") else None
