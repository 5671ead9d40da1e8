"""Median host seconds to enqueue one step, before the loss is asked for."""

import statistics


def read(run):
    spans = run["spans"].get("step.dispatch")
    return statistics.median(spans) if spans else None
