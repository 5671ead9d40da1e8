"""Median wall seconds per epoch on the path the train CLI takes: one
``step()`` per epoch, the loss read back to the host."""

import statistics


def read(run):
    steps = run["samples"].get("step")
    return statistics.median(steps) if steps else None
