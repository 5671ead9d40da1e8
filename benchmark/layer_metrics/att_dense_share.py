"""Share of the device's busy seconds under ``sgcn.dense`` (the projections
``H W``, the score projections, the skips, and their backward products)."""

import scopered


def read(run):
    dense = scopered.scope_seconds(run, "dense")
    red = run.get("trace")
    if not dense or not red or not red.get("busy_s"):
        return None
    return 100.0 * dense / (red["busy_s"] / red["epochs"])
