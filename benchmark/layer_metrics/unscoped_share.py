"""Share of the device's leaf-op seconds in ops that carry no leaf scope of
the program's vocabulary: what the per-scope metrics cannot see."""

import scopered


def read(run):
    red = scopered.table(run)
    if red is None:
        return None
    rows = red["mean"]
    return 100.0 * scopered.seconds(
        rows, scopered.UNSCOPED, scopered.UNSCOPED + ":collective") \
        / sum(rows.values())
