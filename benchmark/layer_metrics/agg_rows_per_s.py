"""Rows the epoch's aggregations gather — one per nonzero of Â, forward and
backward, every layer: the dataset's count, not the plan's padded one — per
second in which a non-collective op ran on the chip; per chip."""

import costmodel


def read(run):
    red = run["trace"]
    if not red or not red["compute_s"]:
        return None
    rows = costmodel.agg_rows_per_epoch(run["nnz"] / run["chips"],
                                        len(run["config"]["widths"]))
    return rows / (red["compute_s"] / red["epochs"])
