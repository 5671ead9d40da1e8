"""What an epoch must do, counted from the dataset and the configuration, and
the chip's peaks.

Nothing here reads the program's layout: a plan's padded slots, send buffers
and halo tables are how the program chose to do the work, and a count taken
from them would rise with its padding.  The counts are per chip: the caller
divides the dataset's nonzeros and rows by the number of chips.

Peaks live in ``peaks.json`` beside this file, keyed by ``device_kind``; a
kind that is not in the table is an error, not a default.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has: {sorted(table)})")
    return table[device_kind]


def layer_dims(f_in: int, widths) -> list:
    """``(input width, output width)`` of each layer."""
    return list(zip([f_in] + list(widths)[:-1], widths))


def agg_rows_per_epoch(nnz: float, nlayers: int) -> float:
    """Rows the epoch's aggregations gather: one per nonzero of Â, in one
    forward and one (symmetric) backward pass for every layer."""
    return 2 * nlayers * nnz


def agg_bytes_per_epoch(nnz: float, f_in: int, widths,
                        itemsize: int = 4) -> float:
    """Least bytes those gathers move.  A layer is ``Â H W``: the aggregation
    may run before the projection or after it, so the narrower of the
    layer's two widths is what each gathered row must carry."""
    lanes = sum(min(fi, fo) for fi, fo in layer_dims(f_in, widths))
    return 2 * nnz * itemsize * lanes


def step_flops(nnz: float, rows: float, f_in: int, widths) -> float:
    """FLOPs of one training step: SpMM forward and backward (one
    multiply-add per nonzero and lane, at the narrower width) and three
    dense products per layer (forward, dX, dW)."""
    dims = layer_dims(f_in, widths)
    spmm = sum(2 * nnz * min(fi, fo) for fi, fo in dims)
    dense = sum(2 * rows * fi * fo for fi, fo in dims)
    return 2 * spmm + 3 * dense


def roofline(nnz: float, rows: float, f_in: int, widths,
             device_kind: str) -> dict:
    """Least seconds the chip could take for one epoch's aggregation and
    dense work, and which peak bounds it."""
    pk = peaks_for(device_kind)
    t_hbm = agg_bytes_per_epoch(nnz, f_in, widths) / (pk["hbm_gbs"] * 1e9)
    t_mxu = step_flops(nnz, rows, f_in, widths) / (pk["bf16_tflops"] * 1e12)
    return {"min_s": max(t_hbm, t_mxu),
            "bound": "hbm" if t_hbm >= t_mxu else "mxu",
            "hbm_s": t_hbm, "mxu_s": t_mxu}
