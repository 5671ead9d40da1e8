"""What an epoch of the multi-head attention configuration must move, counted
from Â's nonzeros and the configuration's model block — never from the
program's layout (``costmodel.py``'s rule).

Per layer with K heads of C channels the aggregation is two passes over the
nonzeros: the forward gathers, per nonzero (i, j), source j's projected row
and its score term (K·C + K lanes); the backward gathers destination i's
gradient row and at least one scalar per head (K·C + K lanes; the program
ships four, s, m, 1/D and c).  The max pass needs no row (LeakyReLU is
monotone) and is left out of a LEAST count, as is a third pass: the sum
``∂L/∂s_i`` can be accumulated by the forward (``models/mhgat.py``).
"""

from __future__ import annotations

import costmodel

PASSES = 2          # forward and backward aggregation of every layer


def lanes_per_pass(model: dict) -> list:
    """Per layer, the f32 lanes one nonzero makes a pass gather at least."""
    return [k * c + k for k, c in zip(model["heads"], model["channels"])]


def agg_bytes_per_epoch(nnz: float, model: dict, itemsize: int = 4) -> float:
    return PASSES * nnz * itemsize * sum(lanes_per_pass(model))


def agg_min_seconds(nnz: float, model: dict, device_kind: str) -> float:
    """Least seconds of one epoch's aggregation passes at the chip's HBM
    bandwidth (``peaks.json``)."""
    return agg_bytes_per_epoch(nnz, model) / (
        costmodel.peaks_for(device_kind)["hbm_gbs"] * 1e9)
