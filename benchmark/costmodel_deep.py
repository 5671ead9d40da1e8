"""What an epoch of the deep residual stack must move, counted from Â's
nonzeros and the configuration's model block — never from the program's
layout (``costmodel.py``'s rule).

Per GENConv layer the softmax aggregation is two passes over the nonzeros:
the forward gathers, per nonzero (i, j) and channel, source j's weighted
message and its weight (numerator and denominator of the per-destination
softmax: 2 · hidden lanes); the backward, the weights being detached,
gathers destination i's gradient over its denominator (hidden lanes).  The
stabilising max needs no pass (a message depends on its source only, so one
per-channel constant serves every destination), and a pass the checkpoints
make the backward RE-RUN is work the program chose, not work the model
needs: it is in the seconds and not in this LEAST count.
"""

from __future__ import annotations

import costmodel


def lanes_per_layer(model: dict) -> int:
    """f32 lanes one nonzero makes a layer's two passes gather at least."""
    return 2 * int(model["hidden"]) + int(model["hidden"])


def agg_bytes_per_epoch(nnz: float, model: dict, itemsize: int = 4) -> float:
    return int(model["layers"]) * nnz * itemsize * lanes_per_layer(model)


def agg_min_seconds(nnz: float, model: dict, device_kind: str) -> float:
    """Least seconds of one epoch's aggregation passes at the chip's HBM
    bandwidth (``peaks.json``)."""
    return agg_bytes_per_epoch(nnz, model) / (
        costmodel.peaks_for(device_kind)["hbm_gbs"] * 1e9)
