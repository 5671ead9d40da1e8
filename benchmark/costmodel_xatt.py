"""What an epoch of the graph-transformer configuration must move, counted
from Â's nonzeros and the configuration — never from the program's layout
(``costmodel.py``'s rule).

The layer attends over the pattern of Â WITHOUT its diagonal: N = nnz − n
edges (Â holds every row's self loop).  Per layer with K heads of C channels
(C = width ÷ K on a hidden layer, whose heads are concatenated; the width
itself on the last, whose heads are averaged) the score is a product of a
destination row and a source row, so every pass gathers rows, per edge at
least:

* forward: the source's ``[k ‖ v]``, 2·K·C lanes;
* backward over the destination's slots (``∂q``): ``[k ‖ v]`` again, 2·K·C;
* backward over the source's slots (``∂k``, ``∂v``): the destination's
  ``[q ‖ g]`` and three scalars a head (m, 1/D, c), 2·K·C + 3K.

A max pass (K·C lanes more) is left out of the LEAST count: a running
rescale folds it into the forward.  For the products-eighth cell,
15,240,250 edges × (780 + 780 + 1,140) lanes × 4 B = 164.6 GB an epoch.
"""

from __future__ import annotations

import costmodel


def heads_and_channels(config: dict) -> list:
    """``(K, C)`` of each layer, from ``widths`` and the model's ``heads``."""
    widths, heads = config["widths"], int(config["model"]["heads"])
    return [(heads, w if i == len(widths) - 1 else w // heads)
            for i, w in enumerate(widths)]


def lanes_per_edge(config: dict) -> int:
    """Σ over layers of the least f32 lanes one edge makes an epoch gather."""
    return sum(6 * k * c + 3 * k for k, c in heads_and_channels(config))


def attention_edges(nnz: float, config: dict) -> float:
    """Edges the layer attends over: Â's nonzeros less its diagonal."""
    return nnz - config["n"]


def agg_bytes_per_epoch(nnz: float, config: dict, itemsize: int = 4) -> float:
    return attention_edges(nnz, config) * lanes_per_edge(config) * itemsize


def agg_min_seconds(nnz: float, config: dict, device_kind: str) -> float:
    """Least seconds of one epoch's aggregation passes at the chip's HBM
    bandwidth (``peaks.json``)."""
    return agg_bytes_per_epoch(nnz, config) / (
        costmodel.peaks_for(device_kind)["hbm_gbs"] * 1e9)
