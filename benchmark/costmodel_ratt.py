"""What an epoch of the typed attention model must move, counted from the
configuration — its model block and the published number of edges per
relation — never from the program's layout (``costmodel.py``'s rule).

Per layer, every relation into the types the layer computes (the labelled
type at the last layer; a layer before it adds the sources of the relations
into the next: ``costmodel_rel.needed_types``) is two passes over its edges,
with K heads of C channels: the forward gathers, per edge, the source's
projected row and its score term (K·C + K lanes); the backward gathers the
destination's gradient row and at least one scalar per head (K·C + K lanes;
the program ships four).  The max pass needs no row and is left out of a
LEAST count, as ``costmodel_att`` leaves it out.  Every layer's backward is
charged: attention projects before it aggregates, so even the first layer's
weights need the gathered gradient.
"""

from __future__ import annotations

import costmodel
import costmodel_rel

PASSES = 2          # forward and backward aggregation of every live relation


def agg_passes(config: dict) -> list:
    """``[{layer, relations, edges, lanes}]``: the live relations of each
    layer, their edges and the lanes a pass gathers per edge."""
    model = config["model"]
    edges = costmodel_rel.relation_edges(config)
    lanes = int(model["hidden"]) + int(model["heads"])
    out = []
    for layer, need in enumerate(costmodel_rel.needed_types(model)):
        names = [n for _, n, d in model["relations"]
                 if d in need and edges[n] > 0]
        out.append({"layer": layer, "relations": names,
                    "edges": sum(edges[n] for n in names), "lanes": lanes})
    return out


def agg_bytes_per_epoch(config: dict, itemsize: int = 4) -> float:
    return float(sum(PASSES * p["edges"] * p["lanes"] * itemsize
                     for p in agg_passes(config)))


def agg_min_seconds(config: dict, device_kind: str) -> float:
    """Least seconds of one epoch's typed attention aggregation at the
    chip's HBM bandwidth (``peaks.json``)."""
    return agg_bytes_per_epoch(config) / (
        costmodel.peaks_for(device_kind)["hbm_gbs"] * 1e9)
