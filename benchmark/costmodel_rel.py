"""What an epoch of the relational model must move, counted from the
configuration — its model block and the published number of edges per
relation — never from the program's layout (``costmodel.py``'s rule).

A layer's aggregation gathers, per edge of a relation and lane, one float of
the source's row.  A program is free to project before it aggregates or
after, so an edge is charged ``min(d_in, d_out)`` lanes.  Only relations ON A
PATH TO A LABELLED ROW are charged (the last layer computes the labelled
type; layer l - 1 the types layer l reads), and a backward pass only where
the table the forward gathered depends on a trainable array: every layer but
the first, and in the first the relations out of embedded types (a pass a
program could avoid — the gradient of a table of features — is not
charged).

The row-owned update reads and writes, per parameter, the parameter and the
optimiser's two moments: six 4-byte accesses.  The gradient is not charged,
since a fused producer need not store it.
"""

from __future__ import annotations

import costmodel

ROW_UPDATE_ACCESSES = 6         # parameter, two moments; read and written


def relation_edges(config: dict) -> dict:
    """Directed edges of each relation of the model block, by name: the
    published count of the pair of types it runs between (``graph``'s
    ``relations``: source type, destination type, distinct pairs), twice
    that where a type is related to itself (symmetrised)."""
    pairs = {}
    for s, d, m in config["graph"]["relations"]:
        pairs[s, d] = pairs[d, s] = int(m) * (2 if s == d else 1)
    return {name: pairs[s, d] for s, name, d in config["model"]["relations"]}


def needed_types(model: dict) -> list:
    """``[D_1, .., D_L]``: the node types each layer must compute."""
    rels = [(s, d) for s, _, d in model["relations"]]
    need = [{model["label_type"]}]
    for _ in range(int(model["layers"]) - 1):
        need.insert(0, need[0] | {s for s, d in rels if d in need[0]})
    return need


def agg_passes(config: dict) -> list:
    """``[{layer, direction, relations, edges, lanes}]`` of the least work."""
    model = config["model"]
    edges = relation_edges(config)
    embedded = {t["name"] for t in model["types"]
                if t["input"] == "embedding"}
    dims = list(zip([config["f_in"]] + config["widths"][:-1],
                    config["widths"]))
    out = []
    for layer, (need, (a, b)) in enumerate(zip(needed_types(model), dims)):
        into = [(s, name) for s, name, d in model["relations"] if d in need]
        fwd = [name for _, name in into]
        bwd = [name for s, name in into if layer > 0 or s in embedded]
        for way, names in (("forward", fwd), ("backward", bwd)):
            out.append({"layer": layer, "direction": way, "relations": names,
                        "edges": sum(edges[n] for n in names),
                        "lanes": min(a, b)})
    return out


def agg_bytes_per_epoch(config: dict, itemsize: int = 4) -> float:
    return float(sum(p["edges"] * p["lanes"] * itemsize
                     for p in agg_passes(config)))


def agg_min_seconds(config: dict, device_kind: str) -> float:
    """Least seconds of one epoch's typed aggregation at the chip's HBM
    bandwidth (``peaks.json``)."""
    return agg_bytes_per_epoch(config) / (
        costmodel.peaks_for(device_kind)["hbm_gbs"] * 1e9)


def row_owned_params(config: dict) -> int:
    """Parameters owned with the rows: the embedded types' tables."""
    return sum(int(t["count"]) for t in config["model"]["types"]
               if t["input"] == "embedding") * int(config["f_in"])


def row_update_min_seconds(config: dict, device_kind: str,
                           itemsize: int = 4) -> float:
    return (row_owned_params(config) * itemsize * ROW_UPDATE_ACCESSES
            / (costmodel.peaks_for(device_kind)["hbm_gbs"] * 1e9))
