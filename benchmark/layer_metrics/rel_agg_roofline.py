"""Share of its roofline the relational model's typed aggregation reaches:
the least seconds its passes could take (``costmodel_rel``: per layer the
edges of the relations on a path to a labelled row, ``min(d_in, d_out)``
lanes each, forward, and backward where the gathered table is trainable;
over HBM bandwidth) over the device seconds per epoch under
``sgcn.agg_slots`` + ``sgcn.agg_tail``.  Counts from the configuration."""

import costmodel_rel
import scopered


def read(run):
    cfg = run["config"]
    took = scopered.scope_seconds(run, "agg_slots", "agg_tail")
    if not took or "relations" not in cfg.get("model", {}):
        return None
    least = costmodel_rel.agg_min_seconds(cfg, run["device_kind"])
    return 100.0 * least / run["chips"] / took
