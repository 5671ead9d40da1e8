"""Share of its roofline the attention aggregation reaches: the least
seconds its passes could take (``costmodel_att``: two passes a layer, every
nonzero gathering K·C + K f32 lanes, over HBM bandwidth) over the device
seconds per epoch under ``sgcn.agg_slots`` + ``sgcn.agg_tail``.  Counts from
the dataset, per chip."""

import costmodel_att
import scopered


def read(run):
    model = run["config"].get("model")
    took = scopered.scope_seconds(run, "agg_slots", "agg_tail")
    if not took or not isinstance(model, dict):
        return None
    least = costmodel_att.agg_min_seconds(run["nnz"] / run["chips"], model,
                                          run["device_kind"])
    return 100.0 * least / took
