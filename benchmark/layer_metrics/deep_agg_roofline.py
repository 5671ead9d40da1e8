"""Share of its roofline the deep stack's softmax aggregation reaches: the
least seconds its passes could take (``costmodel_deep``: per layer every
nonzero gathering 2 · hidden lanes forward and hidden backward, over HBM
bandwidth) over the device seconds per epoch under ``sgcn.agg_slots`` +
``sgcn.agg_tail`` — recomputed passes are in the seconds and not in the
least count.  Counts from the dataset, per chip."""

import costmodel_deep
import scopered


def read(run):
    model = run["config"].get("model")
    took = scopered.scope_seconds(run, "agg_slots", "agg_tail")
    if not took or not isinstance(model, dict) or "hidden" not in model:
        return None
    least = costmodel_deep.agg_min_seconds(run["nnz"] / run["chips"], model,
                                           run["device_kind"])
    return 100.0 * least / took
