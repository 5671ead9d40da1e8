"""Share of its roofline the graph transformer's aggregation reaches: the
least seconds its passes could take (``costmodel_xatt``: Â's nonzeros less
its diagonal, each gathering 6·K·C + 3K f32 lanes a layer over the forward
and the two backward passes, over HBM bandwidth) over the device seconds per
epoch under ``sgcn.agg_slots`` + ``sgcn.agg_tail``.  Counts from the
dataset and the configuration, per chip."""

import costmodel_xatt
import scopered


def read(run):
    cfg = run["config"]
    model = cfg.get("model", {})
    took = scopered.scope_seconds(run, "agg_slots", "agg_tail")
    if not took or model.get("name") != "unimp":
        return None
    least = costmodel_xatt.agg_min_seconds(run["nnz"], cfg,
                                           run["device_kind"])
    return 100.0 * least / run["chips"] / took
