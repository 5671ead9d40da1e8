"""Share of its roofline the typed attention aggregation reaches: the least
seconds its passes could take (``costmodel_ratt``: per layer the edges of
the relations into the types it computes, forward and backward, K·C + K f32
lanes each, over HBM bandwidth) over the device seconds per epoch under
``sgcn.agg_slots`` + ``sgcn.agg_tail``.  Counts from the configuration, per
chip."""

import costmodel_ratt
import scopered


def read(run):
    cfg = run["config"]
    model = cfg.get("model", {})
    took = scopered.scope_seconds(run, "agg_slots", "agg_tail")
    if not took or "relations" not in model or "heads" not in model:
        return None
    least = costmodel_ratt.agg_min_seconds(cfg, run["device_kind"])
    return 100.0 * least / run["chips"] / took
