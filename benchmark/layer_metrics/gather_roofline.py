"""Share of its roofline the epoch's compute reaches: the least seconds the
chip could take (the aggregations' bytes over HBM bandwidth, or step FLOPs
over MXU peak, whichever is larger; here HBM) over the seconds per epoch in
which a non-collective op ran.  Counts from the dataset, per chip."""

import costmodel


def read(run):
    red = run["trace"]
    if not red or not red["compute_s"]:
        return None
    cfg, k = run["config"], run["chips"]
    roof = costmodel.roofline(run["nnz"] / k, cfg["n"] / k, cfg["f_in"],
                              cfg["widths"], run["device_kind"])
    return 100.0 * roof["min_s"] / (red["compute_s"] / red["epochs"])
