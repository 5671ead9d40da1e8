"""Share of its roofline the row-owned update reaches: the least seconds it
could take (``costmodel_rel``: the parameter and two moments of every
row-owned parameter read and written, over HBM bandwidth; per chip) over the
device seconds per epoch under ``sgcn.row_update``."""

import costmodel_rel
import scopered_rel


def read(run):
    cfg = run["config"]
    took = scopered_rel.seconds(run, "row_update")
    if not took or "types" not in cfg.get("model", {}):
        return None
    least = costmodel_rel.row_update_min_seconds(cfg, run["device_kind"])
    return 100.0 * least / run["chips"] / took
