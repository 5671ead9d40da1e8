"""Device seconds per epoch in the attention layer's max pass
(``sgcn.att_max``: the per-destination max of the score terms over slots,
tail and halo edges), mean over chips."""

import scopered_att


def read(run):
    return scopered_att.sub_seconds(run, "att_max")
