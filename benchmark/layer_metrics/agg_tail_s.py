"""Device seconds per epoch in the hub-tail fold (``sgcn.agg_tail``: the tail
gather, its segment sum and the add), forward + backward, mean over chips."""

import scopered


def read(run):
    return scopered.scope_seconds(run, "agg_tail")
