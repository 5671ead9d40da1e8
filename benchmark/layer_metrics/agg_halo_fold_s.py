"""Device seconds per epoch in the halo-edge fold (``sgcn.agg_halo_fold``:
the halo-source gather, its segment sum and ``local + remote``), forward +
backward, mean over chips."""

import scopered


def read(run):
    return scopered.scope_seconds(run, "agg_halo_fold")
