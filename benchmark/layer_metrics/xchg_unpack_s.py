"""Device seconds per epoch in the exchange's receive side
(``sgcn.xchg_unpack``: the reshape, the ``halo_src`` gather into the halo table
and the upcast), forward + backward, mean over chips."""

import scopered


def read(run):
    return scopered.scope_seconds(run, "xchg_unpack")
