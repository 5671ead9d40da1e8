"""Device seconds per epoch in the exchange's send side (``sgcn.xchg_pack``:
the ``send_idx`` gather and the wire cast), forward + backward, mean over
chips."""

import scopered


def read(run):
    return scopered.scope_seconds(run, "xchg_pack")
