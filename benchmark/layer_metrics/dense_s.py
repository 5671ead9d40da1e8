"""Device seconds per epoch in the dense products (``sgcn.dense``), forward +
backward, mean over chips; the weight gradients' ``psum`` the transposition
puts there is booked apart (``scopered``)."""

import scopered


def read(run):
    return scopered.scope_seconds(run, "dense")
