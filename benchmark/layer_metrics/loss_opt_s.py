"""Device seconds per epoch in the loss, forward + backward, and the
optimizer update (``sgcn.loss`` + ``sgcn.optimizer``), mean over chips."""

import scopered


def read(run):
    return scopered.scope_seconds(run, "loss", "optimizer")
