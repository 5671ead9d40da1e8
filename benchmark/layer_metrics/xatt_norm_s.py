"""Device seconds per epoch in fusions rooted in the graph-transformer
model's ``sgcn.xatt_norm`` sub-scope (LayerNorm and the activation, forward
and backward, inside ``sgcn.dense``), mean over chips."""

import scopered_xatt


def read(run):
    return scopered_xatt.seconds(run, "xatt_norm")
