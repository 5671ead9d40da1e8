"""Device seconds per epoch in fusions rooted in the attention layer's
``sgcn.att_score`` sub-scope (per-slot LeakyReLU, exp, and the backward's
recomputation of the coefficients), forward + backward, mean over chips."""

import scopered_att


def read(run):
    return scopered_att.sub_seconds(run, "att_score")
