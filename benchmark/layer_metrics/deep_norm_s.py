"""Device seconds per epoch in fusions rooted in the deep stack's
``sgcn.norm`` sub-scope (BatchNorm's column sums and its apply; forward,
backward and recomputed), without their collectives (booked
``norm:collective``), mean over chips."""

import scopered_deep


def read(run):
    return scopered_deep.seconds(run, "norm")
