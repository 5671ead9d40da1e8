"""Device seconds per epoch in fusions rooted in the deep stack's
``sgcn.softmax_table`` sub-scope (message, exp, the aggregated table, the
divide; forward, backward and recomputed), mean over chips."""

import scopered_deep


def read(run):
    return scopered_deep.seconds(run, "softmax_table")
