"""Device seconds per epoch in fusions rooted in the typed attention model's
``sgcn.ratt_project`` sub-scope (the per-relation projections, the folded
destination scores and the skip, forward and backward, inside
``sgcn.dense``), mean over chips."""

import scopered_ratt


def read(run):
    return scopered_ratt.seconds(run, "ratt_project")
