"""Device seconds per epoch in fusions rooted in the typed attention model's
``sgcn.ratt_norm`` sub-scope (BatchNorm's statistics with their ``psum``,
ELU and the head, inside ``sgcn.dense``), mean over chips."""

import scopered_ratt


def read(run):
    return scopered_ratt.seconds(run, "ratt_norm")
