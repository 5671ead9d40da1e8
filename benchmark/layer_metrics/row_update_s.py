"""Device seconds per epoch in fusions rooted in ``sgcn.row_update`` (the
optimiser on the parameters owned with the rows, inside
``sgcn.optimizer``), mean over chips."""

import scopered_rel


def read(run):
    return scopered_rel.seconds(run, "row_update")
