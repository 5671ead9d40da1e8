"""Device seconds per epoch in fusions rooted in the relational model's
``sgcn.rel_project`` sub-scope (the per-relation and per-type products,
forward and backward, inside ``sgcn.dense``), mean over chips."""

import scopered_rel


def read(run):
    return scopered_rel.seconds(run, "rel_project")
