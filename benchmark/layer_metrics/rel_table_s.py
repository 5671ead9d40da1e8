"""Device seconds per epoch in fusions rooted in the relational model's
``sgcn.rel_table`` sub-scope (building a layer's gather tables: the feature
‖ embedding input, the stacked cotangent; inside ``sgcn.dense``), mean over
chips."""

import scopered_rel


def read(run):
    return scopered_rel.seconds(run, "rel_table")
