"""Device seconds per epoch in fusions rooted in the graph-transformer
model's ``sgcn.xatt_project`` sub-scope (the query, key, value and skip
products, the label embedding, the mean over heads and the gate, forward and
backward, inside ``sgcn.dense``), mean over chips."""

import scopered_xatt


def read(run):
    return scopered_xatt.seconds(run, "xatt_project")
