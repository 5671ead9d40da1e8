"""Device seconds per epoch in fusions rooted in the graph-transformer
model's max pass (``sgcn.xatt_max``: per destination and head the largest
score ``q_i·k_j / √C`` over its slots, tail and halo edges, gathering ``k``),
mean over chips."""

import scopered_xatt


def read(run):
    return scopered_xatt.seconds(run, "xatt_max")
