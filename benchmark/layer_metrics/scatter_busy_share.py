"""Share of the device's leaf-op seconds in ops whose HLO metadata names the
JAX primitive ``scatter-add``: the hub tail's and the halo edges' segment sums."""

import tracered


def read(run):
    return tracered.primitive_share(run["trace"], "scatter-add")
