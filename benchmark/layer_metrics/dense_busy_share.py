"""Share of the device's leaf-op seconds in ops whose HLO metadata names the
JAX primitive ``dot_general``: the dense products, forward and backward."""

import tracered


def read(run):
    return tracered.primitive_share(run["trace"], "dot_general")
