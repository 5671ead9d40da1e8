"""Share of the device's leaf-op seconds in ops whose HLO metadata names the
JAX primitive ``gather``: the ELL slot passes' row gathers (and the loss's label pick)."""

import tracered


def read(run):
    return tracered.primitive_share(run["trace"], "gather")
