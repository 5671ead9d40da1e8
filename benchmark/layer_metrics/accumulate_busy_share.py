"""Share of the device's leaf-op seconds in ops whose HLO metadata names the
JAX primitive ``add``: the slot passes' weighted accumulate into the output rows."""

import tracered


def read(run):
    return tracered.primitive_share(run["trace"], "add")
