"""Share of the device's busy seconds in ops the layers' checkpoints made the
backward re-run (``rematted_computation`` in the op's ``tf_op``): what not
keeping a layer's row-wise work — or, under ``keep = input``, its
aggregation — costs the epoch."""

import scopered_deep


def read(run):
    return scopered_deep.recompute_share(run)
