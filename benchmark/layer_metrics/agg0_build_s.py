"""Host seconds of ``FullBatchTrainer``'s ``agg0.build`` span: the one-off
aggregation of the input features (layer 0's ``Â·h0``, hoisted out of the
step), compile and blocking wait included; first build of the process.
Nothing where the program has no such span (a parent commit, or a path the
hoist does not cover)."""

import scopered


def read(run):
    spans = scopered.span_durations("agg0.build")
    return spans[0] if spans else None
