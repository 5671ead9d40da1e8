"""Device seconds per epoch in the ELL slot passes (``sgcn.agg_slots``: per
slot one gather·weight and the accumulate), forward + backward, mean over
chips."""

import scopered


def read(run):
    return scopered.scope_seconds(run, "agg_slots")
