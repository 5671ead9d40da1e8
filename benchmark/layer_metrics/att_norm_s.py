"""Device seconds per epoch under ``sgcn.att_norm`` (division by the softmax
denominator, the head mean, and the backward's per-row terms), mean over
chips."""

import scopered_att


def read(run):
    return scopered_att.sub_seconds(run, "att_norm")
