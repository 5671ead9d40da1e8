"""Host seconds of ``build_comm_plan``'s ``plan.ell`` span (the bucketed ELL
layout and its tail), first build of the process."""

import scopered


def read(run):
    spans = scopered.span_durations("plan.ell")
    return spans[0] if spans else None
