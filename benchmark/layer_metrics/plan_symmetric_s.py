"""Host seconds of ``build_comm_plan``'s ``plan.symmetric`` span (the symmetry
check of the adjacency), first build of the process."""

import scopered


def read(run):
    spans = scopered.span_durations("plan.symmetric")
    return spans[0] if spans else None
