"""Host seconds of ``build_comm_plan``'s ``plan.relabel`` span (COO conversion,
degree key and the row relabelling), first build of the process."""

import scopered


def read(run):
    spans = scopered.span_durations("plan.relabel")
    return spans[0] if spans else None
