"""Host seconds of ``build_comm_plan``'s ``plan.edges`` span (the per-chip edge
arrays and their local / halo split), first build of the process."""

import scopered


def read(run):
    spans = scopered.span_durations("plan.edges")
    return spans[0] if spans else None
