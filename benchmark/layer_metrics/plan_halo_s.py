"""Host seconds of ``build_comm_plan``'s ``plan.halo`` span (halo lists, send
tables and ``halo_src``), first build of the process."""

import scopered


def read(run):
    spans = scopered.span_durations("plan.halo")
    return spans[0] if spans else None
