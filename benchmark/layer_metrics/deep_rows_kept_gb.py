"""Bytes per chip the deep stack's checkpoints keep from forward to backward
(program counter ``deep.work``, left by the model's setup hook: per layer its
input and, under ``keep = aggregate``, the aggregated table), in GB."""

import scopered


def read(run):
    work = scopered.program_table("counters").get("deep.work")
    if not work:
        return None
    return work["rows_kept_bytes"] / 1e9
