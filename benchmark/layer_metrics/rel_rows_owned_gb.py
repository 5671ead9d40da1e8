"""Bytes per chip of the parameters owned with the rows and of their
optimiser state (program counter ``rel.work``, left by the model's setup
hook: the per-chip embedding tables, 4 B a parameter, and Adam's two
moments), in GB."""

import scopered


def read(run):
    work = scopered.program_table("counters").get("rel.work")
    if not work:
        return None
    owned = work["row_owned_bytes"]
    return (owned["parameters"] + owned["optimizer_state"]) / 1e9
