"""Share of the executed typed-attention slots an epoch that run as virtual
rows (program counter ``ratt.work``, left by the model's setup hook:
``per_step.virtual_row_slots`` ÷ ``per_step.executed_slots``, over every
layer's live relations and their max, forward and backward passes)."""

import scopered


def read(run):
    work = scopered.program_table("counters").get("ratt.work")
    if not work or not work["per_step"]["executed_slots"]:
        return None
    per = work["per_step"]
    return 100.0 * per["virtual_row_slots"] / per["executed_slots"]
