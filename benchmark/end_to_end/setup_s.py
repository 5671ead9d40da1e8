"""Process start to the first measured sample: imports, inputs, partition,
plan, placement, every compile and warm-up call."""


def read(run):
    return run["setup_s"]
