"""Compile requests after set-up ended; anything but 0 fails the run."""


def read(run):
    c = run["counters"]
    return float(c["window"]["compiles"] - c["setup"]["compiles"])
