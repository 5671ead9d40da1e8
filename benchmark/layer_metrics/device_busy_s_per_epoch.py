"""Seconds per epoch in which an op ran on the device (union of the leaf ops
of the op line, mean over chips)."""


def read(run):
    red = run["trace"]
    return red["busy_s"] / red["epochs"] if red else None
