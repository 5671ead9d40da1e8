"""Seconds the program's native partitioner took for this cell's part vector
(kept with the cached vector and re-reported on a hit)."""


def read(run):
    part = run["notes"].get("partition")
    return part["seconds"] if part else None
