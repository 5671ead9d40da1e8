"""Compile requests of the set-up served from the persistent cache."""


def read(run):
    return float(run["counters"]["setup"]["cache_hits"])
