"""Seconds inside XLA backend compiles during set-up, summed over compile
requests (``jax.monitoring``); a persistent-cache hit costs its load only."""


def read(run):
    return run["counters"]["setup"]["compile_s"]
