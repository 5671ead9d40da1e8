"""Share of the window a chip spends inside synchronous collectives waiting
for a later chip (``tracered.reduce_trace``), mean over chips.  ``idle_share``
cannot see it: a chip blocked in an all-to-all counts as busy."""


def read(run):
    red = run["trace"]
    if not red or red["chips"] < 2:
        return None
    return 100.0 * red["collective_wait_s"] / red["window_s"]
