"""Peak device memory on the fullest chip, ``memory_stats()`` read straight
after the window and before the reference runs: the peak of live arrays plus
what the runtime reserved for the loaded programs' temporaries.  The two
peaks need not coincide, so the sum is an upper bound on the true peak."""


def read(run):
    peak = run["memory"]["peak"]
    return max(peak) / 1e9 if peak else None
