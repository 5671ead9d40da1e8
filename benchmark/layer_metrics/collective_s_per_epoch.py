"""Seconds per epoch inside collectives (all-to-all, collective-permute,
all-reduce), mean over chips; waits on the slowest chip included."""


def read(run):
    red = run["trace"]
    if not red or red["chips"] < 2:
        return None
    return red["collective_s"] / red["epochs"]
