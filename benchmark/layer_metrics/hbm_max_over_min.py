"""Resident memory of the fullest chip over the emptiest: partition
imbalance as the device sees it."""


def read(run):
    used = run["memory"]["in_use"]
    return max(used) / min(used) if len(used) > 1 and min(used) > 0 else None
