"""Device memory in use on the fullest chip after set-up: what is resident
between steps (plan arrays, features, weights, optimizer state)."""


def read(run):
    used = run["memory"]["in_use"]
    return max(used) / 1e9 if used else None
