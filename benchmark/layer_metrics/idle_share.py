"""Share of the traced window in which no op ran on the device."""


def read(run):
    red = run["trace"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"]) if red else None
