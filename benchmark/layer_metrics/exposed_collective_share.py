"""Share of collective time with no other op running on that chip."""


def read(run):
    red = run["trace"]
    if not red or red["chips"] < 2 or not red["collective_s"]:
        return None
    return 100.0 * red["exposed_collective_s"] / red["collective_s"]
