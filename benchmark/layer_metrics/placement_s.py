"""Host seconds building the trainer and putting plan arrays, weights,
features and labels on the cell's chips."""


def read(run):
    spans = run["spans"].get("placement")
    return spans[0] if spans else None
