"""Connectivity-1 of the partition, sum over chips of halo rows: the rows one
exchange must deliver, whatever partitioner made the vector."""


def read(run):
    halo = run["halo_counts"]
    return float(sum(halo)) if len(halo) > 1 else None
