"""Degree-corrected stochastic block model: power-law degrees and communities
(the benchmark's copy of the sampling model of
``sgcn_tpu/io/datasets.py::dcsbm_graph``).  ``graph`` keys: ``ncomm``,
``avg_deg``, ``p_in``, ``alpha``."""

import numpy as np


def edges(n: int, rng, graph: dict):
    """Endpoint pairs: Pareto(alpha) propensities, each edge's first endpoint
    drawn in proportion to propensity, its partner from the same community
    with probability ``p_in``, else from anywhere."""
    ncomm, p_in = graph["ncomm"], graph["p_in"]
    comm = rng.integers(0, ncomm, size=n)
    w = rng.pareto(graph["alpha"], size=n) + 1.0
    m = n * graph["avg_deg"] // 2
    order = np.argsort(comm, kind="stable")      # community-contiguous view
    starts = np.searchsorted(comm[order], np.arange(ncomm + 1))
    cum = np.cumsum(w[order])
    # the edge list is a set, so the first endpoints may be drawn in sorted
    # order: every searchsorted below then walks ``cum`` front to back
    pos = np.searchsorted(cum, np.sort(rng.random(m)) * cum[-1])
    pos = np.minimum(pos, n - 1)
    c = comm[order[pos]]
    lo, hi = starts[c], starts[c + 1]
    c_lo = np.where(lo > 0, cum[lo - 1], 0.0)
    pick = c_lo + rng.random(m) * (cum[hi - 1] - c_lo)
    partner = np.minimum(np.searchsorted(cum, pick), n - 1)
    out = np.flatnonzero(rng.random(m) >= p_in)
    partner[out] = np.minimum(
        np.searchsorted(cum, rng.random(len(out)) * cum[-1]), n - 1)
    return order[pos], order[partner]
