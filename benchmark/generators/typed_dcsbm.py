"""Typed degree-corrected block model: node types as id ranges, and one
bipartite (or, between a type and itself, square) relation per entry of
``graph["relations"]``.

``graph`` keys: ``types`` (name -> count; ids contiguous in this order, n
their sum), ``relations`` (``[source type, destination type, edges]``: that
many DISTINCT endpoint pairs; a square relation's pairs are unordered and no
pair is a self-loop), ``alpha`` (Pareto shape of the propensities on both
sides of every relation).  Each endpoint of an edge is drawn in proportion to
its node's propensity ``Pareto(alpha) + 1`` — a fresh draw per relation and
side, so a prolific author is not thereby a well-affiliated one — and pairs
are drawn until the relation has its count.  The harness symmetrises what
this returns, which is the published step of adding each relation's reverse.

Seed 0 at the ogbn-mag counts (``configs/rgcn-mag-2x64.json``) gives
21,111,007 distinct pairs = 42,222,014 directed edges in 27 s, and per type
over all relations (the sandbox's run of this file, PR 33) the maximum / mean
degree: ``paper`` 12,621 / 34.61, ``author`` 3,909 / 7.22 (8,064 authors
without an edge), ``institution`` 14,230 / 119.45, ``field_of_study``
15,914 / 125.16; 5,880,784 directed edges (13.9 %) lie past a row's 64th.
"""

import numpy as np


def _draw(rng, cum, m: int) -> np.ndarray:
    return np.minimum(np.searchsorted(cum, rng.random(m) * cum[-1]),
                      len(cum) - 1)


def _relation(rng, lo_s: int, n_s: int, lo_d: int, n_d: int, m: int,
              alpha: float):
    """``m`` distinct pairs (source id, destination id) of one relation."""
    square = lo_s == lo_d
    cum_s = np.cumsum(rng.pareto(alpha, n_s) + 1.0)
    cum_d = np.cumsum(rng.pareto(alpha, n_d) + 1.0)
    keys = np.zeros(0, np.int64)
    while len(keys) < m:
        want = m - len(keys)
        s = _draw(rng, cum_s, want + want // 4 + 16)
        d = _draw(rng, cum_d, len(s))
        if square:
            s, d = np.minimum(s, d), np.maximum(s, d)
            s, d = s[s != d], d[s != d]
        fresh = np.setdiff1d(s.astype(np.int64) * n_d + d, keys)
        # (setdiff1d sorts: shuffle, so that the cut keeps no id order)
        keys = np.concatenate([keys, rng.permutation(fresh)[:want]])
    return lo_s + keys // n_d, lo_d + keys % n_d


def edges(n: int, rng, graph: dict):
    names = list(graph["types"])
    counts = [int(graph["types"][t]) for t in names]
    if sum(counts) != n:
        raise ValueError(f"typed_dcsbm: the types count {sum(counts)} rows, "
                         f"the configuration {n}")
    start = dict(zip(names, np.concatenate([[0], np.cumsum(counts)[:-1]])))
    size = dict(zip(names, counts))
    src, dst = [], []
    for s, d, m in graph["relations"]:
        a, b = _relation(rng, int(start[s]), size[s], int(start[d]), size[d],
                         int(m), float(graph["alpha"]))
        src.append(a)
        dst.append(b)
    return np.concatenate(src), np.concatenate(dst)
