"""Inputs of a cell, made from seeds by the benchmark's own code.

The graph is the dataset.  Its generator (``generators/<name>.py``), parameters
and seed are fixed in the configuration file, so every run of a cell trains on
the same normalized adjacency ``Â = D^-1/2 (A + I) D^-1/2``.  ``--seed`` drives
what a user brings to a run: features, labels, initial weights.

The normalization is the benchmark's copy of ``sgcn_tpu/prep/normalize.py``;
later PRs may change the program and not the yardstick.  It differs in one
respect: edges are symmetrised, de-duplicated and normalized by one integer
sort of ``row·n + col`` keys instead of scipy's ``a + a.T``, which at the
products shape (124 M nonzeros) took 52 s against 128 s on the sandbox's CPU.

Generated Â is kept under ``benchmark/.cache/`` as ``.npy`` arrays and loaded
memory-mapped, keyed by the hashes of this file and the generator's and by
the graph's parameters.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil

import numpy as np

import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(HERE, ".cache")


# ------------------------------------------------------------------- graph
def normalized_adjacency(n: int, src, dst):
    """CSR arrays of ``D^-1/2 (A + I) D^-1/2`` for the undirected simple graph
    on the given endpoint pairs (self-pairs and duplicates dropped; degrees
    count structural nonzeros of ``A + I``, as the program's prep does)."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    diag = np.arange(n, dtype=np.int64)
    keys = np.concatenate([src * n + dst, dst * n + src, diag * n + diag])
    del src, dst
    keys = np.unique(keys)
    row = keys // n
    indices = (keys - row * n).astype(np.int32)
    del keys
    deg = np.bincount(row, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    dinv = (1.0 / np.sqrt(deg)).astype(np.float32)   # deg >= 1: the loop
    data = dinv[row] * dinv[indices]
    return indptr, indices, data


def _generator_path(graph: dict) -> str:
    return os.path.join(HERE, "generators", graph["generator"] + ".py")


def generate_graph(n: int, graph: dict):
    """(indptr, indices, data) of Â for one ``graph`` block of a config."""
    gen = manifest.load_module(_generator_path(graph))
    src, dst = gen.edges(n, np.random.default_rng(graph["seed"]), graph)
    return normalized_adjacency(n, src, dst)


# --------------------------------------------------------------------- cache
def file_hash(path: str) -> str:
    with open(path, "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()[:12]


def cache_key(kind: str, spec: dict) -> str:
    blob = json.dumps({"src": file_hash(os.path.abspath(__file__)), **spec},
                      sort_keys=True)
    return f"{kind}-{hashlib.sha256(blob.encode()).hexdigest()[:16]}"


def cached_arrays(kind: str, spec: dict, make, names):
    """Arrays ``names`` for ``spec``: from ``.cache/<key>/`` memory-mapped if
    there, else made by ``make()`` and written (into a temporary directory,
    renamed when complete, so a killed run leaves nothing half-written).
    Returns ``(arrays, hit)``."""
    path = os.path.join(CACHE_DIR, cache_key(kind, spec))
    hit = os.path.isdir(path)
    if not hit:
        tmp = f"{path}.tmp{os.getpid()}"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        for name, arr in zip(names, make()):
            np.save(os.path.join(tmp, f"{name}.npy"), arr)
        with open(os.path.join(tmp, "spec.json"), "w") as fh:
            json.dump(spec, fh, sort_keys=True)
        try:
            os.rename(tmp, path)
        except OSError:                 # another run finished first
            shutil.rmtree(tmp, ignore_errors=True)
    return [np.load(os.path.join(path, f"{name}.npy"), mmap_mode="r")
            for name in names], hit


def load_graph(n: int, graph: dict):
    """Â as a scipy CSR matrix over (memory-mapped) cached arrays."""
    import scipy.sparse as sp

    (indptr, indices, data), hit = cached_arrays(
        "graph", {"n": n, "graph": graph,
                  "generator": file_hash(_generator_path(graph))},
        lambda: generate_graph(n, graph), ("indptr", "indices", "data"))
    return sp.csr_matrix((data, indices, indptr), shape=(n, n)), hit


# ------------------------------------------------------------ per-run inputs
def features_and_labels(n: int, f_in: int, classes: int, seed: int):
    """Seeded normal features; labels are the argmax of a fixed random
    projection of the features, so there is something to learn and the loss
    must fall."""
    rng = np.random.default_rng(seed)
    feats = rng.standard_normal((n, f_in), dtype=np.float32)
    proj = rng.standard_normal((f_in, classes), dtype=np.float32)
    labels = (feats @ proj).argmax(axis=1).astype(np.int32)
    return feats, labels
