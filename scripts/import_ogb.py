"""Convert on-disk OGB / Reddit / planetoid-snapshot datasets to the repo's
``.npz`` layout.

Zero egress on this box means the real downloads cannot be fetched HERE, but
the north-star configs (`BASELINE.json`: ogbn-products, ogbn-arxiv, Reddit,
cora) must be one file-drop away from a real-data run.  This script is that
file-drop converter — runnable wherever the download exists, tested in CI on
a synthetic directory mimicking each layout.

Supported inputs:

  * ``--kind ogb <root>`` — an OGB node-prop dataset directory in the raw
    CSV layout the ogb package materializes
    (``<root>/raw/edge.csv.gz``, ``node-feat.csv.gz``, ``node-label.csv.gz``
    and ``<root>/split/<split_name>/{train,valid,test}.csv.gz``), e.g. the
    ``ogbn_products/`` or ``ogbn_arxiv/`` folder.  Directed inputs (arxiv)
    are symmetrized — the reference stacks train on undirected graphs
    (``GPU/PGCN.py:52-63`` densifies A+Aᵀ semantics; the MPI stack's mtx
    inputs are symmetric).
  * ``--kind reddit <root>`` — the GraphSAINT/DGL Reddit pair
    (``reddit_data.npz`` + ``reddit_graph.npz``).
  * ``--kind npz <file>`` — any planetoid-style CSR snapshot the repo
    already reads (``sgcn_tpu.io.datasets.load_npz_dataset``), e.g. the
    public ``cora.npz``; re-emitted in the repo layout with generated
    planetoid splits.

Output: ``<out>.npz`` (the ``save_npz_dataset`` layout every trainer CLI
accepts via ``--npz``) and ``<out>.splits.npz`` with float32 0/1
``train_mask``/``valid_mask``/``test_mask``.

Usage examples (on a machine with the data):
  python scripts/import_ogb.py --kind ogb ~/ogbn_products -o products
  python scripts/import_ogb.py --kind ogb ~/ogbn_arxiv -o arxiv
  python scripts/import_ogb.py --kind reddit ~/reddit -o reddit
  python scripts/import_ogb.py --kind npz ~/cora.npz -o cora
Then e.g.:
  python -m sgcn_tpu.train --npz products.npz -p products.8.hp -s 8 ...
"""

from __future__ import annotations

import argparse
import gzip
import os
import sys

import numpy as np
import scipy.sparse as sp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sgcn_tpu.io.datasets import (          # noqa: E402
    load_npz_dataset, planetoid_split, save_npz_dataset)


def _read_csv_gz(path: str, dtype):
    """Tolerate both .csv.gz and plain .csv (ogb ships gz).  pandas parses
    the products-scale CSVs (~124M edge lines) orders of magnitude faster
    than np.loadtxt; fall back only when pandas is absent."""
    if not os.path.exists(path) and path.endswith(".gz"):
        path = path[:-3]
    try:
        import pandas as pd
        arr = pd.read_csv(path, header=None, dtype=dtype).to_numpy()
        return np.atleast_2d(arr)
    except ImportError:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as fh:
            return np.loadtxt(fh, delimiter=",", dtype=dtype, ndmin=2)


def _find_split_dir(root: str) -> str | None:
    sd = os.path.join(root, "split")
    if not os.path.isdir(sd):
        return None
    subs = [os.path.join(sd, d) for d in sorted(os.listdir(sd))
            if os.path.isdir(os.path.join(sd, d))]
    return subs[0] if subs else None   # ogb has exactly one (time/sales_ranking)


def import_ogb_raw(root: str):
    """OGB raw-CSV layout -> (csr adjacency, features, labels, splits)."""
    raw = os.path.join(root, "raw")
    edges = _read_csv_gz(os.path.join(raw, "edge.csv.gz"), np.int64)
    feats = _read_csv_gz(os.path.join(raw, "node-feat.csv.gz"),
                         np.float32)
    labels = _read_csv_gz(os.path.join(raw, "node-label.csv.gz"),
                          np.int64).ravel().astype(np.int32)
    n = feats.shape[0]
    if labels.shape[0] != n:
        raise ValueError(f"{n} feature rows vs {labels.shape[0]} labels")
    src, dst = edges[:, 0], edges[:, 1]
    a = sp.coo_matrix((np.ones(len(src), np.float32), (src, dst)),
                      shape=(n, n)).tocsr()
    # symmetrize (arxiv is directed; products' one-direction edge list also
    # needs the mirror); COO->CSR summed duplicate edge lines to 2.0, so
    # re-binarize explicitly — non-unit weights would multiply through
    # normalize_adjacency into Â
    a = sp.csr_matrix(a.maximum(a.T))
    a.setdiag(0)
    a.eliminate_zeros()
    a.data[:] = 1.0
    sd = _find_split_dir(root)
    if sd is None:
        raise FileNotFoundError(
            f"no split directory under {root}/split — wrong nesting level "
            f"(point at the dataset dir, e.g. .../ogbn_products) or a "
            f"partial download; a silent empty-splits npz would only crash "
            f"later in the trainer")
    splits = {}
    for name in ("train", "valid", "test"):
        idx = _read_csv_gz(os.path.join(sd, f"{name}.csv.gz"),
                           np.int64).ravel()
        m = np.zeros(n, np.float32)
        m[idx] = 1.0
        splits[f"{name}_mask"] = m
    return a, feats, labels, splits


def import_reddit(root: str):
    """GraphSAINT/DGL Reddit pair -> same tuple as import_ogb_raw."""
    d = np.load(os.path.join(root, "reddit_data.npz"))
    g = np.load(os.path.join(root, "reddit_graph.npz"))
    feats = np.asarray(d["feature"], np.float32)
    labels = np.asarray(d["label"]).ravel().astype(np.int32)
    n = feats.shape[0]
    a = sp.csr_matrix((g["data"], (g["row"], g["col"])), shape=(n, n))
    a = sp.csr_matrix(a.maximum(a.T), dtype=np.float32)
    a.setdiag(0)
    a.eliminate_zeros()
    # node_types: 1=train 2=valid 3=test (the GraphSAINT convention)
    nt = np.asarray(d["node_types"]).ravel()
    splits = {f"{nm}_mask": (nt == code).astype(np.float32)
              for nm, code in (("train", 1), ("valid", 2), ("test", 3))}
    return a, feats, labels, splits


def import_npz(path: str, seed: int = 0):
    a, feats, labels = load_npz_dataset(path)
    a = sp.csr_matrix(a.maximum(a.T), dtype=np.float32)
    a.setdiag(0)
    a.eliminate_zeros()
    train, test = planetoid_split(labels, seed=seed)
    splits = {"train_mask": train, "valid_mask": np.zeros_like(train),
              "test_mask": test}
    return a, feats, labels, splits


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("root", help="dataset directory (or .npz file for "
                               "--kind npz)")
    p.add_argument("--kind", required=True,
                   choices=["ogb", "reddit", "npz"])
    p.add_argument("-o", "--out", required=True,
                   help="output prefix: writes <out>.npz + <out>.splits.npz")
    args = p.parse_args()

    if args.kind == "ogb":
        a, feats, labels, splits = import_ogb_raw(args.root)
    elif args.kind == "reddit":
        a, feats, labels, splits = import_reddit(args.root)
    else:
        a, feats, labels, splits = import_npz(args.root)

    save_npz_dataset(args.out + ".npz", a, feats, labels)
    np.savez_compressed(args.out + ".splits.npz", **splits)
    deg = a.nnz / max(1, a.shape[0])
    print(f"wrote {args.out}.npz: n={a.shape[0]} nnz={a.nnz} "
          f"avg_deg={deg:.1f} f={feats.shape[1]} "
          f"classes={int(labels.max()) + 1}")
    print(f"wrote {args.out}.splits.npz: "
          + " ".join(f"{k}={int(v.sum())}" for k, v in splits.items()))


if __name__ == "__main__":
    main()
