"""Regenerate the committed cora-format fixture under tests/fixtures/.

The reference's accuracy experiment runs on the real cora download
(GPU/PGCN-Accuracy.py, README.md:110); zero egress means the repo instead
commits a deterministic generative stand-in with cora's exact format (sparse
binary bag-of-words features, 7 classes, citation-style graph) emitted in
BOTH real-data ingestion layouts:

  * ``cora_like.npz``          — planetoid/ogbn-style snapshot (--npz);
  * ``cora_like.{A,H,Y}.mtx``  — the reference's MatrixMarket family
                                  (-a/--features-mtx/--labels-mtx);
  * ``cora_like.4.hp``         — native hypergraph partitioner output (-p).

Run from the repo root: ``python scripts/make_cora_fixture.py``.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sgcn_tpu.io.datasets import cora_like, save_fixture, save_npz_dataset
from sgcn_tpu.partition.emit import write_partvec
from sgcn_tpu.partition.native import partition_hypergraph_colnet

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "tests", "fixtures")


def main() -> None:
    os.makedirs(OUT, exist_ok=True)
    # small smoke fixture (fast tests)
    a, feats, labels = cora_like(n=600, nclasses=7, vocab=64, seed=7)
    prefix = os.path.join(OUT, "cora_like")
    save_npz_dataset(prefix + ".npz", a, feats, labels)
    save_fixture(prefix, a, labels=labels, features=feats)
    pv, _km1 = partition_hypergraph_colnet(a, k=4, seed=1)
    write_partvec(prefix + ".4.hp", pv)
    # cora's TRUE shape: 2708 papers x 1433-word binary
    # BoW x 7 classes, ~avg-deg-4 citations (real cora: 5429 edges), real
    # ~18-word documents — the dims of the reference's actual accuracy run
    # (GPU/PGCN-Accuracy.py, README.md:110)
    a, feats, labels = cora_like(n=2708, nclasses=7, vocab=1433,
                                 words_per_doc=18, avg_deg=4, seed=11)
    prefix = os.path.join(OUT, "cora2708")
    save_npz_dataset(prefix + ".npz", a, feats, labels)
    save_fixture(prefix, a, labels=labels, features=feats)
    for k in (4, 8):
        pv, _km1 = partition_hypergraph_colnet(a, k=k, seed=1)
        write_partvec(prefix + f".{k}.hp", pv)
    print("wrote fixture families under", OUT)


if __name__ == "__main__":
    main()
