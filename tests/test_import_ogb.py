"""OGB/Reddit import path, driven on synthetic
directories that mimic each on-disk layout — the real downloads need egress
this box lacks; the converter is what must be ready."""

import gzip
import os
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "import_ogb.py")


def _fake_ogb(root, n=60, f=5, ncls=4, seed=0):
    """Materialize the raw-CSV layout the ogb package writes."""
    rng = np.random.default_rng(seed)
    raw = os.path.join(root, "raw")
    os.makedirs(raw)
    # a directed edge list (arxiv-style): the importer must symmetrize
    m = 4 * n
    edges = rng.integers(0, n, size=(m, 2))
    edges = edges[edges[:, 0] != edges[:, 1]]
    with gzip.open(os.path.join(raw, "edge.csv.gz"), "wt") as fh:
        for s, d in edges:
            fh.write(f"{s},{d}\n")
    feats = rng.standard_normal((n, f)).astype(np.float32)
    with gzip.open(os.path.join(raw, "node-feat.csv.gz"), "wt") as fh:
        for row in feats:
            fh.write(",".join(f"{x:.6f}" for x in row) + "\n")
    labels = rng.integers(0, ncls, n)
    with gzip.open(os.path.join(raw, "node-label.csv.gz"), "wt") as fh:
        fh.write("\n".join(str(x) for x in labels) + "\n")
    sd = os.path.join(root, "split", "time")
    os.makedirs(sd)
    perm = rng.permutation(n)
    cuts = {"train": perm[: n // 2], "valid": perm[n // 2: 3 * n // 4],
            "test": perm[3 * n // 4:]}
    for name, idx in cuts.items():
        with gzip.open(os.path.join(sd, f"{name}.csv.gz"), "wt") as fh:
            fh.write("\n".join(str(x) for x in sorted(idx)) + "\n")
    return edges, feats, labels, cuts


def _run(args):
    return subprocess.run([sys.executable, SCRIPT, *args],
                          capture_output=True, text=True, cwd=REPO)


def test_import_ogb_layout(tmp_path):
    root = tmp_path / "ogbn_tiny"
    edges, feats, labels, cuts = _fake_ogb(str(root))
    out = str(tmp_path / "tiny")
    r = _run([str(root), "--kind", "ogb", "-o", out])
    assert r.returncode == 0, r.stderr

    from sgcn_tpu.io.datasets import load_npz_dataset
    a, f2, y2 = load_npz_dataset(out + ".npz")
    assert (a != a.T).nnz == 0, "importer must symmetrize"
    assert a.diagonal().sum() == 0
    np.testing.assert_allclose(f2, feats, atol=1e-5)
    np.testing.assert_array_equal(y2, labels)
    # every original directed edge is present in the symmetric graph
    al = a.tolil()
    for s, d in edges[:50]:
        assert al[s, d] != 0 and al[d, s] != 0
    z = np.load(out + ".splits.npz")
    for name, idx in cuts.items():
        m = z[f"{name}_mask"]
        np.testing.assert_array_equal(np.flatnonzero(m), np.sort(idx))

    # ...and the output feeds the real trainer pipeline end to end
    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.partition import balanced_random_partition
    from sgcn_tpu.prep import normalize_adjacency
    from sgcn_tpu.train import FullBatchTrainer, make_train_data
    ahat = normalize_adjacency(a)
    plan = build_comm_plan(ahat, balanced_random_partition(a.shape[0], 2), 2)
    tr = FullBatchTrainer(plan, fin=f2.shape[1],
                          widths=[8, int(y2.max()) + 1])
    data = make_train_data(plan, f2, y2, train_mask=z["train_mask"],
                           eval_mask=z["test_mask"])
    assert np.isfinite(tr.step(data))


def test_import_reddit_layout(tmp_path):
    rng = np.random.default_rng(1)
    n, f = 50, 6
    root = tmp_path / "reddit"
    os.makedirs(root)
    feats = rng.standard_normal((n, f)).astype(np.float32)
    labels = rng.integers(0, 5, n)
    nt = rng.choice([1, 2, 3], size=n, p=[0.6, 0.2, 0.2])
    np.savez(root / "reddit_data.npz", feature=feats, label=labels,
             node_types=nt)
    coo = sp.random(n, n, density=0.1, random_state=2, format="coo")
    np.savez(root / "reddit_graph.npz", data=coo.data.astype(np.float32),
             row=coo.row, col=coo.col)
    out = str(tmp_path / "reddit_out")
    r = _run([str(root), "--kind", "reddit", "-o", out])
    assert r.returncode == 0, r.stderr
    from sgcn_tpu.io.datasets import load_npz_dataset
    a, f2, y2 = load_npz_dataset(out + ".npz")
    assert (a != a.T).nnz == 0
    np.testing.assert_allclose(f2, feats, atol=1e-5)
    z = np.load(out + ".splits.npz")
    assert int(z["train_mask"].sum()) == int((nt == 1).sum())


def test_import_npz_passthrough(tmp_path):
    from sgcn_tpu.io.datasets import er_graph, save_npz_dataset
    rng = np.random.default_rng(3)
    n = 80
    a = er_graph(n, 4, seed=0)
    feats = sp.random(n, 9, density=0.3, random_state=1, format="csr")
    labels = rng.integers(0, 3, n)
    src = str(tmp_path / "cora_like.npz")
    save_npz_dataset(src, a, feats, labels)
    out = str(tmp_path / "cora_out")
    r = _run([src, "--kind", "npz", "-o", out])
    assert r.returncode == 0, r.stderr
    z = np.load(out + ".splits.npz")
    assert z["train_mask"].sum() > 0 and z["test_mask"].sum() > 0
    assert not np.any(z["train_mask"] * z["test_mask"])
