"""Real-dataset ingestion + the cora-role accuracy experiment.

The reference's accuracy story is a run on real cora data
(``GPU/PGCN-Accuracy.py``, ``README.md:110``) pulled from sparse.tamu.edu/OGB
as ``.mtx`` (``README.md:11``).  Zero egress, so the repo commits a
deterministic cora-format fixture (``tests/fixtures/cora_like.*``, regenerated
by ``scripts/make_cora_fixture.py``) in both real-data layouts — the
planetoid/ogbn ``.npz`` snapshot and the MatrixMarket ``A/H/Y`` family — and
these tests drive the full CLI pipeline over it.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures")


def run_cli(args, **kw):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # let -b cpu set its own device count
    env["PYTHONPATH"] = REPO
    return subprocess.run([sys.executable, "-m", *args], capture_output=True,
                          text=True, cwd=REPO, env=env, timeout=600, **kw)


def fixture(name):
    return os.path.join(FIX, name)


def test_npz_roundtrip(tmp_path):
    from sgcn_tpu.io.datasets import (cora_like, load_npz_dataset,
                                      save_npz_dataset)
    a, feats, labels = cora_like(n=200, seed=3)
    p = str(tmp_path / "snap.npz")
    save_npz_dataset(p, a, feats, labels)
    a2, f2, y2 = load_npz_dataset(p)
    assert (a != a2).nnz == 0
    np.testing.assert_array_equal(np.asarray(feats.todense()), f2)
    np.testing.assert_array_equal(labels, y2)
    # dense-feature storage flavor
    save_npz_dataset(p, a, f2, labels)
    a3, f3, y3 = load_npz_dataset(p)
    np.testing.assert_array_equal(f2, f3)


def test_npz_fixture_matches_mtx_family():
    """The two committed layouts carry the same dataset."""
    from sgcn_tpu.io.datasets import load_npz_dataset
    from sgcn_tpu.io.mtx import read_mtx
    from sgcn_tpu.prep import normalize_adjacency
    a, feats, labels = load_npz_dataset(fixture("cora_like.npz"))
    ahat = read_mtx(fixture("cora_like.A.mtx"))
    h = read_mtx(fixture("cora_like.H.mtx"))
    y = read_mtx(fixture("cora_like.Y.mtx"))
    assert np.abs(normalize_adjacency(a) - ahat).max() < 1e-6
    np.testing.assert_array_equal(np.asarray(h.todense()), feats)
    np.testing.assert_array_equal(np.asarray(y.todense()).argmax(1), labels)


def test_cora_like_format():
    """Fixture has cora's format: binary sparse BoW, 7 classes, undirected."""
    from sgcn_tpu.io.datasets import load_npz_dataset
    a, feats, labels = load_npz_dataset(fixture("cora_like.npz"))
    assert a.shape == (600, 600)
    assert (a != a.T).nnz == 0
    assert set(np.unique(feats)) <= {0.0, 1.0}
    assert sp.csr_matrix(feats).nnz < 0.25 * feats.size   # sparse, like cora
    assert labels.max() == 6 and labels.min() == 0


def test_planetoid_split_semantics():
    from sgcn_tpu.io.datasets import planetoid_split
    labels = np.arange(300) % 7
    train, test = planetoid_split(labels, per_class=20, ntest=100, seed=0)
    counts = np.bincount(labels[train == 1.0], minlength=7)
    assert (counts == 20).all()                 # exactly per_class per class
    assert test.sum() == 100
    assert ((train == 1.0) & (test == 1.0)).sum() == 0   # disjoint


def test_cli_accuracy_experiment_mtx_family():
    """The PGCN-Accuracy run (GPU/PGCN-Accuracy.py): oracle vs partitioned
    trainer on the committed fixture through the file-based CLI, test
    accuracy parity asserted — the reference's README.md:110 protocol."""
    r = run_cli(["sgcn_tpu.train",
                 "-a", fixture("cora_like.A.mtx"),
                 "--features-mtx", fixture("cora_like.H.mtx"),
                 "--labels-mtx", fixture("cora_like.Y.mtx"),
                 "-p", fixture("cora_like.4.hp"),
                 "-b", "cpu", "-s", "4", "-l", "2", "--hidden", "32",
                 "--experiment", "accuracy", "--epochs", "30"])
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["oracle_test_acc"] > 0.6          # far above 1/7 chance
    assert abs(rep["oracle_test_acc"] - rep["fullbatch_test_acc"]) < 0.05


def test_cli_accuracy_experiment_npz_minibatch():
    """Same experiment from the .npz snapshot, mini-batch flavor included."""
    r = run_cli(["sgcn_tpu.train",
                 "--npz", fixture("cora_like.npz"), "--normalize",
                 "-p", fixture("cora_like.4.hp"),
                 "-b", "cpu", "-s", "4", "-l", "2", "--hidden", "32",
                 "--experiment", "accuracy", "--epochs", "30", "-n", "200"])
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["oracle_test_acc"] > 0.6
    assert abs(rep["oracle_test_acc"] - rep["minibatch_test_acc"]) < 0.05


@pytest.mark.parametrize(
    "k", [4, pytest.param(8, marks=pytest.mark.slow)])  # k=8 re-runs the
    # same 1433-wide CLI pipeline for ~75 s of tier-1 budget; k=4 is the
    # budgeted representative
def test_cli_accuracy_cora_true_shape(k):
    """The accuracy experiment at cora's TRUE dims:
    2708 x 1433 x 7, planetoid split (20/class train, 1000 test), oracle vs
    k-way partitioned full-batch AND mini-batch, through the .npz snapshot
    ingestion path end-to-end.  The reference's protocol is the real-cora
    run of ``GPU/PGCN-Accuracy.py`` (README.md:110); real-cora GCN accuracy
    is ~0.81, and the fixture's learnability is calibrated to land in that
    band (measured 0.85 oracle / 0.85 full-batch / 0.83 mini-batch)."""
    r = run_cli(["sgcn_tpu.train",
                 "--npz", fixture("cora2708.npz"), "--normalize",
                 "-p", fixture(f"cora2708.{k}.hp"),
                 "-b", "cpu", "-s", str(k), "-l", "2", "--hidden", "16",
                 "--experiment", "accuracy", "--epochs", "60", "-n", "256"])
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["oracle_test_acc"] > 0.75           # cora-band accuracy
    assert abs(rep["oracle_test_acc"] - rep["fullbatch_test_acc"]) < 0.03
    assert abs(rep["oracle_test_acc"] - rep["minibatch_test_acc"]) < 0.05
