"""Mini-batch trainer + plan-padding tests (PGCN-Mini-batch capability)."""

import numpy as np
import pytest

from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.parallel.plan import pad_comm_plan
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.train import FullBatchTrainer, make_train_data
from sgcn_tpu.train.minibatch import (
    MiniBatchTrainer, sample_adjacency, sample_batches,
)

K = 4


def test_pad_comm_plan_preserves_forward(ahat):
    n = ahat.shape[0]
    rng = np.random.default_rng(3)
    pv = balanced_random_partition(n, K, seed=1)
    plan = build_comm_plan(ahat, pv, K)
    padded = pad_comm_plan(plan, plan.b + 5, plan.s + 3, plan.r + 7,
                           plan.e + 11)
    feats = rng.standard_normal((n, 9)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    a = FullBatchTrainer(plan, fin=9, widths=[6, 3], seed=2)
    b = FullBatchTrainer(padded, fin=9, widths=[6, 3], seed=2)
    pa = a.predict(make_train_data(plan, feats, labels))
    pb = b.predict(make_train_data(padded, feats, labels))
    np.testing.assert_allclose(pa, pb, rtol=1e-5, atol=1e-6)


def test_sample_batches_shapes():
    bs = sample_batches(100, 32, seed=0)
    assert len(bs) == 3 * (100 // 32 + 1)
    for b in bs:
        assert len(b) == 32
        assert len(np.unique(b)) == 32


def test_sample_adjacency(ahat):
    batch = np.array([0, 3, 5, 10, 11])
    sub = sample_adjacency(ahat, batch)
    assert sub.shape == (5, 5)
    dense = ahat.toarray()[np.ix_(batch, batch)]
    np.testing.assert_allclose(sub.toarray(), dense, rtol=1e-6)


def test_minibatch_training_converges(ahat):
    n = ahat.shape[0]
    rng = np.random.default_rng(5)
    pv = balanced_random_partition(n, K, seed=2)
    feats = rng.standard_normal((n, 8)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    tr = MiniBatchTrainer(ahat, pv, K, fin=8, widths=[8, 3],
                          batch_size=24, nbatches=4, lr=0.02, seed=0)
    report = tr.fit(feats, labels, epochs=6, verbose=False)
    assert report["nbatches"] == 4
    assert report["loss_history"][-1] < report["loss_history"][0]
    assert report["total_exchanged_rows"] > 0
    # batch comm must not exceed full-graph comm per exchange
    full = build_comm_plan(ahat, pv, K)
    for p in tr.plans:
        assert p.predicted_send_volume.sum() <= full.predicted_send_volume.sum()


def test_minibatch_fullgraph_eval(ahat):
    n = ahat.shape[0]
    rng = np.random.default_rng(6)
    pv = balanced_random_partition(n, K, seed=2)
    feats = rng.standard_normal((n, 8)).astype(np.float32)
    labels = (np.arange(n) % 3).astype(np.int32)
    tr = MiniBatchTrainer(ahat, pv, K, fin=8, widths=[8, 3],
                          batch_size=24, nbatches=3, lr=0.05, seed=1)
    tr.fit(feats, labels, epochs=8, verbose=False)
    loss, acc = tr.evaluate_fullgraph(feats, labels)
    assert np.isfinite(loss)
    assert 0.0 <= acc <= 1.0


def test_minibatch_empty_train_batches_no_nan(ahat):
    """A batch with zero train-mask vertices must not NaN-poison the weights
    (semi-supervised masks are sparse; many random batches miss them all)."""
    n = ahat.shape[0]
    rng = np.random.default_rng(9)
    pv = balanced_random_partition(n, K, seed=4)
    feats = rng.standard_normal((n, 6)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    train_mask = np.zeros(n, dtype=np.float32)
    train_mask[rng.choice(n, 4, replace=False)] = 1.0   # 4 labeled vertices
    tr = MiniBatchTrainer(ahat, pv, K, fin=6, widths=[4, 3],
                          batch_size=12, nbatches=6, seed=2)
    report = tr.fit(feats, labels, train_mask, epochs=3, verbose=False)
    assert np.isfinite(report["loss_history"]).all()
    leaves = __import__("jax").tree.leaves(tr.inner.params)
    assert all(np.isfinite(np.asarray(w)).all() for w in leaves)


def test_minibatch_stats_vocabulary(ahat):
    """fit() reports the full-batch trainer's 8-number comm vocabulary, and
    volume equals the sum of per-batch plan predictions."""
    n = ahat.shape[0]
    rng = np.random.default_rng(7)
    pv = balanced_random_partition(n, K, seed=2)
    feats = rng.standard_normal((n, 8)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    epochs, nlayers = 3, 2
    tr = MiniBatchTrainer(ahat, pv, K, fin=8, widths=[8, 3],
                          batch_size=24, nbatches=4, lr=0.02, seed=0)
    report = tr.fit(feats, labels, epochs=epochs, warmup=1, verbose=False)
    for f in ("total_send_volume", "max_send_volume", "total_send_msgs",
              "max_send_msgs", "total_recv_volume", "max_recv_volume",
              "total_recv_msgs", "max_recv_msgs"):
        assert f in report, f
    # every batch stepped `epochs` times + batch 0 stepped once for warm-up;
    # each step = 2·nlayers exchanges of the batch plan's boundary rows
    want = 0
    for i, p in enumerate(tr.plans):
        steps = epochs + (1 if i == 0 else 0)
        want += steps * 2 * nlayers * int(p.predicted_send_volume.sum())
    assert report["total_send_volume"] == want
    assert report["total_send_volume"] == report["total_recv_volume"]
    assert report["total_send_volume"] == report["total_exchanged_rows"]


def test_minibatch_gat_trains(ahat):
    """GAT mini-batch: shared combined-edge envelope (buckets + tail) across
    batch plans, one compiled step, finite decreasing loss."""
    n = ahat.shape[0]
    rng = np.random.default_rng(9)
    pv = balanced_random_partition(n, K, seed=4)
    feats = rng.standard_normal((n, 6)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    tr = MiniBatchTrainer(ahat, pv, K, fin=6, widths=[5, 3],
                          batch_size=16, model="gat", activation="none",
                          seed=0)
    # every batch plan shares ONE combined-edge envelope
    envs = {(p.cell_buckets, p.ctl) for p in tr.plans}
    assert len(envs) == 1
    report = tr.fit(feats, labels, epochs=3, verbose=False)
    assert np.isfinite(report["loss_history"]).all()


def test_fused_epoch_matches_stepwise(ahat):
    """The one-program epoch sweep (fori over batches on-device) must follow
    the exact trajectory of sequential per-batch step() calls."""
    n = ahat.shape[0]
    rng = np.random.default_rng(5)
    pv = balanced_random_partition(n, K, seed=2)
    feats = rng.standard_normal((n, 7)).astype(np.float32)
    labels = rng.integers(0, 3, size=n).astype(np.int32)
    kw = dict(batch_size=16, nbatches=4, lr=0.05, seed=3)
    seq = MiniBatchTrainer(ahat, pv, K, fin=7, widths=[6, 3], **kw)
    fused = MiniBatchTrainer(ahat, pv, K, fin=7, widths=[6, 3], **kw)
    batches = seq.make_batches(feats, labels)
    seq_losses = []
    for _ in range(2):
        seq_losses.append(np.mean([seq.step(b) for b in batches]))
    fused_losses = fused.run_epochs_fused(feats, labels, epochs=2)
    np.testing.assert_allclose(fused_losses, seq_losses, rtol=2e-5, atol=1e-6)
    # params identical afterward
    for a, b in zip(seq.inner.params, fused.inner.params):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    # comm accounting carries the full 8-number vocabulary
    rep = fused.fused_stats_report()
    expected = sum(int(p.predicted_send_volume.sum())
                   for p in fused.plans) * 2 * 2 * 2  # ep × layers × fwd+bwd
    assert rep["total_send_volume"] == expected
