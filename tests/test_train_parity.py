"""Distributed-vs-single-device training parity — the automated form of the
reference's accuracy-parity experiment (GPU/PGCN-Accuracy.py, README.md:110)
with the dense oracle in the DGL/gcn.py role."""

import numpy as np
import pytest

from sgcn_tpu.baselines import DenseOracle
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.train import FullBatchTrainer, make_train_data


def _dataset(ahat, f=6, c=3, seed=9):
    n = ahat.shape[0]
    rng = np.random.default_rng(seed)
    feats = rng.standard_normal((n, f)).astype(np.float32)
    labels = rng.integers(0, c, size=n).astype(np.int32)
    return feats, labels


@pytest.mark.parametrize("k", [2, 4])
def test_loss_parity_with_oracle(ahat, k):
    n = ahat.shape[0]
    feats, labels = _dataset(ahat)
    widths = [8, 3]
    pv = balanced_random_partition(n, k, seed=21)
    plan = build_comm_plan(ahat, pv, k)
    trainer = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths, seed=42)
    data = make_train_data(plan, feats, labels)
    oracle = DenseOracle(ahat, fin=feats.shape[1], widths=widths, seed=42)

    dist_losses = [trainer.step(data) for _ in range(6)]
    oracle_losses = oracle.fit(feats, labels, epochs=6)
    np.testing.assert_allclose(dist_losses, oracle_losses, rtol=2e-4, atol=1e-5)

    got = trainer.predict(data)
    expected = oracle.predict(feats)
    np.testing.assert_allclose(got, expected, rtol=2e-3, atol=2e-4)


def test_eval_and_accuracy(ahat):
    n = ahat.shape[0]
    feats, labels = _dataset(ahat)
    pv = balanced_random_partition(n, 4, seed=22)
    plan = build_comm_plan(ahat, pv, 4)
    trainer = FullBatchTrainer(plan, fin=feats.shape[1], widths=[8, 3], seed=1)
    mask = (np.arange(n) % 2 == 0).astype(np.float32)   # train/eval split
    data = make_train_data(plan, feats, labels, train_mask=mask,
                           eval_mask=1.0 - mask)
    for _ in range(3):
        trainer.step(data)
    loss, acc = trainer.evaluate(data)
    assert np.isfinite(loss)
    assert 0.0 <= acc <= 1.0


def test_fit_reports_reference_stats(ahat):
    n = ahat.shape[0]
    feats, labels = _dataset(ahat)
    pv = balanced_random_partition(n, 4, seed=23)
    plan = build_comm_plan(ahat, pv, 4)
    trainer = FullBatchTrainer(plan, fin=feats.shape[1], widths=[8, 3])
    data = make_train_data(plan, feats, labels)
    report = trainer.fit(data, epochs=2, warmup=1, verbose=False)
    # 3 steps × 2 layers × fwd+bwd exchanges
    assert trainer.stats.exchanges == 3 * 2 * 2
    expected_vol = plan.predicted_send_volume.sum() * trainer.stats.exchanges
    assert report["total_send_volume"] == expected_vol
    assert report["epochs"] == 2 and report["epoch_s"] > 0
    assert len(report["loss_history"]) == 2
    # loss should be decreasing on this easy overfit task
    assert report["loss_history"][-1] < report["loss_history"][0] * 1.5


def test_wide_input_project_first_parity(ahat):
    """Width-aware layer scheduling (project-then-aggregate for wide inputs)
    must match the oracle's fixed aggregate-first order — same math."""
    import numpy as np
    from sgcn_tpu.baselines import DenseOracle
    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.partition import balanced_random_partition
    from sgcn_tpu.train import FullBatchTrainer, make_train_data
    from sgcn_tpu.models.gcn import PROJECT_FIRST_MIN_FIN

    n = ahat.shape[0]
    fin = PROJECT_FIRST_MIN_FIN + 44     # forces the project-first branch
    rng = np.random.default_rng(11)
    feats = rng.standard_normal((n, fin)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    pv = balanced_random_partition(n, 4, seed=6)
    plan = build_comm_plan(ahat, pv, 4)
    tr = FullBatchTrainer(plan, fin=fin, widths=[8, 3], seed=3)
    oracle = DenseOracle(ahat, fin=fin, widths=[8, 3], seed=3)
    data = make_train_data(plan, feats, labels)
    np.testing.assert_allclose(tr.predict(data), oracle.predict(feats),
                               rtol=2e-3, atol=2e-4)
    dist = [tr.step(data) for _ in range(4)]
    orac = oracle.fit(feats, labels, epochs=4)
    np.testing.assert_allclose(dist, orac, rtol=2e-3, atol=2e-4)


def test_bf16_compute_tracks_f32(ahat):
    """Mixed-precision option: same trajectory within bf16 tolerance."""
    import numpy as np
    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.partition import balanced_random_partition
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    n = ahat.shape[0]
    rng = np.random.default_rng(4)
    feats = rng.standard_normal((n, 12)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    pv = balanced_random_partition(n, 4, seed=1)
    plan = build_comm_plan(ahat, pv, 4)
    data = make_train_data(plan, feats, labels)
    f32 = FullBatchTrainer(plan, fin=12, widths=[8, 3], seed=2)
    b16 = FullBatchTrainer(plan, fin=12, widths=[8, 3], seed=2,
                           compute_dtype="bfloat16")
    l32 = [f32.step(data) for _ in range(5)]
    l16 = [b16.step(data) for _ in range(5)]
    np.testing.assert_allclose(l16, l32, rtol=0.05, atol=0.02)
    assert l16[-1] < l16[0]


def test_run_epochs_matches_sequential_steps(ahat):
    """The on-device epoch loop (one dispatch, lax.fori_loop) must follow the
    exact trajectory of sequential step() calls — it exists purely to remove
    per-dispatch host latency from multi-epoch timing (bench protocol)."""
    n = ahat.shape[0]
    feats, labels = _dataset(ahat)
    pv = balanced_random_partition(n, 4, seed=13)
    plan = build_comm_plan(ahat, pv, 4)
    data = make_train_data(plan, feats, labels)
    seq = FullBatchTrainer(plan, fin=feats.shape[1], widths=[8, 3], seed=7)
    fused = FullBatchTrainer(plan, fin=feats.shape[1], widths=[8, 3], seed=7)
    seq_losses = [seq.step(data) for _ in range(5)]
    fused_losses = fused.run_epochs(data, 5)
    np.testing.assert_allclose(fused_losses, seq_losses, rtol=2e-5, atol=1e-6)
    # params identical afterward, and stats counted all 5 steps
    for a, b in zip(np.asarray(seq.params, dtype=object).ravel(),
                    np.asarray(fused.params, dtype=object).ravel()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    assert fused.stats.exchanges == seq.stats.exchanges


def test_remat_matches_plain(ahat):
    """jax.checkpoint rematerialization must not change the math."""
    import numpy as np
    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.partition import balanced_random_partition
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    n = ahat.shape[0]
    rng = np.random.default_rng(8)
    feats = rng.standard_normal((n, 10)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    pv = balanced_random_partition(n, 4, seed=2)
    plan = build_comm_plan(ahat, pv, 4)
    data = make_train_data(plan, feats, labels)
    plain = FullBatchTrainer(plan, fin=10, widths=[8, 8, 3], seed=4)
    rem = FullBatchTrainer(plan, fin=10, widths=[8, 8, 3], seed=4, remat=True)
    lp = [plain.step(data) for _ in range(4)]
    lr = [rem.step(data) for _ in range(4)]
    np.testing.assert_allclose(lr, lp, rtol=1e-5, atol=1e-6)


def test_sgd_step_sums_the_weight_gradients_once(ahat):
    """One SGD step at k = 4 moves every weight by −rate × the oracle's
    gradient: Adam hides a constant factor on the gradients, SGD does not
    (until PR 27 the step summed them twice and they arrived k-fold).  The
    lowered step holds exactly ONE all-reduce per weight matrix — the
    explicit ``psum`` under ``sgcn.grad_psum``."""
    import re

    import jax
    import optax

    n, k, rate, widths = ahat.shape[0], 4, 0.5, [8, 3]
    feats, labels = _dataset(ahat)
    plan = build_comm_plan(ahat, balanced_random_partition(n, k, seed=21), k)
    trainer = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths,
                               seed=42, optimizer=optax.sgd(rate))
    oracle = DenseOracle(ahat, fin=feats.shape[1], widths=widths, seed=42,
                         optimizer=optax.sgd(rate))
    before = jax.tree.map(np.asarray, trainer.params)
    data = make_train_data(plan, feats, labels)
    np.testing.assert_allclose(trainer.step(data),
                               oracle.step(feats, labels), rtol=1e-5)
    for w0, w1, want in zip(before, trainer.params, oracle.params):
        moved = np.asarray(w1) - w0
        assert np.abs(moved).max() > 1e-4           # a step worth comparing
        np.testing.assert_allclose(moved, np.asarray(want) - w0,
                                   rtol=2e-4, atol=1e-6)
    text = trainer.lower_step().as_text()
    # the operand type of each all-reduce follows its reduction body
    reduced = [re.search(r"\}\) : \(tensor<([^>]*)>\)", piece).group(1)
               for piece in text.split('"stablehlo.all_reduce"')[1:]]
    weights = ["x".join(str(d) for d in w.shape) + "xf32" for w in before]
    assert sorted(r for r in reduced if r != "f32") == sorted(weights), reduced
