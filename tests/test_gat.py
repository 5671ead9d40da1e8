"""Distributed GAT vs dense single-device GAT oracle (SURVEY.md §4 strategy)."""

import numpy as np
import pytest

from sgcn_tpu.baselines.gat_oracle import DenseGATOracle
from sgcn_tpu.models.gat import init_gat_params
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.train import FullBatchTrainer, make_train_data

K = 4


@pytest.fixture(scope="module")
def setup(ahat):
    n = ahat.shape[0]
    rng = np.random.default_rng(7)
    partvec = balanced_random_partition(n, K, seed=3)
    plan = build_comm_plan(ahat, partvec, K)
    feats = rng.standard_normal((n, 12)).astype(np.float32)
    labels = (rng.integers(0, 4, n)).astype(np.int32)
    return plan, feats, labels


def test_gat_forward_parity(ahat, setup):
    plan, feats, labels = setup
    widths = [8, 4]
    tr = FullBatchTrainer(plan, fin=12, widths=widths, model="gat",
                          activation="none", final_activation="none", seed=5)
    oracle = DenseGATOracle(ahat, fin=12, widths=widths,
                            activation="none", final_activation="none", seed=5)
    data = make_train_data(plan, feats, labels)
    got = tr.predict(data)
    want = oracle.predict(feats)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_gat_training_parity(ahat, setup):
    plan, feats, labels = setup
    widths = [8, 4]
    tr = FullBatchTrainer(plan, fin=12, widths=widths, model="gat",
                          activation="none", lr=0.01, seed=5)
    oracle = DenseGATOracle(ahat, fin=12, widths=widths,
                            activation="none", lr=0.01, seed=5)
    data = make_train_data(plan, feats, labels)
    dist_losses = [tr.step(data) for _ in range(6)]
    oracle_losses = oracle.fit(feats, labels, epochs=6)
    np.testing.assert_allclose(dist_losses, oracle_losses, rtol=2e-3, atol=2e-4)
    assert dist_losses[-1] < dist_losses[0]


def test_gat_elu_variant_runs(ahat, setup):
    plan, feats, labels = setup
    tr = FullBatchTrainer(plan, fin=12, widths=[8, 4], model="gat",
                          activation="elu", seed=0)
    data = make_train_data(plan, feats, labels)
    losses = [tr.step(data) for _ in range(4)]
    assert np.isfinite(losses).all()


def test_gat_params_shapes():
    import jax
    params = init_gat_params(jax.random.PRNGKey(0), [(12, 8), (8, 4)])
    assert params[0]["w"].shape == (12, 8)
    assert params[0]["a1"].shape == (8,)
    assert params[1]["a2"].shape == (4,)


def test_edge_softmax_matches_dense():
    """The COO-edge-list softmax helper must equal a dense masked softmax."""
    import jax.numpy as jnp
    from sgcn_tpu.models.gat import edge_softmax
    rng = np.random.default_rng(5)
    n, deg = 12, 4
    dst = np.repeat(np.arange(n), deg).astype(np.int32)
    src = rng.integers(0, n, size=n * deg).astype(np.int32)
    scores = rng.standard_normal(n * deg).astype(np.float32)
    mask = rng.random(n * deg) < 0.8          # some padding edges
    alpha = np.asarray(edge_softmax(jnp.asarray(scores), jnp.asarray(mask),
                                    jnp.asarray(dst), n))
    dense = np.full((n, n * deg), -np.inf)
    dense[dst[mask], np.arange(n * deg)[mask]] = scores[mask]
    with np.errstate(invalid="ignore"):
        ref = np.exp(dense - dense.max(axis=1, keepdims=True))
        ref = np.nan_to_num(ref / np.maximum(ref.sum(axis=1, keepdims=True),
                                             1e-9))
    np.testing.assert_allclose(alpha, ref[dst, np.arange(n * deg)],
                               rtol=1e-5, atol=1e-6)


def test_gat_sym_backward_matches_autodiff(ahat):
    """The gather-only symmetric backward must produce the same gradients as
    JAX's mechanical transpose of the streaming forward."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from sgcn_tpu.models.gat import (GAT_PLAN_FIELDS, gat_layer_local,
                                     gat_layer_sym)
    from sgcn_tpu.parallel import make_mesh_1d, shard_stacked
    from sgcn_tpu.parallel.mesh import vary
    from sgcn_tpu.partition import balanced_random_partition

    n, k, fin, fout = ahat.shape[0], 4, 6, 5
    plan = build_comm_plan(ahat, balanced_random_partition(n, k, seed=3), k)
    plan.ensure_cell()
    assert plan.symmetric
    mesh = make_mesh_1d(k)
    rng = np.random.default_rng(2)
    h = rng.standard_normal((n, fin)).astype(np.float32)
    params = init_gat_params(jax.random.PRNGKey(1), [(fin, fout)])[0]
    hb = shard_stacked(mesh, plan.scatter_rows(h))
    pa = shard_stacked(mesh, {f: getattr(plan, f) for f in GAT_PLAN_FIELDS})

    def make(layer):
        def per_chip(pa, h):
            pa = jax.tree.map(lambda x: x[0], pa)

            def obj(w, a1, a2, hl):
                out = layer(w, a1, a2, hl, pa["send_idx"], pa["halo_src"],
                            pa["cell_idx"], pa["cell_w"], pa["ctail_dst"],
                            pa["ctail_src"], pa["ctail_w"],
                            pa["row_valid"], plan.cell_buckets, "v")
                # per-chip LOCAL objective: per-chip partial grads are
                # exactly the trainer's contract (fullbatch psums them)
                return jnp.sum(out * jnp.cos(out * 0.3))

            # as gat_forward_local does: the replicated params are cast to
            # varying first, so the custom VJP's per-chip PARTIAL cotangents
            # carry the primals' type (and autodiff's are partials too)
            pv = vary(params, "v")
            g = jax.grad(obj, argnums=(0, 1, 2, 3))(
                pv["w"], pv["a1"], pv["a2"], h[0])
            return jax.tree.map(lambda x: x[None], g)

        fn = jax.jit(jax.shard_map(per_chip, mesh=mesh,
                                   in_specs=(P("v"), P("v")),
                                   out_specs=P("v")))
        return fn(pa, hb)

    g_auto = make(gat_layer_local)
    g_sym = make(gat_layer_sym)
    # Param grads are per-chip PARTIALS on both paths (the trainer completes
    # them with its explicit psum); compare the chip-summed totals.
    for ga, gs, name in zip(g_auto[:3], g_sym[:3], ("w", "a1", "a2")):
        np.testing.assert_allclose(np.asarray(gs).sum(axis=0),
                                   np.asarray(ga).sum(axis=0),
                                   rtol=2e-4, atol=2e-5, err_msg=name)
    # dh is vertex-sharded (no replication), so it must match per chip
    np.testing.assert_allclose(np.asarray(g_sym[3]), np.asarray(g_auto[3]),
                               rtol=2e-4, atol=2e-5, err_msg="h")


def test_gat_bf16_packed_tracks_f32(ahat):
    """bf16 compute takes the bit-packed one-gather-per-edge aggregation;
    trajectory must track the f32 path within bf16 tolerance."""
    n = ahat.shape[0]
    rng = np.random.default_rng(6)
    feats = rng.standard_normal((n, 8)).astype(np.float32)
    labels = rng.integers(0, 3, size=n).astype(np.int32)
    pv = balanced_random_partition(n, 4, seed=5)
    plan = build_comm_plan(ahat, pv, 4)
    from sgcn_tpu.train import make_train_data
    data = make_train_data(plan, feats, labels)
    # widths even (packing pairs lanes); seed shared
    f32 = FullBatchTrainer(plan, fin=8, widths=[6, 3 + 1], seed=2,
                           model="gat", activation="none")
    b16 = FullBatchTrainer(plan, fin=8, widths=[6, 3 + 1], seed=2,
                           model="gat", activation="none",
                           compute_dtype="bfloat16")
    l32 = [f32.step(data) for _ in range(5)]
    l16 = [b16.step(data) for _ in range(5)]
    np.testing.assert_allclose(l16, l32, rtol=0.05, atol=0.03)
    assert l16[-1] < l16[0]
    # odd layer width: falls back to the two-pass form, which must keep the
    # exchange table in the compute dtype (not silently promote to f32)
    odd = FullBatchTrainer(plan, fin=8, widths=[6, 3], seed=2,
                           model="gat", activation="none",
                           compute_dtype="bfloat16")
    lo = [odd.step(data) for _ in range(3)]
    assert np.isfinite(lo).all() and lo[-1] < lo[0]
