"""DeeperGCN on the partitioned full-batch path (``models/deepergcn.py``,
PR 31): GENConv softmax aggregation factorised per source, pre-activation
residual blocks, BatchNorm over the owned rows of every chip, the layers as
ONE scanned, per-layer-checkpointed body.

  * (a) loss, logits and EVERY gradient leaf equal the plain reference
    (``benchmark/reference/deepergcn_ref.py``) at k = 1 and on 4 virtual
    devices with a real partition, on a graph whose hub spills into the tail;
  * (b) the factorised aggregation is the per-destination softmax, and its
    gradient is the DETACHED one (``softmax_sg``), not the undetached;
  * (c) the statistics ignore padding rows: k = 1 equals k = 4;
  * (d) scanned + checkpointed equals a Python loop without checkpoints, for
    both values of ``keep``;
  * (e) the lowered step holds one aggregating body whatever the depth, its
    size does not grow with depth, and ``analysis``' census passes;
  * (f) the published widths give the published 253,743 parameters;
  * (g) every mode the model has no form for is refused loudly.

(The train CLI's smoke of the model is in ``tests/test_cli.py``.)

CPU, tiny graphs, one to four virtual devices.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import scipy.sparse as sp
from jax.sharding import PartitionSpec as P

from sgcn_tpu.io.datasets import dcsbm_graph
from sgcn_tpu.models import deepergcn
from sgcn_tpu.models.setup import check_memory
from sgcn_tpu.obs import tracing
from sgcn_tpu.ops.pspmm import pspmm_ell_sym, pspmm_ell_sym_detached
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
from sgcn_tpu.parallel.mesh import AXIS
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import manifest  # noqa: E402

ref = manifest.load_module(os.path.join(REPO, "benchmark", "reference",
                                        "deepergcn_ref.py"))

# 643 rows over 4 chips leave padding rows (b · k = 644 > n)
N, FIN, HID, LAYERS, NCLS = 643, 6, 8, 4, 5
WIDTHS = [HID] * LAYERS + [NCLS]
MODEL = {"layers": LAYERS, "hidden": HID, "t": 0.1, "eps": 1e-7}
RATE = 0.1          # one SGD step of this rate moves a parameter by -RATE·g


@pytest.fixture(scope="module")
def ahat():
    """Communities, power-law degrees, and a hub joined to every vertex: its
    row is past the ELL width cap, so the tail store runs."""
    a = sp.lil_matrix(dcsbm_graph(N, ncomm=4, avg_deg=5, seed=0))
    a[3, :] = 1.0
    a[:, 3] = 1.0
    return normalize_adjacency(sp.csr_matrix(a))


@pytest.fixture(scope="module")
def plans(ahat):
    return {k: build_comm_plan(
        ahat, np.zeros(N, np.int64) if k == 1
        else balanced_random_partition(N, k, seed=1), k) for k in (1, 4)}


@pytest.fixture(scope="module")
def inputs():
    rng = np.random.default_rng(0)
    return (rng.standard_normal((N, FIN)).astype(np.float32),
            rng.integers(0, NCLS, N).astype(np.int32))


@pytest.fixture(scope="module")
def edges(ahat):
    return ref.coo_chunks(ahat.indptr, ahat.indices, ahat.data, rows=128)


def _trainer(plan, widths=WIDTHS, args=None, **kw):
    return FullBatchTrainer(plan, fin=FIN, widths=list(widths),
                            mesh=make_mesh_1d(plan.k), seed=3,
                            model="deepergcn", model_args=args, **kw)


def _data(tr, feats, labels):
    data = make_train_data(tr.plan, feats, labels)
    return TrainData(**shard_stacked(tr.mesh, vars(data)))


def _host(tree):
    return jax.tree.map(np.asarray, tree)


_RUNS: dict = {}


def _run(plans, inputs, k, keep="aggregate"):
    """One trainer per (k, keep) for every test that steps it: the logits
    and parameters before, one SGD step, the parameters after."""
    if (k, keep) not in _RUNS:
        tr = _trainer(plans[k], args={**MODEL, "keep": keep},
                      optimizer=optax.sgd(RATE))
        data = _data(tr, *inputs)
        before, logits = _host(tr.params), tr.predict(data)
        loss = tr.step(data)
        _RUNS[k, keep] = dict(trainer=tr, data=data, before=before,
                              after=_host(tr.params), logits=logits,
                              loss=loss)
    return _RUNS[k, keep]


@pytest.fixture(scope="module")
def oracle(inputs, edges):
    """The reference's loss, logits and gradients at the seeded weights
    (the same at every k: weights come from the seed)."""
    feats, labels = inputs
    out = {}

    def at(params0):
        if "loss" not in out:
            with jax.default_matmul_precision("highest"):
                loss, grads = jax.value_and_grad(ref.loss_fn)(
                    ref._f32(params0), feats, labels, edges,
                    ref._static(MODEL))
            out.update(loss=float(loss), grads=_host(grads),
                       logits=ref.logits(params0, edges, feats, "highest",
                                         MODEL))
        return out

    return at


# ------------------------------------------------ (a) against the reference
@pytest.mark.parametrize("k", [1, 4])
def test_loss_logits_and_every_gradient_equal_the_reference(plans, inputs,
                                                            oracle, k):
    work = plans[k].work_counts()["true"]
    assert sum(work["slot_edges"]) and sum(work["tail_edges"])
    assert (k == 1) == (sum(work["halo_edges"]) == 0)
    run = _run(plans, inputs, k)
    want = oracle(run["before"])
    np.testing.assert_allclose(run["logits"], want["logits"], rtol=2e-5,
                               atol=2e-5)
    assert run["loss"] == pytest.approx(want["loss"], rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(run["before"])
    assert len(flat) == 12
    for (path, w0), w1, g in zip(flat, jax.tree.leaves(run["after"]),
                                 jax.tree.leaves(want["grads"])):
        np.testing.assert_allclose(
            (w0 - w1) / RATE, g, rtol=2e-4, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    # a bias before a norm has no gradient; every other leaf moved
    moved = {jax.tree_util.keystr(p): float(np.abs(g).max()) for (p, _), g
             in zip(flat, jax.tree.leaves(want["grads"]))}
    assert all(v > 1e-4 for name, v in moved.items()
               if name not in ("['conv0']['b']", "['layers']['b']")), moved


def test_reference_follows_the_trainer_through_adam_steps(plans, inputs,
                                                          edges):
    tr = _trainer(plans[4], args=MODEL, lr=0.01)
    data = _data(tr, *inputs)
    params0 = _host(tr.params)
    got = [tr.step(data) for _ in range(3)]
    want = ref.training_losses(params0, [(edges, *inputs)] * 3, 0.01, MODEL)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert want[2] < want[0] and ref.RTOL <= 1e-3
    mine = tr.predict(data)
    params = _host(tr.params)
    theirs = ref.logits(params, edges, inputs[0], "highest", MODEL)
    rms = float((theirs.astype("float64") ** 2).mean()) ** 0.5
    assert np.abs(mine - theirs).max() / rms < 1e-4
    # a table held in bfloat16 is another result, and the reference shows it
    narrow = ref.logits(params, edges, inputs[0], "highest", MODEL,
                        table_dtype="bfloat16")
    assert float(((narrow - theirs) ** 2).mean()) ** 0.5 / rms > 1e-4
    # the published settings only
    with pytest.raises(ValueError, match="not the published"):
        ref.logits(params, edges, inputs[0], "highest",
                   {**MODEL, "aggr": "softmax"})


# ---------------------------------------------------- (b) the aggregation
def _to_local(plan, x):
    out = np.zeros((plan.k, plan.b) + x.shape[1:], x.dtype)
    out[plan.owner, plan.local_idx] = x
    return out


def _aggregate_on_chips(tr, x, weights):
    """``(a, dL/dx)`` of ``L = sum(weights · softmax_aggregate(x))`` through
    the program's factorised aggregation, in global row order."""
    plan, st = tr.plan, tr._fwd_static

    def per_chip(pa, x, w):
        pa, x, w = jax.tree.map(lambda v: v[0], (pa, x, w))
        env = deepergcn.make_env(pa, st["ell_buckets"], st["fold_classes"],
                                 st["n_rows"], st["t"], st["eps"])
        a, pull = jax.vjp(lambda v: deepergcn.softmax_aggregate(v, env), x)
        return a[None], pull(w)[0][None]

    fn = jax.jit(jax.shard_map(per_chip, mesh=tr.mesh,
                               in_specs=(P(AXIS),) * 3,
                               out_specs=(P(AXIS),) * 2))
    put = shard_stacked(tr.mesh, {"x": _to_local(plan, x),
                                  "w": _to_local(plan, weights)})
    a, dx = (np.asarray(v)[plan.owner, plan.local_idx]
             for v in fn(tr.pa, put["x"], put["w"]))
    return a, dx


def _dense_softmax_aggregate(x, pattern, t, eps, detach):
    """Per destination and channel over its neighbours, as published;
    ``detach`` is ``softmax_sg``."""
    m = jax.nn.relu(x) + eps
    score = jnp.where(pattern[:, :, None], t * m[None, :, :], -jnp.inf)
    w = jax.nn.softmax(score, axis=1)
    if detach:
        w = jax.lax.stop_gradient(w)
    return (w * m[None, :, :]).sum(axis=1)


@pytest.mark.parametrize("k", [1, 4])
def test_factorised_aggregation_is_the_detached_per_destination_softmax(
        plans, inputs, ahat, edges, k):
    tr = _run(plans, inputs, k)["trainer"]
    rng = np.random.default_rng(5)
    # wide-ranging inputs: the stabiliser has something to do
    x = (4.0 * rng.standard_normal((N, HID))).astype(np.float32)
    weights = rng.standard_normal((N, HID)).astype(np.float32)
    a, dx = _aggregate_on_chips(tr, x, weights)
    pattern = jnp.asarray(ahat.toarray() != 0)
    for detach in (True, False):
        want, pull = jax.vjp(lambda v: _dense_softmax_aggregate(
            v, pattern, MODEL["t"], MODEL["eps"], detach), jnp.asarray(x))
        np.testing.assert_allclose(a, want, rtol=1e-5, atol=1e-6)
        grad = np.asarray(pull(jnp.asarray(weights))[0])
        if detach:
            np.testing.assert_allclose(dx, grad, rtol=1e-4, atol=1e-6)
        else:           # the softmax's own derivative is NOT in the program's
            assert np.abs(dx - grad).max() > 1e-3 * np.abs(grad).max()
    # and the reference's blocked form is the same function
    theirs = ref.softmax_aggregate(jax.nn.relu(x) + MODEL["eps"], edges,
                                   MODEL["t"])
    np.testing.assert_allclose(a, theirs, rtol=1e-5, atol=1e-6)


def test_detached_op_aggregates_all_lanes_forward_and_the_first_backward(
        plans):
    plan = plans[4]
    tr = _trainer(plan, args=MODEL)
    st = tr._fwd_static
    rng = np.random.default_rng(2)
    table = rng.standard_normal((plan.k, plan.b, 2 * HID)).astype(np.float32)
    ct = rng.standard_normal((plan.k, plan.b, 2 * HID)).astype(np.float32)

    def per_chip(pa, x, g):
        pa, x, g = jax.tree.map(lambda v: v[0], (pa, x, g))
        arrays = [pa[f] for f in deepergcn.DEEPERGCN_PLAN_FIELDS[:-1]]
        statics = (st["ell_buckets"], *st["fold_classes"])
        out, pull = jax.vjp(lambda v: pspmm_ell_sym_detached(
            v, *arrays, *statics, HID), x)
        whole = pspmm_ell_sym(x, *arrays, *statics)
        half = pspmm_ell_sym(g[:, :HID], *arrays, *statics)
        return out[None], whole[None], pull(g)[0][None], half[None]

    fn = jax.jit(jax.shard_map(per_chip, mesh=tr.mesh,
                               in_specs=(P(AXIS),) * 3,
                               out_specs=(P(AXIS),) * 4))
    put = shard_stacked(tr.mesh, {"x": table, "g": ct})
    out, whole, back, half = map(np.asarray, fn(tr.pa, put["x"], put["g"]))
    np.testing.assert_array_equal(out, whole)
    np.testing.assert_array_equal(back[..., :HID], half)
    assert not back[..., HID:].any()


# ------------------------------------------------- (c) padding rows, k = 1 / 4
def test_statistics_ignore_padding_rows(plans, inputs):
    assert plans[4].b * 4 > N and plans[1].b == N      # k = 4 pads, k = 1 not
    assert plans[4].row_valid.sum() == N
    one, four = _run(plans, inputs, 1), _run(plans, inputs, 4)
    np.testing.assert_allclose(four["logits"], one["logits"], rtol=2e-5,
                               atol=2e-5)
    assert four["loss"] == pytest.approx(one["loss"], rel=1e-5)
    for a, b in zip(jax.tree.leaves(one["after"]),
                    jax.tree.leaves(four["after"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-6)


# --------------------------------------- (d) scan + checkpoint = Python loop
def _loop_forward(params, h, pa, activation="relu", final_activation="none",
                  symmetric=True, ell_buckets=None, fold_classes=None,
                  layers=0, hidden=0, t=0.1, eps=1e-7, keep="", n_rows=0,
                  **_kw):
    """The same layers, one after another, no scan, no checkpoint."""
    env = deepergcn.make_env(pa, ell_buckets, fold_classes, n_rows, t, eps)
    h = deepergcn.first_layer(params, h, env)
    for i in range(layers - 1):
        h = deepergcn.res_layer(
            h, jax.tree.map(lambda x, i=i: x[i], params["layers"]), env)
    return deepergcn.head(h, params["head"], env)


@pytest.mark.parametrize("keep", ["aggregate", "input"])
def test_scanned_and_checkpointed_equals_an_unchecked_python_loop(
        plans, inputs, keep):
    run = _run(plans, inputs, 4, keep)
    plain = _trainer(plans[4], args={**MODEL, "keep": keep},
                     optimizer=optax.sgd(RATE))
    plain._forward_fn = _loop_forward
    plain._step, plain._eval = plain._build_step(), plain._build_eval()
    # every layer on its own, forward and backward, nothing re-run
    assert plain.lower_step().as_text().count(
        "stablehlo.all_to_all") == 2 * LAYERS
    data = _data(plain, *inputs)
    np.testing.assert_allclose(plain.predict(data), run["logits"],
                               rtol=1e-6, atol=1e-6)
    assert plain.step(data) == pytest.approx(run["loss"], rel=1e-6)
    for a, b in zip(jax.tree.leaves(_host(plain.params)),
                    jax.tree.leaves(run["after"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# ------------------------------------------------------ (e) the lowered step
@pytest.mark.parametrize("keep,exchanges", [("aggregate", 4), ("input", 6)])
def test_lowered_step_holds_one_aggregating_body_whatever_the_depth(
        plans, keep, exchanges):
    sizes = {}
    for layers in (3, 14):
        tr = _trainer(plans[4], widths=[HID] * layers + [NCLS],
                      args={"keep": keep})
        text = tr.lower_step().as_text()
        # layer 0's block and ONE scanned body, forward and backward (and
        # the forward again where the checkpoint keeps the input alone)
        assert text.count("stablehlo.all_to_all") == exchanges, layers
        sizes[layers] = len(text)
        assert tr.nlayers == layers and not tr.agg0_hoisted
    assert sizes[14] < 1.05 * sizes[3], sizes
    # the wire is booked at each direction's own lanes, per layer
    rows = int(tr.stats.send_volume_per_exchange.sum())
    assert tr.stats.report()["halo_bytes_true_per_step"] \
        == rows * 4 * 14 * (2 * HID + HID)
    work = tracing.counters()["deep.work"]
    assert work["keep"] == keep and work["layers"] == 14
    assert work["lanes"] == {"forward": 2 * HID, "backward": HID}
    assert work["agg_passes_per_step"]["recomputed"] == (
        14 if keep == "input" else 0)
    assert work["rows_kept_bytes"] == tr.model_memory["rows_kept"]
    assert work["stat_collectives_per_step"] == {"psum": 56, "pmax": 14}


def test_collective_census_of_the_analysis_passes(plans):
    from sgcn_tpu.analysis.expect import train_expectation
    from sgcn_tpu.analysis.hlo_audit import check_program
    from sgcn_tpu.analysis.modes import Mode, is_supported, supported_modes

    mode = Mode("train", "deepergcn", "a2a")
    assert is_supported(mode)[0] and mode in supported_modes()
    for bad in (Mode("train", "deepergcn", "ragged"),
                Mode("serve", "deepergcn", "a2a"),
                Mode("train", "deepergcn", "a2a", staleness=1),
                Mode("train", "deepergcn", "a2a", pallas=True)):
        assert not is_supported(bad)[0]
    for keep in ("aggregate", "input"):
        tr = _trainer(plans[4], args={**MODEL, "keep": keep})
        exp = train_expectation(tr, mode)
        # two norms (the body's, the head's) of 2 + 2 sums; two stabilisers
        assert exp.stat_shapes == [(HID,)] * 8 and exp.max_psums == 2
        violations, census = check_program(tr.lower_step().as_text(), exp, 4)
        assert not violations, violations
        assert census["all_reduce"]["max"] == 2


def test_lowered_step_names_the_sub_scopes_inside_dense(plans):
    text = _trainer(plans[4], args=MODEL).lower_step().as_text(
        debug_info=True)
    for sub in tracing.DEEP_SUBSCOPES:
        assert f"sgcn.dense/sgcn.{sub}" in text, sub
    for token in ("sgcn.layer0", "sgcn.layer1", "sgcn.agg_slots",
                  "sgcn.agg_tail", "sgcn.agg_halo_fold",
                  "rematted_computation"):
        assert token in text, token
    assert "sgcn.layer2" not in text        # the scan is one token
    assert not set(tracing.DEEP_SUBSCOPES) & set(
        tracing.SCOPES + tracing.SUBSCOPES)
    with pytest.raises(ValueError, match="outside a leaf scope"):
        tracing.subscope("norm")
    with tracing.scope("dense"), tracing.subscope("softmax_table"):
        pass


# ------------------------------------------------------------ (f) the counts
def test_published_widths_give_the_published_parameter_count():
    assert deepergcn.param_count(100, 128, 14, 47) == 253743
    assert (100 * 128 + 128, 14 * (128 * 128 + 128), 14 * 256,
            128 * 47 + 47) == (12928, 231168, 3584, 6063)
    widths = [128] * 14 + [47]
    dims = list(zip([100] + widths[:-1], widths))
    params = deepergcn.init_deepergcn_params(jax.random.PRNGKey(0), dims,
                                             layers=14, hidden=128)
    assert sum(x.size for x in jax.tree.leaves(params)) == 253743
    assert params["layers"]["w"].shape == (13, 128, 128)
    # torch's Linear: weight AND bias inside 1 / sqrt(fan_in)
    for name, fan_in in (("enc", 100), ("conv0", 128), ("head", 128)):
        for leaf in ("w", "b"):
            x = np.asarray(params[name][leaf])
            assert 0.9 / np.sqrt(fan_in) < np.abs(x).max() <= 1 / np.sqrt(
                fan_in)
    assert np.all(np.asarray(params["head"]["gamma"]) == 1)
    assert not np.asarray(params["layers"]["beta"]).any()


def test_memory_estimate_is_itemised_by_what_the_checkpoints_keep(plans):
    est = {keep: _trainer(plans[1], args={**MODEL, "keep": keep}).model_memory
           for keep in ("aggregate", "input")}
    row = plans[1].b * 4 * HID
    assert est["aggregate"]["rows_kept"] == (3 * LAYERS + 2) * row
    assert est["input"]["rows_kept"] == (LAYERS + 2) * row
    for parts in est.values():
        assert parts["total"] == sum(v for k, v in parts.items()
                                     if k != "total")
        assert {"rows_kept", "rows_transient", "slot_temps"} <= set(parts)

    class Small:
        def memory_stats(self):
            return {"bytes_limit": est["aggregate"]["total"]}

    with pytest.raises(RuntimeError, match="shard over more chips"):
        check_memory(Small(), est["aggregate"])


# ------------------------------------------------------------ (g) refusals
@pytest.mark.parametrize("kw,match", [
    ({"comm_schedule": "ragged"}, "dense a2a"),
    ({"halo_staleness": 1}, "GCN hot path"),
    ({"replica_budget": 8}, "GCN feature"),
    ({"compute_dtype": "bfloat16"}, "float32 only"),
    ({"halo_dtype": "bfloat16"}, "GCN-trainer lever"),
    ({"remat": True}, "checkpoints each of its layers itself"),
    ({"activation": "elu"}, "its equations'"),
])
def test_modes_that_refuse_the_model_say_so(plans, inputs, kw, match):
    with pytest.raises(ValueError, match=match):
        tr = _trainer(plans[4], args=MODEL, **kw)
        tr.predict(_data(tr, *inputs))      # the forward's own refusals


def test_minibatch_serving_and_asymmetric_plans_refuse_the_model(ahat):
    from sgcn_tpu.serve.engine import ServeEngine
    from sgcn_tpu.train.minibatch import MiniBatchTrainer

    pv = balanced_random_partition(N, 4, seed=1)
    with pytest.raises(ValueError, match="full-batch model"):
        MiniBatchTrainer(ahat, pv, 4, fin=FIN, widths=WIDTHS, batch_size=100,
                         model="deepergcn")
    plan = build_comm_plan(ahat, pv, 4)
    with pytest.raises(ValueError, match="not served yet"):
        ServeEngine(plan, FIN, WIDTHS, model="deepergcn")
    with pytest.raises(ValueError, match="symmetric edge"):
        _trainer(build_comm_plan(sp.triu(ahat).tocsr(), pv, 4))


@pytest.mark.parametrize("args,match", [
    ({"layers": 3}, "are not 3 layers of 8"),
    ({"hidden": 16}, "are not 4 layers of 16"),
    ({"aggr": "softmax"}, "aggr='softmax' has no form here"),
    ({"norm": "layer"}, "norm='layer' has no form here"),
    ({"block": "res"}, "block='res' has no form here"),
    ({"mlp_layers": 2}, "mlp_layers=2 has no form here"),
    ({"keep": "nothing"}, "keep='nothing' is not one of"),
    ({"t": 0.0}, "must be positive"),
    ({"dropout": 0.5}, "unknown model_args"),
])
def test_the_configuration_is_validated(args, match):
    with pytest.raises(ValueError, match=match):
        deepergcn.resolve_args(WIDTHS, args)
