"""UniMP's graph transformer on the partitioned full-batch path
(``models/unimp.py``): dot-product attention over a row's slots, a gated
residual, LayerNorm, a label input.

  * (a) logits, loss and EVERY gradient leaf equal a dense-matrix reference
    at k = 1 and on 4 virtual devices with a real partition — where the
    exchange runs and a halo row's cotangent reaches its owner; every pass
    gathers one table a slot, and a layer whose K·C is a whole number of
    lane tiles walks its backward once (both forms against the reference);
  * (b) the diagonal is excluded (a row whose only entry is its own gives
    o = 0: the skip alone), the label input reads P's rows only and the loss
    skips them; the benchmark's plain reference agrees with the dense one;
  * (c) the published widths give the published 202,700 parameters;
  * (d) every mode the layer has no form for is refused loudly, and the
    label input's field leaves every other model's program as it was.

CPU, tiny graphs, one to four virtual devices.
"""

import collections
import importlib
import importlib.util
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import scipy.sparse as sp
from jax.core import jaxprs_in_params

from sgcn_tpu.io.datasets import dcsbm_graph
from sgcn_tpu.models import unimp
from sgcn_tpu.obs import tracing
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "unimp_ref", os.path.join(ROOT, "benchmark", "reference", "unimp_ref.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

N, FIN, HID, HEADS, NCLS = 643, 6, 8, 2, 5
WIDTHS = [HID, HID, NCLS]
MODEL = {"layers": 3, "hidden": HID, "heads": HEADS, "label_rate": 0.625,
         "label_seed": 4}
HUB, ALONE = 3, 7           # a row past the ELL width cap; a row with itself
RATE = 0.1          # one SGD step of this rate moves a parameter by -RATE·g
ATOL = 2e-6


@pytest.fixture(scope="module")
def adjacency():
    a = sp.lil_matrix(dcsbm_graph(N, ncomm=4, avg_deg=5, seed=0))
    a[HUB, :] = 1.0
    a[:, HUB] = 1.0
    a[ALONE, :] = 0.0
    a[:, ALONE] = 0.0
    a.setdiag(0.0)
    a = sp.csr_matrix(a)
    a.eliminate_zeros()
    return ((a + a.T) > 0).astype(np.float32)


@pytest.fixture(scope="module")
def plans(adjacency):
    ahat = normalize_adjacency(sp.csr_matrix(adjacency))
    return {k: build_comm_plan(
        ahat, np.zeros(N, np.int64) if k == 1
        else balanced_random_partition(N, k, seed=1), k) for k in (1, 4)}


@pytest.fixture(scope="module")
def inputs():
    rng = np.random.default_rng(1)
    return (rng.standard_normal((N, FIN)).astype(np.float32),
            rng.integers(0, NCLS, N).astype(np.int32))


def _trainer(plan, **kw):
    kw.setdefault("model_args", MODEL)
    kw.setdefault("widths", list(WIDTHS))
    return FullBatchTrainer(plan, fin=FIN, mesh=make_mesh_1d(plan.k), seed=3,
                            model="unimp", **kw)


def _data(tr, inputs):
    data = make_train_data(tr.plan, *inputs)
    return TrainData(**shard_stacked(tr.mesh, vars(data)))


_RUNS: dict = {}


@pytest.fixture(scope="module", autouse=True)
def _scanned_slots():
    """Every bucket wider than two slots as a ``lax.scan`` (the products
    form): the same sums in the same order, and a step that compiles in a
    fraction of the unrolled one's time on the CPU."""
    pspmm = importlib.import_module("sgcn_tpu.ops.pspmm")
    limit, pspmm._CONCURRENT_TEMP_LIMIT = pspmm._CONCURRENT_TEMP_LIMIT, 0
    yield
    pspmm._CONCURRENT_TEMP_LIMIT = limit


def _run(plans, inputs, k):
    """One SGD trainer per k: the parameters before and after one step, the
    logits, the loss, the ``xatt.work`` counter."""
    if k not in _RUNS:
        tr = _trainer(plans[k], optimizer=optax.sgd(RATE))
        work = tracing.counters()["xatt.work"]
        data = _data(tr, inputs)
        before = jax.tree.map(np.asarray, tr.params)
        logits = tr.predict(data)
        loss = tr.step(data)
        after = jax.tree.map(np.asarray, tr.params)
        _RUNS[k] = dict(tr=tr, before=before, after=after, logits=logits,
                        loss=float(loss), work=work)
    return _RUNS[k]


# ------------------------------------------------- the dense-matrix reference
def dense_forward(params, h0, labels, in_p, adj):
    """Every product of the module docstring over the dense (n, n) pattern
    ``adj`` (no diagonal)."""
    x = h0 + jnp.where(in_p[:, None], params["emb"][labels], 0.0)
    layers = params["layers"]
    for i, p in enumerate(layers):
        last = i == len(layers) - 1
        q, k, v = (x @ p[f"w_{t}"] + p[f"b_{t}"] for t in
                   ("query", "key", "value"))
        r = x @ p["w_skip"] + p["b_skip"]
        q, k, v = (t.reshape(N, HEADS, -1) for t in (q, k, v))
        e = jnp.einsum("ihc,jhc->hij", q, k) / np.sqrt(q.shape[-1])
        e = jnp.where(adj[None], e, -1e30)
        w = jnp.exp(e - e.max(axis=2, keepdims=True)) * adj[None]
        alpha = w / jnp.maximum(w.sum(axis=2, keepdims=True), 1e-30)
        o = jnp.einsum("hij,jhc->ihc", alpha, v)
        o = o.mean(axis=1) if last else o.reshape(N, -1)
        beta = jax.nn.sigmoid(jnp.concatenate([o, r, o - r], 1) @ p["w_beta"])
        y = beta * r + (1 - beta) * o
        if last:
            return y
        mu = y.mean(1, keepdims=True)
        var = ((y - mu) ** 2).mean(1, keepdims=True)
        x = jax.nn.relu((y - mu) / jnp.sqrt(var + 1e-5) * p["ln_scale"]
                        + p["ln_bias"])


def dense_loss(params, h0, labels, in_p, adj):
    logp = jax.nn.log_softmax(dense_forward(params, h0, labels, in_p, adj))
    picked = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    off = (~in_p).astype(jnp.float32)
    return -jnp.sum(picked * off) / off.sum()


@pytest.fixture(scope="module")
def reference(plans, inputs, adjacency):
    feats, labels = inputs
    adj = jnp.asarray(adjacency.toarray() > 0)
    in_p = jnp.asarray(unimp.label_rows(N, MODEL["label_rate"],
                                        MODEL["label_seed"]))
    params = jax.tree.map(jnp.asarray, _run(plans, inputs, 1)["before"])
    args = (params, jnp.asarray(feats), jnp.asarray(labels), in_p, adj)
    with jax.default_matmul_precision("highest"):
        logits = dense_forward(*args)
        loss, grads = jax.value_and_grad(dense_loss)(*args)
    return dict(logits=np.asarray(logits), loss=float(loss),
                grads=jax.tree.map(np.asarray, grads), in_p=np.asarray(in_p))


def path_is(path, name: str) -> bool:
    return getattr(path[-1], "key", None) == name


# ------------------------------------------------------------------- (a)
@pytest.mark.parametrize("k", [1, 4])
def test_logits_and_loss_equal_the_dense_reference(plans, inputs, reference,
                                                   k):
    run = _run(plans, inputs, k)
    want = reference["logits"]
    rms = float(np.sqrt((want ** 2).mean()))
    np.testing.assert_allclose(run["logits"], want, atol=2e-6 * rms, rtol=0)
    assert run["loss"] == pytest.approx(reference["loss"], rel=1e-6)
    jax.tree.map(np.testing.assert_array_equal, run["before"],
                 _run(plans, inputs, 1)["before"])


@pytest.mark.parametrize("k", [1, 4])
def test_every_gradient_equals_the_dense_reference(plans, inputs, reference,
                                                   k):
    run = _run(plans, inputs, k)
    got = jax.tree.map(lambda a, b: (a - b) / RATE, run["before"],
                       run["after"])
    leaves = jax.tree_util.tree_leaves_with_path(got)
    names = {jax.tree_util.keystr(p) for p, _ in leaves}
    assert "['emb']" in names and "['layers'][2]['w_beta']" in names
    assert "['layers'][1]['ln_scale']" in names
    want = jax.tree_util.tree_leaves(reference["grads"])
    for (path, g), w in zip(leaves, want):
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))
        # softmax is blind to a key's bias: q_i·b_k is the same for every j
        moved = np.abs(g).max() > 1e-6
        assert moved != path_is(path, 'b_key'), jax.tree_util.keystr(path)


STORES = ("sgcn.agg_slots", "sgcn.agg_tail", "sgcn.agg_halo_fold")


def slot_gathers(tr, monkeypatch) -> collections.Counter:
    """``{(pass tag, store scope): gather ops}`` of the traced training step
    over the slot bodies: a gather under ``sgcn.fold_rows`` reads a virtual
    row's destination-side rows, not a slot's source row, and is left out.
    A nested jaxpr's names are relative to the equation that holds it."""
    build = tr._build_step
    monkeypatch.setattr(tr, "_build_step", lambda mesh=None: SimpleNamespace(
        lower=build(mesh=mesh).trace))
    found = collections.Counter()

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            name = f"{prefix}/{eqn.source_info.name_stack}"
            tokens = name.split("/")
            tags = [t for t in tracing.XATT_PASSES if f"sgcn.{t}" in tokens]
            stores = [t[5:] for t in tokens if t in STORES]
            if (eqn.primitive.name == "gather" and tags
                    and "sgcn.fold_rows" not in tokens):
                found[tags[-1], stores[-1]] += 1
            for sub in jaxprs_in_params(eqn.params):
                walk(sub, name)

    walk(tr.lower_step().jaxpr.jaxpr, "")
    return found


@pytest.mark.parametrize("k", [1, 4])
def test_every_pass_gathers_one_table_a_slot(plans, inputs, monkeypatch, k):
    """Layers whose K·C is not a whole lane tile (8 and 10 here) keep the
    two backward walks, each gathering ONE table a slot: in every store the
    destination walk (``[k ‖ v]``), the source walk (``[q/√C ‖ g ‖ m, 1/D,
    c]``), the forward and the max pass hold as many slot gathers (two
    tables a slot would double them), and the counter says so."""
    run = _run(plans, inputs, k)
    found = slot_gathers(run["tr"], monkeypatch)
    want = {"agg_slots", "agg_tail"} | ({"agg_halo_fold"} if k > 1 else set())
    for tag in tracing.XATT_PASSES:
        assert {s for t, s in found if t == tag} == want, tag
    for store in want:
        assert (found["xatt_src", store] == found["xatt_dst", store]
                == found["xatt_fwd", store] == found["xatt_max", store] > 0)
    work = run["work"]
    assert work["fused_backward"] == [False] * len(WIDTHS)
    assert work["gathers_per_slot"] == {"max": 1, "fwd": 1, "dst": 1,
                                        "src": 1}
    assert work["passes_per_step"]["backward"] == 2 * len(WIDTHS)


ALIGNED = 256       # hidden: 2 heads of 128 lanes, every block on a lane tile


@pytest.mark.parametrize("k", [1, 4])
def test_the_backward_walks_each_store_once(plans, inputs, adjacency,
                                            monkeypatch, k):
    """Hidden layers whose K·C is a whole number of lane tiles walk their
    backward ONCE, gathering one row of ``[k ‖ q/√C ‖ g ‖ v ‖ m, 1/D, c]``
    a slot; the last layer (K·C = 10) keeps its two walks in the same step.
    Logits, loss and every gradient equal the dense reference (at k = 4
    through the fused walk's halo table), and the counter and the traced
    step agree on which layer walks how: under ``xatt_src`` each store
    holds as many slot gathers as the forward and the max pass, under
    ``xatt_dst`` only the last layer's."""
    tr = _trainer(plans[k], widths=[ALIGNED, ALIGNED, NCLS],
                  model_args=dict(MODEL, hidden=ALIGNED),
                  optimizer=optax.sgd(RATE))
    work = tracing.counters()["xatt.work"]
    assert work["fused_backward"] == [True, True, False]
    assert work["passes_per_step"]["backward"] == 1 + 1 + 2
    assert work["gathers_per_slot"] == {"max": 1, "fwd": 1, "bwd": 1,
                                        "dst": 1, "src": 1}
    assert [x.get("bwd") for x in work["lanes_per_pass"]] == [
        4 * ALIGNED + 3 * HEADS] * 2 + [None]
    # Σ lanes a true edge: the two-walk backward's, to the lane
    assert work["lanes_per_edge"] == sum(
        c + 2 * c + 2 * c + (2 * c + 3 * HEADS)
        for c in (ALIGNED, ALIGNED, HEADS * NCLS))
    data = _data(tr, inputs)
    before = jax.tree.map(np.asarray, tr.params)
    logits = tr.predict(data)
    loss = float(tr.step(data))
    got = jax.tree.map(lambda a, b: (a - b) / RATE, before,
                       jax.tree.map(np.asarray, tr.params))
    feats, labels = inputs
    args = (jax.tree.map(jnp.asarray, before), jnp.asarray(feats),
            jnp.asarray(labels),
            jnp.asarray(unimp.label_rows(N, MODEL["label_rate"],
                                         MODEL["label_seed"])),
            jnp.asarray(adjacency.toarray() > 0))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(dense_forward(*args))
        want_loss, grads = jax.value_and_grad(dense_loss)(*args)
    rms = float(np.sqrt((want ** 2).mean()))
    np.testing.assert_allclose(logits, want, atol=2e-6 * rms, rtol=0)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))
    found = slot_gathers(tr, monkeypatch)
    stores = {"agg_slots", "agg_tail"} | ({"agg_halo_fold"} if k > 1
                                          else set())
    for store in stores:
        # three layers' forward, max pass and source-role walk; the last
        # layer's destination walk alone
        assert (found["xatt_src", store] == found["xatt_fwd", store]
                == found["xatt_max", store]
                == 3 * found["xatt_dst", store] > 0)


def test_halo_cotangents_reach_their_owners_at_k4(plans, inputs):
    """At k = 4 every chip has halo-source slots, so ``∂k`` and ``∂v`` of a
    row aggregated on another chip come back through the backward
    exchange — the gradients above equal the reference's only if they do."""
    run = _run(plans, inputs, 4)
    assert run["tr"]._fwd_static["halo_shape"] is not None
    assert run["work"]["exchanges_per_step"] == 2 * len(WIDTHS)
    assert run["work"]["exchange_lanes"] == {
        "forward": [2 * HID, 2 * HID, 2 * HEADS * NCLS],
        "backward": [2 * HID + 3 * HEADS] * 2 + [2 * HEADS * NCLS
                                                 + 3 * HEADS]}


# ------------------------------------------------------------------- (b)
def test_the_diagonal_is_out_and_a_lone_row_reads_its_skip(plans, inputs,
                                                           adjacency):
    """The plan holds every row's self loop; the shipped masks do not.  A row
    whose only entry is its own has an empty N(i): o = 0, and its output is
    the gate's share of the skip, β r."""
    plan = plans[1]
    assert adjacency[ALONE].nnz == 0
    work = _run(plans, inputs, 1)["work"]
    # every row had its diagonal in the ELL slots or the tail
    assert work["masked_diagonal_slots"] == [N]
    assert work["true_edges_per_pass"] == [int(adjacency.nnz)]
    tr = _run(plans, inputs, 1)["tr"]
    ell = np.asarray(tr.pa["ell_w"])[0]
    idx = np.asarray(tr.pa["ell_idx"])[0]
    rows = unimp._slot_rows(plan.ell_buckets)
    assert not (ell[idx == rows] != 0).any()
    assert int((ell != 0).sum()) + int((np.asarray(
        tr.pa["vt_mask"]) != 0).sum()) == adjacency.nnz
    # the hub's row runs past the ELL buckets into the tail's virtual rows
    assert tr._fwd_static["tail_shape"] is not None


def test_the_label_input_reads_p_and_the_loss_skips_it(plans, inputs,
                                                       reference):
    """Labels of rows off P change nothing but the loss; a label of a row in
    P changes the logits; the loss is the mean over the rows off P."""
    run = _run(plans, inputs, 1)
    tr, in_p = run["tr"], reference["in_p"]
    assert 0.5 < in_p.mean() < 0.75
    p_rows = tr.plan.gather_rows(
        np.asarray(tr.pa[unimp.LABEL_INPUT])[..., None])
    assert np.array_equal(p_rows[:, 0].astype(bool), in_p)
    feats, labels = inputs
    params = jax.tree.map(jnp.asarray, run["before"])
    tr.params = tr._place(params)
    base = tr.predict(_data(tr, inputs))
    off, on = np.flatnonzero(~in_p)[0], np.flatnonzero(in_p)[0]
    for row, moves in ((off, False), (on, True)):
        other = labels.copy()
        other[row] = (other[row] + 1) % NCLS
        got = tr.predict(_data(tr, (feats, other)))
        assert (np.abs(got - base).max() > 1e-6) == moves, row
    # the loss: rows in P contribute nothing
    data = _data(tr, inputs)
    logp = jax.nn.log_softmax(jnp.asarray(base))
    picked = np.asarray(jnp.take_along_axis(
        logp, jnp.asarray(labels)[:, None], axis=1))[:, 0]
    want = -picked[~in_p].mean()
    got = float(tr._eval(tr.params, tr.pa, tr._agg0_for(data.h0),
                         data.labels, data.train_valid)[0])
    assert got == pytest.approx(want, rel=1e-6)


def test_the_benchmark_reference_agrees_with_the_dense_one(plans, inputs,
                                                           reference,
                                                           adjacency):
    feats, labels = inputs
    ahat = normalize_adjacency(sp.csr_matrix(adjacency))
    edges = ref.coo_chunks(ahat.indptr, ahat.indices, ahat.data, rows=64)
    params = _run(plans, inputs, 1)["before"]
    got = ref.logits(params, edges, jnp.asarray(feats), "highest", MODEL,
                     "relu", labels=jnp.asarray(labels))
    want = reference["logits"]
    rms = float(np.sqrt((want ** 2).mean()))
    np.testing.assert_allclose(got, want, atol=2e-6 * rms, rtol=0)
    assert np.array_equal(ref.label_rows(N, MODEL), reference["in_p"])
    narrow = ref.logits(params, edges, jnp.asarray(feats), "highest", MODEL,
                        "relu", table_dtype="bfloat16",
                        labels=jnp.asarray(labels))
    assert np.abs(narrow - want).max() > 1e-4 * rms
    losses = ref.training_losses(
        params, [(edges, jnp.asarray(feats), jnp.asarray(labels))], 0.01,
        MODEL)
    assert losses[0] == pytest.approx(reference["loss"], rel=1e-6)


# ------------------------------------------------------------------- (c)
def test_the_published_widths_give_the_published_parameter_count():
    assert unimp.param_count(100, [128, 128, 47], 4) == 202_700
    # the middle layer: q, k, v and skip 4 × 16,512, the gate 384, LayerNorm
    assert unimp.param_count(100, [128, 128, 47], 4) - unimp.param_count(
        100, [128, 47], 4) == 66_432 + 256
    # the label embedding 47 × 100 and the last layer, averaged over heads
    assert unimp.param_count(100, [128, 47], 4) == (
        4_700 + 52_096 + 256 + 3 * 24_252 + 6_063 + 141)


def test_the_trainers_tree_counts_what_param_count_says(plans, inputs):
    tr = _run(plans, inputs, 1)["tr"]
    got = sum(int(np.size(x)) for x in jax.tree.leaves(tr.params))
    assert got == unimp.param_count(FIN, WIDTHS, HEADS)


# ------------------------------------------------------------------- (d)
@pytest.mark.parametrize("kw, match", [
    (dict(comm_schedule="ragged"), "dense a2a"),
    (dict(halo_staleness=1), "GCN hot path"),
    (dict(replica_budget=8), "GCN feature exchange"),
    (dict(halo_dtype="bfloat16"), "GCN-trainer lever"),
    (dict(compute_dtype="bfloat16"), "float32 only"),
    (dict(model_args=dict(MODEL, heads=3)), "cannot concatenate"),
    (dict(model_args=dict(MODEL, layers=2)), "layers against"),
    (dict(model_args=dict(MODEL, hidden=16)), "hidden 16"),
    (dict(model_args=dict(MODEL, slope=0.2)), "unknown model_args"),
])
def test_modes_the_layer_has_no_form_for_are_refused(plans, kw, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        _trainer(plans[4], **kw)


def test_minibatch_serving_pallas_and_asymmetry_refuse_the_model(plans):
    from sgcn_tpu.train.minibatch import MiniBatchTrainer
    from sgcn_tpu.train.fullbatch import MODELS, resolve_forward_setup

    assert MODELS["unimp"].full_batch_only
    setup = resolve_forward_setup(plans[1], FIN, WIDTHS, model="unimp",
                                  model_args=MODEL)
    assert "pallas_tb" not in setup.fwd_static
    assert setup.custom.label_input == unimp.LABEL_INPUT
    with pytest.raises(ValueError, match="full forward only"):
        resolve_forward_setup(plans[1], FIN, WIDTHS, model="unimp",
                              model_args=MODEL, serve_subgraph=True)
    asym = build_comm_plan(sp.csr_matrix(np.triu(np.ones((8, 8),
                                                         np.float32))),
                           np.zeros(8, np.int64), 1)
    with pytest.raises(ValueError, match="asymmetric"):
        resolve_forward_setup(asym, FIN, WIDTHS, model="unimp",
                              model_args=MODEL)
    with pytest.raises(ValueError):
        MiniBatchTrainer(sp.eye(8, format="csr"), np.zeros(8, np.int64), 1,
                         fin=FIN, widths=WIDTHS, batch_size=4, model="unimp")
