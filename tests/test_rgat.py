"""R-GAT on the partitioned full-batch path (``models/rgat.py``, PR 39):
attention inside rgcn's typed layouts, with mhgat's slot bodies.

  * (a) logits, loss and EVERY gradient leaf (the five relations' ``w``,
    ``att_src``, ``att_dst``, ``b``; the skip; both BatchNorms; the head)
    equal the plain reference ``benchmark/reference/rgat_ref.py`` at k = 1
    and on 4 virtual devices with a real partition — where halo rows'
    cotangents reach their owners through the backward exchange;
  * (b) the fixture's edge cases: a hub institution whose fan-in runs past
    the widest ELL bucket into virtual rows, rows with an empty
    neighbourhood, BatchNorm over papers ∪ authors at layer 1, and the bias
    rule (every ``b_r`` of a relation into the layer's targets on every
    target row, whatever its type) with BatchNorm taken out of both sides;
  * (c) the published sizes give the published 12,255,385 parameters,
    analytically, nothing allocated;
  * (d) every mode the model has no form for is refused loudly, and the
    homogeneous attention model builds no typed layout.

CPU, tiny graphs, one to four virtual devices.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import scipy.sparse as sp

from sgcn_tpu.models import rgat, rgcn
from sgcn_tpu.obs import tracing
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "rgat_ref", os.path.join(ROOT, "benchmark", "reference", "rgat_ref.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

COUNTS = {"paper": 300, "author": 341, "inst": 7}
NAMES = list(COUNTS)
RELS = [("author", "writes", "paper"), ("paper", "rev_writes", "author"),
        ("author", "affiliated_with", "inst"),
        ("inst", "rev_affiliated_with", "author"),
        ("paper", "cites", "paper")]
N = sum(COUNTS.values())        # 648 rows over 4 chips: padding rows
FIN, HID, HEADS, NCLS = 6, 8, 2, 4
WIDTHS = [HID, HID, NCLS]
MODEL = {"types": [{"name": n, "count": c, "input": "features"}
                   for n, c in COUNTS.items()],
         "relations": RELS, "label_type": "paper", "hidden": HID,
         "layers": 2, "heads": HEADS}
START = dict(zip(NAMES, np.concatenate([[0], np.cumsum(list(
    COUNTS.values()))[:-1]])))
ISOLATED = 5                    # a paper without an edge
HUB = int(START["inst"])        # every author's institution
RATE = 0.1          # one SGD step of this rate moves a parameter by -RATE·g
# float32 rounding of (before - after) / RATE for parameters of size ~0.5,
# and sums in another order than the reference's (tests/test_rgcn.py)
ATOL = 2e-6


@pytest.fixture(scope="module")
def adjacency():
    rng = np.random.default_rng(0)

    def pairs(s, d, m):
        return (START[s] + rng.integers(0, COUNTS[s], m),
                START[d] + rng.integers(0, COUNTS[d], m))

    # the first institution is EVERY author's: 341 slots on its row of the
    # (author -> inst) layout, past the ELL's width cap on every chip
    hub = (START["author"] + np.arange(COUNTS["author"]),
           np.full(COUNTS["author"], HUB))
    src, dst = (np.concatenate(x) for x in zip(
        pairs("author", "paper", 900), pairs("paper", "paper", 700),
        pairs("author", "inst", 120), hub))
    keep = (src != ISOLATED) & (dst != ISOLATED) & (src != dst)
    a = sp.coo_matrix((np.ones(keep.sum()), (src[keep], dst[keep])),
                      shape=(N, N)).tocsr()
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
    mask = np.zeros(N, np.float32)
    mask[:200] = 1.0                        # the first 200 papers train
    return (rng.standard_normal((N, FIN)).astype(np.float32),
            rng.integers(0, NCLS, N).astype(np.int32), mask)


def _trainer(plan, **kw):
    kw.setdefault("model_args", MODEL)
    kw.setdefault("widths", list(WIDTHS))
    return FullBatchTrainer(plan, fin=FIN, mesh=make_mesh_1d(plan.k), seed=3,
                            model="rgat", activation="elu", **kw)


def _data(tr, inputs):
    feats, labels, mask = inputs
    data = make_train_data(tr.plan, feats, labels, train_mask=mask)
    return TrainData(**shard_stacked(tr.mesh, vars(data)))


_RUNS: dict = {}


def _run(plans, inputs, k):
    """One SGD trainer per k: the parameters before and after one step, the
    logits, the loss, the ``ratt.work`` counter."""
    if k not in _RUNS:
        tr = _trainer(plans[k], optimizer=optax.sgd(RATE))
        work = tracing.counters()["ratt.work"]
        data = _data(tr, inputs)
        before, _ = tr.host_state()
        logits = tr.predict(data)
        loss = tr.step(data)
        after, _ = tr.host_state()
        _RUNS[k] = dict(tr=tr, before=before, after=after, logits=logits,
                        loss=float(loss), work=work)
    return _RUNS[k]


@pytest.fixture(scope="module")
def reference(plans, inputs, adjacency):
    feats, labels, mask = inputs
    ahat = normalize_adjacency(sp.csr_matrix(adjacency))
    edges = ref.coo_chunks(ahat.indptr, ahat.indices, ahat.data, rows=64,
                           model=MODEL)
    params = jax.tree.map(jnp.asarray, _run(plans, inputs, 1)["before"])
    lab, m = ref._labelled(MODEL, jnp.asarray(labels), jnp.asarray(mask))
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(params, jnp.asarray(feats), edges, MODEL)
        loss, grads = jax.value_and_grad(ref.loss_fn)(
            params, jnp.asarray(feats), lab, m, edges, MODEL, "elu")
    return dict(logits=np.asarray(logits), loss=float(loss), edges=edges,
                grads=jax.tree.map(np.asarray, grads))


# ------------------------------------------------------------------- (a)
@pytest.mark.parametrize("k", [1, 4])
def test_logits_and_loss_equal_the_reference(plans, inputs, reference, k):
    run = _run(plans, inputs, k)
    got = run["logits"][START["paper"]:START["paper"] + COUNTS["paper"]]
    want = reference["logits"]
    rms = float(np.sqrt((want ** 2).mean()))
    np.testing.assert_allclose(got, want, atol=2e-6 * rms, rtol=0)
    assert run["loss"] == pytest.approx(reference["loss"], rel=1e-6)
    # the same initial weights whatever k
    jax.tree.map(np.testing.assert_array_equal, run["before"],
                 _run(plans, inputs, 1)["before"])


@pytest.mark.parametrize("k", [1, 4])
def test_every_gradient_equals_the_reference(plans, inputs, reference, k):
    run = _run(plans, inputs, k)
    got = jax.tree.map(lambda a, b: (np.asarray(a) - np.asarray(b)) / RATE,
                       run["before"], run["after"])
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert {jax.tree_util.keystr(p) for p, _ in leaves} >= {
        f"['layers'][{i}]['{n}']" for i in (0, 1) for n in (
            "w", "att_src", "att_dst", "b", "skip_w", "skip_b", "bn_g",
            "bn_b")} | {f"['head']['{n}']" for n in (
                "w1", "b1", "bn_g", "bn_b", "w2", "b2")}
    want = jax.tree_util.tree_leaves(reference["grads"])
    for (path, g), w in zip(leaves, want):
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))
    # every live relation's weights move: both layers, all four relations
    # into papers and authors at layer 1, writes and cites at layer 2
    moved = [np.abs(got["layers"][i]["w"]).max(axis=(1, 2)) > 1e-6
             for i in (0, 1)]
    assert moved[0].tolist() == [True, True, False, True, True]
    assert moved[1].tolist() == [True, False, False, False, True]


def test_halo_cotangents_reach_their_owners_at_k4(plans, inputs):
    """At k = 4 every walked layout has halo-source slots on some chip, so
    a source row's gradient from a destination on another chip comes back
    through the backward exchange — the gradients above equal the
    reference's only if it does."""
    run = _run(plans, inputs, 4)
    spec = run["tr"]._fwd_static["spec"]
    assert spec.exchange
    rels = rgat_relations()
    live = {p for layer in spec.live for r in layer
            for p in ((rels[r][0], rels[r][2]), (rels[r][2], rels[r][0]))}
    layouts = dict(spec.layouts)
    assert all(layouts[p][2] for p in live)
    assert run["work"]["exchanges_per_step"] == 2 * sum(map(len, spec.live))


def rgat_relations():
    return rgat.resolve_args(FIN, WIDTHS, MODEL)["relations"]


# ------------------------------------------------------------------- (b)
def test_the_fixture_exercises_hub_virtual_rows_and_empty_rows(plans,
                                                               adjacency):
    assert adjacency[ISOLATED].nnz == 0
    args = rgat.resolve_args(FIN, WIDTHS, MODEL)
    layout = rgcn.build_typed_layout(plans[1], args)
    inst, author = NAMES.index("inst"), NAMES.index("author")
    buckets, tails, _ = dict(layout["layouts"])[author, inst]
    widest = max((w for _, w in buckets), default=0)
    # the hub's 341 authors run past the widest bucket, into virtual rows
    assert tails and COUNTS["author"] > widest
    # authors who wrote no paper: an empty rev_writes at layer 1
    assert (np.asarray(adjacency[START["author"]:START["inst"],
                                 :START["author"]].sum(1)) == 0).any()


def test_the_layers_targets_and_batchnorm_rows_are_the_samplers(plans):
    tr = _trainer(plans[1])
    spec = tr._fwd_static["spec"]
    paper, author = NAMES.index("paper"), NAMES.index("author")
    assert spec.dst == ((paper, author), (paper,))
    assert spec.rows == (COUNTS["paper"] + COUNTS["author"],
                         COUNTS["paper"], COUNTS["paper"])
    live = [[RELS[r][1] for r in layer] for layer in spec.live]
    assert live == [["writes", "rev_writes", "rev_affiliated_with", "cites"],
                    ["writes", "cites"]]
    work = tracing.counters()["ratt.work"]
    assert work["targets"] == [["paper", "author"], ["paper"]]
    assert len(work["relations"]) == 6
    assert work["per_step"]["virtual_row_slots"] > 0


def test_the_bias_rule_with_batchnorm_taken_out(plans, inputs, reference,
                                                monkeypatch):
    """BatchNorm subtracts every column's mean, so a bias added to every
    target row cannot be seen through it: with the normalisation the
    identity on both sides, distinct biases per relation, the logits still
    agree — every ``b_r`` of a relation into papers or authors is on the
    authors' rows and the papers' alike."""
    monkeypatch.setattr(rgat, "_norm", lambda h, *a, **kw: h)
    monkeypatch.setattr(ref, "_batch_norm", lambda h, g, b: h)
    feats = inputs[0]
    tr = _trainer(plans[1])
    params, _ = tr.host_state()
    for layer in params["layers"]:
        layer["b"] = (np.arange(layer["b"].size, dtype=np.float32)
                      .reshape(layer["b"].shape) % 7 - 3) / 10
    tr.params = tr._place(jax.tree.map(jnp.asarray, params))
    got = tr.predict(_data(tr, inputs))[:COUNTS["paper"]]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(jax.tree.map(jnp.asarray, params),
                                      jnp.asarray(feats),
                                      reference["edges"], MODEL))
    rms = float(np.sqrt((want ** 2).mean()))
    np.testing.assert_allclose(got, want, atol=2e-6 * rms, rtol=0)
    # and the rule matters: the relations' biases move the logits
    params["layers"][0]["b"] = params["layers"][0]["b"] * 0
    with jax.default_matmul_precision("highest"):
        other = np.asarray(ref.forward(jax.tree.map(jnp.asarray, params),
                                       jnp.asarray(feats),
                                       reference["edges"], MODEL))
    assert np.abs(other - want).max() > 1e-3 * rms


# ------------------------------------------------------------------- (c)
def test_the_published_sizes_give_the_published_parameter_count():
    assert rgat.param_count(768, 1024, 153, 5) == 12_255_385
    assert rgat.param_count(768, 1024, 153, 5) - rgat.param_count(
        768, 1024, 153, 5, layers=1) == 6_309_888


def test_the_trainers_tree_counts_what_param_count_says(plans):
    tr = _trainer(plans[1])
    got = sum(int(np.size(x)) for x in jax.tree.leaves(tr.params))
    assert got == rgat.param_count(FIN, HID, NCLS, len(RELS))


# ------------------------------------------------------------------- (d)
TWICE = dict(MODEL, relations=RELS + [("author", "reviews", "paper")])


@pytest.mark.parametrize("kw, match", [
    (dict(comm_schedule="ragged"), "dense a2a"),
    (dict(halo_staleness=1), "GCN hot path"),
    (dict(replica_budget=8), "GCN feature exchange"),
    (dict(halo_dtype="bfloat16"), "GCN-trainer lever"),
    (dict(compute_dtype="bfloat16"), "float32 only"),
    (dict(model_args=dict(MODEL, heads=3)), "do not divide"),
    (dict(model_args=dict(MODEL, slope=-0.1)), "monotone"),
    (dict(model_args=dict(MODEL, head={"hidden": 4})), "published"),
    (dict(model_args=dict(MODEL, types=[dict(t, input="embedding")
                                        for t in MODEL["types"]])),
     "embedded"),
    (dict(model_args=TWICE), "both run author -> paper"),
    (dict(widths=[HID]), "head's output"),
    (dict(model_args=None), "model_args needs"),
])
def test_modes_the_model_has_no_form_for_are_refused(plans, kw, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        _trainer(plans[4], **kw)


def test_minibatch_serving_and_pallas_refuse_the_model(plans):
    from sgcn_tpu.train.fullbatch import (model_takes_args,
                                          resolve_forward_setup)

    assert model_takes_args("rgat")
    setup = resolve_forward_setup(plans[1], FIN, WIDTHS, model="rgat",
                                  model_args=MODEL)
    assert not setup.custom.allow_pallas
    assert "pallas_tb" not in setup.fwd_static
    with pytest.raises(ValueError, match="full forward only"):
        resolve_forward_setup(plans[1], FIN, WIDTHS, model="rgat",
                              model_args=MODEL, serve_subgraph=True)
    asym = build_comm_plan(sp.csr_matrix(np.triu(np.ones((8, 8),
                                                         np.float32))),
                           np.zeros(8, np.int64), 1)
    with pytest.raises(ValueError, match="asymmetric"):
        resolve_forward_setup(asym, FIN, WIDTHS, model="rgat",
                              model_args=MODEL)


def test_the_homogeneous_attention_model_builds_no_typed_layout(
        plans, monkeypatch):
    """mhgat's host work is what it was: its hook never reaches the typed
    layout builder (typed layouts are built inside rgat's hook only)."""
    def refuse(*a, **kw):
        raise AssertionError("a typed layout was built for mhgat")

    monkeypatch.setattr(rgcn, "build_typed_layout", refuse)
    tr = FullBatchTrainer(plans[1], fin=FIN, widths=[8, 5], seed=3,
                          model="mhgat", mesh=make_mesh_1d(1),
                          model_args={"heads": (4, 2),
                                      "concat": (True, False)})
    assert "att.work" in tracing.counters() and tr.model == "mhgat"
    with pytest.raises(AssertionError, match="typed layout"):
        _trainer(plans[1])


def test_a_store_of_several_classes_scatters_one_class_at_a_time():
    """A class's destinations ascend, the concatenation of a store's
    classes need not: the sorted scatter that folds virtual rows into
    their destinations runs once a class (the sort flag is a promise the
    TPU's scatter holds the program to), and once for a store of one class
    — the homogeneous layer's, whose program is pinned."""
    from sgcn_tpu.models import mhgat

    def scatters(shapes, rows):
        nv = sum(n for n, _ in shapes)
        width = sum(n * w for n, w in shapes)
        st = mhgat.Store("agg_tail", shapes, jnp.zeros(width, jnp.int32),
                         jnp.ones(width, jnp.int8), jnp.asarray(rows))

        def run(table):
            with tracing.scope("layer", 0):
                return mhgat._all_stores(
                    (table,), (None,), (), (st,),
                    contrib=lambda tabs, i, w, _d: tabs[0][i],
                    init=lambda nb: jnp.zeros((nb, 2)),
                    slot_bytes=lambda nb: nb, rows=4)

        eqns = jax.make_jaxpr(run)(jnp.ones((3, 2))).jaxpr.eqns
        assert nv == len(rows)
        return [e for e in eqns if e.primitive.name == "scatter-add"]

    two = scatters(((2, 1), (2, 2)), [1, 3, 0, 2])     # unsorted as a whole
    assert len(two) == 2
    assert all(e.params["indices_are_sorted"] for e in two)
    assert len(scatters(((4, 1),), [0, 1, 2, 3])) == 1
