"""R-GCN on the partitioned full-batch path (``models/rgcn.py``, PR 33): typed
rows, one relation per ordered pair of node types, per-node embeddings owned
with the rows.

  * (a) logits and EVERY gradient leaf (relation, root, bias, each embedding
    table) equal a dense per-relation oracle at k = 1 and on 4 virtual
    devices with a real partition, on a four-type graph with a hub type
    (its rows spill into the tail), an isolated node (empty neighbourhood ->
    0) and a training mask; the oracle computes every type at every layer,
    so the pruned program equals the unpruned model;
  * (b) two Adam steps follow the oracle's trajectory at k = 1 and 4;
  * (c) the row-owned leaves and their optimiser state are sharded over the
    mesh axis, take no all-reduce in the lowered step, and a halo copy's
    gradient reaches its owner;
  * (d) the typed aggregation's backward is the transposition of its
    forward (a custom VJP on the same slots);
  * (e) the published sizes give the published 154,366,772 parameters;
  * (f) a second relation on one type pair and every mode the model has no
    form for are refused loudly;
  * (g) save -> restore of the row-owned leaves at k = 4 resumes the
    trajectory, the file holding them in global row order;
  * (h) ``analysis``' census passes for ``train/rgcn/a2a/s0/f32``;
  * (i) the slot layouts, one per ordered pair of types with a relation
    (PR 34): every directed edge in exactly one, once, with both weights;
    padding on distinct in-bounds rows; a pass runs the relations live in
    it and ships nothing else;
  * (j) layer 0's means over data (the relations out of papers) are built
    once per ``h0`` outside the step: the step walks none of their layouts
    in layer 0's forward and equals the unhoisted program to float32
    rounding; a configuration with nothing to hoist, or the hoist switched
    off, lowers the step it lowered before; one build serves steps and
    ``predict()``, a new ``h0`` object rebuilds once, the old means gone
    first.

CPU, tiny graphs, one to four virtual devices.
"""

import dataclasses
import functools
import gc
import hashlib
import os
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import scipy.sparse as sp
from jax.sharding import PartitionSpec as P

from sgcn_tpu.models import rgcn
from sgcn_tpu.obs import tracing
from sgcn_tpu.ops.pspmm import typed_aggregate
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
from sgcn_tpu.parallel.mesh import AXIS
from sgcn_tpu.parallel.plan import padding_fanin, padding_fanin_bound
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

COUNTS = {"paper": 300, "author": 341, "inst": 7, "fos": 5}
NAMES = list(COUNTS)
TYPES = [{"name": n, "count": c,
          "input": "features" if n == "paper" else "embedding"}
         for n, c in COUNTS.items()]
RELS = [("author", "writes", "paper"), ("paper", "cites", "paper"),
        ("paper", "has_topic", "fos"), ("author", "affiliated_with", "inst"),
        ("paper", "rev_writes", "author"), ("fos", "rev_has_topic", "paper"),
        ("inst", "rev_affiliated_with", "author")]
# 653 rows over 4 chips leave padding rows (b · k = 656 > n)
N = sum(COUNTS.values())
FIN, HID, NCLS = 6, 5, 4
WIDTHS = [HID, NCLS]
MODEL = {"types": TYPES, "relations": RELS, "label_type": "paper",
         "hidden": HID, "layers": 2}
START = dict(zip(NAMES, np.concatenate([[0], np.cumsum(list(
    COUNTS.values()))[:-1]])))
ROWS = {n: slice(int(START[n]), int(START[n]) + COUNTS[n]) for n in NAMES}
ISOLATED = 5                    # a paper without an edge
RATE = 0.1          # one SGD step of this rate moves a parameter by -RATE·g
# float32 rounding: a gradient read back as (before - after) / RATE carries
# the subtraction's rounding of parameters of size ~0.4, 0.4 · 2^-24 / RATE
# = 2.4e-7, and the sums run in another order than the oracle's
ATOL = 2e-6


@pytest.fixture(scope="module")
def adjacency():
    rng = np.random.default_rng(0)

    def pairs(s, d, m):
        return (START[s] + rng.integers(0, COUNTS[s], m),
                START[d] + rng.integers(0, COUNTS[d], m))

    # five fields of study over 900 draws are hubs (~150 papers each), and
    # the first is the topic of EVERY paper: past the ELL's width cap of 64
    # on its own chip too, so the tail store runs at k = 1 and at k = 4
    everyone = (START["paper"] + np.arange(COUNTS["paper"]),
                np.full(COUNTS["paper"], START["fos"]))
    src, dst = (np.concatenate(x) for x in zip(
        pairs("author", "paper", 900), pairs("paper", "paper", 700),
        pairs("paper", "fos", 900), pairs("author", "inst", 330), everyone))
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
    return FullBatchTrainer(plan, fin=FIN, widths=list(WIDTHS),
                            mesh=make_mesh_1d(plan.k), seed=3, model="rgcn",
                            **kw)


def _data(tr, inputs):
    feats, labels, mask = inputs
    data = make_train_data(tr.plan, feats, labels, train_mask=mask)
    return TrainData(**shard_stacked(tr.mesh, vars(data)))


# ----------------------------------------------------------------- oracle
def oracle_logits(params, feats, adjacency):
    """The published forward, dense and unpruned: every type, every
    relation, both layers; a mean per relation as a 0/1 block of the
    adjacency over its row sums."""
    adj = jnp.asarray((adjacency.toarray() > 0)
                      & ~np.eye(N, dtype=bool), jnp.float32)
    x = jnp.concatenate([feats[ROWS["paper"]]]
                        + [params["emb"][n] for n in NAMES[1:]])
    for layer, p in enumerate(params["layers"]):
        out = []
        for t, name in enumerate(NAMES):
            h = x[ROWS[name]] @ p["root"][t] + p["bias"][t]
            for r, (s, _, d) in enumerate(RELS):
                if d != name:
                    continue
                block = adj[ROWS[d], ROWS[s]]
                deg = block.sum(1, keepdims=True)
                mean = jnp.where(deg > 0,
                                 block @ x[ROWS[s]] / jnp.maximum(deg, 1), 0)
                h = h + mean @ p["rel"][r]
            out.append(jax.nn.relu(h) if layer == 0 else h)
        x = jnp.concatenate(out)
    return x[ROWS["paper"]]


def oracle_loss(params, feats, labels, mask, adjacency):
    logp = jax.nn.log_softmax(oracle_logits(params, feats, adjacency))
    picked = jnp.take_along_axis(
        logp, jnp.asarray(labels[ROWS["paper"]])[:, None], 1)[:, 0]
    m = jnp.asarray(mask[ROWS["paper"]])
    return -(picked * m).sum() / m.sum()


_RUNS: dict = {}


def _run(plans, inputs, k):
    """One SGD trainer per k for every test that steps it: the parameters
    in global row order before and after one step, the logits, the loss."""
    if k not in _RUNS:
        tr = _trainer(plans[k], optimizer=optax.sgd(RATE))
        data = _data(tr, inputs)
        before, _ = tr.host_state()
        logits = tr.predict(data)
        text = tr.lower_step().as_text()
        loss = tr.step(data)
        after, _ = tr.host_state()
        _RUNS[k] = dict(tr=tr, before=before, after=after, logits=logits,
                        loss=loss, text=text)
    return _RUNS[k]


@pytest.fixture(scope="module")
def oracle(plans, inputs, adjacency):
    feats, labels, mask = inputs
    params = jax.tree.map(jnp.asarray, _run(plans, inputs, 1)["before"])
    with jax.default_matmul_precision("highest"):
        logits = oracle_logits(params, jnp.asarray(feats), adjacency)
        loss, grads = jax.value_and_grad(oracle_loss)(
            params, jnp.asarray(feats), labels, mask, adjacency)
    return dict(logits=np.asarray(logits), loss=float(loss),
                grads=jax.tree.map(np.asarray, grads))


# ------------------------------------------------------------------- (a)
def test_the_fixture_exercises_tail_halo_and_an_empty_neighbourhood(
        plans, adjacency):
    assert adjacency[ISOLATED].nnz == 0
    assert all(int(p.ltail_nnz.sum()) > 0 for p in plans.values())
    assert int(plans[4].hnnz.min()) > 0
    assert plans[4].b * 4 > N               # padding rows exist


@pytest.mark.parametrize("k", [1, 4])
def test_initial_parameters_do_not_depend_on_k(plans, inputs, k):
    one, mine = _run(plans, inputs, 1)["before"], _run(plans, inputs,
                                                       k)["before"]
    jax.tree.map(np.testing.assert_array_equal, one, mine)
    assert mine["emb"]["author"].shape == (COUNTS["author"], FIN)


@pytest.mark.parametrize("k", [1, 4])
def test_logits_equal_the_dense_oracle(plans, inputs, oracle, k):
    got = _run(plans, inputs, k)["logits"]
    np.testing.assert_allclose(got[ROWS["paper"]], oracle["logits"],
                               rtol=0, atol=ATOL)
    # rows of the other types have no logits: predict() reads 0 there
    assert not got[COUNTS["paper"]:].any()
    assert abs(_run(plans, inputs, k)["loss"] - oracle["loss"]) < ATOL


@pytest.mark.parametrize("k", [1, 4])
def test_every_gradient_equals_the_dense_oracle(plans, inputs, oracle, k):
    run = _run(plans, inputs, k)
    grads = jax.tree.map(lambda a, b: (a - b) / RATE, run["before"],
                         run["after"])
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want = dict(jax.tree_util.tree_flatten_with_path(oracle["grads"])[0])
    assert len(flat) == 3 + 2 * 3           # three tables, two layers
    for path, g in flat:
        np.testing.assert_allclose(g, want[path], rtol=0, atol=ATOL,
                                   err_msg=jax.tree_util.keystr(path))
        assert np.abs(want[path]).max() > 1e-4, jax.tree_util.keystr(path)
    # what the program left out keeps a zero gradient, as in the published
    # model: layer 2's weights of every relation that does not end in a
    # paper, and layer 1's affiliated_with (institutions reach no paper)
    into_paper = [r for r, (_, _, d) in enumerate(RELS) if d == "paper"]
    rel2 = grads["layers"][1]["rel"]
    assert not np.delete(rel2, into_paper, axis=0).any()
    assert not grads["layers"][0]["rel"][3].any()
    assert not oracle["grads"]["layers"][0]["rel"][3].any()


# ------------------------------------------------------------------- (b)
@pytest.mark.parametrize("k", [1, 4])
def test_two_adam_steps_follow_the_oracle(plans, inputs, adjacency, k):
    feats, labels, mask = inputs
    tr = _trainer(plans[k], lr=0.01)
    data = _data(tr, inputs)
    params = jax.tree.map(jnp.asarray, tr.host_state()[0])
    opt = optax.adam(0.01)
    state = opt.init(params)
    want = []
    with jax.default_matmul_precision("highest"):
        for _ in range(2):
            loss, grads = jax.value_and_grad(oracle_loss)(
                params, jnp.asarray(feats), labels, mask, adjacency)
            updates, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, updates)
            want.append(float(loss))
    got = [tr.step(data), tr.step(data)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # Adam's first steps divide a gradient by its own size: a leaf whose
    # gradient is rounding noise may step either way, so compare the leaves
    # that have one (1e-4: two steps of 0.01 on noise-free gradients)
    mine = tr.host_state()[0]
    for name in NAMES[1:]:
        np.testing.assert_allclose(mine["emb"][name], params["emb"][name],
                                   rtol=0, atol=1e-4)


# ------------------------------------------------------------------- (c)
def test_row_owned_leaves_and_their_state_are_sharded(plans, inputs):
    tr = _run(plans, inputs, 4)["tr"]
    for name in NAMES[1:]:
        leaf = tr.params["emb"][name]
        assert leaf.sharding.spec == P(AXIS)
        assert leaf.shape[0] == 4 and leaf.shape[2] == FIN
    assert tr.params["layers"][0]["rel"].sharding.spec == P()
    adam = _trainer(plans[4])
    mu = adam.opt_state["owned"][0].mu["emb"]["author"]
    assert mu.sharding.spec == P(AXIS)
    assert mu.shape == adam.params["emb"]["author"].shape
    assert adam.opt_state["shared"][0].mu["layers"][0]["rel"].sharding.spec \
        == P()
    # per-chip rows: every global row exactly once, padding rows apart
    rows = tr._row_owned["emb"]["author"]
    assert sorted(rows[rows >= 0]) == list(range(COUNTS["author"]))


def test_row_owned_gradients_take_no_all_reduce(plans, inputs):
    """The lowered step sums the replicated leaves' gradients (six) and the
    loss's two scalars; the three embedding tables' gradients stay where
    their rows are."""
    from sgcn_tpu.analysis.expect import XENT_SCALAR_PSUMS
    from sgcn_tpu.analysis.hlo import collective_ops

    for k in (1, 4):
        ops = [op for op in collective_ops(_run(plans, inputs, k)["text"])
               if op.kind == "all_reduce"]
        assert len(ops) == 6 + XENT_SCALAR_PSUMS, ops
        # none of them has a table's shape
        assert not [op for op in ops if len(op.wire[0]) == 2
                    and op.wire[0][-1] == FIN and op.wire[0][0] > HID]


def test_a_halo_copys_gradient_reaches_its_owner(plans, inputs, oracle):
    """An author all of whose papers live on other chips still gets the
    oracle's gradient: it can only have come through layer 0's backward
    exchange."""
    plan = plans[4]
    run = _run(plans, inputs, 4)
    grads = (run["before"]["emb"]["author"]
             - run["after"]["emb"]["author"]) / RATE
    remote_only = []
    ids = plan.global_row_ids()
    local_nbrs = {int(g): 0 for g in range(N)}
    for c in range(4):
        n = int(plan.lnnz[c])
        d = ids[c][plan.ledge_dst[c, :n]]
        s = ids[c][plan.ledge_src[c, :n]]
        for g in d[(s != d)]:
            local_nbrs[int(g)] += 1
    for a in range(COUNTS["author"]):
        g = int(START["author"]) + a
        if local_nbrs[g] == 0 and np.abs(
                oracle["grads"]["emb"]["author"][a]).max() > 1e-5:
            remote_only.append(a)
    assert remote_only, "the fixture has no author with remote papers only"
    np.testing.assert_allclose(
        grads[remote_only], oracle["grads"]["emb"]["author"][remote_only],
        rtol=0, atol=ATOL)


def test_commstats_books_the_backward_exchange_of_layer_0(plans, inputs):
    tr = _run(plans, inputs, 4)["tr"]
    # forward: a row's input; backward: the blocks its gradient types want
    # (layer 0: a paper's two, for authors and fields; layer 1: its three)
    assert tr.stats.lane_widths == (FIN, HID)
    assert tr.stats.lane_widths_bwd == (2 * FIN, 3 * HID)
    one = _run(plans, inputs, 1)["tr"]
    assert "all_to_all" not in _run(plans, inputs, 1)["text"]
    assert one.nlayers == 2


# ------------------------------------------------------------------- (d)
@pytest.mark.parametrize("k", [1, 4])
def test_the_custom_backward_is_the_forwards_transposition(plans, k):
    """<A x, y> = <x, Aᵀ y> for the typed aggregation of layer 0, every
    type given a gradient: the backward walks the forward's slots."""
    plan = plans[k]
    args = rgcn.resolve_args(FIN, WIDTHS, MODEL)
    layout = rgcn.build_typed_layout(plan, args)
    # every relation run (none hoisted), every type given a gradient
    spec = rgcn.layer_specs(args, layout, hoist=False)[0]._replace(
        grad=(0, 1, 2, 3))
    mesh = make_mesh_1d(k)
    arrays = shard_stacked(mesh, {
        "rels": layout["arrays"]["rels"],
        "send_rows": layout["arrays"]["send_rows"],
        "halo_src": plan.halo_src})
    rng = np.random.default_rng(2)
    ok = [np.asarray(r >= 0, np.float32)[..., None]
          for r in layout["table_rows"]]
    x = [rng.standard_normal((k, h, 3)).astype(np.float32) * m
         for h, m in zip(layout["heights"], ok)]
    y = [tuple(rng.standard_normal((k, layout["heights"][d], 3)).astype(
        np.float32) * ok[d] for _ in spec.sources[d]) for d in spec.dst]

    def per_chip(x, y, arrays):
        x, y, arrays = jax.tree.map(lambda a: a[0], (x, y, arrays))
        out, vjp = jax.vjp(
            lambda blocks: typed_aggregate(blocks, arrays, spec), tuple(x))
        (back,) = vjp(tuple(tuple(b) for b in y))
        lhs = sum(jnp.vdot(a, b) for a, b in zip(jax.tree.leaves(out),
                                                 jax.tree.leaves(y)))
        rhs = sum(jnp.vdot(a, b) for a, b in zip(x, back))
        return jax.lax.psum(jnp.stack([lhs, rhs]), AXIS)

    lhs, rhs = jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P(AXIS),) * 3, out_specs=P()))(
        shard_stacked(mesh, x), shard_stacked(mesh, y), arrays)
    assert abs(float(lhs)) > 1.0
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-5)


# ------------------------------------------------------------------- (e)
MAG_TYPES = [("paper", 736389, "features"), ("author", 1134649, "embedding"),
             ("institution", 8740, "embedding"),
             ("field_of_study", 59965, "embedding")]


def test_the_published_sizes_give_the_published_parameter_count():
    types = [{"name": n, "count": c, "input": i} for n, c, i in MAG_TYPES]
    rels = [(s.replace("inst", "institution").replace("fos",
                                                      "field_of_study"), n,
             d.replace("inst", "institution").replace("fos",
                                                      "field_of_study"))
            for s, n, d in RELS]
    args = rgcn.resolve_args(128, [64, 349], {
        "types": types, "relations": rels, "label_type": "paper",
        "hidden": 64, "layers": 2})
    assert rgcn.param_count(128, [64, 349], args["types"],
                            args["relations"]) == 154_366_772
    assert sum(c for _, c, i in MAG_TYPES if i == "embedding") * 128 \
        == 154_029_312
    # what a layer must compute: institutions reach no paper in two layers
    need = rgcn.reachable(4, args["relations"], args["label"], 2)
    assert need == [(0, 1, 2, 3), (0, 1, 3), (0,)]


def test_the_tree_counts_every_parameter_pruned_or_not(plans, inputs):
    tr = _run(plans, inputs, 1)["tr"]
    count = sum(x.size for x in jax.tree.leaves(tr.host_state()[0]))
    emb = sum(c for n, c in COUNTS.items() if n != "paper") * FIN
    assert count == emb + 11 * (FIN * HID + HID * NCLS) + 4 * (HID + NCLS)
    assert count == rgcn.param_count(
        FIN, WIDTHS, *(rgcn.resolve_args(FIN, WIDTHS, MODEL)[key]
                       for key in ("types", "relations")))


# ------------------------------------------------------------------- (f)
def test_a_second_relation_on_one_type_pair_is_refused(plans):
    twice = dict(MODEL, relations=RELS + [("author", "reviews", "paper")])
    with pytest.raises(ValueError, match="both run author -> paper"):
        _trainer(plans[1], model_args=twice)


@pytest.mark.parametrize("kw, match", [
    (dict(comm_schedule="ragged"), "dense a2a"),
    (dict(halo_staleness=1), "GCN hot path"),
    (dict(replica_budget=8), "GCN feature exchange"),
    (dict(halo_dtype="bfloat16"), "GCN-trainer lever"),
    (dict(compute_dtype="bfloat16"), "float32 only"),
    (dict(model_args=dict(MODEL, label_type="venue")), "label_type"),
    (dict(model_args=dict(MODEL, hidden=9)), "widths"),
    (dict(model_args=dict(MODEL, types=TYPES[:3])), "relation"),
    (dict(model_args=None), "model_args needs"),
])
def test_modes_the_model_has_no_form_for_are_refused(plans, kw, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        _trainer(plans[4], **kw)


def test_minibatch_serving_and_pallas_refuse_the_model(plans):
    from sgcn_tpu.train.fullbatch import MODELS, resolve_forward_setup

    assert MODELS["rgcn"].full_batch_only
    setup = resolve_forward_setup(plans[1], FIN, WIDTHS, model="rgcn",
                                  model_args=MODEL)
    assert "pallas_tb" not in setup.fwd_static
    with pytest.raises(ValueError, match="full forward only"):
        resolve_forward_setup(plans[1], FIN, WIDTHS, model="rgcn",
                              model_args=MODEL, serve_subgraph=True)
    asym = build_comm_plan(sp.csr_matrix(np.triu(np.ones((8, 8),
                                                         np.float32))),
                           np.zeros(8, np.int64), 1)
    with pytest.raises(ValueError, match="asymmetric"):
        resolve_forward_setup(asym, FIN, WIDTHS, model="rgcn",
                              model_args=MODEL)


# ------------------------------------------------------------------- (g)
def test_save_and_restore_of_row_owned_leaves_at_k4(plans, inputs, tmp_path):
    from sgcn_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    tr = _trainer(plans[4], lr=0.01)
    data = _data(tr, inputs)
    tr.step(data)
    path = save_checkpoint(tr, os.path.join(tmp_path, "ck"), step=1)
    want = [tr.step(data), tr.step(data)]
    # the file holds the tables in global row order, whatever k was
    with np.load(path) as saved:
        shapes = [saved[k].shape for k in saved.files
                  if k.startswith("leaf_")]
    assert (COUNTS["author"], FIN) in shapes
    assert tuple(tr.params["emb"]["author"].shape) not in shapes
    again = _trainer(plans[4], lr=0.01)
    assert load_checkpoint(again, path) == 1
    assert again.params["emb"]["author"].sharding.spec == P(AXIS)
    got = [again.step(_data(again, inputs)), again.step(_data(again, inputs))]
    np.testing.assert_array_equal(got, want)
    # ... and restores into another partition of the same graph
    other = _trainer(plans[1], lr=0.01)
    assert load_checkpoint(other, path, verify=False) == 1
    np.testing.assert_allclose(other.step(_data(other, inputs)), want[0],
                               rtol=1e-6)


# ------------------------------------------------------------------- (i)
PAIR_ARRAY = re.compile(r"rel_\d+_\d+_[eth]_\w+")   # rel_<s>_<d>_<array>


@pytest.fixture(scope="module")
def layouts(plans):
    args = rgcn.resolve_args(FIN, WIDTHS, MODEL)
    return {k: rgcn.build_typed_layout(plans[k], args) for k in plans}


def _slot_rows(stores, arrays, store):
    """Per slot of one store of a layout, the typed destination row: a
    width-major bucket's own rows (ELL), or its virtual rows' ``row``."""
    out, r0 = [], 0
    for n, w in stores:
        rows = (np.arange(r0, r0 + n) if store == "e"
                else arrays[f"{store}_row"][r0:r0 + n])
        out.append(np.tile(rows, w))
        r0 += n
    return np.concatenate(out) if out else np.zeros(0, np.int64)


def _layout_edges(plan, layout):
    """Every real slot of every layout as (destination, source, wf, wb) in
    global ids, and every padding slot's (index, table height) per store."""
    first = [int(START[n]) for n in NAMES]
    halo_ids = plan.halo_global_rows()
    edges, padding = [], []
    for (s, d), stores in layout["layouts"]:
        per = layout["arrays"]["rels"][s, d]
        for c in range(plan.k):
            for store, shapes in zip("eth", stores):
                arrays = {name: x[c] for name, x in per.items()}
                idx, wf, wb = (arrays[f"{store}_{n}"]
                               for n in ("idx", "wf", "wb"))
                assert sum(n * w for n, w in shapes) == len(idx)
                real = (wf != 0) | (wb != 0)
                rows = _slot_rows(shapes, arrays, store)[real]
                dst = first[d] + layout["table_rows"][d][c][rows]
                src = (halo_ids[c][idx[real]] if store == "h" else
                       first[s] + layout["table_rows"][s][c][idx[real]])
                assert (layout["table_rows"][d][c][rows] >= 0).all()
                edges.append(np.stack([dst, src, wf[real], wb[real]], 1))
                padding.append((idx[~real],
                                plan.r if store == "h"
                                else layout["heights"][s]))
    return np.concatenate(edges), padding


@pytest.mark.parametrize("k", [1, 4])
def test_every_edge_lies_in_one_layout_once_with_both_weights(
        plans, layouts, adjacency, k):
    got, _ = _layout_edges(plans[k], layouts[k])
    coo = adjacency.tocoo()
    type_of = np.searchsorted(list(START.values()), np.arange(N),
                              "right") - 1
    # a row's neighbours by type, and which ordered pairs are relations
    deg = np.zeros((N, len(NAMES)))
    np.add.at(deg, (coo.row, type_of[coo.col]), 1)
    is_rel = np.zeros((len(NAMES),) * 2, bool)
    for src, _, dst in RELS:
        is_rel[NAMES.index(src), NAMES.index(dst)] = True
    ts, td = type_of[coo.col], type_of[coo.row]
    held = is_rel[ts, td] | is_rel[td, ts]
    assert held.sum() == adjacency.nnz      # the fixture: every edge typed
    want = np.stack([
        coo.row, coo.col,
        is_rel[ts, td] / deg[coo.row, ts],       # the mean of s -> d at i
        is_rel[td, ts] / deg[coo.col, td]], 1)[held]   # of d -> s at j
    assert len(got) == len(want)                 # ... exactly once
    order = lambda e: e[np.lexsort((e[:, 1], e[:, 0]))]  # noqa: E731
    got, want = order(got), order(want)
    np.testing.assert_array_equal(got[:, :2], want[:, :2])
    np.testing.assert_allclose(got[:, 2:], want[:, 2:], rtol=1e-6)
    # one layout per ordered pair with a relation, here the seven
    pairs = [pair for pair, _ in layouts[k]["layouts"]]
    assert sorted(pairs) == sorted(
        (NAMES.index(src), NAMES.index(dst)) for src, _, dst in RELS)
    # a relation's edges, counted in its layout (k = 1: one chip has all)
    if k == 1:
        for src, name, dst in RELS:
            pair = (NAMES.index(src), NAMES.index(dst))
            assert layouts[1]["counts"][pair]["edges"] \
                == layouts[1]["edges"][name] > 0


@pytest.mark.parametrize("k", [1, 4])
def test_layout_padding_names_distinct_rows_in_bounds(plans, layouts, k):
    _, padding = _layout_edges(plans[k], layouts[k])
    assert sum(len(idx) for idx, _ in padding) > 0
    for idx, height in padding:
        assert idx.min(initial=0) >= 0 and idx.max(initial=0) < height
        assert padding_fanin(idx) <= padding_fanin_bound(len(idx), height)
    # a virtual row's destination is a row of the type, padding included
    for (s, d), _ in layouts[k]["layouts"]:
        for store in "th":
            rows = layouts[k]["arrays"]["rels"][s, d][f"{store}_row"]
            assert rows.min(initial=0) >= 0
            assert rows.max(initial=0) < layouts[k]["heights"][d]


@pytest.mark.parametrize("k", [1, 4])
def test_a_pass_runs_the_live_relations_and_ships_nothing_else(
        plans, layouts, k):
    from sgcn_tpu.train.fullbatch import resolve_forward_setup

    args = rgcn.resolve_args(FIN, WIDTHS, MODEL)
    specs = rgcn.layer_specs(args, layouts[k])
    rel = {name: r for r, (_, name, _) in enumerate(args["relations"])}
    pair = {name: (s, d) for s, name, d in args["relations"]}
    (fwd0, bwd0), (fwd1, bwd1) = rgcn.typed_passes(specs, args["relations"])
    # forward: a relation walks its own pair's slots at the mean's weight —
    # at layer 0 not those out of papers, whose means over data the step
    # reads (hoisted)
    data = ("cites", "has_topic", "rev_writes")
    assert sorted(specs[0].hoisted) == sorted(pair[n] for n in data)
    assert not specs[1].hoisted
    assert sorted(fwd0) == sorted(
        (rel[n], pair[n], "wf") for n in rel
        if n != "affiliated_with" and n not in data)
    assert sorted(fwd1) == sorted(
        (rel[n], pair[n], "wf") for n in ("cites", "writes", "rev_has_topic"))
    # backward: the REVERSE pair's slots, and only where the source's table
    # is trainable (layer 0: papers are data) and the forward ran
    assert sorted(bwd0) == sorted(
        (rel[n], pair[n][::-1], "wb")
        for n in ("writes", "rev_affiliated_with", "rev_has_topic"))
    assert sorted(bwd1) == sorted(
        (rel[n], pair[n][::-1], "wb")
        for n in ("cites", "writes", "rev_has_topic"))
    # what ships: the pairs some pass walks, with the weights it picks — the
    # authors' institution slots (pair institution -> author) run forward
    # for rev_affiliated_with and never backward for affiliated_with
    extra = resolve_forward_setup(plans[k], FIN, WIDTHS, model="rgcn",
                                  model_args=MODEL).custom.extra_arrays
    inst, author = NAMES.index("inst"), NAMES.index("author")
    assert f"rel_{inst}_{author}_e_wf" in extra
    assert f"rel_{inst}_{author}_e_wb" not in extra
    assert f"rel_{author}_{inst}_t_wb" in extra
    assert f"rel_{author}_{inst}_t_wf" not in extra
    shipped = rgcn.shipped_layouts(layouts[k], specs, args["relations"])
    assert {n for n in extra if PAIR_ARRAY.fullmatch(n)} == set(shipped)


# ------------------------------------------------------------------- (h)
def test_counters_scopes_and_memory(plans, inputs):
    tr = _trainer(plans[4])         # (the counter is the newest trainer's)
    work = tracing.counters()["rel.work"]
    assert set(work["relations"]) == {name for _, name, _ in RELS}
    # edges per relation: a relation and its reverse count the same pairs
    assert work["relations"]["writes"]["edges"] \
        == work["relations"]["rev_writes"]["edges"] > 0
    assert work["relations"]["cites"]["edges"] % 2 == 0
    assert [p["direction"] for p in work["passes"]] \
        == ["forward", "backward"] * 2
    assert work["passes"][0]["into"] == ["paper", "author", "fos"]
    assert work["passes"][1]["into"] == ["author", "inst", "fos"]
    assert work["passes"][2]["into"] == ["paper"]
    assert work["left_out"][0]["relations"] == ["affiliated_with"]
    assert len(work["left_out"][1]["relations"]) == 4
    # per pass: the relations run, those of its types left out as dead, and
    # per relation run the edges, slots, virtual rows, buckets + classes
    l0f, l0b, l1f, l1b = work["passes"]
    assert l1f["left_out"] == []
    # layer 0's forward leaves out the relations whose means it reads
    assert l0f["relations"] == ["writes", "rev_has_topic",
                                "rev_affiliated_with"]
    assert l0f["why"] == {"cites": "hoisted", "has_topic": "hoisted",
                          "rev_writes": "hoisted"}
    assert sorted(l0f["left_out"]) == sorted(l0f["why"])
    assert l0b["relations"] == ["writes", "rev_affiliated_with",
                                "rev_has_topic"]
    assert l0b["left_out"] == ["affiliated_with"]
    assert l0b["why"] == {"affiliated_with": "dead"}
    assert l1f["relations"] == ["cites", "writes", "rev_has_topic"]
    assert sorted(l1b["relations"]) == sorted(l1f["relations"])
    assert sorted(l1b["left_out"]) == ["affiliated_with", "has_topic",
                                       "rev_writes"]
    for p in work["passes"]:
        assert [r["relation"] for r in p["run"]] == p["relations"]
        assert p["slots"] == sum(r["slots"] for r in p["run"])
        assert p["edges"] == sum(r["edges"] for r in p["run"])
        for r in p["run"]:
            assert 0 < r["edges"] <= r["slots"]
            assert r["rows"] >= 0 and r["classes"] >= 1
    assert 0 < work["live_edges_per_step"] == sum(
        p["edges"] for p in work["passes"]) \
        <= work["executed_slots_per_step"] == sum(
            p["slots"] for p in work["passes"])
    owned = work["row_owned_bytes"]
    assert owned["optimizer_state"] == 2 * owned["parameters"] > 0
    hlo = tr.lower_step().as_text(debug_info=True)
    for sub in tracing.REL_SUBSCOPES + ("agg_slots", "agg_tail",
                                        "agg_halo_fold"):
        assert f"sgcn.{sub}" in hlo, sub
    assert not set(tracing.REL_SUBSCOPES) & set(
        tracing.SCOPES + tracing.SUBSCOPES + tracing.DEEP_SUBSCOPES)
    est = tr.model_memory
    assert est["total"] == sum(est[name] for name in (
        "row_owned", "rows_kept", "rows_transient", "slot_temps", "plan",
        "features", "hoisted", "params"))
    assert est["row_owned"] == owned["parameters"] * 3
    # the layout's line is the bytes of the relation arrays a chip is sent
    from sgcn_tpu.train.fullbatch import resolve_forward_setup
    extra = resolve_forward_setup(plans[4], FIN, WIDTHS, model="rgcn",
                                  model_args=MODEL).custom.extra_arrays
    assert est["plan"] == sum(x[0].nbytes for name, x in extra.items()
                              if PAIR_ARRAY.fullmatch(name)) > 0
    assert owned["parameters"] == sum(
        x.shape[1] for x in tr.params["emb"].values()) * FIN * 4
    # what one chip holds of the tree is what its step donates
    assert tr.memory.block()["families"]["params"]["model_bytes"] \
        == est["param_bytes"]
    assert tr.memory.block()["families"]


def test_the_census_mode_passes():
    from sgcn_tpu.analysis.hlo_audit import audit_mode
    from sgcn_tpu.analysis.modes import Mode, supported_modes

    mode = Mode("train", "rgcn", "a2a")
    assert mode.mode_id == "train/rgcn/a2a/s0/f32"
    assert mode in supported_modes()
    entry = audit_mode(mode)
    assert entry["ok"], entry
    # the census: the embedded type's table takes no all-reduce
    census = entry["programs"]["step"]["census"]
    assert census


# ------------------------------------------------------------------- (j)
# sha256 of ``lower_step().as_text()`` of the step as it lowered before
# layer 0's means over data were hoisted out of it: the configuration whose
# layer-0 sources are all embedded (nothing to hoist), and this file's
# configuration with the hoist switched off
UNHOISTED_STEP_SHA = {
    ("embedded", 1):
        "5bc94eede25030a67ee9a8b19d04b7ff31cbacadfdce70e93a76bad282617dd4",
    ("embedded", 4):
        "11f4d849adc0ea930b83e112568aeaec918696391d13ac1930beb08635dfc693",
    ("features", 1):
        "71d3841824b19cdede706ee4e9b5e9b45dcb13bb9d7f639ebd551bdd5b1a484c",
    ("features", 4):
        "59ea6515afe81328a9b660bc45d9263cefb7937072b0c5f89b8c282741a9eeb4",
}
NAME = re.compile(r'"([^"]*sgcn\.[^"]*)"')
PAIR = re.compile(r"sgcn\.(pair_\d+_\d+)")
LAYER = re.compile(r"sgcn\.layer(\d+)")
DATA = ("cites", "has_topic", "rev_writes")     # out of papers: data


def _unhoisted(monkeypatch):
    """Trainers built from here on run every relation in their step, as
    before the hoist (a test-local switch, not a user option)."""
    monkeypatch.setattr(rgcn, "layer_specs", functools.partial(
        rgcn.layer_specs, hoist=False))


def _walked(text: str) -> set:
    """``{(layer, "fwd" | "bwd", "pair_<s>_<d>")}`` over the op names of a
    lowered step with debug info."""
    found = set()
    for name in NAME.findall(text):
        pair, layer = PAIR.findall(name), LAYER.findall(name)
        if pair and layer:
            found.add((int(layer[-1]),
                       "bwd" if "transpose(" in name else "fwd", pair[-1]))
    return found


@pytest.mark.parametrize("k", [1, 4])
def test_the_step_walks_no_layout_of_a_hoisted_relation(plans, inputs, k):
    tr = _run(plans, inputs, k)["tr"]
    specs = tr._fwd_static["specs"]
    rels = rgcn.resolve_args(FIN, WIDTHS, MODEL)["relations"]
    pair = {name: (s, d) for s, name, d in rels}
    assert sorted(specs[0].hoisted) == sorted(pair[n] for n in DATA)
    want = {(layer, way, f"pair_{s}_{d}")
            for layer, both in enumerate(rgcn.typed_passes(specs, rels))
            for way, run in zip(("fwd", "bwd"), both) for _, (s, d), _ in run}
    found = _walked(tr.lower_step().as_text(debug_info=True))
    assert found == want
    # layer 0's forward walks writes, rev_has_topic, rev_affiliated_with
    assert {p for layer, way, p in found if (layer, way) == (0, "fwd")} \
        == {"pair_1_0", "pair_3_0", "pair_2_1"}
    for s, d in specs[0].hoisted:
        assert (0, "fwd", f"pair_{s}_{d}") not in found
    # cites (paper -> paper) still runs in layer 1: its input is not data
    assert (1, "fwd", f"pair_{pair['cites'][0]}_{pair['cites'][1]}") in found


@pytest.mark.parametrize("k", [1, 4])
def test_an_all_embedded_configuration_hoists_nothing(plans, k):
    embedded = dict(MODEL, types=[dict(t, input="embedding") for t in TYPES])
    tr = _trainer(plans[k], model_args=embedded)
    assert tr.hoist is None
    assert not any(s.hoisted for s in tr._fwd_static["specs"])
    assert not tracing.counters()["rel.work"]["hoisted"]["relations"]
    text = tr.lower_step().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == UNHOISTED_STEP_SHA["embedded", k]


@pytest.mark.parametrize("k", [1, 4])
def test_without_the_hoist_the_step_lowers_as_before(plans, k, monkeypatch):
    _unhoisted(monkeypatch)
    tr = _trainer(plans[k])
    assert tr.hoist is None
    assert hashlib.sha256(tr.lower_step().as_text().encode()).hexdigest() \
        == UNHOISTED_STEP_SHA["features", k]


def test_one_build_serves_steps_and_predict_and_a_new_h0_rebuilds(
        plans, inputs):
    tr = _trainer(plans[4])
    work = tracing.counters()["rel.work"]["hoisted"]
    layout = rgcn.build_typed_layout(plans[4], rgcn.resolve_args(
        FIN, WIDTHS, MODEL))
    pair = {name: (s, d) for s, name, d in rgcn.resolve_args(
        FIN, WIDTHS, MODEL)["relations"]}
    assert sorted(work["relations"]) == sorted(DATA)
    for name, got in work["relations"].items():
        counts = layout["counts"][pair[name]]
        assert {key: got[key] for key in ("edges", "slots", "rows")} == {
            key: counts[key] for key in ("edges", "slots", "rows")}
        assert got["bytes"] == layout["heights"][pair[name][1]] * FIN * 4
        assert 0 < got["edges"] <= got["slots"]
    assert (work["builds"], work["steps_served"]) == (0, 0)
    assert tr.model_memory["hoisted"] == sum(
        r["bytes"] for r in work["relations"].values())
    data = _data(tr, inputs)
    tracing.reset_spans()           # (spans of the trainers before this)
    client = jax.devices()[0].client
    gc.collect()        # executables other tests left for the collector
    before = len(client.live_executables())
    for _ in range(3):
        tr.step(data)
    tr.predict(data)
    # the step's and predict's executables stay loaded; the build's went
    assert len(client.live_executables()) == before + 2
    work = tracing.counters()["rel.work"]["hoisted"]
    assert (work["builds"], work["steps_served"]) == (1, 4)
    assert tracing.span_totals()["rel.hoist.build"]["count"] == 1
    assert "agg0.build" not in tracing.span_totals()
    assert not tracing.counters()["agg0"]["engaged"]
    # what is kept: one (k, height_d, f) array a hoisted pair
    made = tr._agg0
    assert [m.shape for m in made] == [
        (4, layout["heights"][d], FIN)
        for _, d in tr._fwd_static["specs"][0].hoisted]
    # the same features again, as another array: identity, not value — and
    # the old means are gone before the new ones are built
    old = [weakref.ref(m) for m in made]
    del made
    seen = []
    build = tr.hoist.build

    def watched(pa, h):
        seen.append(tr._agg0)
        return build(pa, h)

    tr.hoist = dataclasses.replace(tr.hoist, build=watched)
    again = _data(tr, inputs)
    tr.step(again)
    tr.step(again)
    assert seen == [None]
    gc.collect()
    assert all(ref() is None for ref in old)
    work = tracing.counters()["rel.work"]["hoisted"]
    assert (work["builds"], work["steps_served"]) == (2, 6)
    assert tracing.span_totals()["rel.hoist.build"]["count"] == 2


@pytest.mark.parametrize("k", [1, 4])
def test_the_hoisted_step_equals_the_unhoisted(plans, inputs, k,
                                               monkeypatch):
    """The means made at set-up are the step's own sums: losses of two SGD
    steps and the logits before and after equal the program that runs every
    relation in its step, to float32 rounding."""
    runs = []
    for hoist in (True, False):
        if not hoist:
            _unhoisted(monkeypatch)
        tr = _trainer(plans[k], optimizer=optax.sgd(RATE))
        assert (tr.hoist is not None) is hoist
        data = _data(tr, inputs)
        logits = tr.predict(data)
        losses = [tr.step(data), tr.step(data)]
        runs.append((logits, losses, tr.predict(data)))
    (l0, losses, l1), (w0, want, w1) = runs
    np.testing.assert_allclose(losses, want, rtol=1e-6)
    np.testing.assert_allclose(l0, w0, rtol=0, atol=1e-6)
    np.testing.assert_allclose(l1, w1, rtol=0, atol=1e-6)
    assert np.abs(w1 - w0).max() > 1e-3        # the steps moved the logits
