"""Multi-head graph attention as published (``models/mhgat.py``, PR 27):
LeakyReLU scores that do not factorise, K heads, a softmax per destination
over the ELL slots, the hub tail and the halo edges together, bias, linear
skip, head mean — on the normal path (``build_comm_plan`` →
``resolve_forward_setup`` → ``FullBatchTrainer``).

  * (a) logits and EVERY parameter's gradient equal the dense oracle's
    (``baselines/gat_oracle.py::DenseMHGATOracle``) at k = 1, 4 and 8, on a
    graph whose hubs spill into the tail and whose partitions cut edges;
  * (b) the losses of six ``step()``s equal the oracle's ``fit``;
  * (c) heads = 1, slope = 1, no bias, no skip is the factorised layer
    (``gat_layer_sym``) to rounding;
  * (d) PR 26's hoist is off, and the lowered step holds one exchange per
    layer and pass — layer 0's backward included;
  * (e) the modes that refuse the model do so loudly;
  * (f) the configuration's counts, the sub-scopes, the memory estimate;
  * (g) the products by the 0/1 head matrix (PR 32): the forward slot's
    spread as ONE pass over exact bfloat16 splits, to the bit, and its two
    factors off one signed spread; the ``HIGHEST`` spread to the bit and
    the sum within 4 ulp of the f64 sum; and ``att.work["head_products"]``
    says what the lowered step holds.

CPU, tiny graphs, one to eight virtual devices.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import scipy.sparse as sp

from sgcn_tpu.baselines.gat_oracle import DenseMHGATOracle
from sgcn_tpu.io.datasets import dcsbm_graph
from sgcn_tpu.models import mhgat
from sgcn_tpu.models.setup import check_memory
from sgcn_tpu.obs import tracing
from sgcn_tpu.parallel import (build_comm_plan, make_mesh_1d, replicate,
                               shard_stacked)
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

N, FIN, WIDTHS = 640, 6, [8, 5]
ARGS = {"heads": (4, 2), "concat": (True, False)}
RATE = 0.1          # one SGD step of this rate moves a parameter by -RATE·g


@pytest.fixture(scope="module")
def ahat():
    """Communities, power-law degrees, and a hub joined to every vertex: at
    k = 8 it still has ~80 local neighbours, past the ELL width cap."""
    a = sp.lil_matrix(dcsbm_graph(N, ncomm=4, avg_deg=5, seed=0))
    a[3, :] = 1.0
    a[:, 3] = 1.0
    return normalize_adjacency(sp.csr_matrix(a))


@pytest.fixture(scope="module")
def plans(ahat):
    return {k: build_comm_plan(
        ahat, np.zeros(N, np.int64) if k == 1
        else balanced_random_partition(N, k, seed=1), k) for k in (1, 4, 8)}


@pytest.fixture(scope="module")
def inputs():
    rng = np.random.default_rng(0)
    return (rng.standard_normal((N, FIN)).astype(np.float32),
            rng.integers(0, WIDTHS[-1], N).astype(np.int32))


def _trainer(plan, widths=WIDTHS, args=ARGS, activation="elu", **kw):
    return FullBatchTrainer(plan, fin=FIN, widths=list(widths),
                            mesh=make_mesh_1d(plan.k), seed=3, model="mhgat",
                            model_args=args, activation=activation, **kw)


def _data(tr, feats, labels):
    data = make_train_data(tr.plan, feats, labels)
    return TrainData(**shard_stacked(tr.mesh, vars(data)))


def _host(tree):
    return jax.tree.map(np.asarray, tree)


_RUNS: dict = {}


def _run(plans, inputs, k):
    """One trainer per k for every test that steps it (a step compiles for
    most of a minute on the CPU): the logits and parameters before, six SGD
    steps, the parameters after the first."""
    if k not in _RUNS:
        tr = _trainer(plans[k], optimizer=optax.sgd(RATE))
        data = _data(tr, *inputs)
        before, logits = _host(tr.params), tr.predict(data)
        losses = [tr.step(data)]
        after = _host(tr.params)
        losses += [tr.step(data) for _ in range(5)]
        _RUNS[k] = dict(trainer=tr, data=data, before=before, after=after,
                        logits=logits, losses=losses)
    return _RUNS[k]


@pytest.fixture(scope="module")
def oracle(ahat, inputs):
    orc = DenseMHGATOracle(ahat, FIN, WIDTHS, seed=3, model_args=ARGS,
                           optimizer=optax.sgd(RATE))
    out = dict(params=_host(orc.params), logits=orc.predict(inputs[0]))
    out["loss"], grads = orc.grads(*inputs)
    out["grads"] = _host(grads)
    out["losses"] = orc.fit(*inputs, epochs=6)
    return out


# ------------------------------------------------ (a) logits and gradients
@pytest.mark.parametrize("k", [1, 4, 8])
def test_logits_and_every_gradient_equal_the_dense_oracle(plans, inputs,
                                                          oracle, k):
    work = plans[k].work_counts()["true"]
    assert sum(work["slot_edges"]) and sum(work["tail_edges"])
    assert (k == 1) == (sum(work["halo_edges"]) == 0)
    run = _run(plans, inputs, k)
    for mine, theirs in zip(jax.tree.leaves(run["before"]),
                            jax.tree.leaves(oracle["params"])):
        assert np.array_equal(mine, theirs)         # one init, both sides
    np.testing.assert_allclose(run["logits"], oracle["logits"],
                               rtol=2e-5, atol=2e-5)
    assert run["losses"][0] == pytest.approx(float(oracle["loss"]), rel=1e-6)
    for layer, (p0, p1, g) in enumerate(zip(run["before"], run["after"],
                                            oracle["grads"])):
        assert set(p0) == {"w", "a_src", "a_dst", "b", "w_skip", "b_skip"}
        for name in p0:
            assert np.abs(g[name]).max() > 1e-5, (layer, name)  # not vacuous
            np.testing.assert_allclose(
                (p0[name] - p1[name]) / RATE, g[name], rtol=5e-4, atol=5e-6,
                err_msg=f"layer {layer} {name}")


# ------------------------------------------------------ (b) the trajectory
@pytest.mark.parametrize("k", [1, 4, 8])
def test_six_steps_follow_the_oracles_fit(plans, inputs, oracle, k):
    run = _run(plans, inputs, k)
    np.testing.assert_allclose(run["losses"], oracle["losses"], rtol=2e-5)
    assert run["losses"][-1] < run["losses"][0]
    loss, acc = run["trainer"].evaluate(run["data"])
    assert np.isfinite(loss) and 0.0 <= acc <= 1.0


def test_the_scanned_slot_form_the_chip_runs_follows_the_oracle(
        plans, inputs, oracle, monkeypatch):
    """At the cell's size every bucket's slots — the tail's and the halo
    edges' virtual rows included — run under ``lax.scan``; the tiny plans
    here unroll unless the budget is taken away."""
    import sys

    # (``sgcn_tpu.ops`` exports a function of the module's name)
    monkeypatch.setattr(sys.modules["sgcn_tpu.ops.pspmm"],
                        "_CONCURRENT_TEMP_LIMIT", 0)
    tr = _trainer(plans[4], optimizer=optax.sgd(RATE))
    text = tr.lower_step().as_text()
    assert text.count("stablehlo.while") >= 2 * 3 * len(WIDTHS)
    data = _data(tr, *inputs)
    np.testing.assert_allclose(tr.predict(data), oracle["logits"],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose([tr.step(data) for _ in range(3)],
                               oracle["losses"][:3], rtol=2e-5)


def test_virtual_rows_hold_every_edge_of_a_coo_store_once(plans):
    """``CommPlan.virtual_rows``: each destination's edges cut into runs of
    ``VROW_WIDTH``, one virtual row a run, width-major; nothing lost, nothing
    doubled; a store without edges has no layout."""
    from sgcn_tpu.parallel.plan import VROW_WIDTH

    plan = plans[4]
    layouts = plan.virtual_rows()
    for vs, (dst, src, counts) in (
            (layouts["tail"],
             (plan.ltail_dst, plan.ltail_src, plan.ltail_nnz)),
            (layouts["halo"], (plan.hedge_dst, plan.hedge_src, plan.hnnz))):
        (nv, wd), = vs["classes"]
        mask = vs["w"] != 0
        assert wd == VROW_WIDTH and nv % 8 == 0
        assert vs["idx"].shape == (4, nv * wd) and vs["row"].shape == (4, nv)
        for p in range(4):
            cnt = int(counts[p])
            got = sorted(
                (int(vs["row"][p, v]), int(vs["idx"][p, t * nv + v]))
                for t in range(wd) for v in range(nv)
                if mask[p, t * nv + v])
            want = sorted(zip(dst[p, :cnt].tolist(), src[p, :cnt].tolist()))
            assert got == want and len(got) == cnt
            assert np.all(np.diff(vs["row"][p]) >= 0)       # a sorted scatter
            # a destination's runs are full but for its last
            per_row = mask[p].reshape(wd, nv).sum(axis=0)
            rows = vs["row"][p]
            for r in np.unique(rows[per_row > 0]):
                runs = per_row[(rows == r) & (per_row > 0)]
                assert np.all(np.sort(runs)[1:] == wd)
    # one chip has no halo edges: no layout, and the program has neither the
    # halo fold nor an exchange
    assert plans[1].virtual_rows()["halo"] is None
    tr = _trainer(plans[1])
    assert tr._fwd_static["halo_shape"] is None and "vh_idx" not in tr.pa
    text = tr.lower_step().as_text(debug_info=True)
    assert "sgcn.agg_halo_fold" not in text and "sgcn.xchg_pack" not in text
    assert "sgcn.agg_tail" in text
    assert tracing.counters()["att.work"]["exchanges_per_step"] == 0


# ------------------------------------- (c) the tie to the factorised layer
@pytest.mark.parametrize("k", [4])
def test_one_head_of_slope_one_is_the_factorised_layer(plans, inputs, k):
    """Without the rectifier ``s_i`` cancels in the softmax and the layer is
    ``gat_layer_sym`` (``a_src`` = its ``a2``; ``a_dst`` = its ``a1``, which
    gets no gradient there and here)."""
    plan, widths = plans[k], [6, 5]
    one = {"heads": (1, 1), "concat": (True, False), "slope": 1.0,
           "bias": False, "skip": False}
    new = _trainer(plan, widths=widths, args=one, activation="none",
                   optimizer=optax.sgd(1.0))
    old = FullBatchTrainer(plan, fin=FIN, widths=widths,
                           mesh=make_mesh_1d(k), seed=3, model="gat",
                           activation="none", optimizer=optax.sgd(1.0))
    new.params = replicate(new.mesh, [
        {"w": p["w"], "a_src": p["a2"][None], "a_dst": p["a1"][None]}
        for p in _host(old.params)])
    data = _data(new, *inputs)
    np.testing.assert_allclose(new.predict(data), old.predict(data),
                               rtol=2e-5, atol=2e-6)
    before = _host(old.params)
    assert new.step(data) == pytest.approx(old.step(data), rel=2e-6)
    for p0, mine, theirs in zip(before, _host(new.params),
                                _host(old.params)):
        for a, b in (("w", "w"), ("a_src", "a2"), ("a_dst", "a1")):
            np.testing.assert_allclose(p0[b] - mine[a].reshape(p0[b].shape),
                                       p0[b] - theirs[b],
                                       rtol=5e-4, atol=2e-6, err_msg=a)
        assert np.array_equal(mine["a_dst"][0], p0["a1"])   # no gradient


# ----------------------------------------------- (d) the hoist, the exchanges
def test_the_hoist_is_off_and_each_layer_and_pass_has_one_exchange(plans,
                                                                   inputs):
    run = _run(plans, inputs, 4)
    tr = run["trainer"]
    assert tr.hoist is None             # layer 0 projects first; W0 moves
    text = tr.lower_step().as_text()
    # forward [Z ‖ t] and backward [g ‖ s, m, 1/D, c] of every layer: the
    # backward of layer 0 too, since ∂L/∂W0 needs ∂L/∂Z0
    assert text.count("stablehlo.all_to_all") == 2 * len(WIDTHS)
    agg0 = tracing.counters()["agg0"]
    assert agg0["engaged"] is False and agg0["builds"] == 0
    # the wire is booked at each direction's own lanes
    fwd, bwd = mhgat.mhgat_exchange_lane_widths(FIN, WIDTHS, **ARGS)
    assert fwd == (12, 12) and bwd == (24, 18)
    rows = int(tr.stats.send_volume_per_exchange.sum())
    assert tr.stats.report()["halo_bytes_true_per_step"] \
        == rows * 4 * (sum(fwd) + sum(bwd))
    _trainer(plans[4])                  # the newest counter is this plan's
    work = tracing.counters()["att.work"]
    assert work["heads"] == [4, 2] and work["channels"] == [2, 5]
    assert work["exchange_lanes"] == {"forward": list(fwd),
                                      "backward": list(bwd)}
    assert work["passes_per_step"] == {"max": 2, "aggregate": 4}
    assert work["exchanges_per_step"] == 4
    assert len(work["true_edges_per_pass"]) == 4
    assert max(work["true_edges_per_pass"]) <= work["executed_slots_per_pass"]
    assert set(work["virtual_rows"]) == {"tail", "halo"}


def test_the_lowered_step_names_the_sub_scopes_inside_leaf_scopes(plans):
    text = _trainer(plans[4]).lower_step().as_text(debug_info=True)
    for sub in tracing.SUBSCOPES:
        assert f"sgcn.{sub}" in text, sub
    for leaf, sub in (("agg_slots", "att_score"), ("agg_tail", "att_score"),
                      ("agg_halo_fold", "att_score"), ("agg_slots", "att_max"),
                      ("agg_tail", "att_max"), ("agg_halo_fold", "att_max"),
                      ("dense", "att_project"), ("agg_slots", "att_norm")):
        # directly inside its leaf scope, or inside one of the leaf's
        # buckets (PR 35: the slot reduce names each, and a slot's score
        # arithmetic is traced inside its bucket)
        assert re.search(rf"sgcn\.{leaf}/(sgcn\.bkt_\w+/)?sgcn\.{sub}\b",
                         text), (leaf, sub)
    with pytest.raises(ValueError, match="unknown sub-scope"):
        tracing.subscope("att_everything")
    with pytest.raises(ValueError, match="outside a leaf scope"):
        tracing.subscope("att_score")
    with tracing.scope("layer", 0):             # a layer is not a leaf
        with pytest.raises(ValueError, match="outside a leaf scope"):
            tracing.subscope("att_score")
    with tracing.scope("agg_slots"), tracing.subscope("att_score"):
        pass
    assert not set(tracing.SUBSCOPES) & set(tracing.SCOPES)


# ------------------------------------------------------------ (e) refusals
@pytest.mark.parametrize("kw,match", [
    ({"comm_schedule": "ragged"}, "dense a2a"),
    ({"halo_staleness": 1}, "GCN hot path"),
    ({"replica_budget": 8}, "GCN feature"),
    ({"compute_dtype": "bfloat16"}, "float32 only"),
    ({"halo_dtype": "bfloat16"}, "GCN-trainer lever"),
])
def test_modes_that_refuse_the_model_say_so(plans, kw, match):
    with pytest.raises(ValueError, match=match):
        _trainer(plans[4], **kw)


def test_minibatch_serving_and_asymmetric_plans_refuse_the_model(ahat):
    from sgcn_tpu.serve.engine import ServeEngine
    from sgcn_tpu.train.minibatch import MiniBatchTrainer

    pv = balanced_random_partition(N, 4, seed=1)
    with pytest.raises(ValueError, match="full-batch model"):
        MiniBatchTrainer(ahat, pv, 4, fin=FIN, widths=WIDTHS, batch_size=100,
                         model="mhgat")
    plan = build_comm_plan(ahat, pv, 4)
    with pytest.raises(ValueError, match="not served yet"):
        ServeEngine(plan, FIN, WIDTHS, model="mhgat")
    lop = sp.triu(ahat).tocsr()
    with pytest.raises(ValueError, match="symmetric edge"):
        _trainer(build_comm_plan(lop, pv, 4))
    with pytest.raises(ValueError, match="takes no model_args"):
        FullBatchTrainer(plan, fin=FIN, widths=WIDTHS, model="gcn",
                         model_args=ARGS)


@pytest.mark.parametrize("args,match", [
    ({"heads": (4, 4, 4)}, "one of each per layer"),
    ({"heads": (3, 2)}, "cannot concatenate 3 heads"),
    ({"heads": (4, 2), "slope": -0.1}, "outside"),
    ({"heads": (4, 2), "dropout": 0.5}, "unknown model_args"),
])
def test_the_configuration_is_validated(args, match):
    with pytest.raises(ValueError, match=match):
        mhgat.resolve_args(WIDTHS, args)


# ------------------------------------------------- (f) counts and estimates
def test_the_published_widths_give_the_published_parameter_count():
    heads, concat = (4, 4, 4), (True, True, False)
    assert mhgat.param_count(100, [512, 512, 47], heads, concat) == 751574
    assert mhgat.param_count(100, [512, 512, 47], heads, concat,
                             skip=False) == 52736 + 263680 + 96679
    assert mhgat.layer_shapes(100, [512, 512, 47], heads, concat) == [
        (100, 4, 128, 512), (512, 4, 128, 512), (512, 4, 47, 47)]
    params = mhgat.init_mhgat_params(
        jax.random.PRNGKey(0), [(100, 512), (512, 512), (512, 47)],
        heads=heads, concat=concat)
    assert sum(x.size for x in jax.tree.leaves(params)) == 751574
    assert mhgat.mhgat_exchange_lane_widths(
        100, [512, 512, 47], heads, concat) == ((516, 516, 192),
                                                (528, 528, 204))


def test_the_memory_estimate_is_itemised_and_guards_a_small_device(plans):
    tr = _trainer(plans[1])
    est = tr.model_memory
    assert est["total"] == sum(v for k, v in est.items() if k != "total")
    b = plans[1].b
    # per layer: H, Z, O, P, the pre-activation and 8 scalars a head
    assert est["rows_kept"] == 4 * b * sum(
        f + 3 * k * c + out + 8 * k for f, k, c, out in mhgat.layer_shapes(
            FIN, WIDTHS, **ARGS))

    class Small:
        def memory_stats(self):
            return {"bytes_limit": est["total"]}

    with pytest.raises(RuntimeError, match="shard over more chips"):
        check_memory(Small(), est)

    class NoStats:
        def memory_stats(self):
            return None

    check_memory(NoStats(), est)        # nothing to guard


# ----------------------------------- (g) the head products as exact splits
def _coefficients(rng, n, k):
    """Coefficients 1 … 2⁻¹⁰⁰, exact zeros (masked slots) and whole masked
    rows among them."""
    p = (rng.uniform(0.5, 1.0, (n, k))
         * np.exp2(-rng.integers(0, 101, (n, k)))).astype(np.float32)
    p[rng.uniform(size=(n, k)) < 0.1] = 0.0
    p[::7] = 0.0
    return p


def test_split3_recombines_to_the_bit():
    rng = np.random.default_rng(0)
    a = (rng.standard_normal(1 << 16)
         * np.exp2(rng.integers(-100, 30, 1 << 16))).astype(np.float32)
    a[:64] = 0.0
    a = a[(np.abs(a) >= 2.0 ** -100) | (a == 0)]    # the pieces stay normal
    pieces = jax.jit(mhgat.split3)(a)
    assert all(x.dtype == jnp.bfloat16 for x in pieces)
    hi, mid, lo = (np.asarray(x, np.float32) for x in pieces)
    assert np.array_equal((hi + mid) + lo, a)
    assert np.array_equal(hi + (mid + lo), a)       # exact in any order
    assert np.abs(mid).max() > 0 and np.abs(lo).max() > 0


@pytest.mark.parametrize("k,f", [(2, 188), (4, 188), (2, 512), (4, 512)])
def test_both_spreads_are_the_f32_broadcast_to_the_bit(k, f):
    rng = np.random.default_rng(f + k)
    p = _coefficients(rng, 1024, k)
    rows = rng.standard_normal((1024, f)).astype(np.float32)
    want = np.asarray(jax.jit(
        lambda p, r: r * jnp.repeat(p, f // k, axis=1))(p, rows))
    for spread in (mhgat._scale_heads,          # HIGHEST, six passes
                   lambda p, r: r * mhgat._spread_heads(p, f)):     # one
        got = np.asarray(jax.jit(spread)(p, rows))
        assert np.array_equal(got.view(np.int32), want.view(np.int32))
    assert np.count_nonzero(want) > want.size // 2          # not vacuous


@pytest.mark.parametrize("k,f", [(2, 188), (4, 188), (2, 512), (4, 512)])
def test_dot_heads_is_as_near_the_f64_sum_as_the_highest_product(k, f):
    rng = np.random.default_rng(f - k)
    a = rng.standard_normal((1024, f)).astype(np.float32)
    b = rng.standard_normal((1024, f)).astype(np.float32)
    dot = jax.jit(lambda a, b: mhgat._dot_heads(a, b, k))
    highest = jax.jit(lambda x: jnp.dot(
        x, mhgat._head_lanes(k, f).T, precision=jax.lax.Precision.HIGHEST))

    def f64(x):
        return x.astype(np.float64).reshape(len(x), k, f // k).sum(-1)

    # positive terms: an ulp of the sum is an ulp of its terms' size
    pos = np.abs(a * b)
    ulp = np.spacing(f64(pos).astype(np.float32))
    err = np.abs(np.asarray(dot(np.abs(a), np.abs(b))) - f64(pos))
    assert (err / ulp).max() <= 4.0
    # terms of both signs: no further from the f64 sum than HIGHEST is
    err = np.abs(np.asarray(dot(a, b)) - f64(a * b)).max()
    assert err <= max(np.abs(np.asarray(highest(a * b)) - f64(a * b)).max(),
                      4.0 * np.spacing(np.float32(np.abs(a * b).max())))


def test_one_head_takes_no_product():
    rows = np.arange(12, dtype=np.float32).reshape(3, 4)
    text = jax.jit(lambda p, r: (mhgat._scale_heads(p, r),
                                 mhgat._dot_heads(r, r, 1))).lower(
        rows[:, :1], rows).as_text()
    assert "dot_general" not in text


def test_a_forward_slot_reads_both_factors_off_one_signed_spread():
    """``_aggregate_fwd``'s slot spreads ±p once, the sign saying
    [x > 0]: the magnitude is p's spread and the positive part q's, to the
    bit (a −0 for a +0 apart, which adds nothing)."""
    rng = np.random.default_rng(5)
    k, f = 4, 188
    p = _coefficients(rng, 1024, k)
    x = rng.standard_normal((1024, k)).astype(np.float32)
    rows = rng.standard_normal((1024, f)).astype(np.float32)
    q = np.where(x > 0, p, 0.0).astype(np.float32)

    @jax.jit
    def slot(p, x, rows):
        signed = mhgat._spread_heads(jnp.where(x > 0, p, -p), f)
        return rows * jnp.abs(signed), rows * jnp.maximum(signed, 0.0)

    num, pnum = (np.asarray(a) for a in slot(p, x, rows))
    assert np.array_equal(num, np.asarray(mhgat._scale_heads(p, rows)))
    assert np.array_equal(pnum, np.asarray(mhgat._scale_heads(q, rows)))
    assert np.count_nonzero(pnum) > 0 and np.count_nonzero(num != pnum) > 0


_DOT = re.compile(r"stablehlo\.dot_general.*precision = \[(\w+), (\w+)\]"
                  r".*: \(tensor<(\d+)x(\d+)x(\w+)>, "
                  r"tensor<(\d+)x(\d+)x(\w+)>\)")


@pytest.mark.parametrize("k", [1, 4])
def test_the_lowered_step_holds_the_products_the_counter_names(plans, k):
    """Every bfloat16 product of the lowered step is a forward slot's
    spread (contraction 3K deep: the stacked pieces, ONE pass, default
    precision); every ``HIGHEST`` product is the backward slot's spread or
    sum, or a layer's row-wise one — as many of each as
    ``att.work["head_products"]`` says (slots unrolled at this size);
    nothing else runs at ``HIGHEST``, nothing at a six-pass algorithm."""
    plan = plans[k]
    tr = _trainer(plan)
    work = tracing.counters()["att.work"]["head_products"]
    assert work["forward_slot"] == {"spread": 1, "form": "split3",
                                    "passes": 1}
    assert work["backward_slot"]["form"] == "highest"
    text = tr.lower_step().as_text()
    assert "algorithm" not in text
    found = {"split3": {}, "highest": {}}
    for m in _DOT.finditer(text):
        p0, p1, _, _, lhs, deep, wide, rhs = m.groups()
        assert p0 == p1 and lhs == rhs
        if lhs == "bf16":                       # (3K, K·C) stacked 0/1 rows
            assert p0 == "DEFAULT" and int(deep) % 3 == 0
            lanes = int(wide)
            found["split3"][lanes] = found["split3"].get(lanes, 0) + 1
        elif p0 == "HIGHEST":                   # (K, K·C) or its transpose
            lanes = max(int(deep), int(wide))
            found["highest"][lanes] = found["highest"].get(lanes, 0) + 1
        else:
            assert p0 == "DEFAULT"              # the dense layer's own
    assert text.count("HIGHEST") == 2 * sum(found["highest"].values())
    # slot bodies in the program, each store unrolled: Σ bucket widths
    slots = sum(wb for _, wb in plan.ell_buckets) + sum(
        sh[1] for sh in (tr._fwd_static["tail_shape"],
                         tr._fwd_static["halo_shape"]) if sh is not None)
    want = {"split3": {}, "highest": {}}
    for _, kh, c, _ in mhgat.layer_shapes(FIN, WIDTHS, **ARGS):
        for where, count in (("forward_slot", slots),
                             ("backward_slot", slots), ("layer_rows", 1)):
            w = work[where]
            want[w["form"]][kh * c] = want[w["form"]].get(kh * c, 0) + (
                count * (w["spread"] + w.get("sum", 0)))
    assert found == want
