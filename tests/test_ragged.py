"""Ragged neighbor-exchange schedule (``comm_schedule='ragged'``): the
per-round-sized ppermute halo ring replacing the globally-padded all_to_all.

Contract pinned here (docs/comm_schedule.md):

  * f32 BIT-parity with the dense a2a schedule — forward, gradients, and
    whole training trajectories on the cora fixture are exactly equal (the
    plan sorts halo edges in round order so the ragged fold applies per-row
    updates in the dense segment-sum's sequence);
  * per-round sizing: round d's buffer is max_p send_counts[p, (p+d)%k],
    empty rounds vanish from the traced program, and the wire-row total is
    strictly below the dense k²·S whenever the partition is skewed;
  * the shard proxy runs the ragged program on one device under the same
    optimization_barrier fidelity contract as the dense exchange;
  * composition with the stale pipelined exchange is SUPPORTED since the
    round-structured carry (``pspmm_stale_ragged``) — its parity and gauge
    coverage lives in tests/test_stale_ragged.py.
"""

import os
import re

import numpy as np
import pytest
import scipy.sparse as sp

from sgcn_tpu.io.datasets import er_graph, load_npz_dataset
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.parallel.mesh import AXIS, make_mesh_1d, shard_stacked
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.partition.emit import read_partvec
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, make_train_data

# The exact step with its hub tail and halo-source edges as COO lists
# (``pspmm_ell_sym_coo``): the addition order the carried-halo and ragged
# programs reproduce bit for bit.  The exact full-batch step itself folds
# both stores as slot passes since PR 30 — same edges, another order;
# ``tests/test_fold_slots.py`` bounds the difference.
COO_EXACT = {"shared_envelope": True}

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def ring_graph(n: int) -> sp.csr_matrix:
    """Cycle graph: vertex i ~ i±1 (mod n) — under a contiguous partition
    each part talks ONLY to its two neighbors, the maximally skewed
    send-count pattern (most (src, dst) pairs empty)."""
    i = np.arange(n)
    rows = np.concatenate([i, i])
    cols = np.concatenate([(i + 1) % n, (i - 1) % n])
    return sp.csr_matrix((np.ones(2 * n, np.float32), (rows, cols)),
                         shape=(n, n))


@pytest.fixture(scope="module")
def skewplan():
    """Ring graph, 8 contiguous parts: only ring distances 1 and k−1 carry
    rows, so the dense a2a pads 56 of 64 peer buckets for nothing —
    padding_efficiency far below the 0.5 auto-select threshold."""
    n, k = 512, 8
    ahat = normalize_adjacency(ring_graph(n))
    pv = np.repeat(np.arange(k), n // k)
    plan = build_comm_plan(ahat, pv, k)
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((n, 16)).astype(np.float32)
    labels = rng.integers(0, 4, n).astype(np.int32)
    return plan, feats, labels


@pytest.fixture(scope="module")
def asymplan():
    """ER graph under an UNBALANCED partition: symmetric Â (the ragged
    op's requirement) but asymmetric send_counts — the general shape the
    bit-parity claim must survive."""
    n, k = 600, 4
    ahat = normalize_adjacency(er_graph(n, 8, seed=0))
    pv = np.zeros(n, dtype=np.int64)
    pv[n // 2: n // 2 + n // 4] = 1
    pv[n // 2 + n // 4: n // 2 + n // 4 + n // 8] = 2
    pv[n // 2 + n // 4 + n // 8:] = 3
    plan = build_comm_plan(ahat, pv, k)
    assert not np.array_equal(plan.send_counts, plan.send_counts.T)
    rng = np.random.default_rng(1)
    feats = rng.standard_normal((n, 16)).astype(np.float32)
    labels = rng.integers(0, 4, n).astype(np.int32)
    return plan, feats, labels


@pytest.fixture(scope="module")
def cora():
    a, feats, labels = load_npz_dataset(os.path.join(FIX, "cora_like.npz"))
    ahat = normalize_adjacency(a)
    pv = read_partvec(os.path.join(FIX, "cora_like.4.hp"))
    plan = build_comm_plan(ahat, pv, 4)
    return plan, feats.astype(np.float32), labels.astype(np.int32)


def test_round_sizes_and_empty_round_skip(skewplan):
    """rr_sizes follows S_d = max_p send_counts[p, (p+d)%k]; ring distances
    2..k−2 are empty and must vanish from the traced program."""
    plan, *_ = skewplan
    plan.ensure_ragged()
    k, sc = plan.k, plan.send_counts
    idx = np.arange(k)
    for d in range(1, k):
        assert plan.rr_sizes[d - 1] == int(sc[idx, (idx + d) % k].max())
    assert plan.rr_sizes[0] > 0 and plan.rr_sizes[-1] > 0
    assert all(s == 0 for s in plan.rr_sizes[1:-1])      # middle rounds empty
    # empty rounds carry no edges either
    assert all(e == 0 for e in plan.rr_edge_sizes[1:-1])
    # wire rows: 2 live rounds of the per-round max vs the global k²·S pad
    assert plan.wire_rows_per_exchange("ragged") == \
        plan.k * (plan.rr_sizes[0] + plan.rr_sizes[-1])
    assert plan.wire_rows_per_exchange("ragged") < \
        plan.wire_rows_per_exchange("a2a")
    assert plan.padding_efficiency() < 0.5


def test_ensure_ragged_receive_layout(asymplan):
    """Every receive slot lands in the contiguous per-owner halo slice, in
    send order — the invariant the fold-as-you-arrive split rides on."""
    plan, *_ = asymplan
    plan.ensure_ragged()
    k, s = plan.k, plan.s
    owner_rank = plan.halo_src // s
    off = 0
    for d, sd in enumerate(plan.rr_sizes, start=1):
        for p in range(k):
            o = (p - d) % k
            rc = int(plan.send_counts[o, p])
            got = plan.rhalo_dst[p, off: off + rc]
            hs = int(plan.halo_counts[p])
            expect = np.nonzero(owner_rank[p, :hs] == o)[0]
            np.testing.assert_array_equal(got, expect)
            # padding slots target the drop row r
            assert np.all(plan.rhalo_dst[p, off + rc: off + sd] == plan.r)
        off += sd


def test_op_level_bit_parity_fwd_and_grad(asymplan):
    """pspmm_ragged_sym vs pspmm_ell_sym_coo on the asymmetric-count plan:
    forward AND gradients bitwise equal, and halo_exchange_ragged delivers
    the dense exchange's exact halo rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from sgcn_tpu.ops.pspmm import (halo_exchange, halo_exchange_ragged,
                                    pspmm_ell_sym_coo, pspmm_ragged_sym)

    plan, *_ = asymplan
    plan.ensure_ragged()
    k = plan.k
    mesh = make_mesh_1d(k)
    rng = np.random.default_rng(0)
    h = shard_stacked(mesh, rng.standard_normal(
        (k, plan.b, 8)).astype(np.float32))
    fields = ("send_idx", "halo_src", "ell_idx", "ell_w", "ltail_dst",
              "ltail_src", "ltail_w", "hedge_dst", "hedge_src", "hedge_w",
              "rsend_idx", "rhalo_dst", "redge_dst", "redge_src", "redge_w")
    pa = shard_stacked(mesh, {f: getattr(plan, f) for f in fields})
    bk, rrs, rre, r = (plan.ell_buckets, plan.rr_sizes, plan.rr_edge_sizes,
                       plan.r)

    def dense_chip(pa, h):
        pa, h = jax.tree.map(lambda x: x[0], (pa, h))
        out = pspmm_ell_sym_coo(
            h, pa["send_idx"], pa["halo_src"], pa["ell_idx"], pa["ell_w"],
            pa["ltail_dst"], pa["ltail_src"], pa["ltail_w"], pa["hedge_dst"],
            pa["hedge_src"], pa["hedge_w"], bk)
        halo = halo_exchange(h, pa["send_idx"], pa["halo_src"])
        return out[None], halo[None]

    def ragged_chip(pa, h):
        pa, h = jax.tree.map(lambda x: x[0], (pa, h))
        out = pspmm_ragged_sym(h, pa["rsend_idx"], pa["ell_idx"], pa["ell_w"],
                               pa["ltail_dst"], pa["ltail_src"],
                               pa["ltail_w"], pa["redge_dst"],
                               pa["redge_src"], pa["redge_w"], bk, rrs, rre)
        halo = halo_exchange_ragged(h, pa["rsend_idx"], pa["rhalo_dst"],
                                    rrs, r)
        return out[None], halo[None]

    specs = dict(mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
                 out_specs=(P(AXIS), P(AXIS)))
    dj = jax.jit(jax.shard_map(dense_chip, **specs))
    rj = jax.jit(jax.shard_map(ragged_chip, **specs))
    od, hd = dj(pa, h)
    orr, hr = rj(pa, h)
    np.testing.assert_array_equal(np.asarray(od), np.asarray(orr))
    hd, hr = np.asarray(hd), np.asarray(hr)
    for p in range(k):
        hc = int(plan.halo_counts[p])
        np.testing.assert_array_equal(hd[p, :hc], hr[p, :hc])

    gd = jax.grad(lambda x: jnp.sum(jnp.sin(dj(pa, x)[0])))(h)
    gr = jax.grad(lambda x: jnp.sum(jnp.sin(rj(pa, x)[0])))(h)
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(gr))


def test_trainer_bit_identical_on_cora(cora):
    """THE acceptance contract: the ragged schedule's epoch losses and
    trained parameters are f32-BIT-identical to the dense a2a schedule's on
    the cora fixture (exact ELL path; stale composition is deferred)."""
    plan, feats, labels = cora
    tr_a = FullBatchTrainer(plan, fin=feats.shape[1], widths=[16, 7], seed=3,
                            **COO_EXACT)
    tr_r = FullBatchTrainer(plan, fin=feats.shape[1], widths=[16, 7], seed=3,
                            comm_schedule="ragged")
    assert tr_r.comm_schedule == "ragged"
    d = make_train_data(plan, feats, labels)
    la = [tr_a.step(d) for _ in range(3)]
    lr = [tr_r.step(d) for _ in range(3)]
    assert la == lr                                  # bitwise, not allclose
    for wa, wr in zip(tr_a.params, tr_r.params):
        np.testing.assert_array_equal(np.asarray(wa), np.asarray(wr))
    # the a2a step as it ships (both stores as slot passes): the same sums
    # in another order
    tr_s = FullBatchTrainer(plan, fin=feats.shape[1], widths=[16, 7], seed=3)
    assert "fold_classes" in tr_s._fwd_static
    np.testing.assert_allclose([tr_s.step(d) for _ in range(3)], la,
                               rtol=1e-5)
    # the two schedules agree on the TRUE volume and disagree on the wire
    ra, rr = tr_a.stats.report(), tr_r.stats.report()
    assert ra["true_rows_per_exchange"] == rr["true_rows_per_exchange"]
    assert rr["wire_rows_per_exchange"] < ra["wire_rows_per_exchange"]
    assert ra["comm_schedule"] == "a2a" and rr["comm_schedule"] == "ragged"


def test_attribution_wire_below_dense_on_skew(skewplan):
    """Acceptance: on a skewed-partition fixture with padding_efficiency
    < 0.5, attribution reports halo_bytes_wire strictly below the dense
    schedule's — and the roofline event fields validate + reconcile with
    CommStats' gauges."""
    import time

    from sgcn_tpu.obs.attribution import roofline_fields, step_cost
    from sgcn_tpu.obs.schema import validate_event
    from sgcn_tpu.utils.stats import CommStats

    plan, *_ = skewplan
    assert plan.padding_efficiency() < 0.5
    ca = step_cost(plan, 16, [8, 4], comm_schedule="a2a")
    cr = step_cost(plan, 16, [8, 4], comm_schedule="ragged")
    assert cr.halo_bytes_true_per_step == ca.halo_bytes_true_per_step
    assert cr.halo_bytes_wire_per_step < ca.halo_bytes_wire_per_step
    assert ca.halo_bytes_wire_per_step >= ca.halo_bytes_true_per_step
    # legacy field keeps its true-volume meaning (old readers unchanged)
    assert ca.halo_bytes_per_step == ca.halo_bytes_true_per_step

    for cost, schedule in ((ca, "a2a"), (cr, "ragged")):
        st = CommStats.from_plan(plan, schedule=schedule)
        assert st.wire_rows_per_exchange == cost.halo_wire_rows
        assert st.padding_efficiency == cost.padding_efficiency
        rf = roofline_fields(cost, 0.1, exchanges=4, exposed_exchanges=4)
        # exposed bytes charge the WIRE, not the true volume
        assert rf["exposed_halo_bytes"] == cost.halo_bytes_wire_per_step
        validate_event({"kind": "step", "v": 1, "ts": time.time(),
                        "step": 1, "loss": 1.0, "wall_s": 0.1,
                        "roofline": rf})


def test_auto_select_and_env(skewplan, monkeypatch):
    """'auto' picks ragged below the padding-efficiency threshold, a2a on a
    well-packed plan; $SGCN_COMM_SCHEDULE supplies the default."""
    plan, feats, labels = skewplan
    tr = FullBatchTrainer(plan, fin=16, widths=[8, 4], comm_schedule="auto")
    assert tr.comm_schedule == "ragged"

    # near-uniform counts: balanced random partition of an ER expander has
    # every peer bucket filled, efficiency ≈ (k−1)/k — a2a wins
    n, k = 600, 4
    ahat = normalize_adjacency(er_graph(n, 8, seed=2))
    pv = balanced_random_partition(n, k, seed=3)
    uplan = build_comm_plan(ahat, pv, k)
    assert uplan.padding_efficiency() >= 0.5
    tr_u = FullBatchTrainer(uplan, fin=16, widths=[8, 4],
                            comm_schedule="auto")
    assert tr_u.comm_schedule == "a2a"

    monkeypatch.setenv("SGCN_COMM_SCHEDULE", "ragged")
    tr_env = FullBatchTrainer(plan, fin=16, widths=[8, 4])
    assert tr_env.comm_schedule == "ragged"


def test_proxy_runs_ragged_program(skewplan):
    """k>1-plan-on-1-device: the ragged layout built BEFORE slicing rides
    the proxy, the per-round sends stay materialized (optimization_barrier
    fidelity, like a2a_or_identity), and training is finite."""
    from sgcn_tpu.parallel.proxy import shard_proxy_data, shard_proxy_plan

    plan, feats, labels = skewplan
    plan.ensure_ragged()
    proxy = shard_proxy_plan(plan, chip=2)
    assert proxy.rr_sizes == plan.rr_sizes          # static tuple rides along
    assert proxy.rsend_idx.shape == (1,) + plan.rsend_idx.shape[1:]
    np.testing.assert_array_equal(proxy.redge_w[0], plan.redge_w[2])
    tr = FullBatchTrainer(proxy, fin=16, widths=[8, 4], seed=2,
                          comm_schedule="ragged")
    data = shard_proxy_data(plan, 2, feats, labels)
    losses = tr.run_epochs(data, 2)
    assert np.all(np.isfinite(losses))
    txt = tr._step.lower(
        tr.params, tr.opt_state, tr.pa, data.h0, data.labels,
        data.train_valid).as_text()
    # one barrier per LIVE round per exchange direction — at least the two
    # live ring rounds must stay pinned
    assert txt.count("optimization_barrier") >= 2


def test_ensure_ragged_needs_full_plan(skewplan):
    """Building the ragged layout from an already-sliced plan must fail
    loudly (round sizes are maxes over ALL chips)."""
    from sgcn_tpu.parallel.proxy import shard_proxy_plan

    plan, *_ = skewplan
    sliced = shard_proxy_plan(
        build_comm_plan(normalize_adjacency(ring_graph(128)),
                        np.repeat(np.arange(4), 32), 4), chip=0)
    with pytest.raises(ValueError, match="BEFORE shard_proxy_plan"):
        sliced.ensure_ragged()


def test_gating(asymplan, cora):
    """Invalid combinations fail loudly at construction: asymmetric plans,
    unknown values.  GAT + ragged is a SUPPORTED contract since the
    multi-lane ring (tests/test_gat_ragged.py owns its parity coverage),
    and ragged + staleness is the SUPPORTED composed mode since the
    round-structured carry (tests/test_stale_ragged.py owns its parity
    coverage)."""
    plan, *_ = cora
    tr_comp = FullBatchTrainer(plan, fin=8, widths=[8, 7], halo_staleness=1,
                               comm_schedule="ragged")
    assert tr_comp.comm_schedule == "ragged" and tr_comp.halo_staleness == 1
    tr_gat = FullBatchTrainer(plan, fin=8, widths=[8, 7], model="gat",
                              comm_schedule="ragged")
    assert tr_gat.comm_schedule == "ragged"
    with pytest.raises(ValueError, match="a2a"):
        FullBatchTrainer(plan, fin=8, widths=[8, 7], comm_schedule="bogus")
    # stale + auto resolves by the wire-byte-only rule (the hidden exchange
    # makes the latency threshold moot), which picks ragged whenever the
    # ring ships fewer wire rows — true on any supported k>1 plan
    tr = FullBatchTrainer(plan, fin=8, widths=[8, 7], halo_staleness=1,
                          comm_schedule="auto")
    assert tr.comm_schedule == "ragged"
    assert "wire-byte rule" in tr.comm_decision["rule"]

    import dataclasses
    aplan = dataclasses.replace(asymplan[0], symmetric=False)
    with pytest.raises(ValueError, match="asymmetric"):
        FullBatchTrainer(aplan, fin=16, widths=[8, 4],
                         comm_schedule="ragged")


def test_minibatch_ragged_shared_envelope(skewplan):
    """The mini-batch trainer pads every batch plan's round sizes to a
    shared envelope (one compiled step) and stays bit-identical to its a2a
    twin, batch for batch."""
    from sgcn_tpu.train.minibatch import MiniBatchTrainer

    _, feats, labels = skewplan
    n, k = 512, 8
    ahat = normalize_adjacency(ring_graph(n))
    pv = np.repeat(np.arange(k), n // k)
    kw = dict(fin=16, widths=[8, 4], batch_size=128, nbatches=2, seed=4)
    tr_a = MiniBatchTrainer(ahat, pv, k, comm_schedule="a2a", **kw)
    tr_r = MiniBatchTrainer(ahat, pv, k, comm_schedule="ragged", **kw)
    assert tr_r.inner.comm_schedule == "ragged"
    assert len({p.rr_sizes for p in tr_r.plans}) == 1   # shared envelope
    ba = tr_a.make_batches(feats, labels)
    br = tr_r.make_batches(feats, labels)
    la = [tr_a.step(b) for b in ba]
    lr = [tr_r.step(b) for b in br]
    assert la == lr                                  # bitwise, not allclose
    # the per-step comm snapshot carries the same wire gauges as the
    # full-batch path (docs/observability.md) and stays self-consistent
    snap = tr_r._comm_snapshot(br[0].stats)
    assert snap["comm_schedule"] == "ragged"
    assert snap["wire_rows_per_exchange"] == \
        tr_r.plans[0].wire_rows_per_exchange("ragged")
    assert snap["wire_rows_total"] == \
        snap["exchanges"] * snap["wire_rows_per_exchange"]
    # a batch may sample NO cross-partition edges while the shared wire
    # envelope stays nonzero — efficiency 0.0 is then the honest figure
    assert 0 <= snap["padding_efficiency"] <= 1
