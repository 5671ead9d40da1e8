"""Pallas SpMM kernel vs the default XLA path (interpret mode on CPU)."""

import numpy as np
import jax.numpy as jnp

from sgcn_tpu.ops import spmm_local
from sgcn_tpu.ops.pallas_spmm import build_dst_tiles, spmm_pallas
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.partition import balanced_random_partition


def test_build_dst_tiles_roundtrip(ahat):
    n = ahat.shape[0]
    plan = build_comm_plan(ahat, np.zeros(n, dtype=np.int64), 1)
    ed, es, ew = plan.edge_dst[0], plan.edge_src[0], plan.edge_w[0]
    tsrc, tld, tw, padded = build_dst_tiles(ed, es, ew, plan.b, tb=16)
    assert padded % 16 == 0
    # every real edge appears exactly once with its weight (pads are 0)
    np.testing.assert_allclose(np.sort(tw[tw != 0]), np.sort(ew[ew != 0]),
                               rtol=0, atol=0)


def test_pallas_matches_xla(ahat):
    n = ahat.shape[0]
    rng = np.random.default_rng(0)
    plan = build_comm_plan(ahat, np.zeros(n, dtype=np.int64), 1)
    ed, es, ew = plan.edge_dst[0], plan.edge_src[0], plan.edge_w[0]
    f = 8
    table = jnp.asarray(rng.standard_normal((plan.b + plan.r, f)), jnp.float32)
    want = np.asarray(spmm_local(
        jnp.asarray(ed), jnp.asarray(es), jnp.asarray(ew), table, plan.b))
    tb = 16
    tsrc, tld, tw, padded = build_dst_tiles(ed, es, ew, plan.b, tb=tb)
    got = np.asarray(spmm_pallas(
        jnp.asarray(tsrc), jnp.asarray(tld), jnp.asarray(tw), table,
        tb=tb, interpret=True))[: plan.b]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_pallas_partitioned_blocks(ahat):
    """Kernel also serves per-chip blocks (table = [local; halo])."""
    n = ahat.shape[0]
    rng = np.random.default_rng(1)
    pv = balanced_random_partition(n, 4, seed=2)
    plan = build_comm_plan(ahat, pv, 4)
    f = 8
    for p in range(4):
        table = jnp.asarray(
            rng.standard_normal((plan.b + plan.r, f)), jnp.float32)
        want = np.asarray(spmm_local(
            jnp.asarray(plan.edge_dst[p]), jnp.asarray(plan.edge_src[p]),
            jnp.asarray(plan.edge_w[p]), table, plan.b))
        tsrc, tld, tw, _ = build_dst_tiles(
            plan.edge_dst[p], plan.edge_src[p], plan.edge_w[p], plan.b, tb=8)
        got = np.asarray(spmm_pallas(
            jnp.asarray(tsrc), jnp.asarray(tld), jnp.asarray(tw), table,
            tb=8, interpret=True))[: plan.b]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _build_dst_tiles_reference(edge_dst, edge_src, edge_w, num_rows, tb):
    """The ORIGINAL per-tile Python-loop builder, kept verbatim as the
    equality oracle for the vectorized ``build_dst_tiles`` (ISSUE-15
    satellite: the O(T) interpreted loop was replaced by sliced numpy
    assignment; output must be bit-identical)."""
    edge_dst = np.asarray(edge_dst)
    edge_src = np.asarray(edge_src)
    edge_w = np.asarray(edge_w)
    t = -(-num_rows // tb)
    tile_of_edge = edge_dst // tb
    counts = np.bincount(tile_of_edge, minlength=t)
    emax = max(8, int(counts.max()))
    emax = -(-emax // 8) * 8
    tsrc = np.zeros((t, emax), np.int32)
    tw = np.zeros((t, emax), np.float32)
    tld = np.full((t, emax), tb - 1, np.int32)
    starts = np.zeros(t + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    for i in range(t):
        s, e = starts[i], starts[i + 1]
        c = e - s
        tsrc[i, :c] = edge_src[s:e]
        tw[i, :c] = edge_w[s:e]
        tld[i, :c] = edge_dst[s:e] - i * tb
    return tsrc, tld, tw, t * tb


def test_vectorized_build_dst_tiles_matches_old_loop(ahat):
    """Satellite pin: the vectorized builder's output equals the old
    per-tile loop's EXACTLY (same pads, same slot order) on a real plan's
    edge families, across tile sizes."""
    n = ahat.shape[0]
    pv = balanced_random_partition(n, 4, seed=2)
    plan = build_comm_plan(ahat, pv, 4)
    for p in range(4):
        for dst, src, w in ((plan.ledge_dst[p], plan.ledge_src[p],
                             plan.ledge_w[p]),
                            (plan.hedge_dst[p], plan.hedge_src[p],
                             plan.hedge_w[p])):
            for tb in (8, 16, 64):
                want = _build_dst_tiles_reference(dst, src, w, plan.b, tb)
                got = build_dst_tiles(dst, src, w, plan.b, tb=tb)
                for a, b in zip(got, want[:3]):
                    np.testing.assert_array_equal(a, b)
                assert got[3] == want[3]


class _FitsPlan:
    """Minimal plan stub for the VMEM budget rule."""

    def __init__(self, b, r):
        self.b, self.r = b, r
        self.rr_sizes = None
        self.symmetric = True

    def ragged_round_sizes(self):
        raise ValueError("stub has no square counts")


def test_pallas_fits_table_boundary(monkeypatch):
    """The VMEM budget check charges the f32 table, exactly at the
    boundary."""
    from sgcn_tpu.ops.pallas_spmm import pallas_spmm_fits

    b, r, fmax = 100, 80, 32
    plan = _FitsPlan(b, r)
    monkeypatch.setenv("SGCN_PALLAS_VMEM", str(b * fmax * 4))
    assert pallas_spmm_fits(plan, fmax, [8])
    monkeypatch.setenv("SGCN_PALLAS_VMEM", str(b * fmax * 4 - 1))
    assert not pallas_spmm_fits(plan, fmax, [8])


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_bf16_compute_never_selects_kernel(monkeypatch):
    """The kernel's single-row dynamic load of a bf16 table does not
    compile on the chip (PERF.md bring-up), so a bf16 compute_dtype never
    selects it — forced on, on a TPU, for either model."""
    from sgcn_tpu.ops import pallas_spmm as ps

    plan = _FitsPlan(100, 80)
    monkeypatch.setenv("SGCN_PALLAS_SPMM", "1")
    monkeypatch.setattr(ps, "kernel_device",
                        lambda: _Dev("tpu", "TPU v5 lite"))
    for model in ("gcn", "gat"):
        assert ps.use_pallas_spmm(plan, 32, [8], model=model)
        assert not ps.use_pallas_spmm(plan, 32, [8], model=model,
                                      compute_dtype="bfloat16")


def test_selection_needs_a_known_tpu_kind(monkeypatch):
    """Auto mode fires on a TPU whose SMEM capacity the table knows; an
    unknown TPU kind selects no kernel even when forced; off the chip only
    the forced (emulated) selection fires."""
    from sgcn_tpu.ops import pallas_spmm as ps

    plan = _FitsPlan(100, 80)
    monkeypatch.delenv("SGCN_PALLAS_SPMM", raising=False)
    assert not ps.use_pallas_spmm(plan, 32, [8])          # CPU, auto
    monkeypatch.setattr(ps, "kernel_device",
                        lambda: _Dev("tpu", "TPU v5 lite"))
    assert ps.use_pallas_spmm(plan, 32, [8])
    monkeypatch.setattr(ps, "kernel_device",
                        lambda: _Dev("tpu", "TPU v99"))
    assert not ps.use_pallas_spmm(plan, 32, [8])
    monkeypatch.setenv("SGCN_PALLAS_SPMM", "1")
    assert not ps.use_pallas_spmm(plan, 32, [8])


def test_over_smem_class_takes_ell():
    """A tile class whose three prefetch operands exceed the chip's SMEM
    takes the XLA gather form — the escape the Emax cap already uses.  The
    class the v5e compiler refused (10 tiles × Emax 6912: 3 × 432 KiB
    against 1 MiB) is the pinned case."""
    from sgcn_tpu.ops.pallas_spmm import (SMEM_BYTES, _assign_kernels,
                                          prefetch_smem_bytes)

    assert prefetch_smem_bytes(10, 6912) == 3 * 16 * 6912 * 4   # rows → 16
    assert prefetch_smem_bytes(64, 1288) == 3 * 64 * 1408 * 4   # cols → 128
    classes = ((10, 6912), (11, 2048), (2, 24288))
    smem = SMEM_BYTES["TPU v5 lite"]
    assert _assign_kernels(classes, smem) == (
        (10, 6912, "ell"), (11, 2048, "vmem"), (2, 24288, "ell"))
    # no SMEM limit (the emulated path): only the Emax cap applies
    assert _assign_kernels(classes) == (
        (10, 6912, "vmem"), (11, 2048, "vmem"), (2, 24288, "ell"))
    assert _assign_kernels(classes, 0) == tuple(
        (t, e, "ell") for t, e in classes)


def test_pallas_fits_gat_and_ragged_tables(monkeypatch):
    """The fits rule charges the GAT combined (B+R)·(fout+1) table and,
    on the ragged schedule, the ring concat's ΣS_d height instead of the
    dense halo pad."""
    from sgcn_tpu.ops.pallas_spmm import pallas_spmm_fits

    plan = _FitsPlan(100, 80)
    widths = [15]                                    # fout+1 = 16 lanes
    need = (plan.b + plan.r) * 16 * 4
    monkeypatch.setenv("SGCN_PALLAS_VMEM", str(need))
    assert pallas_spmm_fits(plan, 8, widths, model="gat")
    monkeypatch.setenv("SGCN_PALLAS_VMEM", str(need - 1))
    assert not pallas_spmm_fits(plan, 8, widths, model="gat")
    # ragged: a pre-built ring larger than r must be charged
    plan.rr_sizes = (200, 0, 40)
    fmax = 32
    monkeypatch.setenv("SGCN_PALLAS_VMEM", str(240 * fmax * 4 - 1))
    assert not pallas_spmm_fits(plan, fmax, [8], schedule="ragged")
    monkeypatch.setenv("SGCN_PALLAS_VMEM", str(240 * fmax * 4))
    assert pallas_spmm_fits(plan, fmax, [8], schedule="ragged")


def test_tile_classes_cover_and_align():
    """Class structure: covers every tile, aligns to bucket row boundaries
    rounded to tiles, collapses to one class for a flat histogram."""
    from sgcn_tpu.ops.pallas_spmm import tile_classes_from_buckets

    assert tile_classes_from_buckets(((64, 4),), 64, 16) == (4,)
    assert tile_classes_from_buckets(((16, 28), (48, 2)), 64, 16) == (1, 3)
    assert tile_classes_from_buckets(None, 100, 16) == (7,)
    # boundaries inside a tile round UP, never split a tile
    ct = tile_classes_from_buckets(((10, 9), (54, 2)), 64, 16)
    assert sum(ct) == 4 and all(c > 0 for c in ct)


def test_trainer_plan_driven_pallas_parity(ahat, monkeypatch):
    """Plan-driven kernel choice: with SGCN_PALLAS_SPMM=1
    the symmetric GCN trainer must auto-select the VMEM Pallas aggregator
    (per-chip tables fit the budget at this size) and train to the SAME
    losses and predictions as the default ELL path."""
    from sgcn_tpu.ops.pallas_spmm import PALLAS_PLAN_FIELDS, use_pallas_spmm
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    n = ahat.shape[0]
    k, fin, widths = 4, 12, [8, 4]
    pv = balanced_random_partition(n, k, seed=5)
    plan = build_comm_plan(ahat, pv, k)
    assert plan.symmetric
    rng = np.random.default_rng(3)
    feats = rng.standard_normal((n, fin)).astype(np.float32)
    labels = rng.integers(0, 4, n).astype(np.int32)

    def run():
        tr = FullBatchTrainer(plan, fin=fin, widths=widths, seed=2)
        data = make_train_data(plan, feats, labels)
        losses = [tr.step(data) for _ in range(4)]
        return tr, losses, tr.predict(data)

    monkeypatch.setenv("SGCN_PALLAS_SPMM", "0")
    _, losses_ell, pred_ell = run()

    monkeypatch.setenv("SGCN_PALLAS_SPMM", "1")
    assert use_pallas_spmm(plan, fin, widths)
    tr_p, losses_pal, pred_pal = run()
    assert tr_p.plan_fields == PALLAS_PLAN_FIELDS     # choice actually taken
    np.testing.assert_allclose(losses_pal, losses_ell, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(pred_pal, pred_ell, rtol=1e-3, atol=1e-4)


def test_minibatch_shared_step_never_resolves_pallas(ahat, monkeypatch):
    """The mini-batch trainer's ONE compiled step serves EVERY per-batch
    plan, but the Pallas tile layout is per-plan (per-class Emax_c statics,
    ptile_* arrays built by ensure_pallas_tiles on plans[0] only) — so the
    shared envelope must stay on the slot-pass/ELL aggregators even when
    the VMEM rule would fire (allow_pallas=False through
    resolve_forward_setup).  Before the guard, batch 1's step crashed
    stacking the never-built ptile_* arrays of its plan."""
    from sgcn_tpu.ops.pallas_spmm import use_pallas_spmm
    from sgcn_tpu.train.minibatch import MiniBatchTrainer

    n = ahat.shape[0]
    k, fin, widths = 4, 12, [8, 4]
    pv = balanced_random_partition(n, k, seed=5)
    monkeypatch.setenv("SGCN_PALLAS_SPMM", "1")
    # non-vacuous: the full-batch rule WOULD fire at this size
    assert use_pallas_spmm(build_comm_plan(ahat, pv, k), fin, widths)

    mb = MiniBatchTrainer(ahat, pv, k, fin=fin, widths=widths,
                          batch_size=n // 2, nbatches=2)
    assert not any(f.startswith("ptile_") for f in mb.inner.plan_fields)
    rng = np.random.default_rng(3)
    feats = rng.standard_normal((n, fin)).astype(np.float32)
    labels = rng.integers(0, 4, n).astype(np.int32)
    batches = mb.make_batches(feats, labels)
    assert len(batches) == 2
    for b in batches:                  # batch != 0 was the crash scenario
        assert np.isfinite(mb.step(b))


def test_gat_pallas_mask_tiles_ship_int8(ahat, monkeypatch):
    """ship_arrays narrows the GAT 0/1 mask tiles (ptile_cw) to int8 like
    cell_w/ctail_w — the padded f32 tile form is real per-chip argument
    bytes at products scale; gat_pallas_pass upcasts in-program."""
    from sgcn_tpu.train.fullbatch import resolve_forward_setup

    n = ahat.shape[0]
    k, fin, widths = 4, 12, [8, 4]
    pv = balanced_random_partition(n, k, seed=5)
    plan = build_comm_plan(ahat, pv, k)
    monkeypatch.setenv("SGCN_PALLAS_SPMM", "1")
    setup = resolve_forward_setup(plan, fin, widths, model="gat",
                                  comm_schedule="a2a")
    assert "ptile_cw" in setup.plan_fields
    arrays = setup.ship_arrays(plan)
    assert arrays["ptile_cw"].dtype == np.int8
    assert set(np.unique(arrays["ptile_cw"])) <= {0, 1}
