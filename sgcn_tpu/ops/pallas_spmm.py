"""Pallas TPU SpMM kernel (dst-tiled) — the hand-written alternative to the
XLA gather/segment-sum path in ``sgcn_tpu.ops.pspmm``.

Status (PERF.md has the record): the graph SpMM is the framework's hot op
and is row-rate-bound in XLA's gather.  Mosaic exposes no batched-row DMA
and its ``tpu.dynamic_gather`` is single-vreg, so a Pallas kernel cannot
beat the row rate from HBM.  This kernel instead holds the whole feature
table VMEM-resident and accumulates per edge from SMEM-prefetched indices.
Its speed against the XLA path on the current code is not measured (the
builders' figure of ~1.3× at n≈2k, f=128 was taken on an earlier shared
development chip, ≤ 2026-07-31, before the PR-15 rewrite); what IS
established on the chip is that the selected forms compile and run and
match the ELL path (``chip_smoke.py``, kernel leg).  Two limits bound it:
the table must fit VMEM, and each call's three scalar-prefetch operands
must fit the chip's SMEM (``SMEM_BYTES``) — past either the Mosaic compile
fails, so the standalone ``spmm_pallas`` is opt-in and the trainers reach
the kernel only through ``use_pallas_spmm`` / ``choose_pallas_dispatch``.
It is the starting point for per-chip blocks small enough to pin in VMEM —
which is exactly what k-way partitioning produces as k grows (n/k ≈ 2k
rows at k≈64 for ogbn-arxiv).

Layout: edges are grouped into tiles of ``TB`` consecutive dst rows (plan
edge lists are dst-sorted already) and tiles into DEGREE-BINNED CLASSES
aligned with the plan's degree-bucket histogram (``ell_buckets`` /
``cell_buckets``): each class pads its tiles to its OWN ``Emax_c`` instead
of the hub tile's global max (Accel-GCN-style, arXiv:2308.11825 — a
one-hub BA graph no longer inflates every tile), and the kernel × schedule
choice is made PER CLASS (``choose_pallas_dispatch``): a hub class whose
serial per-tile edge chain exceeds ``pallas_emax_cap()``, or whose
prefetch operands exceed the chip's SMEM, stays on the XLA
gather/segment-sum form while the dense low-degree mass rides the VMEM
kernel.  The schedule-agnostic family:

  * ``pspmm_pallas_sym`` — dense-a2a exchange + class-dispatched kernels;
  * ``pspmm_pallas_ragged`` — the per-round ppermute ring's receive
    buffers feed the kernel DIRECTLY (the round-major concat is the
    kernel's halo-side table, tile sources re-based to ring positions at
    plan time, ``CommPlan.ensure_pallas_ragged_tiles``): no HBM halo
    table is ever materialized — the audit (``sgcn_tpu/analysis``) pins
    the absence of the ``(R, f)`` scatter per mode;
  * ``gat_pallas_pass`` — the GAT fused/split attention-table slot pass as
    a mask-weighted run of the same kernel over combined-edge tiles.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def tile_classes_from_buckets(buckets, num_rows: int, tb: int) -> tuple:
    """Per-class TILE counts, classes aligned to the degree-bucket
    histogram's row boundaries (rounded up to tile multiples) — the plan's
    existing degree profile drives the binning, so hub rows and the dense
    low-degree mass land in different classes and each class pads to its
    own ``Emax_c``.  Always covers all ``ceil(num_rows/tb)`` tiles."""
    t = max(1, -(-num_rows // tb))
    cuts = {t}
    cum = 0
    for nb, _wb in (buckets or ()):
        cum += int(nb)
        cuts.add(min(t, -(-cum // tb)))
    bounds = sorted(c for c in cuts if 0 < c <= t)
    out, prev = [], 0
    for c in bounds:
        if c > prev:
            out.append(c - prev)
            prev = c
    if prev < t:
        out.append(t - prev)
    return tuple(out)


def build_dst_tile_classes(edge_dst, edge_src, edge_w, num_rows: int,
                           tb: int, class_tiles) -> list:
    """Group dst-sorted edges into tiles of ``tb`` rows, binned into the
    given tile classes; per class, tiles pad to that class's own edge max.

    Returns a list over classes of ``(tsrc, tld, tw)`` — each
    ``(T_c, Emax_c)``, pad edges carrying weight 0 and local dst tb−1.
    The fill is ONE sliced numpy assignment per class (no per-tile Python
    loop — the O(T) interpreted loop of the original ``build_dst_tiles``
    was the preprocessing cost OGB-scale plans would pay).
    """
    edge_dst = np.asarray(edge_dst)
    edge_src = np.asarray(edge_src)
    edge_w = np.asarray(edge_w)
    t = int(sum(class_tiles))
    tile_of = edge_dst // tb
    counts = np.bincount(tile_of, minlength=t)
    starts = np.zeros(t + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    # position of each edge within its tile — edges are dst-sorted, so
    # per-tile runs are contiguous and this is pure arithmetic
    pos = np.arange(edge_dst.shape[0], dtype=np.int64) - starts[tile_of]
    out = []
    t0 = 0
    for tc in class_tiles:
        emax = max(8, int(counts[t0: t0 + tc].max()) if tc else 8)
        emax = -(-emax // 8) * 8
        tsrc = np.zeros((tc, emax), np.int32)
        tw = np.zeros((tc, emax), np.float32)
        tld = np.full((tc, emax), tb - 1, np.int32)
        sel = slice(int(starts[t0]), int(starts[t0 + tc]))
        ti = tile_of[sel] - t0
        pj = pos[sel]
        tsrc[ti, pj] = edge_src[sel]
        tw[ti, pj] = edge_w[sel]
        tld[ti, pj] = edge_dst[sel] - (ti + t0) * tb
        out.append((tsrc, tld, tw))
        t0 += tc
    return out


def build_dst_tiles(edge_dst, edge_src, edge_w, num_rows: int, tb: int = 256):
    """Group dst-sorted edges into ceil(num_rows/tb) row tiles (the single
    global-Emax layout — one class covering every tile).

    Returns ``(tsrc, tld, tw, padded_rows)`` — the first three in the exact
    positional order ``spmm_pallas`` consumes, each (T, Emax); pad edges
    carry weight 0 and local dst tb-1.  Output is bit-identical to the
    original per-tile Python loop (pinned by ``tests/test_pallas_spmm``).
    """
    t = max(1, -(-num_rows // tb))
    (tsrc, tld, tw), = build_dst_tile_classes(
        edge_dst, edge_src, edge_w, num_rows, tb, (t,))
    return tsrc, tld, tw, t * tb


@partial(jax.jit, static_argnames=("tb", "interpret", "emulate", "vma"))
def spmm_pallas(tsrc, tld, tw, table, tb: int = 256, interpret: bool = False,
                emulate: bool = False, vma: tuple | None = None):
    """Â·table via the tiled Pallas kernel.

    Args:
      tsrc/tld/tw: (T, Emax) tile arrays from ``build_dst_tiles``.
      table: (N, f) f32 feature rows (local ‖ halo), f a multiple of 128
        ideally, held VMEM-resident.  A bf16 table does NOT compile on
        the chip (the single-row dynamic load below is not provably
        8-row-aligned for a packed dtype — ``use_pallas_spmm`` never
        selects the kernel under a bf16 ``compute_dtype``).
      interpret: run ``pl.pallas_call`` in interpreter mode (CPU CI) — the
        kernel BODY executes, off-TPU.
      emulate: skip pallas entirely and run an exact jnp emulation of the
        tile semantics — used by the shard_map path off-TPU, where
        pallas interpret mode trips a JAX vma-analysis bug in its internal
        scan, and by tile classes whose kernel assignment is ``'ell'``
        (the XLA gather/segment-sum form IS this emulation).  Standalone
        CI keeps ``interpret=True`` so the kernel body and the
        vma-annotated out_shape stay covered off-TPU.
      vma: mesh axis names the output varies over — REQUIRED when called
        inside ``shard_map`` (pallas_call outputs must declare their
        varying axes under check_vma).

    Returns (T·tb, f) f32; slice to the true row count.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, emax = tsrc.shape
    f = table.shape[-1]
    if emulate:
        gathered = jnp.take(table, tsrc.reshape(-1), axis=0) \
            * tw.reshape(-1)[:, None]
        flat_dst = (jnp.arange(t, dtype=jnp.int32)[:, None] * tb
                    + tld).reshape(-1)
        return jax.ops.segment_sum(gathered.astype(jnp.float32), flat_dst,
                                   num_segments=t * tb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,     # tsrc, tld, tw land in SMEM (scalar reads)
        grid=(t,),
        in_specs=[
            # whole feature table resident in VMEM — the kernel's premise
            # (and its size limit; see module docstring)
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tb, f), lambda i, *pf: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tb, f), jnp.float32)],
    )

    def kernel(tsrc_pf, tld_pf, tw_pf, table_ref, out_ref, acc_ref):
        i = pl.program_id(0)
        acc_ref[:] = jnp.zeros_like(acc_ref)

        def body(e, _):
            src = tsrc_pf[i, e]
            ld = tld_pf[i, e]
            w = tw_pf[i, e]
            row = table_ref[pl.ds(src, 1), :].astype(jnp.float32)
            acc_ref[pl.ds(ld, 1), :] += w * row
            return 0

        jax.lax.fori_loop(0, tsrc_pf.shape[1], body, 0)
        out_ref[:] = acc_ref[:]

    out_shape = (jax.ShapeDtypeStruct((t * tb, f), jnp.float32)
                 if vma is None else
                 jax.ShapeDtypeStruct((t * tb, f), jnp.float32,
                                      vma=frozenset(vma)))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(tsrc, tld, tw, table)


def spmm_pallas_classes(flat_src, flat_ld, flat_w, table, classes,
                        tb: int, interpret: bool = False,
                        emulate: bool = False, vma: tuple | None = None):
    """Degree-binned kernel dispatch over flat tile-class arrays.

    ``classes = ((t_c, emax_c, kernel_c), ...)`` is the static per-class
    structure (``choose_pallas_dispatch``): class c owns the next
    ``t_c·emax_c`` flat slots, reshaped to its own ``(t_c, emax_c)`` pad,
    and runs the VMEM kernel (``'vmem'``) or the XLA gather/segment-sum
    form (``'ell'`` — hub classes whose serial per-tile chain would
    exceed the cap).  Per-row addition order is identical either way
    (edges stay in flat dst-sorted order; XLA's sorted scatter-add applies
    updates in order), so mixing kernels per class preserves the f32
    bit-parity contracts of the callers.  Returns ``(Σ t_c·tb, f)`` f32.
    """
    outs, off = [], 0
    for tc, ec, kern in classes:
        sl = slice(off, off + tc * ec)
        outs.append(spmm_pallas(
            flat_src[sl].reshape(tc, ec), flat_ld[sl].reshape(tc, ec),
            flat_w[sl].reshape(tc, ec), table, tb=tb, interpret=interpret,
            emulate=emulate or kern == "ell", vma=vma))
        off += tc * ec
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


# ------------------------------------------------- plan-driven selection
# Per-table VMEM budget for auto-selecting this kernel; past VMEM the Mosaic
# compile fails outright.  SGCN_PALLAS_SPMM=1 forces the choice wherever it
# FITS (tests), =0 disables, unset/auto selects on TPU only (off the chip
# the trainers run a jnp emulation — a correctness tool, not a fast path).
# SGCN_PALLAS_VMEM overrides the byte budget.
import os as _os

# SMEM one kernel call may spend on its scalar-prefetch operands, by
# ``device_kind``.  "TPU v5 lite": the compiler's own figure (``Used 1.27M
# of 1.00M smem``, PERF.md bring-up).  A TPU kind missing here selects no
# kernel — an assumed capacity is how a shape the compiler refuses gets
# picked.
SMEM_BYTES = {"TPU v5 lite": 1 << 20}
# the compiler's own SMEM use beside the operands measured ~2 KB per call
_SMEM_HEADROOM = 16 * 1024


def kernel_device():
    """The device the selection rule decides for (the default backend's
    first; meshes are homogeneous).  Off the chip the kernel is emulated."""
    return jax.devices()[0]


def _smem_budget() -> int | None:
    """SMEM bytes a kernel call's prefetch operands may take on
    ``kernel_device()``: ``None`` off-TPU (the emulation uses no SMEM),
    0 for a TPU kind the table does not know."""
    dev = kernel_device()
    if dev.platform != "tpu":
        return None
    return max(0, SMEM_BYTES.get(dev.device_kind, 0) - _SMEM_HEADROOM)


def prefetch_smem_bytes(t: int, emax: int) -> int:
    """SMEM the three ``(t, emax)`` 4-byte prefetch operands of one
    ``spmm_pallas`` call occupy: the compiler pads rows to 8 and columns to
    128 (10×6912 measured 432 KiB = 16×6912×4).  An upper bound for t < 8,
    where rows pad to the next power of two only."""
    return 3 * 4 * (-(-t // 8) * 8) * (-(-emax // 128) * 128)


def _pallas_table_budget() -> int:
    # read at call time so SGCN_PALLAS_VMEM set after import (monkeypatch,
    # programmatic use) takes effect — ADVICE r4
    return int(_os.environ.get("SGCN_PALLAS_VMEM", 4 * 1024 * 1024))


def pallas_emax_cap() -> int:
    """Per-class serial-chain cap of the kernel dispatch: a tile class
    whose ``Emax_c`` exceeds this runs the XLA gather/segment-sum form
    instead (the kernel's fori_loop is SERIAL per tile, so one hub row's
    edge count is wall-clock; the gather form vectorizes over rows).
    ``SGCN_PALLAS_EMAX`` overrides (read at call time, ADVICE r4)."""
    return int(_os.environ.get("SGCN_PALLAS_EMAX", 8192))


def _halo_table_rows(plan, schedule: str) -> int:
    """Rows of the halo-side kernel table: the dense halo pad for the a2a
    schedule, the ring's round-major receive concat (Σ_d S_d — it IS the
    table, no (R, f) halo buffer exists) for the ragged one."""
    if schedule == "ragged":
        try:
            sizes = (plan.rr_sizes if plan.rr_sizes is not None
                     else plan.ragged_round_sizes())
            return max(1, int(sum(sizes)))
        except ValueError:
            pass           # sliced plan: fall back to the dense halo pad
    return plan.r


def pallas_spmm_fits(plan, fin: int, widths, model: str = "gcn",
                     schedule: str = "a2a") -> bool:
    """True when every layer's per-chip f32 kernel tables fit the VMEM
    budget — the k-way-sharded regime the kernel was kept for (plan.b ≈ n/k
    shrinks as k grows).  GCN charges the [local] and [halo] tables
    separately (two kernel passes); GAT the combined ``[local ‖ halo]``
    (fout+1)-lane attention table (one pass).
    """
    budget = _pallas_table_budget()
    if model == "gat":
        lanes = max(int(w) + 1 for w in widths)
        rows = plan.b + _halo_table_rows(plan, schedule)
        return rows * lanes * 4 <= budget
    fmax = max([fin, *widths])
    return (plan.b * fmax * 4 <= budget
            and _halo_table_rows(plan, schedule) * fmax * 4 <= budget)


def use_pallas_spmm(plan, fin: int, widths, model: str = "gcn",
                    compute_dtype=None, schedule: str = "a2a") -> bool:
    """THE kernel-selection rule (schedule- and model-agnostic): the VMEM
    aggregator fires for symmetric f32 plans whose tables fit the budget,
    on either transport and for both models, on a TPU kind whose SMEM the
    table above knows.  A bf16 ``compute_dtype`` never selects it: the
    kernel's single-row dynamic load of a bf16 table does not compile
    (``cannot statically prove that index in dimension 0 is a multiple of
    8``), and GAT's packed wire form bit-pairs bf16 lanes into f32 words
    the f32 accumulate cannot consume — the slot-pass path serves both."""
    env = _os.environ.get("SGCN_PALLAS_SPMM", "auto")
    if env == "0":
        return False
    if compute_dtype is not None \
            and jnp.dtype(compute_dtype) == jnp.bfloat16:
        return False
    if not (plan.symmetric and pallas_spmm_fits(
            plan, fin, widths, model=model, schedule=schedule)):
        return False
    dev = kernel_device()
    on_tpu = dev.platform == "tpu"
    if on_tpu and dev.device_kind not in SMEM_BYTES:
        return False
    return env == "1" or on_tpu


def _assign_kernels(classes, smem_bytes: int | None = None) -> tuple:
    """((t_c, emax_c), ...) → ((t_c, emax_c, 'vmem'|'ell'), ...): the
    per-class kernel choice.  A class takes the XLA gather form when its
    serial chain exceeds ``pallas_emax_cap()`` or its prefetch operands
    exceed ``smem_bytes`` (``None`` = no SMEM limit, the emulated path)."""
    cap = pallas_emax_cap()

    def fits(t, e):
        return e <= cap and (smem_bytes is None
                             or prefetch_smem_bytes(t, e) <= smem_bytes)

    return tuple((t, e, "vmem" if fits(t, e) else "ell")
                 for t, e in classes)


def _classes_log(classes) -> list:
    return [{"tiles": t, "emax": e, "kernel": kern}
            for t, e, kern in classes]


def choose_pallas_dispatch(plan, model: str = "gcn",
                           schedule: str = "a2a", tb: int = 256,
                           decision: dict | None = None) -> dict:
    """Build the plan's tile-class layouts and assign a kernel per class —
    the degree-binned auto-dispatch of the ISSUE-15 tentpole.  Returns the
    static structures the forward threads through (``fwd_static``), and
    fills ``decision['pallas_dispatch']`` (landing in the run manifest's
    ``comm_schedule`` block) so the per-bucket choice is reconstructible
    from the run directory alone."""
    smem = _smem_budget()
    out: dict = {"pallas_tb": tb,
                 "pallas_emulate": kernel_device().platform != "tpu"}
    log = {"model": model, "schedule": schedule, "tb": tb,
           "emax_cap": pallas_emax_cap(), "smem_budget": smem}
    if model == "gat":
        plan.ensure_pallas_cell_tiles(tb)
        if schedule == "ragged":
            plan.ensure_pallas_cell_ragged_tiles()
        out["pallas_cclasses"] = _assign_kernels(plan.pallas_cclasses, smem)
        log["combined"] = _classes_log(out["pallas_cclasses"])
    else:
        plan.ensure_pallas_tiles(tb)
        if schedule == "ragged":
            plan.ensure_pallas_ragged_tiles()
        out["pallas_lclasses"] = _assign_kernels(plan.pallas_lclasses, smem)
        out["pallas_hclasses"] = _assign_kernels(plan.pallas_hclasses, smem)
        log["local"] = _classes_log(out["pallas_lclasses"])
        log["halo"] = _classes_log(out["pallas_hclasses"])
    if decision is not None:
        decision["pallas_dispatch"] = log
    return out


# plan arrays the Pallas GCN forwards ship.  The a2a flavor keeps the
# dense exchange layout + both tile-class families; the ragged flavor
# swaps (send_idx, halo_src, ptile_hsrc) for the ring layout: halo tiles
# re-based to RING positions (``ptile_hrsrc``) read the round-major
# receive concat directly — no (R, f) halo table exists in the program
# (the sgcn_tpu/analysis ``halo-materialization`` rule pins that).
PALLAS_PLAN_FIELDS = ("send_idx", "halo_src", "ptile_lsrc", "ptile_lld",
                      "ptile_lw", "ptile_hsrc", "ptile_hld", "ptile_hw")
PALLAS_PLAN_FIELDS_RAGGED = ("rsend_idx", "ptile_lsrc", "ptile_lld",
                             "ptile_lw", "ptile_hrsrc", "ptile_hld",
                             "ptile_hw")


def pallas_ring_concat(x, rsend_idx, rr_sizes, axis_name, halo_dtype=None):
    """The ragged ring's receive buffers, round-major-concatenated — the
    kernel's halo-side table.  Per live round (``ragged_live_rounds``, the
    shared elision rule) one ppermute ships the round's send gather;
    received buffers are NOT scattered into an (R, f) halo table — they
    concatenate in round order and the halo tile sources (re-based to ring
    positions at plan time) read them in place, so the fold happens inside
    the VMEM tile accumulator.  ``halo_dtype`` narrows the wire only."""
    from .pspmm import ppermute_or_identity, ragged_live_rounds

    segs = []
    live = ragged_live_rounds(rr_sizes)
    off = 0
    for d, sd in enumerate(rr_sizes, start=1):
        if d not in live:
            off += sd      # keep slice bookkeeping right under ANY rule
            continue
        buf = jnp.take(x, rsend_idx[off: off + sd], axis=0)
        if halo_dtype is not None:
            buf = buf.astype(halo_dtype)
        segs.append(ppermute_or_identity(buf, axis_name, d).astype(x.dtype))
        off += sd
    if not segs:                           # k=1 / all-empty ring
        return jnp.zeros((1, x.shape[-1]), x.dtype)
    return segs[0] if len(segs) == 1 else jnp.concatenate(segs)


def _pspmm_pallas_once(h, send_idx, halo_src, lsrc, lld, lw, hsrc, hld, hw,
                       tb, lclasses, hclasses, emulate, axis_name,
                       halo_dtype=None):
    from .pspmm import halo_exchange

    halo = halo_exchange(h, send_idx, halo_src, axis_name, halo_dtype)
    b = h.shape[0]
    # tile weights ride SMEM as f32
    local = spmm_pallas_classes(lsrc, lld, lw.astype(jnp.float32), h,
                                lclasses, tb, emulate=emulate,
                                vma=(axis_name,))[:b]
    remote = spmm_pallas_classes(hsrc, hld, hw.astype(jnp.float32), halo,
                                 hclasses, tb, emulate=emulate,
                                 vma=(axis_name,))[:b]
    return (local + remote).astype(h.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13, 14))
def pspmm_pallas_sym(h, send_idx, halo_src, lsrc, lld, lw, hsrc, hld, hw,
                     tb=256, lclasses=((1, 8, "vmem"),),
                     hclasses=((1, 8, "vmem"),), emulate=False,
                     axis_name="v", halo_dtype=None):
    """``pspmm_ell_sym`` with the VMEM-resident Pallas kernel as the local
    aggregator — same overlap structure (local pass independent of the
    exchange), same symmetric gather-only backward.  Selected by the
    trainer via ``use_pallas_spmm`` when per-chip tables fit VMEM.
    ``lclasses``/``hclasses`` are the degree-binned per-class kernel
    dispatch (``choose_pallas_dispatch``); ``emulate=True`` (the off-TPU
    shard_map path) swaps in the jnp emulation — see ``spmm_pallas``."""
    return _pspmm_pallas_once(h, send_idx, halo_src, lsrc, lld, lw,
                              hsrc, hld, hw, tb, lclasses, hclasses,
                              emulate, axis_name, halo_dtype)


def _pspmm_pallas_sym_fwd(h, send_idx, halo_src, lsrc, lld, lw, hsrc, hld,
                          hw, tb, lclasses, hclasses, emulate, axis_name,
                          halo_dtype):
    out = _pspmm_pallas_once(h, send_idx, halo_src, lsrc, lld, lw,
                             hsrc, hld, hw, tb, lclasses, hclasses,
                             emulate, axis_name, halo_dtype)
    return out, (send_idx, halo_src, lsrc, lld, lw, hsrc, hld, hw)


def _pspmm_pallas_sym_bwd(tb, lclasses, hclasses, emulate, axis_name,
                          halo_dtype, res, g):
    send_idx, halo_src, lsrc, lld, lw, hsrc, hld, hw = res
    gh = _pspmm_pallas_once(g, send_idx, halo_src, lsrc, lld, lw,
                            hsrc, hld, hw, tb, lclasses, hclasses,
                            emulate, axis_name, halo_dtype)
    return (gh,) + (None,) * 8


pspmm_pallas_sym.defvjp(_pspmm_pallas_sym_fwd, _pspmm_pallas_sym_bwd)


def _pspmm_pallas_ragged_once(h, rsend_idx, lsrc, lld, lw, rsrc, rld, rw,
                              tb, lclasses, hclasses, rr_sizes, emulate,
                              axis_name, halo_dtype=None):
    ring = pallas_ring_concat(h, rsend_idx, rr_sizes, axis_name, halo_dtype)
    b = h.shape[0]
    local = spmm_pallas_classes(lsrc, lld, lw.astype(jnp.float32), h,
                                lclasses, tb, emulate=emulate,
                                vma=(axis_name,))[:b]
    # fold-as-you-arrive inside the kernel: the halo tiles (same tile/edge
    # order as the a2a flavor's, sources re-based to ring positions) read
    # the receive concat directly — per-row addition sequence identical to
    # the a2a-pallas halo pass, hence f32-bit-identical outputs
    remote = spmm_pallas_classes(rsrc, rld, rw.astype(jnp.float32), ring,
                                 hclasses, tb, emulate=emulate,
                                 vma=(axis_name,))[:b]
    return (local + remote).astype(h.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12, 13, 14))
def pspmm_pallas_ragged(h, rsend_idx, lsrc, lld, lw, rsrc, rld, rw,
                        tb=256, lclasses=((1, 8, "vmem"),),
                        hclasses=((1, 8, "vmem"),), rr_sizes=(),
                        emulate=False, axis_name="v", halo_dtype=None):
    """``pspmm_pallas_sym`` on the ragged ppermute ring: per-round-sized
    ppermutes (empty rounds elided per ``ragged_live_rounds``) whose
    receive buffers ARE the kernel's halo-side table — the ragged fold is
    fused into the VMEM tile accumulator instead of materializing the HBM
    halo table first (``pallas_ring_concat``).  f32-bit-identical to the
    a2a flavor (same tile fold order; tile sources re-based at plan time,
    ``CommPlan.ensure_pallas_ragged_tiles``); the symmetric custom
    backward reuses the forward form on ``g`` — the gradient rides the
    same ring at the same round sizes.  Symmetric-Â plans only."""
    return _pspmm_pallas_ragged_once(h, rsend_idx, lsrc, lld, lw,
                                     rsrc, rld, rw, tb, lclasses, hclasses,
                                     rr_sizes, emulate, axis_name,
                                     halo_dtype)


def _pspmm_pallas_ragged_fwd(h, rsend_idx, lsrc, lld, lw, rsrc, rld, rw,
                             tb, lclasses, hclasses, rr_sizes, emulate,
                             axis_name, halo_dtype):
    out = _pspmm_pallas_ragged_once(h, rsend_idx, lsrc, lld, lw,
                                    rsrc, rld, rw, tb, lclasses, hclasses,
                                    rr_sizes, emulate, axis_name,
                                    halo_dtype)
    return out, (rsend_idx, lsrc, lld, lw, rsrc, rld, rw)


def _pspmm_pallas_ragged_bwd(tb, lclasses, hclasses, rr_sizes, emulate,
                             axis_name, halo_dtype, res, g):
    rsend_idx, lsrc, lld, lw, rsrc, rld, rw = res
    gh = _pspmm_pallas_ragged_once(g, rsend_idx, lsrc, lld, lw,
                                   rsrc, rld, rw, tb, lclasses, hclasses,
                                   rr_sizes, emulate, axis_name, halo_dtype)
    return (gh,) + (None,) * 7


pspmm_pallas_ragged.defvjp(_pspmm_pallas_ragged_fwd,
                           _pspmm_pallas_ragged_bwd)


def gat_pallas_pass(csrc, cld, cw, table, cclasses, tb: int,
                    emulate: bool, axis_name: str, num_rows: int):
    """One GAT attention slot pass on the VMEM kernel: a MASK-weighted
    (``cw`` ∈ {0, 1}, built at plan time — attention ignores Â's values)
    run of the class-dispatched kernel over the combined-edge tiles.  The
    caller feeds whichever table the form ships — the fused
    ``[p ‖ u]`` ``(·, fout+1)`` table (both lanes aggregate in one pass:
    ``out[:, :fout]`` = N, ``out[:, fout]`` = D) or the split pair's
    feature / scalar tables in two calls.  ``cw`` arrives at whatever
    width the trainer shipped it (``ForwardSetup.ship_arrays`` narrows the
    0/1 tiles to int8 — the f32 form is real per-chip argument bytes at
    products scale) and upcasts here, like the GCN wrappers' ``lw``.
    Returns ``(num_rows, lanes)`` f32."""
    return spmm_pallas_classes(csrc, cld, cw.astype(jnp.float32), table,
                               cclasses, tb, emulate=emulate,
                               vma=(axis_name,))[:num_rows]
