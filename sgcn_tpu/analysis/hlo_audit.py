"""The compiled-program contract auditor (pass 1 of ``sgcn_tpu.analysis``).

For every supported configuration of the mode matrix (``modes``), lower
the REAL program — the trainer's step via ``FullBatchTrainer.lower_step``
(both the stale and full-sync programs for pipelined modes), the
mini-batch shared-envelope step via ``MiniBatchTrainer.lower_step``, the
serve bucket program via ``ServeEngine.lower_bucket`` — on the virtual
8-device mesh (``.lower()`` only: no compile, no execution) and check the
module text against the plan-derived :class:`~.expect.Expectation`:

  * **collective census** — exactly one ``all_to_all`` per dense exchange;
    exactly one ``collective_permute`` per LIVE ragged round (empty rounds
    elided, pinned on a banded fixture whose ring keeps 2 of k−1 rounds);
    one full-mesh grad-psum per parameter leaf; one logit-gather psum per
    serve program; the GAT per-layer softmax ``pmax``; nothing else — no
    ``all_gather``/``reduce_scatter``, no sub-mesh replica groups;
  * **wire dtype** — bf16 actually ON the wire when ``--halo-dtype
    bfloat16`` (or the GAT packed form) was requested, and the full f32
    wire on ``--halo-delta`` sync-step re-bases;
  * **wire shape** — buffers match ``CommPlan.wire_buffer_shapes`` ×
    the model's lane widths (the ``(k, S, f)`` pad / per-round ``S_d``);
  * **host callbacks** — no python-callback custom calls, no
    infeed/outfeed/send/recv, no unknown custom-call targets;
  * **donation** — params, optimizer state and stale carries carry
    ``jax.buffer_donor`` (the lowering-time face of ``donate_argnums``);
    plan arrays and batch data do NOT; serve programs donate NOTHING.

A violation names its rule (``collective-census`` / ``wire-dtype`` /
``wire-shape`` / ``host-callback`` / ``donation`` /
``halo-materialization`` — the ragged-Pallas modes' "no HBM halo table"
contract) so the tier-1 mutation checks (``tests/test_analysis.py``,
``tests/test_pallas_ragged.py``) can prove each rule class fails on a
seeded violation.

A second, COMPILING pass (``run_memory_audit`` / ``memory_audit_mode``)
shares the same program builders via ``lower_mode_programs`` and joins
``compiled.memory_analysis()`` against the owner's analytic per-chip
footprint model (``sgcn_tpu.obs.memory``) — the ``memory-model`` rule:
measured peak within tolerance of the analytic total, argument bytes a
subset of the modeled residency, and donation aliasing at least the
params+opt floor (zero for serve).  Mutation-checked by seeding a
stripped ``donate_argnums`` (``tests/test_memory_obs.py``).
"""

from __future__ import annotations

import contextlib
import os
import re
from collections import Counter
from functools import lru_cache

import numpy as np

from . import expect
from .hlo import (HOST_TRANSFER_KINDS, collective_ops, host_callback_targets,
                  main_args, unknown_custom_calls)
from .modes import Mode, fast_modes, supported_modes

# audit fixture dimensions: small enough that a full-matrix run is tens of
# seconds of pure lowering, structured enough that nothing degenerates
# (k=8 chips, every chip has real halo traffic, widths hit both the
# aggregate-first order and an even fout for the GAT packed form)
AUDIT_K = 8
AUDIT_N = 96
AUDIT_FIN = 8
AUDIT_WIDTHS = (8, 4)
# the deep stack's widths are [hidden] * layers + [classes]: four layers,
# so the scanned body stands for three
AUDIT_DEEP_WIDTHS = (8, 8, 8, 8, 4)
# replica-mode audits run at this fixed budget: large enough that the
# shrunken nrep pads differ from the full ones on the ER fixture (the
# wire-shape rule sees real shrinkage), small enough that every chip
# keeps non-replica traffic (all rounds stay live)
AUDIT_REPLICA_B = 12
# the relational model's configuration over the fixture's rows: a featured,
# labelled type and an embedded one, related both ways, the first to itself
AUDIT_REL_ARGS = {
    "types": [{"name": "a", "count": AUDIT_N // 2, "input": "features"},
              {"name": "b", "count": AUDIT_N - AUDIT_N // 2,
               "input": "embedding"}],
    "relations": [("a", "aa", "a"), ("a", "ab", "b"), ("b", "ba", "a")],
    "label_type": "a"}


@lru_cache(maxsize=None)
def audit_plan(kind: str = "er"):
    """The audit's graph fixtures.

    ``'er'``: an Erdős–Rényi graph under a balanced random partition —
    every chip pair exchanges rows, so all k−1 ragged rounds are live (the
    dense census).  ``'banded'``: a ±2-ring graph under a CONTIGUOUS
    partition — each part talks only to its neighbors, so exactly rounds
    d ∈ {1, k−1} are live and the other k−3 must be ELIDED from the traced
    program (the empty-round census).
    """
    import scipy.sparse as sp

    from ..io.datasets import er_graph
    from ..parallel import build_comm_plan
    from ..partition import balanced_random_partition
    from ..prep import normalize_adjacency

    if kind == "er":
        ahat = normalize_adjacency(er_graph(AUDIT_N, 6, seed=0))
        pv = balanced_random_partition(AUDIT_N, AUDIT_K, seed=1)
    elif kind == "banded":
        n = AUDIT_N
        rows = np.concatenate([np.arange(n), np.arange(n)])
        cols = np.concatenate([(np.arange(n) + 1) % n,
                               (np.arange(n) + 2) % n])
        a = sp.coo_matrix((np.ones(2 * n, np.float32),
                           (rows, cols)), shape=(n, n))
        ahat = normalize_adjacency(((a + a.T) > 0).astype(np.float32))
        pv = np.arange(n) * AUDIT_K // n           # contiguous parts
    else:
        raise ValueError(f"unknown audit fixture {kind!r}")
    plan = build_comm_plan(ahat, pv, AUDIT_K)
    return plan


@contextlib.contextmanager
def _pallas_env(on: bool):
    """Pin the kernel-family selection for the duration of a trace:
    ``use_pallas_spmm`` reads ``$SGCN_PALLAS_SPMM`` at call time, and the
    audit must be deterministic BOTH ways — a pallas mode forces the
    kernel on, every other mode forces it off (an ambient =1 in the
    operator's shell must not flip the non-pallas census)."""
    old = os.environ.get("SGCN_PALLAS_SPMM")
    os.environ["SGCN_PALLAS_SPMM"] = "1" if on else "0"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("SGCN_PALLAS_SPMM", None)
        else:
            os.environ["SGCN_PALLAS_SPMM"] = old


@contextlib.contextmanager
def _gat_form_env(form: str | None):
    """Pin the GAT table form for the duration of a trace: the forward
    reads ``$SGCN_GAT_FUSED`` at call time (``models.gat._fused_form``),
    so the env must hold while ``.lower()`` traces."""
    if form is None or form == "packed":
        # packed is selected by compute_dtype, not env
        yield
        return
    old = os.environ.get("SGCN_GAT_FUSED")
    os.environ["SGCN_GAT_FUSED"] = {"fused": "2", "split": "0"}[form]
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("SGCN_GAT_FUSED", None)
        else:
            os.environ["SGCN_GAT_FUSED"] = old


# ------------------------------------------------------------------ checks
def _viol(rule: str, detail: str) -> dict:
    return {"rule": rule, "detail": detail}


def _multiset_diff(expected, observed):
    e, o = Counter(expected), Counter(observed)
    missing = list((e - o).elements())
    extra = list((o - e).elements())
    return missing, extra


def _full_mesh_groups(op, k: int) -> bool:
    """True iff the op reduces over ONE group of all ``k`` devices.  A
    sub-mesh reduction prints as MULTIPLE groups (``dense<[[0, 1, 2, 3],
    [4, 5, 6, 7]]>``) — the realistic regression shape — or as one group
    smaller than ``k``; both must fail."""
    m = re.search(r"replica_groups\s*=\s*dense<\[(.*?)\]>\s*:", op.text,
                  re.S)
    if not m:
        return True        # unusual print form: do not false-positive
    groups = re.findall(r"\[([0-9,\s]*)\]", m.group(1))
    if not groups:
        # a 1-group form may print without inner brackets
        groups = [m.group(1)]
    if len(groups) != 1:
        return False
    return len([x for x in groups[0].split(",") if x.strip()]) == k


def check_program(text: str, exp: "expect.Expectation", k: int) -> tuple:
    """Audit one lowered module against its expectation; returns
    ``(violations, census)``."""
    ops = collective_ops(text)
    violations: list[dict] = []

    # ---- census of exchange collectives (count + shape + dtype)
    ex_ops = [op for op in ops
              if op.kind in ("all_to_all", "collective_permute")]
    observed = [(op.kind, op.wire[0], op.wire[1]) for op in ex_ops]
    if Counter(observed) != Counter(exp.exchanges):
        by_kind_o = Counter(kind for kind, _, _ in observed)
        by_kind_e = Counter(kind for kind, _, _ in exp.exchanges)
        if by_kind_o != by_kind_e:
            violations.append(_viol(
                "collective-census",
                f"exchange dispatch counts {dict(by_kind_o)} != expected "
                f"{dict(by_kind_e)} (one all_to_all per dense exchange, "
                "one collective_permute per LIVE ragged round)"))
        shp_o = Counter((kk, s) for kk, s, _ in observed)
        shp_e = Counter((kk, s) for kk, s, _ in exp.exchanges)
        if shp_o != shp_e:
            miss, extra = _multiset_diff(
                [(kk, s) for kk, s, _ in exp.exchanges],
                [(kk, s) for kk, s, _ in observed])
            violations.append(_viol(
                "wire-shape",
                f"wire buffer shapes drifted from the plan pads: "
                f"missing={miss} unexpected={extra}"))
        dt_o = Counter((kk, d) for kk, _, d in observed)
        dt_e = Counter((kk, d) for kk, _, d in exp.exchanges)
        if dt_o != dt_e:
            miss, extra = _multiset_diff(
                [(kk, d) for kk, _, d in exp.exchanges],
                [(kk, d) for kk, _, d in observed])
            violations.append(_viol(
                "wire-dtype",
                f"wire operand dtypes != requested: missing={miss} "
                f"unexpected={extra}"))
        if by_kind_o == by_kind_e and shp_o == shp_e and dt_o == dt_e:
            violations.append(_viol(
                "wire-dtype",
                "exchange (shape, dtype) pairing drifted: "
                f"observed={sorted(map(str, observed))} "
                f"expected={sorted(map(str, exp.exchanges))}"))

    # ---- census of reductions
    reduces = [op for op in ops if op.kind == "all_reduce"]
    grad_like, scalar_adds, maxes, other = [], 0, 0, []
    tensor_expected = (Counter(exp.grad_shapes) + Counter(exp.gather_shapes)
                       + Counter(exp.stat_shapes))
    for op in reduces:
        shape, _dt = op.wire
        if op.reducer == "maximum":
            maxes += 1
        elif op.reducer == "add" and shape == ():
            scalar_adds += 1
        elif op.reducer == "add":
            grad_like.append(shape)
        else:
            other.append((op.reducer, shape))
        if not _full_mesh_groups(op, k):
            violations.append(_viol(
                "collective-census",
                f"all_reduce at line {op.line} reduces over a sub-mesh "
                "replica group — every psum in these programs is "
                "full-mesh"))
    if Counter(grad_like) != tensor_expected:
        miss, extra = _multiset_diff(list(tensor_expected.elements()),
                                     grad_like)
        violations.append(_viol(
            "collective-census",
            "grad-sync/logit-gather/statistics psum census: one full-mesh add-"
            f"allreduce per tensor expected; missing={miss} "
            f"unexpected={extra}"))
    if scalar_adds != exp.scalar_psums:
        violations.append(_viol(
            "collective-census",
            f"{scalar_adds} scalar add-allreduces, expected "
            f"{exp.scalar_psums} (the masked-loss machinery — "
            "expect.XENT_SCALAR_PSUMS)"))
    if maxes != exp.max_psums:
        violations.append(_viol(
            "collective-census",
            f"{maxes} max-allreduces, expected {exp.max_psums} (the "
            "softmax stabilizer pmax: per GAT layer, per deep-stack body)"))
    if other:
        violations.append(_viol(
            "collective-census", f"unclassifiable all_reduce ops: {other}"))
    stray = [op.kind for op in ops
             if op.kind in ("all_gather", "reduce_scatter")]
    if stray:
        violations.append(_viol(
            "collective-census",
            f"unexpected collective kinds {Counter(stray)} — these "
            "programs ship halos by all_to_all/ppermute and reduce by "
            "psum only"))

    # ---- halo materialization (ragged-Pallas modes): the ring's receive
    # buffers must feed the kernel directly — a scatter producing the
    # (R, f_ℓ) halo-table signature means the program assembled the HBM
    # halo table first (expect.pallas_ragged_forbidden_scatters; shapes
    # colliding with legitimate scatters were dropped at build time)
    if exp.forbidden_scatters:
        from .hlo import scatter_result_types

        seen = {tuple(s) for s, _d in scatter_result_types(text)}
        hits = [s for s in exp.forbidden_scatters if tuple(s) in seen]
        if hits:
            violations.append(_viol(
                "halo-materialization",
                f"scatter(s) with halo-table result shape(s) {hits} — "
                "the ragged-Pallas program must fold ring receives "
                "inside the VMEM tile accumulator, never assemble the "
                "(R, f) halo table in HBM"))

    # ---- host transfers / callbacks
    transfers = [op.kind for op in ops if op.kind in HOST_TRANSFER_KINDS]
    if transfers:
        violations.append(_viol(
            "host-callback",
            f"host-transfer ops {Counter(transfers)} inside a step "
            "program"))
    cbs = host_callback_targets(text)
    if cbs:
        violations.append(_viol(
            "host-callback",
            f"python-callback custom calls {cbs} inside a step program — "
            "a host round-trip on the hot path"))
    unknown = unknown_custom_calls(text)
    if unknown:
        violations.append(_viol(
            "host-callback",
            f"unrecognized custom-call targets {sorted(set(unknown))} — "
            "extend hlo.BENIGN_CUSTOM_CALLS only after establishing the "
            "target stays on-device"))

    # ---- donation / aliasing (ONE parse of the argument list — a printer
    # change that breaks @main parsing must land as a reported violation,
    # never as an uncaught exception aborting the whole audit)
    try:
        args = main_args(text)
    except ValueError as e:
        args = None
        violations.append(_viol("donation", str(e)))
    if args is not None:
        violations += check_donation(args, exp)

    census = {
        "all_to_all": sum(1 for o in observed if o[0] == "all_to_all"),
        "collective_permute": sum(1 for o in observed
                                  if o[0] == "collective_permute"),
        "all_reduce": {"tensor_add": len(grad_like),
                       "scalar_add": scalar_adds, "max": maxes},
        "wire_dtypes": sorted({d for _, _, d in observed}),
        "donated_args": (None if args is None
                         else sum(1 for a in args if a.donated)),
    }
    return violations, census


def check_donation(args, exp: "expect.Expectation") -> list[dict]:
    """Align the module's arguments with the expected (shape, dtype, class)
    layout and verify ``jax.buffer_donor`` markers: every surviving
    donate-class argument (params, optimizer state, stale carries) must
    carry one; no keep-class argument (plan arrays, batch data, serve
    inputs) may.  Arguments jit pruned as unused (e.g. the non-delta base
    placeholders, a dead ghalo) show up as skips in the order-preserving
    alignment — donation of a DEAD buffer is not a contract.  ``args`` is
    the module's parsed ``hlo.main_args`` list (the caller parses once,
    shared with the census)."""
    violations = []
    ei = 0
    for a in args:
        while ei < len(exp.args) and \
                (exp.args[ei][0], exp.args[ei][1]) != a.type:
            ei += 1                    # expected arg pruned from the module
        if ei == len(exp.args):
            violations.append(_viol(
                "donation",
                f"%arg{a.index} tensor<{a.type}> does not align with the "
                "expected argument layout (params, opt state, carries, "
                "plan arrays, data) — argument-order drift"))
            return violations
        shape, dt, klass = exp.args[ei]
        ei += 1
        if klass == "donate" and not a.donated:
            violations.append(_viol(
                "donation",
                f"%arg{a.index} tensor{shape}x{dt} (params/opt-state/"
                "stale-carry class) carries no jax.buffer_donor — "
                "donate_argnums dropped; the step would double-buffer "
                "every update"))
        elif klass == "keep" and a.donated:
            violations.append(_viol(
                "donation",
                f"%arg{a.index} tensor{shape}x{dt} (plan-array/data "
                "class) is donated — reused buffers must not be"))
    return violations


# -------------------------------------------------------------- mode audit
def lower_mode_programs(mode: Mode, plan=None) -> tuple:
    """Build the real trainer/engine for ``mode`` and lower its program(s)
    WITHOUT rendering; returns ``(owner, [(label, lowered, expectation)])``.

    ``owner`` is the trainer/engine that built the programs — it carries
    the analytic per-chip footprint model as ``.memory`` — and each
    ``lowered`` is the un-compiled jax AOT lowering: the text audit renders
    it (``.as_text()``), the memory audit compiles it (``.compile()``) and
    joins ``compiled.memory_analysis()`` against ``owner.memory``.  Both
    passes share the SAME builders so they can never audit divergent
    programs."""
    from ..train import FullBatchTrainer

    plan = audit_plan() if plan is None else plan
    if mode.workload == "train":
        kw: dict = {"comm_schedule": mode.schedule}
        if mode.model == "gcn":
            kw.update(halo_dtype=mode.halo_dtype,
                      halo_staleness=mode.staleness,
                      halo_delta=mode.delta,
                      sync_every=2 if (mode.staleness or mode.replica)
                      else 0,
                      replica_budget=AUDIT_REPLICA_B if mode.replica
                      else 0)
        elif mode.model == "gat":
            kw.update(compute_dtype=mode.compute_dtype)
        elif mode.model == "rgcn":
            kw.update(model_args=AUDIT_REL_ARGS)
        widths = (AUDIT_DEEP_WIDTHS if mode.model == "deepergcn"
                  else AUDIT_WIDTHS)
        with _gat_form_env(mode.gat_form), \
                _pallas_env(getattr(mode, "pallas", False)):
            tr = FullBatchTrainer(plan, fin=AUDIT_FIN, widths=list(widths),
                                  model=mode.model, **kw)
            # the audit must never silently check the WRONG aggregator:
            # a pallas mode that fell back to the slot-pass path would
            # share its census and pass vacuously
            if getattr(mode, "pallas", False) != \
                    ("pallas_tb" in tr._fwd_static):
                raise RuntimeError(
                    f"mode {mode.mode_id}: Pallas selection "
                    f"{'did not fire' if mode.pallas else 'fired'} "
                    "(fwd_static keys "
                    f"{sorted(tr._fwd_static)})")
            if mode.staleness:
                return tr, [
                    ("stale", tr.lower_step(kind="stale"),
                     expect.train_expectation(tr, mode, fresh=False)),
                    ("sync", tr.lower_step(kind="sync"),
                     expect.train_expectation(tr, mode, fresh=True)),
                ]
            if mode.replica:
                # both programs of a replica mode are audited: the replica
                # step must ship the SHRUNKEN wire shapes, the refresh step
                # the full exact exchange (with every backward exchange
                # kept alive by the gradient-replica refresh)
                return tr, [
                    ("rep", tr.lower_step(kind="rep"),
                     expect.train_expectation(tr, mode, fresh=False)),
                    ("sync", tr.lower_step(kind="rep_sync"),
                     expect.train_expectation(tr, mode, fresh=True)),
                ]
            return tr, [("step", tr.lower_step(),
                         expect.train_expectation(tr, mode))]
    if mode.workload == "minibatch":
        from ..train.minibatch import MiniBatchTrainer

        if plan is not None and plan is not audit_plan():
            raise ValueError(
                "the minibatch audit entry builds its own per-batch plans "
                "from the ER fixture graph; a custom plan would be "
                "silently ignored here — extend lower_mode_programs "
                "instead")
        with _pallas_env(False):
            mb = MiniBatchTrainer(
                _audit_ahat(), np.asarray(audit_plan().owner), AUDIT_K,
                fin=AUDIT_FIN, widths=list(AUDIT_WIDTHS),
                batch_size=AUDIT_N // 2, nbatches=2,
                comm_schedule=mode.schedule)
            return mb, [("envelope-step", mb.lower_step(),
                         expect.train_expectation(mb.inner, mode))]
    if mode.workload == "serve":
        from ..serve.engine import ServeEngine

        bucket = 8
        with _gat_form_env(mode.gat_form), _pallas_env(False):
            eng = ServeEngine(plan, fin=AUDIT_FIN,
                              widths=list(AUDIT_WIDTHS), model=mode.model,
                              comm_schedule=mode.schedule,
                              halo_dtype=mode.halo_dtype,
                              max_batch=bucket, buckets=(bucket,),
                              precompile=False)
            return eng, [(f"bucket{bucket}",
                          eng.lower_bucket(bucket),
                          expect.serve_expectation(eng, mode, bucket))]
    if mode.workload == "serve_subgraph":
        from ..serve.engine import ServeEngine

        with _gat_form_env(mode.gat_form), _pallas_env(False):
            eng = ServeEngine(plan, fin=AUDIT_FIN,
                              widths=list(AUDIT_WIDTHS), model=mode.model,
                              comm_schedule=mode.schedule,
                              halo_dtype=mode.halo_dtype,
                              max_batch=8, buckets=(8,),
                              precompile=False, mode="subgraph")
            from ..serve.subgraph import representative_key

            key = representative_key(eng.sgindex)
            return eng, [("subgraph",
                          eng.lower_subgraph(key),
                          expect.serve_subgraph_expectation(eng, mode, key))]
    raise ValueError(f"unknown workload {mode.workload!r}")


def lower_mode(mode: Mode, plan=None) -> list[tuple]:
    """Build the real trainer/engine for ``mode`` and lower its program(s);
    returns ``[(program_label, module_text, expectation)]``."""
    _owner, programs = lower_mode_programs(mode, plan=plan)
    return [(label, lowered.as_text(), exp)
            for label, lowered, exp in programs]


@lru_cache(maxsize=1)
def _audit_ahat():
    from ..io.datasets import er_graph
    from ..prep import normalize_adjacency

    return normalize_adjacency(er_graph(AUDIT_N, 6, seed=0))


def audit_mode(mode: Mode, plan=None) -> dict:
    """Lower and audit one mode; returns its report entry."""
    programs = lower_mode(mode, plan=plan)
    entry: dict = {"ok": True, "programs": {}}
    for label, text, exp in programs:
        violations, census = check_program(text, exp, AUDIT_K)
        entry["programs"][label] = {
            "ok": not violations,
            "violations": violations,
            "census": census,
        }
        entry["ok"] = entry["ok"] and not violations
    return entry


# the banded fixture's ragged modes (the empty-round-elision census)
BANDED_MODES = (
    Mode("train", "gcn", "ragged"),
    Mode("train", "gcn", "ragged", staleness=1),
    # the composed replica × stale ring: the SHRUNKEN nrep ring's empty
    # rounds must elide too
    Mode("train", "gcn", "ragged", staleness=1, replica=True),
    # the ragged-Pallas ring rides the same elision rule
    # (pallas_ring_concat skips S_d = 0 rounds at trace time) — and the
    # halo-materialization rule must hold on a partially-live ring too
    Mode("train", "gcn", "ragged", pallas=True),
)


def run_audit(modes=None, fast: bool = False) -> dict:
    """Audit the mode matrix; returns the ``hlo`` block of the analysis
    report.  ``fast`` audits the 2-mode smoke subset; the full run also
    audits the banded fixture's ragged modes (the empty-round-elision
    census: only 2 of k−1 rounds may appear in the program)."""
    if modes is None:
        modes = fast_modes() if fast else supported_modes()
    out: dict = {"modes": {}, "ok": True}
    for mode in modes:
        entry = audit_mode(mode)
        out["modes"][mode.mode_id] = entry
        out["ok"] = out["ok"] and entry["ok"]
    if not fast:
        from ..ops.pspmm import ragged_live_rounds

        banded = audit_plan("banded")
        live = ragged_live_rounds(banded.ragged_round_sizes())
        assert len(live) < AUDIT_K - 1, (
            "banded fixture lost its empty rounds — the elision census "
            "checks nothing")
        for mode in BANDED_MODES:
            entry = audit_mode(mode, plan=banded)
            out["modes"][mode.mode_id + "@banded"] = entry
            out["ok"] = out["ok"] and entry["ok"]
    out["n_modes"] = len(out["modes"])
    return out


# ------------------------------------------------------------ memory audit
def memory_audit_mode(mode: Mode, plan=None,
                      tol: float | None = None) -> dict:
    """COMPILE every program of ``mode`` and reconcile XLA's own
    ``memory_analysis()`` figures against the owner's analytic footprint
    model (``trainer.memory`` / ``engine.memory``); returns the mode's
    report entry.  Violations carry the ``memory-model`` rule:

      * measured peak must stay within ``MEM_MODEL_TOL`` × the analytic
        total (the model is the residency upper envelope);
      * measured argument bytes must not exceed the modeled resident
        arguments (jit prunes inputs, it never invents them);
      * aliased (donated) bytes must cover the params+opt floor on train
        programs and be exactly zero on serve programs — a stripped
        ``donate_argnums`` trips this deterministically (the mutation
        check of ``tests/test_memory_obs.py``).

    Unlike the text audit this pass compiles (~1 s/program on the CPU
    mesh), so callers subset the matrix: the tier-1 test pins family
    representatives, the full sweep rides ``python -m sgcn_tpu.analysis
    --memory``.
    """
    from ..obs.memory import MEM_MODEL_TOL, measure_compiled, reconcile

    owner, programs = lower_mode_programs(mode, plan=plan)
    model = owner.memory
    entry: dict = {"ok": True, "model_bytes": model.total_bytes,
                   "programs": {}}
    for label, lowered, _exp in programs:
        measured = measure_compiled(lowered.compile())
        if measured is None:
            # the backend exposes no memory_analysis(): the measured side
            # is unverifiable here — surface that, don't fail (every CI
            # backend exposes it; the analytic side still gates budgets)
            entry["programs"][label] = {"ok": True, "skipped": True,
                                        "violations": [], "measured": None}
            continue
        rec = reconcile(model, measured,
                        tol=MEM_MODEL_TOL if tol is None else tol)
        violations = [_viol("memory-model", v) for v in rec["violations"]]
        entry["programs"][label] = {
            "ok": not violations,
            "violations": violations,
            "measured": measured,
            "ratio": rec["block"]["total"]["ratio"],
        }
        entry["ok"] = entry["ok"] and not violations
    return entry


def run_memory_audit(modes=None, fast: bool = False) -> dict:
    """Memory-reconcile the mode matrix; returns the ``memory`` block of
    the analysis report.  Same shape contract as :func:`run_audit`
    (``{modes: {mode_id: entry}, ok, n_modes, tol}``) so the report
    renderer and the gate logic treat both passes uniformly."""
    from ..obs.memory import MEM_MODEL_TOL

    if modes is None:
        modes = fast_modes() if fast else supported_modes()
    out: dict = {"modes": {}, "ok": True, "tol": MEM_MODEL_TOL}
    for mode in modes:
        entry = memory_audit_mode(mode)
        out["modes"][mode.mode_id] = entry
        out["ok"] = out["ok"] and entry["ok"]
    out["n_modes"] = len(out["modes"])
    return out
