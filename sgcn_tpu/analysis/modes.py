"""The mode-matrix enumerator — ONE source of truth for "which
configurations does this repo support", shared by the HLO auditor, the
tests, and the composition matrix in ``docs/comm_schedule.md``.

A :class:`Mode` names one point of the support matrix:

    {train, serve} × {gcn, gat} × {a2a, ragged} × staleness {0, 1}
    × halo-dtype {f32, bf16} × delta {off, on} × GAT table form

plus the one mode of the deep residual stack (``deepergcn``: exact
full-batch training on the a2a schedule, float32).

``supported_modes()`` enumerates exactly the combinations the trainers and
the serve engine accept — the same gates ``FullBatchTrainer.__init__`` and
``ServeEngine.__init__`` enforce at construction time, encoded ONCE more
here so the auditor cannot silently skip a supported mode and the doc
matrix cannot drift (``tests/test_analysis.py`` cross-checks the table).

``MODE_FLAGS`` maps every mode-selecting CLI flag to its matrix axis; the
AST hygiene pass (``ast_rules``) asserts every ``--comm-*`` / ``--halo-*``
flag any CLI defines appears here, so a new transport/wire knob cannot
land without extending the enumerator (and therefore the audit).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field

# mode-selecting CLI flags → matrix axis.  The AST pass enforces the
# reverse direction too: every MODE_FLAGS key must exist on the trainer
# CLI (no dead axes).
MODE_FLAGS = {
    "--model": "model",
    "--comm-schedule": "schedule",
    "--halo-staleness": "staleness",
    "--halo-dtype": "halo_dtype",
    "--halo-delta": "delta",
    "--replica-budget": "replica",
}

# knobs that look mode-like but are deliberately NOT matrix axes — named
# here so the exclusion is a recorded decision, not an oversight
NON_AXIS_FLAGS = {
    "--sync-every": "continuous schedule knob — audited via the stale/sync "
                    "program PAIR every stale mode lowers, not as an axis",
    "--refresh-band": "continuous refresh-policy knob of the replica mode "
                      "(drift-banded partial refresh) — its program is "
                      "exercised by tests/test_replica_stale.py; deferred "
                      "as an audit axis",
}

GAT_FORMS = ("fused", "split", "packed")


@dataclass(frozen=True)
class Mode:
    """One point of the supported configuration matrix."""

    workload: str                  # 'train' | 'serve' | 'serve_subgraph'
    #                                | 'minibatch'; 'serve_subgraph' is the
    #                                query-proportional serving program
    #                                (docs/serving.md phase 2): no
    #                                per-layer exchange, one logit psum
    model: str                     # 'gcn' | 'gat' | 'deepergcn' | 'rgcn'
    #                                (the deep residual stack and the
    #                                relational model: ONE mode each —
    #                                exact full-batch training on the a2a
    #                                schedule, f32)
    schedule: str                  # 'a2a' | 'ragged'
    staleness: int = 0             # 0 exact | 1 pipelined
    halo_dtype: str | None = None  # None (f32 wire) | 'bfloat16'
    delta: bool = False            # halo-delta cache (stale GCN only)
    gat_form: str | None = None    # 'fused' | 'split' | 'packed' (GAT only)
    replica: bool = False          # hot-halo replication, B > 0 (GCN only;
    #                                the axis is binary — the audit runs at
    #                                a fixed small B, hlo_audit.AUDIT_REPLICA_B)
    pallas: bool = False           # VMEM Pallas aggregator (exact mode,
    #                                both models × both schedules — the
    #                                env-selected kernel family,
    #                                ops/pallas_spmm.py::use_pallas_spmm;
    #                                the audit pins SGCN_PALLAS_SPMM per
    #                                mode)

    @property
    def mode_id(self) -> str:
        parts = [self.workload, self.model, self.schedule]
        if self.model == "gat":
            parts.append(self.gat_form or "fused")
        else:
            parts.append(f"s{self.staleness}")
            parts.append("bf16" if self.halo_dtype == "bfloat16" else "f32")
            if self.delta:
                parts.append("delta")
            if self.replica:
                parts.append("rep")
        if self.pallas:
            parts.append("pallas")
        return "/".join(parts)

    @property
    def compute_dtype(self) -> str | None:
        """The trainer-level lever that selects the GAT packed wire form
        (``models.gat.gat_table_form``); GCN modes never set it — their
        narrow-wire lever is ``halo_dtype``."""
        return "bfloat16" if self.gat_form == "packed" else None


def is_supported(mode: Mode) -> tuple[bool, str]:
    """(supported?, reason) — the construction-time gates of the trainers
    and the serve engine, restated.  The reason strings mirror the errors
    the constructors raise, so a drift shows up as a wording mismatch in
    review, not a silent matrix hole."""
    m = mode
    if m.workload not in ("train", "serve", "serve_subgraph", "minibatch"):
        return False, f"unknown workload {m.workload!r}"
    if m.model not in ("gcn", "gat", "deepergcn", "rgcn"):
        return False, f"unknown model {m.model!r}"
    if m.model == "rgcn" and (
            m.workload != "train" or m.schedule != "a2a" or m.staleness
            or m.halo_dtype is not None or m.delta or m.replica or m.pallas
            or m.gat_form is not None):
        return False, ("rgcn runs the dense a2a schedule and the full "
                       "forward only, float32, exact, full-batch: its "
                       "setup hook refuses every other mode "
                       "(models/rgcn.py::model_setup)")
    if m.model == "deepergcn" and (
            m.workload != "train" or m.schedule != "a2a" or m.staleness
            or m.halo_dtype is not None or m.delta or m.replica or m.pallas
            or m.gat_form is not None):
        return False, ("deepergcn runs the dense a2a schedule and the "
                       "full forward only, float32, exact, full-batch: "
                       "its setup hook refuses every other mode "
                       "(models/deepergcn.py::model_setup)")
    if m.schedule not in ("a2a", "ragged"):
        return False, f"unknown schedule {m.schedule!r}"
    if m.model == "gat":
        if m.staleness:
            return False, ("the GAT exchange ships per-layer attention "
                           "tables whose staleness is not supported")
        if m.halo_dtype is not None:
            return False, ("halo_dtype is a GCN lever; GAT narrows via its "
                           "table forms (compute_dtype)")
        if m.delta:
            return False, "halo_delta requires halo_staleness=1 (GCN only)"
        if m.replica:
            return False, ("the GAT exchange ships per-layer attention "
                           "tables whose replication is not supported")
        if m.gat_form not in GAT_FORMS:
            return False, f"unknown GAT table form {m.gat_form!r}"
    else:
        if m.gat_form is not None:
            return False, "gat_form is a GAT axis"
    if m.delta and not m.staleness:
        return False, "halo_delta accumulates into the stale halo carry"
    if m.replica and m.delta:
        return False, ("replica_budget composed with halo_delta is "
                       "deferred: the delta baseline and the replica "
                       "carry would disagree on what a stale step ships")
    if m.workload in ("serve", "serve_subgraph", "minibatch") and (
            m.staleness or m.delta or m.replica):
        return False, ("staleness/delta/replication are full-batch "
                       "TRAINING levers; serving always runs the exact "
                       "forward and the mini-batch trainer re-plans per "
                       "batch (replica carries have no stable identity "
                       "across batch plans)")
    if m.workload == "serve_subgraph" and m.schedule != "a2a":
        return False, ("the sub-graph serve program ships NO per-layer "
                       "exchange — its per-row fold is schedule-"
                       "independent by construction (the hedge family is "
                       "(dst, round, pos)-sorted), so the matrix audits "
                       "it once under the a2a-constructed engine")
    if m.workload == "serve_subgraph" and m.gat_form not in (None, "fused"):
        return False, ("the sub-graph engine is f32 (no compute_dtype "
                       "lever) and audits the compact table forms at the "
                       "plan's natural width — one GAT entry")
    if m.workload == "minibatch" and m.model == "gat":
        # supported by the trainer, but the audit covers the mini-batch
        # envelope once (GCN) — the GAT program is the same per-layer
        # structure already audited full-batch
        return False, "mini-batch audit entry covers the GCN envelope"
    if m.workload == "serve" and m.gat_form == "packed":
        return False, ("the serve engine has no compute_dtype lever — the "
                       "packed form is a training-side wire shape")
    if m.pallas:
        if m.workload != "train":
            return False, ("the Pallas kernel family is audited on the "
                           "train step programs; serving rides the "
                           "identical resolve_forward_setup branch (and "
                           "the sub-graph engine refuses it outright — "
                           "its compact mirror reproduces the ELL fold), "
                           "while the mini-batch envelope passes "
                           "allow_pallas=False (one compiled step, many "
                           "per-batch plans — no shared tile layout)")
        if m.staleness or m.delta or m.replica:
            return False, ("the stale/replica carry contracts are built "
                           "around the ELL + hedge fold; the Pallas "
                           "aggregator is an exact-mode lever")
        if m.gat_form == "packed":
            return False, ("the packed bf16 table bit-pairs lanes into "
                           "f32 words the kernel's f32 accumulate cannot "
                           "consume without an in-kernel unpack — "
                           "deferred (use_pallas_spmm gates it)")
    return True, "supported"


def supported_modes() -> list[Mode]:
    """Every supported configuration, audited by ``hlo_audit.run_audit``.

    Enumerates the FULL cross product per workload and filters through
    ``is_supported`` — so adding an axis value here automatically widens
    the audit, and a combination silently missing from the output is a
    bug in ``is_supported``, not in a hand-maintained list.
    """
    modes: list[Mode] = []
    # train / GCN: schedule × staleness × halo-dtype × delta × replica
    # (is_supported filters the deferred stale × replica composition)
    for sched, stale, hd, delta, rep in itertools.product(
            ("a2a", "ragged"), (0, 1), (None, "bfloat16"), (False, True),
            (False, True)):
        modes.append(Mode("train", "gcn", sched, stale, hd, delta,
                          replica=rep))
    # train / GCN / Pallas: schedule × halo-dtype at exact mode — the
    # schedule-agnostic VMEM kernel family (pspmm_pallas_sym/_ragged)
    for sched, hd in itertools.product(("a2a", "ragged"),
                                       (None, "bfloat16")):
        modes.append(Mode("train", "gcn", sched, halo_dtype=hd,
                          pallas=True))
    # train / GAT: schedule × table form (× the Pallas slot pass for the
    # f32 fused/split forms — is_supported filters packed+pallas)
    for sched, form, pal in itertools.product(("a2a", "ragged"), GAT_FORMS,
                                              (False, True)):
        modes.append(Mode("train", "gat", sched, gat_form=form,
                          pallas=pal))
    # train / deep residual stack: its one mode
    modes.append(Mode("train", "deepergcn", "a2a"))
    # train / relational model (typed rows, row-owned embeddings): its one
    modes.append(Mode("train", "rgcn", "a2a"))
    # serve: model × schedule (× halo-dtype for GCN, × form for GAT)
    for sched, hd in itertools.product(("a2a", "ragged"),
                                       (None, "bfloat16")):
        modes.append(Mode("serve", "gcn", sched, halo_dtype=hd))
    for sched in ("a2a", "ragged"):
        modes.append(Mode("serve", "gat", sched, gat_form="fused"))
    # sub-graph serving (docs/serving.md phase 2): the query-proportional
    # program — no per-layer exchange (schedule-independent fold, audited
    # once), GCN × wire-cast {f32, bf16} + the GAT compact table form
    for hd in (None, "bfloat16"):
        modes.append(Mode("serve_subgraph", "gcn", "a2a", halo_dtype=hd))
    modes.append(Mode("serve_subgraph", "gat", "a2a", gat_form="fused"))
    # the mini-batch shared-envelope program (one entry: the envelope padding
    # and forced ragged round sizes are what differ from full-batch)
    modes.append(Mode("minibatch", "gcn", "ragged"))
    return [m for m in modes if is_supported(m)[0]]


def fast_modes() -> list[Mode]:
    """The ``--fast`` subset: one exact mode, one composed mode — enough to
    smoke the whole lower-and-check pipeline in a couple of lowers."""
    return [
        Mode("train", "gcn", "a2a"),
        Mode("train", "gcn", "ragged", staleness=1,
             halo_dtype="bfloat16"),
    ]


def train_matrix_verdicts() -> dict:
    """The ``docs/comm_schedule.md`` composition-matrix rows (schedule ×
    staleness × delta × replicas × model) as enumerator verdicts — the
    machine-readable face of that table.  ``tests/test_analysis.py`` pins
    the two against each other."""
    out = {}
    for sched, stale, delta, rep, model in itertools.product(
            ("a2a", "ragged"), (0, 1), (False, True), (False, True),
            ("gcn", "gat")):
        mode = Mode("train", model, sched, stale, None, delta,
                    gat_form="fused" if model == "gat" else None,
                    replica=rep)
        ok, reason = is_supported(mode)
        out[(sched, stale, delta, rep, model)] = (ok, reason)
    return out
