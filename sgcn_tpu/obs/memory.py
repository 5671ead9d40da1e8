"""Analytic per-chip HBM footprint — the memory side of model-vs-measured.

One home for every "how many bytes does this (plan, mode, model) put on a
chip" number, derived purely from the ``CommPlan``'s exact padded layout and
the model config — the same discipline ``attribution.step_cost`` applies to
time.  Per array FAMILY, in ``step_cost``'s vocabulary:

  * **params / opt_state** — replicated weights + Adam moments (donated
    every step, so they are resident ONCE despite the functional update);
  * **features** — the owned ``(b, fin)`` feature rows plus the train-only
    labels/valid masks;
  * **plan_arrays / pallas_tiles** — exactly what ``ForwardSetup
    .ship_arrays`` puts on the device (including the GAT int8 narrowing),
    split on the ``ptile_*`` prefix so the Pallas tile layout is its own
    line item;
  * **halo_tables** — the gathered ``(R, f_ℓ)`` receive tables of the dense
    a2a aggregators; ZERO under the ragged ring (receives fold as they
    arrive) and under the Pallas VMEM kernels (the fold runs in VMEM);
  * **wire_buffers** — one exchange's send+receive buffers at the selected
    schedule's padded shapes (``plan.wire_buffer_shapes``) and the wire
    dtype;
  * **halo_carries / replica_carries** — the cross-step stale/ring carries
    and replica tables (``plan.stale_carry_shapes`` /
    ``plan.replica_carry_shapes``, partial-refresh baselines included);
  * **workspace** — layer activations (and their backward mirrors for
    training) at the compute dtype.

The MEASURED side joins this against XLA's own figures:
``measure_compiled`` reads ``compiled.memory_analysis()`` (argument /
output / temp / alias bytes — all PER DEVICE on every backend this repo
runs) and ``reconcile`` produces the per-family ``{model_bytes,
measured_bytes, ratio}`` join that lands in the schema-v6 manifest
``memory`` block and the ``memory`` event kind.  The reconciliation
contract (``MEM_MODEL_TOL``, checked per audit mode by
``analysis/hlo_audit.py::run_memory_audit``):

  * measured peak ≤ model total × tol — the analytic model is the
    residency upper envelope (a program may touch a subset, e.g. the
    sub-graph forward; it may never exceed the envelope by more than the
    band);
  * measured argument bytes ≤ modeled resident-argument bytes — jit may
    prune dead inputs, never invent live ones (reconciles to the byte on
    the exact modes);
  * measured ``alias_size`` ≥ modeled params+opt bytes for training
    programs (params and opt state are always donated and never pruned —
    a stripped ``donate_argnums`` zeroes the alias and fails this
    deterministically), and == 0 for serve programs (no donation by
    design).

Nothing here imports jax at module scope — the CLIs configure the backend
before heavy imports, and the analytic side must be importable first.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

# Reconciliation band of measured-peak vs analytic-total (two-sided, see
# module docstring).  Calibrated on the audit fixture across the supported
# matrix (tests/test_memory_obs.py pins representatives of every family):
# the analytic model counts resident arrays exactly (argument bytes
# reconcile to the byte) but prices XLA's scratch conservatively, so the
# observed peak/total ratios sit in ~[0.25, 1.9] on CPU-compiled programs;
# 2.5 leaves headroom for backend scratch-allocator differences while still
# catching a doubled working set (the dropped-donation failure mode trips
# the alias floor first — deterministically).
MEM_MODEL_TOL = 2.5

# Families whose arrays enter the step program as ARGUMENTS (resident for
# the life of the trainer/engine) — their sum is what `memory_analysis()`'s
# argument_size_in_bytes must reconcile against.
ARGUMENT_FAMILIES = ("params", "opt_state", "features", "plan_arrays",
                     "pallas_tiles", "halo_carries", "replica_carries",
                     "subgraph_batch")
# Families the program materializes while running (XLA temp/output space).
SCRATCH_FAMILIES = ("halo_tables", "wire_buffers", "workspace")
# Donate-class families (jax.buffer_donor markers — the PR-9 donation
# contract): params + opt state always; carries in the stale/replica kinds.
DONATED_FAMILIES = ("params", "opt_state", "halo_carries",
                    "replica_carries")


class MemoryBudgetError(ValueError):
    """A (plan, mode) combination's analytic footprint exceeds the
    ``--memory-budget`` — raised at PLAN time (trainer/engine __init__),
    before any array ships, with the itemized per-family table."""


@dataclass
class MemoryModel:
    """Analytic per-chip HBM footprint of ONE (plan, mode, model) — plan
    arrays are padded identically across chips, so one chip's footprint is
    every chip's footprint."""

    workload: str                 # 'train' | 'serve' | 'serve_subgraph'
    families: dict                # family name -> modeled bytes per chip
    config: dict = field(default_factory=dict)   # scoping identity (n, nnz,
    #                               k, mode flags) — the trend-series key
    overlays: dict = field(default_factory=dict)  # informational figures
    #                               NOT summed into the total (pad_overhead
    #                               would double-count wire_buffers' pads)

    @property
    def total_bytes(self) -> int:
        return int(sum(self.families.values()))

    @property
    def argument_bytes(self) -> int:
        return int(sum(self.families.get(f, 0) for f in ARGUMENT_FAMILIES))

    @property
    def donated_bytes(self) -> int:
        return int(sum(self.families.get(f, 0) for f in DONATED_FAMILIES))

    @property
    def donated_floor_bytes(self) -> int:
        """The donation bytes NO mode may prune: params + opt state (the
        carries can legitimately be absent from the exact-mode program, so
        the audit's alias lower bound uses this floor, not donated_bytes)."""
        return int(self.families.get("params", 0)
                   + self.families.get("opt_state", 0))

    def table(self) -> str:
        """Human-readable itemized breakdown — the loud half of the
        ``--memory-budget`` failure."""
        lines = [f"  {name:<16} {int(b):>14,} B"
                 for name, b in sorted(self.families.items(),
                                       key=lambda kv: -kv[1]) if b]
        lines.append(f"  {'TOTAL':<16} {self.total_bytes:>14,} B")
        for name, b in sorted(self.overlays.items()):
            lines.append(f"  ({name:<14} {int(b):>14,} B — informational, "
                         "not summed)")
        return "\n".join(lines)

    def block(self, measured: dict | None = None) -> dict:
        """The schema-v6 manifest ``memory`` block: per-family
        ``{model_bytes, measured_bytes, ratio}``.  ``measured`` (a
        ``measure_compiled`` dict) fills the aggregate rows XLA itemizes —
        total↔peak, arguments↔argument_size, donated↔alias_size; the
        per-family detail stays model-only (XLA reports aggregates)."""
        fams = {name: {"model_bytes": int(b), "measured_bytes": None,
                       "ratio": None}
                for name, b in self.families.items()}

        def join(model_b, measured_b):
            e = {"model_bytes": int(model_b),
                 "measured_bytes": None if measured_b is None
                 else int(measured_b), "ratio": None}
            if measured_b is not None and model_b > 0:
                e["ratio"] = float(measured_b) / float(model_b)
            return e

        m = measured or {}
        out = {
            "workload": self.workload,
            "config": dict(self.config),
            "families": fams,
            "total": join(self.total_bytes, m.get("peak_bytes")),
            "arguments": join(self.argument_bytes, m.get("argument_bytes")),
            "donated": join(self.donated_bytes, m.get("alias_bytes")),
        }
        if self.overlays:
            out["overlays"] = {k: int(v) for k, v in self.overlays.items()}
        return out


def _prod(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


def memory_model(plan, fin: int, widths, *, workload: str = "train",
                 model: str = "gcn", comm_schedule: str = "a2a",
                 compute_dtype: str | None = None,
                 halo_dtype: str | None = None, halo_staleness: int = 0,
                 halo_delta: bool = False, replica_budget: int = 0,
                 refresh_band: float | None = None,
                 setup=None) -> MemoryModel:
    """Build the analytic footprint for one resolved mode.

    ``setup`` is the caller's ``ForwardSetup`` (the trainer and the serve
    engine already hold one — ``resolve_forward_setup`` is NOT re-run here,
    so the model prices exactly the fields/statics the live program ships,
    including the Pallas selection and the GAT int8 narrowing).  When
    ``None`` (standalone analytic use: bench blocks, trend baselines), the
    resolver runs with the given knobs — that path imports jax-adjacent
    modules, so call it only after backend setup."""
    widths = [int(w) for w in widths]
    fin = int(fin)
    if setup is None:
        from ..train.fullbatch import resolve_forward_setup
        setup = resolve_forward_setup(
            plan, fin, widths, model=model, comm_schedule=comm_schedule,
            compute_dtype=compute_dtype, halo_staleness=halo_staleness,
            replica_budget=replica_budget, refresh_band=refresh_band,
            serve_subgraph=(workload == "serve_subgraph"),
            halo_dtype=halo_dtype, halo_delta=halo_delta)
    comm_schedule = setup.comm_schedule
    replica_budget = int(setup.replica_budget or 0)
    pallas = "pallas_tb" in setup.fwd_static
    train = workload == "train"
    k, b = int(plan.k), int(plan.b)
    compute_isize = 2 if compute_dtype == "bfloat16" else 4

    families: dict[str, int] = {}
    custom = setup.custom       # the model's hook prices what it knows
    estimate = (custom.estimate_memory(train=train)
                if custom.estimate_memory is not None else None)
    # (a model with leaves owned with the rows says what ONE chip holds of
    # its tree: ``param_bytes`` of its estimate; f32 masters otherwise)
    families["params"] = (estimate or {}).get("param_bytes",
                                              4 * custom.param_count)
    # Adam: count scalar + one mu and one nu tree (optax.adam — the only
    # optimizer the CLIs construct); inference carries no optimizer state
    families["opt_state"] = (2 * families["params"] + 4) if train else 0
    families["features"] = b * fin * 4 + (2 * b * 4 if train else 0)

    plan_b = pallas_b = 0
    for name, arr in setup.ship_arrays(plan).items():
        per_chip = int(arr.nbytes) // k      # stacked (k, ...) per-chip pad
        if name.startswith("ptile_"):
            pallas_b += per_chip
        else:
            plan_b += per_chip
    families["plan_arrays"] = plan_b
    families["pallas_tiles"] = pallas_b

    # per-layer exchanged row widths (f32-lane equivalents, the wider of
    # the two directions' tables) + the feature wire's itemsize — the same
    # split CommStats/step_cost price the wire with
    lane_widths = [max(f, g) for f, g in zip(
        custom.lane_widths, custom.lane_widths_bwd or custom.lane_widths)]
    wire_isize = custom.wire_itemsize[0]

    # halo tables: the dense a2a aggregators gather a (R, f_ℓ) receive
    # table per exchange direction; the ragged ring folds receives as they
    # arrive and the Pallas kernels fold in VMEM — neither materializes it
    ndir = 2 if train else 1                  # forward (+ gradient) halos
    if comm_schedule == "a2a" and not pallas:
        families["halo_tables"] = ndir * sum(
            int(plan.r) * f * compute_isize for f in lane_widths)
    else:
        families["halo_tables"] = 0

    # one exchange's send + receive wire buffers at the schedule's padded
    # shapes and the widest layer's lane width (XLA reuses across layers)
    wire_rows = sum(_prod(s) for s in plan.wire_buffer_shapes(comm_schedule))
    fmax = max(lane_widths) if lane_widths else 0
    families["wire_buffers"] = 2 * wire_rows * fmax * wire_isize

    families["halo_carries"] = 0
    families["replica_carries"] = 0
    if train and halo_staleness:
        shapes = plan.stale_carry_shapes(fin, widths, delta=halo_delta,
                                         comm_schedule=comm_schedule)
        families["halo_carries"] = sum(
            _prod(s) * 4 for shps in shapes.values() for s in shps)
    if train and replica_budget and not halo_staleness:
        shapes = plan.replica_carry_shapes(
            fin, widths, partial=refresh_band is not None)
        families["replica_carries"] = sum(
            _prod(s) * 4 for shps in shapes.values() for s in shps)

    # layer activations (+ backward mirrors when training) — XLA's scratch
    # working set, priced at the compute dtype over every layer width
    npass = 2 if train else 1
    workspace = npass * b * (fin + sum(widths)) * compute_isize
    # the per-slot temporaries a pass materialises beside the rows (GAT's
    # edge-softmax scores over the combined-edge layout), per direction
    workspace += npass * custom.slot_bytes
    if estimate is not None:
        # the model's own itemised estimate replaces the activation-mirror
        # figure (mhgat: per-row state kept for the backward and whole-row
        # slot temporaries; deepergcn: the rows its per-layer checkpoints
        # keep under the configured ``keep``, ONE layer's recomputed and
        # backward rows, the slot scans' budgets — the figure above would
        # price every layer's rows live at once, which is what per-layer
        # checkpointing exists to avoid)
        workspace = (estimate["rows_kept"] + estimate["rows_transient"]
                     + estimate["slot_temps"])
        # what a model makes once from the features and keeps beside them
        # (rgcn: layer 0's means over data; models/setup.py::Hoist)
        families["features"] += estimate.get("hoisted", 0)
    if pallas:
        # the VMEM kernel family's per-tile-block working set (operand
        # windows + accumulator at the tile row count ``pallas_tb``) — in
        # HBM terms an upper envelope: on TPU it lives in VMEM, under the
        # CPU emulation XLA materializes it as temp
        tb = int(setup.fwd_static.get("pallas_tb", 0))
        workspace += npass * tb * (fin + sum(widths)) * compute_isize
    families["workspace"] = workspace

    # pad overhead (informational overlay — the wire_buffers family already
    # contains its pads; summing this too would double-count): the padded
    # wire rows the selected schedule ships beyond the true Σ(λ−1) volume
    true_rows = int(plan.send_counts.sum())
    padded_rows = int(plan.wire_rows_per_exchange(comm_schedule))
    overlays = {"pad_overhead_bytes":
                max(0, padded_rows - true_rows) * fmax * wire_isize}

    config = {
        "workload": workload, "model": model, "n": int(plan.n),
        "nnz": int(plan.nnz.sum()), "k": k, "fin": fin,
        "widths": list(widths), "comm_schedule": comm_schedule,
        "compute_dtype": compute_dtype or "float32",
        "halo_dtype": halo_dtype or "float32",
        "halo_staleness": int(halo_staleness), "halo_delta": bool(halo_delta),
        "replica_budget": replica_budget,
        "partial_refresh": refresh_band is not None, "pallas": pallas,
    }
    return MemoryModel(workload=workload, families=families, config=config,
                       overlays=overlays)


# ---------------------------------------------------------------- measured
def measure_compiled(compiled) -> dict | None:
    """Read ``compiled.memory_analysis()`` into a plain per-device byte
    dict; ``None`` when the backend does not expose the analysis (the
    join is then simply absent — never fabricated)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:                  # noqa: BLE001 — backend-optional API
        return None
    if ma is None:
        return None
    try:
        arg = int(ma.argument_size_in_bytes)
        out = int(ma.output_size_in_bytes)
        tmp = int(ma.temp_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
        gen = int(ma.generated_code_size_in_bytes)
    except AttributeError:
        return None
    # donated buffers appear in BOTH argument and output totals; peak
    # residency counts them once
    return {"argument_bytes": arg, "output_bytes": out, "temp_bytes": tmp,
            "alias_bytes": alias, "generated_code_bytes": gen,
            "peak_bytes": arg + out + tmp - alias}


def reconcile(model: MemoryModel, measured: dict | None,
              tol: float = MEM_MODEL_TOL) -> dict:
    """Join one compiled program's measured figures against the analytic
    model; returns ``{ok, violations, block}`` where ``block`` is the
    manifest-shaped per-family join and ``violations`` lists human-readable
    contract breaches (the ``memory-model`` audit rule's payload)."""
    violations: list[str] = []
    if measured is not None:
        peak, total = measured["peak_bytes"], model.total_bytes
        if total > 0 and peak > total * tol:
            violations.append(
                f"measured peak {peak:,} B exceeds the analytic total "
                f"{total:,} B x tol {tol} (ratio {peak / total:.2f}) — "
                "the model is the residency upper envelope; a program "
                "above it holds buffers the model does not know about "
                "(e.g. an un-donated double-buffered update)")
        # the program's arguments are a SUBSET of the modeled resident
        # arrays (jit prunes dead inputs; it never invents live ones) —
        # this side reconciles to the byte on the exact modes, so only a
        # small absolute slack for step-counter scalars is allowed
        arg_model = model.argument_bytes
        if measured["argument_bytes"] > arg_model + 256:
            violations.append(
                f"measured argument bytes {measured['argument_bytes']:,} B "
                f"exceed the modeled resident arguments {arg_model:,} B — "
                "the program takes inputs the footprint model does not "
                "price")
        floor = model.donated_floor_bytes
        if model.workload == "train":
            if measured["alias_bytes"] < floor:
                violations.append(
                    f"measured alias {measured['alias_bytes']:,} B below "
                    f"the donated params+opt floor {floor:,} B — "
                    "donate_argnums dropped; the step double-buffers "
                    "every update")
        elif measured["alias_bytes"] != 0:
            violations.append(
                f"serve program aliases {measured['alias_bytes']:,} B — "
                "engine buffers are reused across batches and must not "
                "be donated")
    return {"ok": not violations, "violations": violations,
            "block": model.block(measured)}


# ------------------------------------------------------------------ budget
def check_memory_budget(model: MemoryModel, budget_bytes: int | None,
                        what: str = "this run") -> None:
    """Raise ``MemoryBudgetError`` when the analytic footprint exceeds the
    budget — called at plan time (trainer/engine ``__init__``), before any
    array ships, so an over-budget (plan, mode) fails in milliseconds with
    the itemized table instead of OOMing mid-compile."""
    if budget_bytes is None:
        return
    budget_bytes = int(budget_bytes)
    if budget_bytes <= 0:
        raise ValueError(f"--memory-budget must be > 0 bytes, got "
                         f"{budget_bytes}")
    total = model.total_bytes
    if total > budget_bytes:
        raise MemoryBudgetError(
            f"{what}: analytic per-chip HBM footprint {total:,} B exceeds "
            f"--memory-budget {budget_bytes:,} B "
            f"(workload={model.workload}) — per-family breakdown:\n"
            f"{model.table()}")


_SUFFIX = {"K": 1024, "M": 1024 ** 2, "G": 1024 ** 3, "T": 1024 ** 4}


def parse_bytes(text: str) -> int:
    """Parse a ``--memory-budget`` value: plain bytes or a K/M/G/T binary
    suffix (``16G`` = 16 GiB)."""
    s = str(text).strip().upper().removesuffix("B")
    mult = 1
    if s and s[-1] in _SUFFIX:
        mult, s = _SUFFIX[s[-1]], s[:-1]
    try:
        val = float(s)
    except ValueError:
        raise ValueError(
            f"--memory-budget {text!r} is not BYTES or a K/M/G/T-suffixed "
            "size") from None
    if not math.isfinite(val) or val <= 0:
        raise ValueError(f"--memory-budget {text!r} must be positive")
    return int(val * mult)
