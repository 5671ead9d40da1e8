"""Measured-time profiling layer — spans, trace parsing, model reconciliation.

Every perf gauge in ``attribution.py`` is ANALYTIC: derived from the
``CommPlan``, it says how fast a step *should* be.  This module is the
measured-time source of truth next to it, in two halves:

**Span primitive** (``span`` / ``span_totals`` / ``reset_spans``) — ONE
host-span context manager for the whole program: it opens a
``jax.profiler.TraceAnnotation("sgcn.<name>")``, so the span lies on the
profiler's clock beside the device ops whenever a trace is being taken, and
it keeps a process-wide in-memory table (count, total seconds, parent, the
last 256 durations) that the benchmark's ``program_span`` readers read.
``scope`` is its compiled-program counterpart: ``jax.named_scope`` over the
fixed vocabulary ``SCOPES``, which reaches the device trace through each
op's HLO metadata; ``subscope``, ``bucket_scope`` and ``pair_scope`` name
what lies below and beside the leaf scopes (a model's own work, every bucket
of the slot passes, a relation's pass).  ``set_counter`` / ``counters`` hold
plan-time counts (``CommPlan.work_counts``, the step's pass list
``slots.work``) the same way.

**Span API** (``SpanTimer``) — named,
optionally nested wall-clock spans with ``block_until_ready`` sync points;
``SpanTimer.span`` enters the primitive above, so every trainer span
(``warmup``, ``train_step``, ``step``, ``eval``) is on the profiler too.
It generalizes ``utils.timers.PhaseTimer`` (every span IS a phase: the timer
keeps the CAGNET-vocabulary self-time breakdown, the span additionally
becomes a schema-v2 ``span`` event in the run's ``events.jsonl``), so
measured phase times land in the SAME stream as the analytic gauges.  Both
trainers thread their step/epoch paths through it.

**Trace parser** (``find_trace_files`` / ``summarize_trace``) — parses the
trace-event JSON ``jax.profiler.trace`` writes (``--profile DIR`` →
``DIR/plugins/profile/<run>/*.trace.json.gz``), classifies device ops into
the attribution vocabulary (spmm / dense / exchange / collective-wait /
other; table below and in ``docs/observability.md``) and computes MEASURED
overlap fraction, exposed-comm time and per-device skew (the straggler
gauge) — the quantities the analytic model only predicts.

**Reconciliation** (``measured_vs_model_block``) — joins a step's measured
span times against ``attribution.step_cost`` into the per-step
``measured_vs_model`` block (ratio + absolute error per component,
schema-validated), so a mispredicting cost model is a visible gauge instead
of a footnote.  ``scripts/obs_report.py`` renders both the per-step blocks
and the post-hoc trace join.

Nothing here imports jax at module scope (CLIs configure the backend before
heavy imports).
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import threading
import time
from collections import deque
from dataclasses import dataclass, field

# NOTE: no module-scope import of ..utils.timers — it imports jax, and the
# trace parser half of this module must stay importable in a jax-free
# context (SpanTimer imports PhaseTimer lazily)

# ---------------------------------------------------------- span primitive

PREFIX = "sgcn."            # every span and scope name, wherever it lands
SPAN_KEEP = 256             # durations kept per span name

# The compiled step's scope vocabulary (``scope``).  ``layer`` is indexed
# (``sgcn.layer0``, ...); every other name is a LEAF scope: an op belongs to
# the last leaf token of its HLO ``op_name``, whatever ``jvp(...)`` /
# ``transpose(...)`` wrappers the transforms put around it.
# ``benchmark/scopes.json`` is the benchmark's own copy (a test pins them
# equal); docs/observability.md says what each one holds.
SCOPES = ("layer", "dense", "xchg_pack", "xchg_a2a", "xchg_unpack",
          "agg_slots", "agg_tail", "agg_halo_fold", "loss", "grad_psum",
          "optimizer")

# Sub-scopes of the multi-head attention layer (``models/mhgat.py``): what is
# new in that layer's work, named INSIDE the leaf scope whose work it is
# (``sgcn.agg_slots/sgcn.att_score/...``).  A reader of ``SCOPES`` skips
# them (a token outside its vocabulary is no scope), so the op still books
# to its leaf.  ``benchmark/scopes_att.json`` is the benchmark's own copy.
SUBSCOPES = ("att_project", "att_max", "att_score", "att_norm")

# Sub-scopes of the deep residual stack (``models/deepergcn.py``), likewise
# legal for ``subscope``: both open inside ``sgcn.dense``, the layer's
# row-wise work — ``norm`` (BatchNorm's statistics, their collectives, the
# apply) and ``softmax_table`` (message, exp, the aggregated table, the
# divide).  ``benchmark/scopes_deep.json`` is the benchmark's own copy.
DEEP_SUBSCOPES = ("norm", "softmax_table")

# Sub-scopes of the relational model (``models/rgcn.py``) and of the
# trainer's row-owned parameters: ``rel_project`` (the per-relation and
# per-type products, forward and backward) and ``rel_table`` (building a
# layer's gather tables: the feature ‖ embedding input, the stacked
# cotangent) open inside ``sgcn.dense``; ``row_update`` (the optimiser on
# the leaves sharded with the rows) inside ``sgcn.optimizer``.
# ``benchmark/scopes_rel.json`` is the benchmark's own copy.
REL_SUBSCOPES = ("rel_project", "rel_table", "row_update")

# Sub-scopes of attention inside the typed layouts (``models/rgat.py``), both
# inside ``sgcn.dense``: ``ratt_project`` (the per-relation projections, the
# folded destination scores and the skip, forward and backward) and
# ``ratt_norm`` (BatchNorm's statistics with their ``psum``, ELU and the
# head).  Its slot passes keep ``agg_*`` and the attention sub-scopes above.
# ``benchmark/scopes_ratt.json`` is the benchmark's own copy.
RATT_SUBSCOPES = ("ratt_project", "ratt_norm")

# What the slot passes name of themselves (``ops/pspmm.py``), BELOW the three
# aggregation leaf scopes — three token families, all outside ``SCOPES`` so
# that every op still books to its leaf (``benchmark/scopes_slots.json`` is
# the benchmark's own copy of the three patterns):
#   * ``sgcn.bkt_<rows>x<width>_u`` / ``..._s<unroll>``: one bucket or width
#     class of ``bucketed_slot_reduce``, with the form that ran (unrolled, or
#     scanned at that unroll) — ``bucket_scope``, inside a leaf scope;
#   * ``sgcn.fold_rows``: the sorted row scatter of a class of virtual rows
#     (and the gather of its destination-side rows) — ``subscope``;
#   * ``sgcn.pair_<s>_<d>``: one relation's pass of the typed aggregation,
#     named after the layout it walks (source type -> row type) —
#     ``pair_scope``, OUTSIDE the leaf scopes like ``sgcn.layer<i>``.
# The counter ``slots.work`` (``models/setup.py::leave_slot_work``) lists
# the same buckets, forms and pairs per pass, so a trace's seconds under a
# token divide by the slots the token names.
SLOT_SUBSCOPES = ("fold_rows",)
BUCKET_TOKEN = r"bkt_(\d+)x(\d+)_(u|s\d+)"
PAIR_TOKEN = r"pair_(\d+)_(\d+)"

_spans: dict = {}           # name -> {count, total_s, parent, durations}
_spans_lock = threading.Lock()  # spans close on more than one thread
_open = threading.local()   # .stack: this thread's open span names;
#                             .leaves: its open leaf scopes, while tracing
_counters: dict = {}


def scope(name: str, index: int | None = None):
    """``jax.named_scope("sgcn.<name>[index]")`` for a name of ``SCOPES`` —
    HLO metadata only, no arithmetic changes."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; the vocabulary is {SCOPES}")
    return _named(f"{PREFIX}{name}{'' if index is None else int(index)}",
                  leaf=name != SCOPES[0])


@contextlib.contextmanager
def _named(full: str, leaf: bool):
    """The named scope, counted while open if it is a leaf (``subscope``)."""
    import jax

    _open.leaves = getattr(_open, "leaves", 0) + leaf
    try:
        with jax.named_scope(full):
            yield
    finally:
        _open.leaves -= leaf


def subscope(name: str):
    """``jax.named_scope("sgcn.<name>")`` for a name of ``SUBSCOPES``,
    ``DEEP_SUBSCOPES``, ``REL_SUBSCOPES``, ``RATT_SUBSCOPES`` or
    ``SLOT_SUBSCOPES``, legal only inside a leaf
    ``scope`` — a sub-scope on its own would leave its ops unscoped for
    every reader of ``SCOPES``."""
    known = (SUBSCOPES + DEEP_SUBSCOPES + REL_SUBSCOPES + RATT_SUBSCOPES
             + SLOT_SUBSCOPES)
    if name not in known:
        raise ValueError(f"unknown sub-scope {name!r}; the vocabulary is "
                         f"{known}")
    if not in_leaf_scope():
        raise ValueError(f"sub-scope {name!r} opened outside a leaf scope "
                         f"of {SCOPES[1:]}")
    import jax

    return jax.named_scope(PREFIX + name)


def form_token(unroll: int | None) -> str:
    """``"u"`` for an unrolled bucket, ``"s<unroll>"`` for a scanned one —
    the ``form`` of the counter ``slots.work`` and the tail of a bucket's
    token."""
    return "u" if unroll is None else f"s{int(unroll)}"


def bucket_token(nb: int, wb: int, unroll: int | None) -> str:
    return f"bkt_{int(nb)}x{int(wb)}_{form_token(unroll)}"


def parse_bucket_token(token: str) -> tuple | None:
    """``(rows, width, form)`` of a bucket token (``PREFIX`` or not), else
    ``None``."""
    m = re.fullmatch(BUCKET_TOKEN, token.removeprefix(PREFIX))
    return (int(m.group(1)), int(m.group(2)), m.group(3)) if m else None


def in_leaf_scope() -> bool:
    """Whether a leaf ``scope`` is open on this thread (while tracing)."""
    return bool(getattr(_open, "leaves", 0))


def bucket_scope(nb: int, wb: int, unroll: int | None):
    """``jax.named_scope("sgcn.bkt_<nb>x<wb>_u")`` around an unrolled bucket
    of ``nb`` rows × ``wb`` slots, ``..._s<unroll>`` around a scanned one;
    legal only inside a leaf ``scope``, like ``subscope``."""
    if not in_leaf_scope():
        raise ValueError(f"bucket scope {bucket_token(nb, wb, unroll)!r} "
                         f"opened outside a leaf scope of {SCOPES[1:]}")
    import jax

    return jax.named_scope(PREFIX + bucket_token(nb, wb, unroll))


def pair_scope(s: int, d: int):
    """``jax.named_scope("sgcn.pair_<s>_<d>")`` around one relation's pass
    of the typed aggregation — the layout of the ordered pair (source type
    ``s`` -> the type ``d`` whose rows it fills); like ``sgcn.layer<i>`` it
    is no leaf and opens outside them."""
    return _named(f"{PREFIX}pair_{int(s)}_{int(d)}", leaf=False)


@contextlib.contextmanager
def span(name: str):
    """Host span ``sgcn.<name>``: a ``TraceAnnotation`` on the profiler's
    clock (a disabled ``TraceMe`` when no trace is being taken) and one row
    of the in-memory table ``span_totals`` reads.  Nothing is written to
    disk; the parent is the innermost span open on this thread."""
    from jax.profiler import TraceAnnotation    # first use, not import time

    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    parent = stack[-1] if stack else None
    stack.append(name)
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(PREFIX + name):
            yield
    finally:
        dur = time.perf_counter() - t0
        stack.pop()
        with _spans_lock:
            row = _spans.get(name)
            if row is None:
                row = _spans[name] = {"count": 0, "total_s": 0.0,
                                      "parent": parent,
                                      "durations": deque(maxlen=SPAN_KEEP)}
            row["count"] += 1
            row["total_s"] += dur
            row["durations"].append(dur)


def span_totals() -> dict:
    """``{name: {count, total_s, parent, durations}}`` of every span closed
    in this process since ``reset_spans`` (a copy; durations oldest first)."""
    with _spans_lock:
        return {name: dict(row, durations=list(row["durations"]))
                for name, row in _spans.items()}


def reset_spans() -> None:
    with _spans_lock:
        _spans.clear()


def set_counter(name: str, value) -> None:
    """Leave a plan-time count where a reader in the same process finds it
    (``counters``); the newest value of a name wins."""
    _counters[name] = value


def counters() -> dict:
    return dict(_counters)


# ---------------------------------------------------------------- span API


@dataclass
class Span:
    """Handle yielded by ``SpanTimer.span`` — filled at exit."""

    name: str
    parent: str | None = None
    depth: int = 0
    dur_s: float = 0.0


class SpanTimer:
    """Nested measured spans over a shared ``PhaseTimer``.

    One instance per trainer: ``timer`` keeps the phase breakdown (self
    time per name — the ``PhaseTimer`` nesting contract), and, when a
    ``RunRecorder`` is attached, every span exit appends one validated
    ``span`` event.  Every span also enters the module's ``span``
    primitive (profiler annotation + in-memory table); without a recorder
    that and the timer's ``perf_counter`` reads are the only cost.
    """

    def __init__(self, timer=None, recorder=None):
        from ..utils.timers import PhaseTimer

        self.timer = timer if timer is not None else PhaseTimer()
        self.recorder = recorder
        self._stack: list[str] = []

    @contextlib.contextmanager
    def span(self, name: str, sync=None, step: int | None = None,
             phase: str | None = None):
        """Time a named span (nesting under any open span).  ``sync`` is the
        ``PhaseTimer.phase`` sync callable — evaluated after the body, so
        the span duration includes the device-side completion it blocks on.
        Yields a ``Span`` whose ``dur_s`` is valid after exit."""
        sp = Span(name=name,
                  parent=self._stack[-1] if self._stack else None,
                  depth=len(self._stack))
        self._stack.append(name)
        t0 = time.perf_counter()
        try:
            with span(name), self.timer.phase(name, sync=sync):
                yield sp
        finally:
            sp.dur_s = time.perf_counter() - t0
            self._stack.pop()
            if self.recorder is not None:
                kw = {}
                if step is not None:
                    kw["step"] = int(step)
                if phase is not None:
                    kw["phase"] = str(phase)
                self.recorder.record_span(
                    name=sp.name, dur_s=sp.dur_s, parent=sp.parent,
                    depth=sp.depth, **kw)


# ------------------------------------------------------------ trace parser

# The ONE collective-op name alternation both comm classes build on: the
# `collective_wait` pattern matches these names' `-done` halves and the
# `exchange` pattern the ops themselves, so a new collective (a ragged
# all-to-all lowering, say) added here lands in BOTH — two hand-kept copies
# would silently diverge and skew comm_s with no test failing.
_COLLECTIVES = (
    r"all-to-all|all_to_all|collective-permute|collective_permute|"
    r"ppermute|all-reduce|all_reduce|all-gather|all_gather|"
    r"reduce-scatter|reduce_scatter")

# Ordered op-classification table (first match wins, case-insensitive).
# The vocabulary is attribution.py's: spmm (the gather/scatter aggregation
# streams), dense (projections), exchange (the halo transport collectives),
# collective_wait (blocked-on-peer time), other (remaining device compute —
# copies, broadcasts, elementwise fusions).  docs/observability.md carries
# the human-readable form of this table; this tuple is the executable one.
TRACE_OP_CLASSES: tuple = (
    # only COLLECTIVE -done ops are comm wait: a bare `(^|-)done` would also
    # catch XLA's async `copy-done` (host/device copies) and inflate comm_s
    ("collective_wait", re.compile(
        r"rendezvous|^wait\b|^wait:|"
        r"(" + _COLLECTIVES + r"|send|recv)[-.]done", re.I)),
    # paired point-to-point transfers (multi-host / pipelined lowerings)
    # count as exchange too — booking `send.3` as compute would understate
    # comm_s and overstate the measured overlap gauge
    ("exchange", re.compile(
        _COLLECTIVES + r"|\bsend\b|\brecv\b", re.I)),
    # `convolution`, not `conv`: a bare `conv` would classify every bf16
    # `convert` cast as dense in a codebase with no convolutions at all
    ("dense", re.compile(
        r"\bdot\b|^dot|dot_general|gemm|matmul|convolution", re.I)),
    ("spmm", re.compile(
        r"gather|scatter|select_slice|dynamic.?slice|dynamic.?update|"
        r"segment", re.I)),
)

# events that are host/runtime scaffolding, not device op time
_TRACE_SKIP = re.compile(
    r"^\$|^end: |^ThreadpoolListener|^ThunkExecutor|^PjitFunction|"
    r"^XlaModule|^Pjit|^jit[_(]|^BufferAssignment|^TransferManager|"
    r"^Stream|^Execute$|^RunExecutable|^CopyToDevice|^CopyFromDevice",
    re.I)

TRACE_CLASSES = ("spmm", "dense", "exchange", "collective_wait", "other")


def classify_op(name: str) -> str | None:
    """Map one trace-event name into the attribution vocabulary; ``None``
    for host/runtime scaffolding that is not device op time."""
    if not name or _TRACE_SKIP.search(name):
        return None
    for cls, pat in TRACE_OP_CLASSES:
        if pat.search(name):
            return cls
    return "other"


def find_trace_files(profile_dir: str) -> list[dict]:
    """Locate the trace-event JSON files under a ``--profile`` directory
    (``plugins/profile/<run>/*.trace.json.gz``), newest run first.
    Returns ``[{path, bytes}]`` — the shape the manifest ``profile`` block
    records, so ``obs_report`` can find the trace from the run dir alone."""
    hits = sorted(
        glob.glob(os.path.join(profile_dir, "**", "*.trace.json.gz"),
                  recursive=True),
        key=lambda p: os.path.getmtime(p), reverse=True)
    return [{"path": os.path.abspath(p), "bytes": os.path.getsize(p)}
            for p in hits]


def _interval_union(iv: list) -> list:
    """Merge [start, end) intervals into a disjoint sorted union."""
    if not iv:
        return []
    iv = sorted(iv)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap_len(a: list, b: list) -> float:
    """Total intersection length of two DISJOINT SORTED interval unions."""
    total = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class TraceSummary:
    """Measured per-device attribution of one profiler trace."""

    path: str
    n_events: int
    devices: dict = field(default_factory=dict)   # name -> per-class seconds
    classes: dict = field(default_factory=dict)   # per-class totals (s)
    # comm WALL-CLOCK: per-pid interval union of the exchange +
    # collective_wait ops, summed over pids — ≤ the per-class op-second
    # sums whenever async collectives overlap each other on one device
    # (the same de-overlapping the exposed/hidden split needs)
    comm_s: float = 0.0
    exposed_comm_s: float = 0.0    # comm not covered by concurrent compute
    measured_overlap_frac: float | None = None    # 1 − exposed/comm
    skew: dict | None = None       # straggler gauge (multi-device only)

    def per_step(self, nsteps: int) -> dict:
        """Average the trace totals over ``nsteps`` optimizer steps — the
        per-step measured figures to join against ``step_cost``.

        ``nsteps`` must count EVERY optimizer step the trace covers — the
        recorded step events do (the trainer records warmup steps too), so
        ``len(log.steps())`` is the right denominator for a ``--profile``
        run.  Anything else executing inside the profiled region that is
        not a recorded step (``evaluate()`` forward passes, first-dispatch
        autotuning) still lands in the numerator, so these per-step figures
        are UPPER bounds there — ``obs_report`` prints the eval count next
        to the join when a run carries both."""
        n = max(int(nsteps), 1)
        out = {f"{c}_s": self.classes.get(c, 0.0) / n
               for c in TRACE_CLASSES}
        out["comm_s"] = self.comm_s / n
        out["exposed_comm_s"] = self.exposed_comm_s / n
        return out


def summarize_trace(path: str) -> TraceSummary:
    """Parse one ``*.trace.json.gz`` (or plain ``.json``) trace-event file
    into measured per-device op-class times, overlap/exposed-comm figures
    and the straggler gauge.

    Device attribution: trace processes (``pid``) map to devices on TPU
    (one pid per ``/device:TPU:n``); the CPU backend runs every virtual
    device in one ``/host:CPU`` pid, so per-device skew is only emitted
    when the trace distinguishes more than one device-like pid.  When any
    ``/device:…`` pid exists, host/runtime pids are dropped entirely —
    their wall time is not device op time and must not skew the gauges.  Overlap is
    computed per pid: comm intervals (exchange + collective-wait) minus
    their intersection with the union of concurrent compute intervals
    (spmm/dense/other, any thread of the pid) — comm time under compute is
    hidden, the remainder is EXPOSED comm sitting on the critical path."""
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rt") as fh:
        doc = json.load(fh)
    events = doc.get("traceEvents", [])
    proc_names: dict = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc_names[e.get("pid")] = e.get("args", {}).get("name",
                                                             str(e.get("pid")))
    per_dev: dict = {}
    intervals: dict = {}           # pid -> {"comm": [...], "compute": [...]}
    pid_counts: dict = {}          # per pid, so the filter below keeps
    for e in events:               # n_events consistent with the gauges
        if e.get("ph") != "X":
            continue
        cls = classify_op(e.get("name", ""))
        if cls is None:
            continue
        dur = float(e.get("dur", 0.0)) * 1e-6      # trace units: µs
        ts = float(e.get("ts", 0.0)) * 1e-6
        pid = e.get("pid")
        dev = per_dev.setdefault(pid, {c: 0.0 for c in TRACE_CLASSES})
        dev[cls] += dur
        bucket = intervals.setdefault(pid, {"comm": [], "compute": []})
        bucket["comm" if cls in ("exchange", "collective_wait")
               else "compute"].append((ts, ts + dur))
        pid_counts[pid] = pid_counts.get(pid, 0) + 1

    # a real TPU profile carries host/runtime pids next to the device pids
    # (enqueue threads, transfer spans) — when the trace distinguishes any
    # `/device:…` pid, only those are devices: host wall time must not
    # inflate class totals, and a host pid must never be elected straggler.
    # A CPU-backend trace has no `/device:` pid at all, so every pid (the
    # single `/host:CPU`) stays in — its op classes ARE the measurement.
    dev_pids = [p for p in per_dev
                if "/device:" in proc_names.get(p, str(p)).lower()]
    if dev_pids:
        per_dev = {p: per_dev[p] for p in dev_pids}
    n_classified = sum(pid_counts[p] for p in per_dev)

    classes = {c: sum(d[c] for d in per_dev.values()) for c in TRACE_CLASSES}
    comm_s = exposed_s = 0.0
    devices = {}
    busies = {}
    for pid, dev in per_dev.items():
        name = proc_names.get(pid, str(pid))
        if name in devices:
            # distinct pids can share process_name metadata (merged
            # multi-host captures) — collapsing them would shrink the
            # straggler denominator and overwrite per-class seconds
            name = f"{name} [pid {pid}]"
        busy_union = _interval_union(intervals[pid]["comm"]
                                     + intervals[pid]["compute"])
        busy = sum(e - s for s, e in busy_union)
        compute_union = _interval_union(intervals[pid]["compute"])
        comm_union = _interval_union(intervals[pid]["comm"])
        cm = sum(e - s for s, e in comm_union)
        hidden = _overlap_len(comm_union, compute_union)
        comm_s += cm
        exposed_s += max(0.0, cm - hidden)
        devices[name] = dict(dev, busy_s=busy)
        busies[name] = busy
    skew = None
    if len(busies) > 1:
        mean = sum(busies.values()) / len(busies)
        straggler = max(busies, key=busies.get)
        skew = {"busy_max_over_mean": (busies[straggler] / mean
                                       if mean > 0 else 1.0),
                "straggler": straggler}
    overlap = None
    if comm_s > 0:
        overlap = 1.0 - exposed_s / comm_s
    return TraceSummary(path=path, n_events=n_classified, devices=devices,
                        classes=classes, comm_s=comm_s,
                        exposed_comm_s=exposed_s,
                        measured_overlap_frac=overlap, skew=skew)


def trace_path_for_run(manifest: dict, rundir: str | None = None) -> str | None:
    """Resolve the run's trace-event file from its manifest ``profile``
    block (falling back to re-globbing the recorded profile dir, then the
    run directory itself) — how ``obs_report`` finds the trace from the run
    directory alone.  The manifest records ABSOLUTE paths from the machine
    the run executed on; for a relocated run dir (the normal way a TPU run
    is inspected) those are stale, so the last resort globs ``rundir`` —
    copying the profile tree into the run dir makes the claim literally
    true anywhere."""
    prof = manifest.get("profile") if isinstance(manifest, dict) else None
    if isinstance(prof, dict):
        for entry in prof.get("trace_files") or []:
            p = entry.get("path")
            if p and os.path.exists(p):
                return p
        d = prof.get("dir")
        if d and os.path.isdir(d):
            hits = find_trace_files(d)
            if hits:
                return hits[0]["path"]
    if rundir and os.path.isdir(rundir):
        hits = find_trace_files(rundir)
        if hits:
            return hits[0]["path"]
    return None


# ----------------------------------------------------------- reconciliation

def _sig(x: float, n: int = 6) -> float:
    return float(f"{x:.{n}g}")


def _mvm_entry(model_s: float, measured_s: float | None) -> dict:
    """One measured_vs_model component: model/measured endpoints plus the
    derived join (ratio + absolute error) whenever both are present."""
    d = {"model_s": _sig(model_s)}
    if measured_s is None:
        d["measured_s"] = None
        return d
    d["measured_s"] = _sig(float(measured_s))
    if d["model_s"] > 0:
        d["ratio"] = d["measured_s"] / d["model_s"]
        d["abs_err_s"] = d["measured_s"] - d["model_s"]
    return d


def exchange_join(trace_per_step: dict, exposed_halo_bytes: float) -> dict:
    """The ``exchange`` component of ``measured_vs_model``: measured
    per-step EXPOSED comm seconds (``TraceSummary.per_step``'s
    ``exposed_comm_s`` — comm minus what ran under concurrent compute)
    joined against the analytic exposed wire bytes serialized at the
    nominal ICI rate (``exposed_halo_bytes / ICI_CEILING_GBS`` — the
    roofline's ``exposed_halo_bytes`` gauge restated in seconds, exactly
    how ``gather_stream`` restates ``stream_ceiling_frac``).  Both sides
    are exposed figures — joining the measured TOTAL collective seconds
    here would conflate overlap (hidden comm) with cost-model error — and
    both are exchange-shaped: ``exposed_comm_frac`` is a fraction of the
    step's EXCHANGES, not of its wall, so an earlier ``frac × wall_s``
    model side equated "all exchanges exposed" with "the whole step is
    comm" and reported a 1/comm-share ratio as model error on every exact
    run.  The ONE implementation of this join — ``measured_vs_model_block``
    embeds it per step, ``scripts/obs_report.py`` renders it post-hoc over
    the whole-run trace."""
    from .attribution import ICI_CEILING_GBS

    return _mvm_entry(
        max(float(exposed_halo_bytes), 0.0) / (ICI_CEILING_GBS * 1e9),
        trace_per_step.get("exposed_comm_s", 0.0))


def measured_vs_model_block(cost, wall_s: float,
                            phase_total_s: float | None = None,
                            trace_per_step: dict | None = None,
                            exposed_halo_bytes: float | None = None) -> dict:
    """Join measured step time against the analytic ``StepCostModel`` into
    the schema-validated per-step ``measured_vs_model`` block.

    Components (each ``{model_s, measured_s, ratio, abs_err_s}``; ratio =
    measured/model — >1 means the step ran SLOWER than the analytic model
    predicts, the drift gauge for a stale cost model):

      * ``gather_stream`` — model: ``gather_bytes / STREAM_CEILING_GBS``
        (the analytic gather-bound step time — the workload's roofline
        axis); measured: the step's span-measured wall time.  The ratio is
        exactly ``1 / stream_ceiling_frac`` — the same reconciliation the
        roofline block states as a fraction, restated as seconds so model
        error is readable as absolute time.
      * ``exchange`` (only when a parsed profiler trace is joined —
        ``trace_per_step`` from ``TraceSummary.per_step`` plus the
        analytic ``exposed_halo_bytes`` from the roofline block): measured
        per-step EXPOSED comm seconds (``exposed_comm_s``) against the
        analytic exposed wire bytes serialized at the nominal ICI rate
        (``exposed_halo_bytes / ICI_CEILING_GBS``) — exposed vs exposed
        and both exchange-shaped, so the ratio reads as cost-model error,
        not overlap.  The other trace classes are NOT joined here
        — the analytic model predicts no per-class seconds for them
        (bytes and FLOPs, not times); ``obs_report`` renders their
        measured figures next to this block instead.

    ``phase_total_s`` defaults to ``wall_s`` — the span-measured total this
    block anchors on must reconcile with ``PhaseTimer.report()`` (tier-1
    pins <1% on the cora fixture)."""
    from .attribution import STREAM_CEILING_GBS

    wall_s = float(wall_s)
    comps = {
        "gather_stream": _mvm_entry(
            cost.gather_bytes / (STREAM_CEILING_GBS * 1e9), wall_s),
    }
    if trace_per_step is not None and exposed_halo_bytes is not None:
        comps["exchange"] = exchange_join(trace_per_step, exposed_halo_bytes)
    return {
        "phase_total_s": _sig(wall_s if phase_total_s is None
                              else float(phase_total_s)),
        "components": comps,
    }
