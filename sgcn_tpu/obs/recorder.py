"""RunRecorder — the run manifest + append-only JSONL event stream.

One ``RunRecorder`` per run directory (``--metrics-out DIR``):

  * ``manifest.json``   — what ran: config, argv, git rev, backend/mesh,
    plan digest and partitioner metadata.  Rewritten in place as late
    facts arrive (``set_plan``/``set_backend``) — it is a small dict, and
    a crash mid-run must still leave a parseable manifest.
  * ``events.jsonl``    — one line per event (``step``/``eval``/``summary``
    and recorder-side ``heartbeat``), appended and flushed per event so a
    killed run keeps every completed step.
  * ``heartbeat.jsonl`` — liveness pings from OTHER layers/processes
    (``heartbeat()`` below): the launch rendezvous and the multichip
    dryrun write here through the ``SGCN_METRICS_OUT`` env var, so an
    operator can distinguish "slow" (heartbeats advancing) from "stalled"
    (last heartbeat stale) without attaching a debugger.

Every record is validated against ``schema`` BEFORE it is written, and
``load_run`` re-validates on read — a run directory either loads clean or
fails loudly.  Nothing here imports jax at module scope (the CLIs set up
the backend env before heavy imports).
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass

from . import schema


def _git_rev() -> str | None:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))))
        return out.stdout.strip() if out.returncode == 0 else None
    except Exception:                   # noqa: BLE001 — best-effort metadata
        return None


def plan_digest(plan) -> str:
    """Stable 16-hex digest of a CommPlan's comm structure — enough to tell
    "same partition/layout" apart across runs without storing the arrays."""
    h = hashlib.sha256()
    h.update(repr((plan.n, plan.k, plan.b, plan.s, plan.r, plan.e,
                   bool(plan.symmetric), tuple(plan.ell_buckets))).encode())
    for arr in (plan.send_counts, plan.halo_counts, plan.nnz,
                plan.part_sizes):
        h.update(arr.tobytes())
    return h.hexdigest()[:16]


def plan_manifest_block(plan) -> dict:
    return {
        "n": int(plan.n), "k": int(plan.k), "b": int(plan.b),
        "s": int(plan.s), "r": int(plan.r), "e": int(plan.e),
        "symmetric": bool(plan.symmetric),
        "send_rows_per_exchange": int(plan.predicted_send_volume.sum()),
        "messages_per_exchange": int(plan.predicted_message_count.sum()),
        "digest": plan_digest(plan),
    }


class RunRecorder:
    """Owns one run directory; see module docstring."""

    def __init__(self, outdir: str, config: dict | None = None,
                 run_kind: str = "train", argv: list | None = None):
        self.dir = outdir
        os.makedirs(outdir, exist_ok=True)
        # sweep manifest temp litter from previous killed runs (one shared
        # sweep policy, resilience.atomic): a RunRecorder is only ever
        # constructed by the run directory's single writer (the
        # coordinator), so anything matching here is from a dead process
        from ..resilience.atomic import sweep_temp_litter

        sweep_temp_litter(outdir, schema.MANIFEST_NAME)
        self.manifest: dict = {
            "v": schema.SCHEMA_VERSION,
            "ts": time.time(),
            "run_kind": run_kind,
            "config": _jsonable(config or {}),
            "argv": list(sys.argv if argv is None else argv),
            "git_rev": _git_rev(),
        }
        self._events = open(os.path.join(outdir, schema.EVENTS_NAME), "a")
        self._write_manifest()

    # ------------------------------------------------------------- manifest
    def _write_manifest(self) -> None:
        schema.validate_manifest(self.manifest)
        # the ONE atomic-write helper (resilience.atomic): temp + fsync +
        # rename — a kill during set_profile/set_plan leaves the previous
        # manifest parseable, and the fsync makes the rewrite durable (the
        # bare os.replace this used to do ordered metadata only)
        from ..resilience.atomic import atomic_write_json

        atomic_write_json(os.path.join(self.dir, schema.MANIFEST_NAME),
                          self.manifest, indent=1)

    def set_plan(self, plan, partitioner: dict | None = None) -> None:
        """Record the comm plan's identity (and the partitioner provenance
        that produced its partvec) in the manifest."""
        self.manifest["plan"] = plan_manifest_block(plan)
        if partitioner is not None:
            self.manifest["partitioner"] = _jsonable(partitioner)
        self._write_manifest()

    def set_partitioner(self, partitioner: dict) -> None:
        """Record partitioner provenance alone (the mini-batch trainer has
        one plan per batch, so there is no single plan block to digest)."""
        self.manifest["partitioner"] = _jsonable(partitioner)
        self._write_manifest()

    def set_comm_schedule(self, decision: dict) -> None:
        """Record the transport-selection decision log
        (``parallel/plan.py::resolve_comm_schedule``): what was asked, what
        resolved, which rule fired, and the wire-row inputs — so an
        ``auto`` pick is reconstructible from the run directory alone."""
        self.manifest["comm_schedule"] = _jsonable(decision)
        self._write_manifest()

    def set_profile(self, profile_dir: str) -> None:
        """Record where the jax.profiler trace of this run landed (the
        ``--profile`` + ``--metrics-out`` composition): the directory plus
        every trace-event JSON found under it with its gzip'd size, so
        ``scripts/obs_report.py`` can find and parse the trace from the run
        directory alone (``tracing.trace_path_for_run``)."""
        from .tracing import find_trace_files

        self.manifest["profile"] = {
            "dir": os.path.abspath(profile_dir),
            "trace_files": find_trace_files(profile_dir),
        }
        self._write_manifest()

    def set_memory(self, block: dict) -> None:
        """Record the per-chip HBM footprint block (schema v6,
        ``obs/memory.py::MemoryModel.block()``): per-family ``{model_bytes,
        measured_bytes, ratio}`` plus the total/arguments/donated aggregate
        joins.  Rewritten as measured joins arrive (the serve engine
        re-publishes after each bucket compile), like every other late
        manifest fact."""
        self.manifest["memory"] = _jsonable(block)
        self._write_manifest()

    def set_backend(self, mesh=None) -> None:
        """Record the live jax backend + mesh (call after backend init)."""
        import jax

        self.manifest["backend"] = {
            "platform": jax.default_backend(),
            "device_count": jax.device_count(),
            "process_count": jax.process_count(),
        }
        if mesh is not None:
            self.manifest["mesh"] = {
                "axes": {str(k): int(v)
                         for k, v in mesh.shape.items()},
            }
        self._write_manifest()

    # --------------------------------------------------------------- events
    def _emit(self, ev: dict) -> None:
        ev.setdefault("v", schema.SCHEMA_VERSION)
        ev.setdefault("ts", time.time())
        ev = _jsonable(ev)
        schema.validate_event(ev)
        self._events.write(json.dumps(ev) + "\n")
        self._events.flush()

    def record_step(self, step: int, loss: float, wall_s: float,
                    err: float | None = None, grad_norm: float | None = None,
                    comm: dict | None = None, phases: dict | None = None,
                    roofline: dict | None = None, drift: dict | None = None,
                    **extra) -> None:
        ev = {"kind": "step", "step": int(step), "loss": float(loss),
              "wall_s": float(wall_s)}
        if err is not None:
            ev["err"] = float(err)
        if grad_norm is not None:
            ev["grad_norm"] = float(grad_norm)
        for k, val in (("comm", comm), ("phases", phases),
                       ("roofline", roofline), ("drift", drift)):
            if val is not None:
                ev[k] = val
        ev.update({k: v for k, v in extra.items() if v is not None})
        self._emit(ev)

    def record_eval(self, step: int, loss: float, acc: float | None = None,
                    wall_s: float | None = None) -> None:
        ev = {"kind": "eval", "step": int(step), "loss": float(loss)}
        if acc is not None:
            ev["acc"] = float(acc)
        if wall_s is not None:
            ev["wall_s"] = float(wall_s)
        self._emit(ev)

    def record_span(self, name: str, dur_s: float, parent: str | None = None,
                    depth: int = 0, **fields) -> None:
        """One measured wall-clock span (``obs.tracing.SpanTimer``) — the
        schema-v2 event that puts measured phase times in the same stream
        as the analytic gauges."""
        ev = {"kind": "span", "name": str(name), "dur_s": float(dur_s),
              "depth": int(depth)}
        if parent is not None:
            ev["parent"] = str(parent)
        ev.update(fields)
        self._emit(ev)

    def record_serve(self, queries: int, achieved_qps: float,
                     latency_p50_ms: float, latency_p95_ms: float,
                     latency_p99_ms: float, **fields) -> None:
        """One serving latency/throughput window (schema v3,
        ``sgcn_tpu/serve/engine.py``): measured per-query latency quantiles
        + achieved QPS, with the batching/compile counters and the analytic
        per-query wire-row gauge riding along as optional fields."""
        ev = {"kind": "serve", "queries": int(queries),
              "achieved_qps": float(achieved_qps),
              "latency_p50_ms": float(latency_p50_ms),
              "latency_p95_ms": float(latency_p95_ms),
              "latency_p99_ms": float(latency_p99_ms)}
        ev.update({k: v for k, v in fields.items() if v is not None})
        self._emit(ev)

    def record_checkpoint(self, step: int, path: str,
                          wall_s: float | None = None,
                          bytes: int | None = None) -> None:
        """One COMMITTED durable checkpoint (schema v4,
        ``resilience.runner``): emitted after the atomic rename, so this
        event in the stream certifies the named file was fully on disk."""
        ev = {"kind": "checkpoint", "step": int(step), "path": str(path)}
        for k, val in (("wall_s", wall_s), ("bytes", bytes)):
            if val is not None:
                ev[k] = val
        self._emit(ev)

    def record_resume(self, step: int, path: str, fallback: bool = False,
                      partial_state: bool = False,
                      skipped: list | None = None) -> None:
        """One restore (schema v4, the trainer CLI's ``--resume``):
        ``fallback`` marks a corrupted-latest → previous-intact fallback,
        ``partial_state`` a params-only restore of a pre-full-state file."""
        ev = {"kind": "resume", "step": int(step), "path": str(path),
              "fallback": bool(fallback), "partial_state": bool(partial_state)}
        if skipped:
            ev["skipped"] = [str(s) for s in skipped]
        self._emit(ev)

    def record_swap(self, path: str, weights_rev: int,
                    checkpoint_step: int | None = None,
                    wall_s: float | None = None) -> None:
        """One zero-recompile weight hot-swap (schema v5,
        ``ServeEngine.swap_weights``): emitted after provenance
        verification and the leaf swap, so every serve event after it
        describes ``weights_rev``."""
        ev = {"kind": "swap", "path": str(path),
              "weights_rev": int(weights_rev)}
        for k, val in (("checkpoint_step", checkpoint_step),
                       ("wall_s", wall_s)):
            if val is not None:
                ev[k] = val
        self._emit(ev)

    def record_memory(self, program: str, model, measured: dict | None = None,
                      budget_bytes: int | None = None) -> None:
        """One compiled program's analytic-vs-measured HBM join (schema v6,
        ``obs/memory.py``): ``model`` is a ``MemoryModel``, ``measured`` a
        ``measure_compiled`` dict (``None`` when the backend exposes no
        memory analysis — the join is then simply absent)."""
        ev = {"kind": "memory", "program": str(program),
              "model_bytes": int(model.total_bytes),
              "workload": model.workload,
              "families": {name: int(b)
                           for name, b in model.families.items()}}
        if measured is not None:
            # measure_compiled's "peak_bytes" lands as "measured_peak_bytes"
            # in the event vocabulary (the model side owns the bare names)
            ev.update({("measured_peak_bytes" if k == "peak_bytes" else k):
                       int(v) for k, v in measured.items()})
            if model.total_bytes > 0:
                ev["ratio"] = measured["peak_bytes"] / model.total_bytes
        if budget_bytes is not None:
            ev["budget_bytes"] = int(budget_bytes)
        self._emit(ev)

    def record_heartbeat(self, event: str, **fields) -> None:
        self._emit({"kind": "heartbeat", "event": str(event),
                    "pid": os.getpid(), **fields})

    def record_summary(self, report: dict) -> None:
        """End-of-run report (the trainer's ``fit()`` dict, the bench JSON)."""
        self._emit({"kind": "summary", "report": _jsonable(report)})

    def close(self) -> None:
        self._events.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ------------------------------------------------- out-of-recorder emission
def heartbeat(event: str, **fields) -> None:
    """Append a liveness ping to ``$SGCN_METRICS_OUT/heartbeat.jsonl``.

    No-op unless the env var names a directory — callers sprinkle these at
    phase boundaries unconditionally (launch rendezvous, multichip dryrun)
    and pay nothing when telemetry is off.  Best-effort by design: a full
    disk must not kill the run it is observing.
    """
    outdir = os.environ.get("SGCN_METRICS_OUT")
    if not outdir:
        return
    ev = {"v": schema.SCHEMA_VERSION, "ts": time.time(), "kind": "heartbeat",
          "event": str(event), "pid": os.getpid(), **fields}
    try:
        schema.validate_event(ev)
        os.makedirs(outdir, exist_ok=True)
        with open(os.path.join(outdir, schema.HEARTBEAT_NAME), "a") as fh:
            fh.write(json.dumps(_jsonable(ev)) + "\n")
    except (OSError, ValueError):
        pass


# -------------------------------------------------------------------- loader
@dataclass
class RunLog:
    path: str
    manifest: dict
    events: list          # validated events.jsonl records, in write order
    heartbeats: list      # validated heartbeat.jsonl records (may be empty)

    def steps(self) -> list:
        return [e for e in self.events if e["kind"] == "step"]

    def evals(self) -> list:
        return [e for e in self.events if e["kind"] == "eval"]

    def summaries(self) -> list:
        return [e for e in self.events if e["kind"] == "summary"]

    def serves(self) -> list:
        return [e for e in self.events if e["kind"] == "serve"]

    def checkpoints(self) -> list:
        return [e for e in self.events if e["kind"] == "checkpoint"]

    def resumes(self) -> list:
        return [e for e in self.events if e["kind"] == "resume"]


def load_run(path: str) -> RunLog:
    """Load + validate one run directory.  Raises on schema violations —
    a telemetry consumer must never silently chart garbage.

    A directory holding ONLY ``heartbeat.jsonl`` or ``events.jsonl`` is
    valid: the launch/dryrun layers write heartbeats through
    ``$SGCN_METRICS_OUT`` without a ``RunRecorder`` (no manifest), and a
    killed run's completed measurements must be loadable from exactly
    that.  ``manifest`` is then ``{}``."""
    mpath = os.path.join(path, schema.MANIFEST_NAME)
    if os.path.exists(mpath):
        with open(mpath) as fh:
            manifest = json.load(fh)
        schema.validate_manifest(manifest)
    elif any(os.path.exists(os.path.join(path, n))
             for n in (schema.HEARTBEAT_NAME, schema.EVENTS_NAME)):
        manifest = {}
    else:
        raise FileNotFoundError(
            f"{path}: no {schema.MANIFEST_NAME}, {schema.HEARTBEAT_NAME} "
            f"or {schema.EVENTS_NAME} — not a run directory")

    def read_jsonl(name):
        p = os.path.join(path, name)
        if not os.path.exists(p):
            return []
        out = []
        with open(p) as fh:
            for i, line in enumerate(fh):
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError as e:
                    raise ValueError(
                        f"{p}:{i + 1}: not valid JSON ({e})") from e
                schema.validate_event(ev)
                out.append(ev)
        return out

    return RunLog(path=path, manifest=manifest,
                  events=read_jsonl(schema.EVENTS_NAME),
                  heartbeats=read_jsonl(schema.HEARTBEAT_NAME))


def _jsonable(x):
    """Coerce numpy scalars/arrays and other non-JSON leaves to JSON types."""
    import numpy as np

    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, np.ndarray):
        return [_jsonable(v) for v in x.tolist()]
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    # jax arrays and anything else scalar-like: try float, else repr
    try:
        return float(x)
    except (TypeError, ValueError):
        return repr(x)
