"""Run-telemetry subsystem: manifest + per-step JSONL events + attribution.

Four layers (see ``docs/observability.md`` for the operator guide):

  * ``recorder``    — ``RunRecorder`` (manifest, append-only event stream,
    heartbeats) and the ``load_run`` loader;
  * ``attribution`` — the analytic step cost model (plan-derived SpMM/dense
    FLOPs, gather bytes, halo wire bytes) joined against measured step time
    into roofline fields;
  * ``tracing``     — the MEASURED-time profiling layer: the span API
    (nested wall-clock spans emitted as ``span`` events), the
    ``jax.profiler`` trace parser (per-device op timelines classified into
    the attribution vocabulary → measured overlap / exposed comm /
    straggler skew), and the per-step ``measured_vs_model`` reconciliation
    of the two;
  * ``schema``      — the versioned event vocabulary all of the above are
    validated against.

Wired through the trainers (``FullBatchTrainer.attach_recorder`` /
``MiniBatchTrainer.attach_recorder``), the trainer CLI (``--metrics-out``)
and the launch/dryrun layers (heartbeats via ``$SGCN_METRICS_OUT``).
Rendered by ``scripts/obs_report.py``.
"""

from .attribution import (STREAM_CEILING_GBS, StepCostModel,
                          gather_bytes_per_epoch, roofline_fields, step_cost)
from .memory import (MEM_MODEL_TOL, MemoryBudgetError, MemoryModel,
                     check_memory_budget, measure_compiled, memory_model,
                     parse_bytes, reconcile)
from .recorder import RunLog, RunRecorder, heartbeat, load_run, plan_digest
from .schema import SCHEMA_VERSION, validate_event, validate_manifest
from .tracing import (SpanTimer, TraceSummary, classify_op,
                      find_trace_files, measured_vs_model_block,
                      summarize_trace, trace_path_for_run)

__all__ = [
    "MEM_MODEL_TOL", "SCHEMA_VERSION", "STREAM_CEILING_GBS",
    "MemoryBudgetError", "MemoryModel", "RunLog", "RunRecorder",
    "SpanTimer", "StepCostModel", "TraceSummary",
    "check_memory_budget", "classify_op",
    "find_trace_files", "gather_bytes_per_epoch", "heartbeat", "load_run",
    "measure_compiled", "measured_vs_model_block", "memory_model",
    "parse_bytes", "plan_digest", "reconcile", "roofline_fields",
    "step_cost", "summarize_trace", "trace_path_for_run",
    "validate_event", "validate_manifest",
]
