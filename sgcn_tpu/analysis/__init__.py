"""Static-analysis subsystem: compiled-program contract audit + AST
hot-path hygiene (``docs/static_analysis.md``).

The reference earns its overlap guarantee structurally (Irecv → local
SpMM → Waitany); ours lives in compiled XLA programs, where a silent
dispatch regression — an extra ``all_to_all``, an f32 wire under
``--halo-dtype bfloat16``, a dropped donation, a host callback inside a
step — passes every loss-parity test while destroying the TPU-relevant
wins.  This package makes those contracts machine-checked:

  * :mod:`~sgcn_tpu.analysis.modes` — the mode-matrix enumerator (ONE
    source of truth with the ``docs/comm_schedule.md`` composition
    matrix);
  * :mod:`~sgcn_tpu.analysis.hlo` — the shared HLO/StableHLO parser (also
    ridden by ``tests/test_overlap_hlo.py``);
  * :mod:`~sgcn_tpu.analysis.expect` — plan-derived expectations;
  * :mod:`~sgcn_tpu.analysis.hlo_audit` — lower every supported mode's
    real program on the virtual 8-dev mesh and check census / wire dtype
    / wire shape / host-callback / donation contracts;
  * :mod:`~sgcn_tpu.analysis.ast_rules` — the source-hygiene rule
    registry;
  * :mod:`~sgcn_tpu.analysis.registry` — the ``CommPlan`` consumer
    contract tuples (ridden by ``tests/test_plan_contract.py``).

CLI: ``python -m sgcn_tpu.analysis [--fast] [--json] [--out FILE]
[--memory]`` — emits the JSON report (``--out`` writes one on demand;
no copy is committed); ``--memory`` adds the compiling
footprint-reconciliation pass (the ``memory-model`` rule of
``hlo_audit.run_memory_audit``).
"""

from __future__ import annotations

ANALYSIS_SCHEMA = "sgcn_analysis_report"
ANALYSIS_SCHEMA_VERSION = 1


def build_report(fast: bool = False, hlo: bool = True,
                 ast_pass: bool = True, root: str | None = None,
                 memory: bool = False) -> dict:
    """Run the requested passes and assemble the analysis report.

    ``memory`` adds the COMPILING memory-reconciliation pass
    (``hlo_audit.run_memory_audit``): every supported mode's programs are
    compiled and XLA's ``memory_analysis()`` joined against the analytic
    per-chip footprint model under the ``memory-model`` rule.  Off by
    default — it compiles (~3 min for the full matrix) where the text
    audit only lowers."""
    report: dict = {
        "schema": ANALYSIS_SCHEMA,
        "v": ANALYSIS_SCHEMA_VERSION,
        "fast": bool(fast),
        "ok": True,
    }
    if ast_pass:
        from .ast_rules import run_ast_pass

        report["ast"] = run_ast_pass(root)
        report["ok"] = report["ok"] and report["ast"]["ok"]
    if hlo:
        import jax

        from .hlo_audit import run_audit

        report["jax"] = jax.__version__
        report["hlo"] = run_audit(fast=fast)
        report["ok"] = report["ok"] and report["hlo"]["ok"]
    if memory:
        from .hlo_audit import run_memory_audit

        report["memory"] = run_memory_audit(fast=fast)
        report["ok"] = report["ok"] and report["memory"]["ok"]
    return report
