"""Analysis CLI — ``python -m sgcn_tpu.analysis``.

Runs the AST hygiene pass and the compiled-program audit over the
supported mode matrix on a FORCED virtual 8-device CPU mesh (lowering
only — deterministic on any host, no accelerator needed), and emits the
JSON report.  ``--fast`` audits the 2-mode smoke subset (the CI smoke in
``tests/test_cli.py``); the full run is what
``tests/test_analysis.py`` checks mode by mode.  ``--out FILE`` writes the
report on demand; no copy is committed.

Exit code 1 on any violation — wire this wherever a lint belongs.
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> int:
    p = argparse.ArgumentParser(
        description="sgcn_tpu static analysis: HLO contract audit + AST "
                    "hygiene")
    p.add_argument("--fast", action="store_true",
                   help="audit the 2-mode smoke subset instead of the "
                        "full matrix")
    p.add_argument("--json", action="store_true",
                   help="print the full report as ONE JSON line on stdout")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="also write the report JSON to FILE")
    p.add_argument("--no-hlo", action="store_true",
                   help="skip the HLO audit (AST pass only; no jax)")
    p.add_argument("--no-ast", action="store_true",
                   help="skip the AST pass (HLO audit only)")
    p.add_argument("--memory", action="store_true",
                   help="also COMPILE every mode's programs and reconcile "
                        "XLA memory_analysis() against the analytic "
                        "footprint model (the memory-model rule; ~1 s per "
                        "program)")
    args = p.parse_args()

    if args.memory and args.no_hlo:
        p.error("--memory needs the jax mesh; drop --no-hlo")

    if not args.no_hlo:
        # the audit's programs are lowered against the virtual 8-chip mesh;
        # force it BEFORE jax initializes a backend (same mechanism as the
        # trainer CLI's `-b cpu`)
        from ..utils.backend import use_cpu_devices
        from .hlo_audit import AUDIT_K

        use_cpu_devices(AUDIT_K)

    from . import build_report

    report = build_report(fast=args.fast, hlo=not args.no_hlo,
                          ast_pass=not args.no_ast, memory=args.memory)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
            fh.write("\n")
    if args.json:
        print(json.dumps(report, sort_keys=True))
    else:
        _human(report)
    return 0 if report["ok"] else 1


def _human(report: dict) -> None:
    if "ast" in report:
        for name, entry in sorted(report["ast"]["rules"].items()):
            print(f"ast  {name:24s} "
                  f"{'ok' if entry['ok'] else 'FAIL'}")
            for v in entry["violations"]:
                print(f"     - {v}")
    if "hlo" in report:
        for mode_id, entry in sorted(report["hlo"]["modes"].items()):
            print(f"hlo  {mode_id:32s} "
                  f"{'ok' if entry['ok'] else 'FAIL'}")
            for label, prog in sorted(entry["programs"].items()):
                for v in prog["violations"]:
                    print(f"     - [{label}] {v['rule']}: {v['detail']}")
    if "memory" in report:
        for mode_id, entry in sorted(report["memory"]["modes"].items()):
            ratios = ", ".join(
                f"{label} {prog['ratio']:.2f}"
                for label, prog in sorted(entry["programs"].items())
                if prog.get("ratio") is not None)
            print(f"mem  {mode_id:32s} "
                  f"{'ok' if entry['ok'] else 'FAIL'}"
                  f"  model={entry['model_bytes']:,}B"
                  f"{'  peak/model: ' + ratios if ratios else ''}")
            for label, prog in sorted(entry["programs"].items()):
                for v in prog["violations"]:
                    print(f"     - [{label}] {v['rule']}: {v['detail']}")
    print(f"analysis: {'clean' if report['ok'] else 'VIOLATIONS'}")


if __name__ == "__main__":
    sys.exit(main())
