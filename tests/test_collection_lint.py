"""Suite-hygiene lint: expensive tests must be slow-marked or budgeted.

The tier-1 run executes under ONE external timeout (ROADMAP.md); the seed
regressed to rc=124 because unmarked expensive tests ate it silently.  Two
mechanisms now guard that, and this module asserts both exist and bite:

  * **static half** (here): every test module that spawns subprocess
    meshes — re-execing Python with a forced device count, multi-process
    rendezvous, trainer-CLI children — must either carry a
    ``@pytest.mark.slow`` marking for its expensive tests or appear in the
    explicit tier-1 budget allowlist below WITH a justification.  A new
    subprocess-spawning module therefore forces a conscious decision at
    review time instead of a silent timeout at driver time.
  * **runtime half** (``conftest.pytest_runtest_makereport``): any unmarked
    test whose call phase overruns the per-test budget is turned into a
    failure naming the fix.
"""

import ast
import os
import re

import conftest

TESTS = os.path.dirname(os.path.abspath(__file__))

# Modules that spawn subprocesses yet legitimately run in the tier-1 budget:
# each entry records WHY (the measured cost under the 870 s tier-1 budget at
# the time it was added).  Adding a module here is a reviewed decision —
# that is the point of the lint.
SUBPROCESS_BUDGET_ALLOWLIST = {
    "test_cli.py": "end-to-end file-pipeline CLIs on a 150-vertex graph; "
                   "~10 children, each seconds on the forced-CPU backend, "
                   "plus the sgcn_tpu.analysis --fast smoke (2-mode HLO "
                   "subset, ~15 s) and the chip_smoke.py no-chip refusal "
                   "(exits at the platform check, ~3 s)",
    "test_multihost.py": "2-process x 4-vdev rendezvous on a 48-vertex "
                         "graph — the only multi-process coverage tier-1 has",
    "test_import_ogb.py": "offline importer script on a tiny synthetic "
                          "snapshot; no mesh, no training",
    "test_real_datasets.py": "k=4 CLI train on the committed cora fixture "
                             "(k=8 variant IS slow-marked)",
    "test_metrics_cli.py": "two trainer children on the small cora fixture "
                           "(--metrics-out + --profile telemetry smoke, and "
                           "the ragged-schedule wire-reconciliation smoke; "
                           "~50 s together)",
    "test_serve.py": "one serve-CLI child + one obs_report render on the "
                     "small cora fixture (closed-loop micro-batch smoke, "
                     "24 queries, one compiled bucket; ~1 min)",
    "test_resilience.py": "the PR-13 crash-resume acceptance matrix: 9 "
                          "kill/corrupt + resume triples (3 trainer-CLI "
                          "children each) on the cora graph fixture with "
                          "the SYNTHETIC f=16 feature harness (narrow "
                          "features keep each child ~5 s) plus one "
                          "obs_report render — the bit-identity contract "
                          "is only provable by killing REAL subprocess "
                          "runs (docs/resilience.md); whole module "
                          "measured 127 s at PR-13",
    "test_scopes.py": "one python child that loads obs/tracing.py alone to "
                      "prove the module imports without jax (~1 s, no mesh)",
    "test_backend.py": "two pairs of CPU children sharing a temporary "
                       "compile-cache directory, each compiling one 64x64 "
                       "jit — whether a cached executable lends its scope "
                       "names can only be seen across processes (~9 s)",
}

# Modules that run the static-analysis MATRIX auditor
# (sgcn_tpu.analysis.hlo_audit.run_audit — a full run lowers every
# supported mode's real program, ~75 s at HEAD and growing with the
# matrix): same reviewed-budget contract as the subprocess allowlist.  A
# single one-program .lower() is cheap and not gated; the matrix sweep is
# the class that can silently eat the tier-1 budget as modes are added.
MATRIX_AUDIT_BUDGET_ALLOWLIST = {
    "test_analysis.py": "ONE module-scoped full-matrix run (~130 s at "
                        "PR-15 HEAD, 48 mode entries incl. the eight "
                        "pallas modes, lowering only — no "
                        "compile/execute) shared by every matrix "
                        "assertion, plus per-mode mutation audits "
                        "(~2-4 s each)",
    "test_cli.py": "the analysis CLI smoke child runs --fast (2 modes), "
                   "never the full matrix",
    "test_memory_obs.py": "ONE module-scoped COMPILE sweep over the "
                          "8-mode representative slice (~30 s at HEAD — "
                          "one mode per array family the footprint model "
                          "itemizes) shared by every reconciliation "
                          "assertion; the full 48-mode compile matrix "
                          "(run_memory_audit, ~3 min) is slow-marked",
}

# matches ANY invocation of the auditor — in-process (run_audit, or its
# compiling sibling run_memory_audit/memory_audit_mode, ISSUE 18 — that
# one COMPILES every program, strictly pricier than lowering) or the
# CLI in either flavor: a full-matrix CLI child is exactly the expensive
# case this lint exists to catch, so --fast must NOT be required to match
# (the allowlist notes say which flavor each entry is budgeted for).  The
# lookahead excludes plain SUBMODULE imports (sgcn_tpu.analysis.registry
# etc. — cheap, no audit); naming the package itself (the `-m` CLI form
# or a package import) still matches.
_MATRIX_AUDIT_RE = re.compile(
    r"run_(memory_)?audit\(|memory_audit_mode\(|sgcn_tpu\.analysis(?![.\w])")

_SPAWN_RE = re.compile(
    r"subprocess\.(run|Popen|check_output|check_call)"
    r"|_run_vdev_child\(")


def _module_matches(path: str, pattern: re.Pattern) -> bool:
    with open(path) as fh:
        return bool(pattern.search(fh.read()))


def _module_has_slow_marker(path: str) -> bool:
    with open(path) as fh:
        src = fh.read()
    return "mark.slow" in src


def _budget_lint_offenders(pattern: re.Pattern, allowlist: dict) -> list:
    """ONE implementation of the budget lint walk (subprocess meshes AND
    matrix-audit sweeps ride it): modules matching ``pattern`` must be
    slow-marked or allowlisted.  This module itself is excluded — it NAMES
    the patterns."""
    offenders = []
    for name in sorted(os.listdir(TESTS)):
        if not (name.startswith("test_") and name.endswith(".py")):
            continue
        if name == os.path.basename(__file__):
            continue
        path = os.path.join(TESTS, name)
        if not _module_matches(path, pattern):
            continue
        if name in allowlist:
            continue
        if _module_has_slow_marker(path):
            continue
        offenders.append(name)
    return offenders


def _assert_allowlist_live(pattern: re.Pattern, allowlist: dict,
                           what: str) -> None:
    """A stale allowlist is its own hygiene failure: every entry must name
    a live module that still matches (else the entry is dead weight
    masking future regressions)."""
    for name in allowlist:
        path = os.path.join(TESTS, name)
        assert os.path.exists(path), f"allowlisted {name} no longer exists"
        assert _module_matches(path, pattern), (
            f"allowlisted {name} no longer {what} — drop the entry")


def test_subprocess_mesh_tests_are_slow_marked_or_budgeted():
    offenders = _budget_lint_offenders(_SPAWN_RE,
                                       SUBPROCESS_BUDGET_ALLOWLIST)
    assert not offenders, (
        f"test modules {offenders} spawn subprocess meshes but carry no "
        "@pytest.mark.slow and are not in SUBPROCESS_BUDGET_ALLOWLIST — "
        "mark the expensive tests slow, or allowlist the module here WITH "
        "a measured tier-1 budget justification")


def test_matrix_audit_tests_are_slow_marked_or_budgeted():
    """The PR-9 extension of this lint: a module invoking the mode-matrix
    auditor carries a slow mark or a reviewed budget justification — the
    audit's cost scales with the supported matrix, so a new audit-driven
    test is a conscious budget decision exactly like a subprocess mesh."""
    offenders = _budget_lint_offenders(_MATRIX_AUDIT_RE,
                                       MATRIX_AUDIT_BUDGET_ALLOWLIST)
    assert not offenders, (
        f"test modules {offenders} run the static-analysis matrix auditor "
        "but carry no @pytest.mark.slow and are not in "
        "MATRIX_AUDIT_BUDGET_ALLOWLIST — the matrix sweep's cost grows "
        "with every supported mode; budget it consciously")


def test_matrix_audit_allowlist_entries_exist_and_audit():
    _assert_allowlist_live(_MATRIX_AUDIT_RE, MATRIX_AUDIT_BUDGET_ALLOWLIST,
                           "runs the matrix auditor")


def test_allowlist_entries_exist_and_spawn():
    _assert_allowlist_live(_SPAWN_RE, SUBPROCESS_BUDGET_ALLOWLIST,
                           "spawns subprocesses")


def test_runtime_budget_hook_active():
    """The conftest per-test wall-clock tripwire exists, has a sane default,
    and is wired as a hookwrapper (the runtime half of this lint)."""
    assert conftest.TIER1_PER_TEST_BUDGET_S > 0
    assert conftest.TIER1_PER_TEST_BUDGET_S <= 870, (
        "per-test budget exceeds the whole tier-1 suite budget")
    hook = conftest.pytest_runtest_makereport
    # pluggy attaches the hookimpl opts dict to the function; a plain
    # function here means the @pytest.hookimpl(hookwrapper=True) decorator
    # was dropped and the tripwire silently stopped firing
    opts = None
    for attr in dir(hook):
        v = getattr(hook, attr, None)
        if isinstance(v, dict) and ("hookwrapper" in v or "wrapper" in v):
            opts = v
            break
    assert opts is not None and (opts.get("hookwrapper")
                                 or opts.get("wrapper")), (
        "pytest_runtest_makereport lost its hookimpl(hookwrapper=True) "
        "registration")


def test_every_slow_marker_is_collectable():
    """Slow markers must parse as real pytest marks (a typo'd marker would
    silently run the expensive test in tier-1): every module using
    ``mark.slow`` must import pytest and apply it via pytestmark, a
    decorator, or pytest.param marks."""
    for name in sorted(os.listdir(TESTS)):
        if not (name.startswith("test_") and name.endswith(".py")):
            continue
        if name == os.path.basename(__file__):
            continue                    # this module NAMES the marker in prose
        path = os.path.join(TESTS, name)
        with open(path) as fh:
            src = fh.read()
        if "mark.slow" not in src:
            continue
        tree = ast.parse(src)
        imports = {a.name for node in ast.walk(tree)
                   if isinstance(node, ast.Import) for a in node.names}
        assert "pytest" in imports, (
            f"{name} uses mark.slow without importing pytest")
