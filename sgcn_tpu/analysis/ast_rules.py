"""The AST hot-path hygiene pass (pass 2 of ``sgcn_tpu.analysis``).

A registry of repo-source rules run over the package
with ``ast`` — no imports of the scanned modules, so a rule can never be
defeated by import-time side effects, and every rule function takes
``(relpath, src)`` so the tier-1 mutation checks can feed it a seeded
violation directly (``tests/test_analysis.py``).

Rules (see ``docs/static_analysis.md`` for the table):

  * ``traced-host-free`` — no ``time.*`` / ``np.random.*`` calls in the
    traced-code modules (``ops/``, ``models/``): a host clock or host RNG
    inside per-chip shard_map code either burns at trace time (silently
    constant-folded into the program — a frozen "random" number) or forces
    a host callback;
  * ``sanctioned-sync-only`` — no direct ``block_until_ready`` /
    ``device_get`` in the trainer/serve/op/model/obs/utils layers: every
    sync point goes through the ``sync=`` callables of ``PhaseTimer`` /
    ``SpanTimer`` (``utils/timers.py``, the one allowlisted home) so
    measured-time accounting cannot silently bypass the span machinery;
  * ``consumer-registered`` — every module-level ``*_FIELDS*`` tuple of
    strings is registered in ``registry.CONSUMER_TUPLE_SOURCES`` (or is one of
    the two classification tuples): an unregistered consumer tuple is a
    plan-shipping contract the plan-contract lint cannot see;
  * ``mode-flag-enumerated`` — every ``--comm-*`` / ``--halo-*`` flag any
    CLI defines maps to a mode-matrix axis (``modes.MODE_FLAGS``) or is a
    recorded non-axis (``modes.NON_AXIS_FLAGS``), and every axis flag
    exists on the trainer CLI: a new transport/wire knob cannot land
    outside the audited matrix;
  * ``model-name-free`` — no comparison against a model's name (a
    ``MODELS`` key) in the layers below the models: the trainers (bar the
    CLI's input validation), the memory model, checkpoints and
    ``parallel/``.  What differs between models is a field of their
    ``models/setup.py`` record or of what their setup hook returns.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass

# modules whose function bodies are (almost entirely) traced per-chip code
TRACED_PREFIXES = ("sgcn_tpu/ops/", "sgcn_tpu/models/")
# layers where a raw sync call would bypass the span accounting (utils/
# included — that is what makes the allowlist below LIVE rather than
# documentation)
SYNC_SCOPED_PREFIXES = ("sgcn_tpu/train/", "sgcn_tpu/serve/",
                        "sgcn_tpu/ops/", "sgcn_tpu/models/",
                        "sgcn_tpu/obs/", "sgcn_tpu/utils/")
# the ONE sanctioned home of jax.block_until_ready (PhaseTimer's sync=
# hook — every other module in scope must route through it)
SYNC_ALLOWLIST = ("sgcn_tpu/utils/timers.py",)

# the CLIs whose mode-like flags must be enumerator-covered
MODE_FLAG_FILES = ("sgcn_tpu/train/__main__.py",
                   "sgcn_tpu/serve/__main__.py")
_MODE_LIKE_RE = re.compile(r"^--(comm|halo)-")

_FIELDS_NAME_RE = re.compile(r"^_?[A-Z][A-Z0-9_]*_FIELDS[A-Z0-9_]*$")

# the keys of train/fullbatch.py::MODELS (tests/test_model_seam.py holds
# the two equal), and the modules that must not compare against them
MODEL_NAMES = ("gcn", "gat", "mhgat", "deepergcn", "rgcn", "rgat", "unimp")
NAME_FREE_PREFIXES = ("sgcn_tpu/train/", "sgcn_tpu/parallel/",
                      "sgcn_tpu/obs/memory.py", "sgcn_tpu/utils/checkpoint.py")
NAME_FREE_EXEMPT = ("sgcn_tpu/train/__main__.py",)

# PREFIX roots (the whole dotted name starts with these — bare "random."
# must not be a containment match or jax.random.* would false-positive)
_HOST_TIME_ROOTS = ("time.", "random.")
# CONTAINMENT roots (numpy's RNG namespace, wherever it is reached from)
_HOST_RNG_ROOTS = ("np.random.", "numpy.random.")
_SYNC_ATTRS = ("block_until_ready", "device_get")


def _dotted(node: ast.AST) -> str:
    """Dotted name of a call target anchored at a plain Name
    ('np.random.default_rng'); '' for chains rooted in a call/subscript —
    a method on a computed object is not a module-qualified call and must
    not resolve to a bare root like 'random.'."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return ""
    parts.append(node.id)
    return ".".join(reversed(parts))


@dataclass(frozen=True)
class Rule:
    name: str
    scope: str          # human-readable scope description (docs table)
    fn: object          # (relpath, src) -> list[str]

    def applies(self, relpath: str) -> bool:
        return _SCOPES[self.name](relpath)


def _import_aliases(tree: ast.AST) -> dict:
    """Local name → dotted origin for every import binding, so aliased
    spellings (``import time as t``, ``from numpy.random import
    default_rng``) resolve to the canonical dotted name before matching —
    the natural spellings of a violation must not slip the rule."""
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    aliases[a.asname] = a.name
                else:
                    top = a.name.split(".")[0]
                    aliases[top] = top
        elif isinstance(node, ast.ImportFrom) and node.module \
                and not node.level:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def rule_traced_host_free(relpath: str, src: str) -> list[str]:
    tree = ast.parse(src)
    aliases = _import_aliases(tree)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if not name:
            continue
        head, _, rest = name.partition(".")
        resolved = aliases.get(head, head) + (f".{rest}" if rest else "")
        dn = resolved + "."
        if dn.startswith(_HOST_TIME_ROOTS) or any(
                r in dn for r in _HOST_RNG_ROOTS):
            out.append(f"{relpath}:{node.lineno}: call to {name}() "
                       f"(= {resolved}) in a traced-code module — host "
                       "clocks/RNG inside per-chip code freeze at trace "
                       "time or force a host callback; compute it offline "
                       "and pass it in")
    return out


def rule_sanctioned_sync_only(relpath: str, src: str) -> list[str]:
    out = []
    for node in ast.walk(ast.parse(src)):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        attr = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if attr in _SYNC_ATTRS:
            out.append(f"{relpath}:{node.lineno}: direct {attr}() — sync "
                       "points go through the sync= callables of "
                       "PhaseTimer/SpanTimer (utils/timers.py) so the "
                       "measured-time accounting sees them")
    return out


def rule_consumer_registered(relpath: str, src: str) -> list[str]:
    from .registry import known_fields_names

    known = known_fields_names()
    out = []
    tree = ast.parse(src)
    for node in tree.body:                      # module level only
        targets = []
        if isinstance(node, ast.Assign):
            targets = [t for t in node.targets if isinstance(t, ast.Name)]
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                            ast.Name):
            targets = [node.target]
        for t in targets:
            if not _FIELDS_NAME_RE.match(t.id):
                continue
            val = node.value
            if not (isinstance(val, ast.Tuple) and val.elts and all(
                    isinstance(e, ast.Constant) and isinstance(e.value, str)
                    for e in val.elts)):
                continue                        # not a field-name tuple
            if t.id not in known:
                out.append(
                    f"{relpath}:{node.lineno}: {t.id} is a *_FIELDS* "
                    "string tuple not registered in analysis/registry.py "
                    "CONSUMER_TUPLE_SOURCES — the plan-contract lint "
                    "cannot validate what it does not know about")
    return out


def rule_model_name_free(relpath: str, src: str) -> list[str]:
    out = []
    for node in ast.walk(ast.parse(src)):
        if not isinstance(node, ast.Compare):
            continue
        for operand in (node.left, *node.comparators):
            items = (operand.elts if isinstance(
                operand, (ast.Tuple, ast.List, ast.Set)) else [operand])
            names = [e.value for e in items if isinstance(e, ast.Constant)
                     and e.value in MODEL_NAMES]
            if names:
                out.append(f"{relpath}:{node.lineno}: compares against the "
                           f"model name(s) {names} — read a field of the "
                           "model's record or ModelSetup (models/setup.py) "
                           "instead")
                break
    return out


def rule_mode_flag_enumerated(sources: dict) -> list[str]:
    """Cross-file rule over ``MODE_FLAG_FILES``: takes ``{relpath: src}``."""
    from .modes import MODE_FLAGS, NON_AXIS_FLAGS

    out = []
    train_flags: set = set()
    for relpath, src in sources.items():
        for node in ast.walk(ast.parse(src)):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_argument" and node.args):
                continue
            for arg in node.args:
                if not (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)):
                    continue
                flag = arg.value
                if relpath.endswith("train/__main__.py"):
                    train_flags.add(flag)
                if _MODE_LIKE_RE.match(flag) and flag not in MODE_FLAGS \
                        and flag not in NON_AXIS_FLAGS:
                    out.append(
                        f"{relpath}:{node.lineno}: mode-like flag {flag} "
                        "is neither a mode-matrix axis (modes.MODE_FLAGS) "
                        "nor a recorded non-axis (modes.NON_AXIS_FLAGS) — "
                        "a transport/wire knob outside the audited matrix")
    if train_flags:
        for flag in MODE_FLAGS:
            if flag not in train_flags:
                out.append(
                    f"modes.MODE_FLAGS names {flag}, which the trainer CLI "
                    "does not define — a dead matrix axis")
    return out


_SCOPES = {
    "traced-host-free":
        lambda p: p.startswith(TRACED_PREFIXES),
    "sanctioned-sync-only":
        lambda p: (p.startswith(SYNC_SCOPED_PREFIXES)
                   and p not in SYNC_ALLOWLIST),
    "consumer-registered":
        lambda p: p.startswith("sgcn_tpu/"),
    "mode-flag-enumerated":
        lambda p: p in MODE_FLAG_FILES,
    "model-name-free":
        lambda p: (p.startswith(NAME_FREE_PREFIXES)
                   and p not in NAME_FREE_EXEMPT),
}

RULES = (
    Rule("traced-host-free", "sgcn_tpu/{ops,models}/",
         rule_traced_host_free),
    Rule("sanctioned-sync-only",
         "sgcn_tpu/{train,serve,ops,models,obs,utils}/ minus "
         "utils/timers.py",
         rule_sanctioned_sync_only),
    Rule("consumer-registered", "sgcn_tpu/**", rule_consumer_registered),
    Rule("mode-flag-enumerated",
         "train/serve CLIs (cross-file)",
         rule_mode_flag_enumerated),
    Rule("model-name-free",
         "sgcn_tpu/{train,parallel}/ minus train/__main__.py, "
         "obs/memory.py, utils/checkpoint.py",
         rule_model_name_free),
)


def _iter_sources(root: str):
    pkg = os.path.join(root, "sgcn_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for name in sorted(files):
            if name.endswith(".py"):
                full = os.path.join(dirpath, name)
                yield os.path.relpath(full, root).replace(os.sep, "/"), full


def run_ast_pass(root: str | None = None) -> dict:
    """Run every rule over the repo; returns the ``ast`` block of the
    analysis report: ``{rules: {name: {ok, violations}}, ok}``."""
    if root is None:
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    per_file_rules = [r for r in RULES if r.name != "mode-flag-enumerated"]
    results = {r.name: [] for r in RULES}
    mode_sources: dict = {}
    for relpath, full in _iter_sources(root):
        with open(full) as fh:
            src = fh.read()
        for r in per_file_rules:
            if r.applies(relpath):
                results[r.name] += r.fn(relpath, src)
        if relpath in MODE_FLAG_FILES:
            mode_sources[relpath] = src
    results["mode-flag-enumerated"] = rule_mode_flag_enumerated(mode_sources)
    return {
        "rules": {name: {"ok": not v, "violations": v}
                  for name, v in results.items()},
        "ok": all(not v for v in results.values()),
    }
