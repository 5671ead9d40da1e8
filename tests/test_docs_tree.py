"""The documents name files that exist, and every kept record has a reader.

``test_named_paths_exist`` — one case per tracked document that describes
the system as it is: every code-formatted word that looks like a repo path
(ends in ``.py .md .json .sh .gz .npz``; a ``::name`` or ``:line`` suffix
stripped; globs, ``<placeholders>`` and ``$VARS`` skipped) resolves — with a
directory, against the root, ``sgcn_tpu/`` or the document's own directory;
a bare file name, against the base names of the files in the tree.  A
sentence that must name a file that is gone says so without code format.

``test_artifact_has_a_reader`` — one case per file under
``bench_artifacts/``: some file outside that directory names it.
"""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = (
    "README.md", "PARITY.md", "bench_artifacts/README.md",
    ".claude/skills/verify/SKILL.md",
    "docs/comm_schedule.md", "docs/observability.md", "docs/replication.md",
    "docs/resilience.md", "docs/serving.md", "docs/stale_halo.md",
    "docs/static_analysis.md",
)
# exempt, each for its reason: docs/MIGRATION.md names the reference's
# files; PERF.md, ROADMAP.md, CHANGES.md are histories (they name what was
# deleted, on purpose); SURVEY.md, PAPER.md, PAPERS.md, SNIPPETS.md describe
# the system this repo was modelled on; ISSUE.md is the driver's.

# the reference implementation's own directories (PARITY.md and the READMEs
# cite its files beside ours), and file names a reader chooses in the
# example commands
REFERENCE_DIRS = ("GPU/", "DGL/", "preprocess/")
EXAMPLE_FILES = {"ckpt.npz", "snap.npz", "cora.npz"}

_SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", ".jax_cache",
              "chiprun_out", "build", ".cache"}
_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_PATH = re.compile(r"^[\w./-]+\.(?:py|md|json|sh|gz|npz)$")
_SUFFIX = re.compile(r"(::[\w.\[\]-]+|:\d+(?:[-,]\d+)*)+$")


def _tree_files():
    for dirpath, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        for name in files:
            yield os.path.relpath(os.path.join(dirpath, name), REPO)


FILES = frozenset(_tree_files())
BASENAMES = frozenset(os.path.basename(f) for f in FILES)


def named_paths(text: str):
    for span in _CODE.findall(text):
        for word in span.strip("`").split():
            word = _SUFFIX.sub("", word.strip("()[],;:'\"")).rstrip(".,")
            if _PATH.match(word) and not word.startswith(("/", "-")):
                yield word


def resolves(path: str, doc: str) -> bool:
    if path.startswith(REFERENCE_DIRS) or path in EXAMPLE_FILES:
        return True
    if "/" not in path:
        return path in BASENAMES
    roots = ("", "sgcn_tpu", os.path.dirname(doc))
    return any(os.path.normpath(os.path.join(r, path)) in FILES
               for r in roots)


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    with open(os.path.join(REPO, doc)) as fh:
        missing = sorted({p for p in named_paths(fh.read())
                          if not resolves(p, doc)})
    assert not missing, f"{doc} names files that are not in the tree: {missing}"


ARTIFACTS = sorted(f for f in FILES if f.startswith("bench_artifacts/")
                   and f != "bench_artifacts/README.md")


@functools.cache
def _text_outside_artifacts() -> dict:
    """Every text file that may count as a reader, read once."""
    skip = {"bench_artifacts/README.md", "ISSUE.md", "CHANGES.md"}
    out = {}
    for f in FILES - skip:
        if f.endswith((".py", ".md", ".json", ".cpp", ".sh")) \
                and not f.startswith("bench_artifacts/"):
            with open(os.path.join(REPO, f), errors="ignore") as fh:
                out[f] = fh.read()
    return out


@pytest.mark.parametrize("artifact", ARTIFACTS)
def test_artifact_has_a_reader(artifact):
    name = os.path.basename(artifact)
    assert any(name in text for text in _text_outside_artifacts().values()), \
        f"nothing outside bench_artifacts/ names {name}"
