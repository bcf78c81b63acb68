"""Tier-1 gate: the tree has ONE benchmark and ONE set of process entries,
and its documents name only files that are there.

PR 31 took out the pre-chip measurement estate (a kernel microbenchmark
and the chain harnesses of the CPU rounds, a third copy of the replica and
sidecar process entries among them) while eighteen README lines, nine of
the verify skill and twelve of COVERAGE.md still pointed at it.  These
tests keep that from growing back: a document that names a file that is not
there, a second ``*_main.py`` for a role, or a library module that imports
one of its own tools fails here, not in the next reader's head.

Every walk skips dot-directories: the ignored ``.checkout/`` holds whole
copies of the tree.
"""

import ast
import os
import re

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ``python[3] [-flags] path.py`` anywhere in a document, fenced blocks too.
_COMMAND = re.compile(r"\bpython3?\s+(?:-\w+\s+)*([\w./-]+\.py)\b")
_FENCED = re.compile(r"```.*?```", re.S)
_BACKTICKED = re.compile(r"`([^`]+)`")
#: ``path.py::name`` and ``path.py:12-30`` point INTO a file.
_POINTER = re.compile(r"(::.*|:\d+(-\d+)?)$")


def _tree_files():
    """Repo-relative paths of every file outside dot-directories."""
    out = []
    for root, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d != "__pycache__"]
        rel = os.path.relpath(root, _REPO)
        out.extend(os.path.normpath(os.path.join(rel, f)) for f in files)
    return out


def _named_paths(text: str):
    """(commands, back-ticked paths) a document names.  A word of a
    back-ticked span counts when it ends in ``.py`` or ``/`` and is a
    literal relative path: no placeholder (``<checkout>/``), no glob,
    nothing absolute."""
    commands = set(_COMMAND.findall(text))
    ticked = set()
    spans = _BACKTICKED.findall(_FENCED.sub("", text))
    for token in (word for span in spans for word in span.split()):
        token = _POINTER.sub("", token)
        if not token.endswith((".py", "/")) or token.startswith(("/", "~", "-")):
            continue
        if re.search(r"[<>*{}$|=,()\\]", token) or "://" in token:
            continue
        ticked.add(token)
    return commands, ticked


def _exists(path: str, files) -> bool:
    """Documents abbreviate (``core/pool.py`` for
    ``consensus_tpu/core/pool.py``): a path is there when some file's (or
    directory's) path ends with it at a component boundary."""
    path = path.removeprefix("./")
    if path.endswith("/"):
        return any("/" + path in "/" + f for f in files)
    return any(f == path or f.endswith("/" + path) for f in files)


@pytest.mark.parametrize(
    "document", ["README.md", ".claude/skills/verify/SKILL.md", "COVERAGE.md"]
)
def test_a_document_names_only_files_that_are_in_the_tree(document):
    with open(os.path.join(_REPO, document), encoding="utf-8") as fh:
        commands, ticked = _named_paths(fh.read())
    assert ticked, "the extraction found nothing to check"
    files = _tree_files()
    # A command runs from the repo root: its path is whole, not abbreviated.
    gone = sorted(c for c in commands if c.removeprefix("./") not in files)
    gone += sorted(t for t in ticked if not _exists(t, files))
    assert not gone, f"{document} names files that are not in the tree: {gone}"


@pytest.mark.parametrize("role", ["replica", "sidecar", "driver"])
def test_each_role_has_exactly_one_process_entry(role):
    """``consensus_tpu/deploy/<role>_main.py`` is how a process of that role
    starts, for the benchmark, the chip smoke, the soak and the tests alike:
    a second one is a second answer the next launch-path PR must keep true."""
    entries = [
        f for f in _tree_files()
        if os.path.basename(f).endswith(f"{role}_main.py")
        and not f.startswith("tests" + os.sep)
    ]
    assert entries == [os.path.join("consensus_tpu", "deploy", f"{role}_main.py")]


def test_the_library_imports_none_of_its_tools():
    tools = {"served_bench", "chip_smoke", "examples", "scripts"}
    offenders = []
    for f in _tree_files():
        if not (f.startswith("consensus_tpu" + os.sep) and f.endswith(".py")):
            continue
        with open(os.path.join(_REPO, f), encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=f)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            else:
                continue
            offenders += [
                f"{f}:{node.lineno}: {name}"
                for name in names if name.split(".")[0] in tools
            ]
    assert not offenders, offenders
