"""The documents name what is in the tree.

One case a document, over ``README.md`` and every ``docs/*.md``.  In what a
document sets as code (back-quoted spans and fenced blocks):

- a path under ``tools/``, ``tests/``, ``docs/``, ``benchmark/`` or the
  package (``pytorch_zappa_serverless_tpu/serving/x.py`` or, as the documents
  mostly write it, ``serving/x.py``) exists, and so does the script or module
  a ``python`` command runs;
- an ``UPPER_CASE=value`` variable set in front of a command is read by some
  ``*.py`` of the tree (a ``TPUSERVE_<FIELD>`` override of a config field is
  read by ``config.py``);
- a ``tpuserve <sub>`` it shows is a subcommand ``cli.py``'s parser has.

A document that tells an operator to run what is gone fails here, in
milliseconds, with the line's number.
"""

import argparse
import dataclasses
import re
from pathlib import Path

import pytest

from pytorch_zappa_serverless_tpu.cli import build_parser
from pytorch_zappa_serverless_tpu.config import _ENV_PREFIX, ServeConfig

REPO = Path(__file__).resolve().parents[1]
PACKAGE = REPO / "pytorch_zappa_serverless_tpu"
DOCUMENTS = [REPO / "README.md", *sorted((REPO / "docs").glob("*.md"))]

# Where a path the documents name can lie: the repo's own directories, and
# the package's, which the documents write without the package's name.
_ROOTS = ("tools", "tests", "docs", "benchmark", PACKAGE.name)
_PACKAGE_DIRS = tuple(sorted(
    p.name for p in PACKAGE.iterdir() if p.is_dir() and p.name[0] not in "._"))
_PATH = re.compile(
    r"(?<![\w./-])((?:%s)/[\w./*<>{}-]*)" % "|".join(_ROOTS + _PACKAGE_DIRS))
_PYTHON = re.compile(r"\bpython3?\s+(-m\s+)?([\w./-]+)")
_ASSIGNMENT = re.compile(r"(?<![\w$-])([A-Z][A-Z0-9_]{2,})=(?=\S)")
_SUBCOMMAND = re.compile(
    r"(?:\btpuserve|pytorch_zappa_serverless_tpu\.cli)\s+([a-z][a-z-]*)\b")
_SPAN = re.compile(r"`([^`\n]+)`")


def code_of(text: str):
    """(line number, code) for every back-quoted span and fenced line."""
    fenced = False
    for number, line in enumerate(text.splitlines(), 1):
        if line.lstrip().startswith("```"):
            fenced = not fenced
        elif fenced:
            yield number, line
        else:
            for span in _SPAN.findall(line):
                yield number, span


def _exists(path: str) -> bool:
    """A directory (``benchmark/``), a file, or a name inside a module
    (``engine/weights.save_adapter``: ``engine/weights.py`` exists).  What
    holds a pattern or a placeholder, and what has neither a suffix nor a
    closing slash (``deploy/update/tail/undeploy``), names no one path."""
    path = re.split(r"::|:\d", path.rstrip(".,:;"))[0]  # x.py::test, x.py:12
    if re.search(r"[*<>{}]", path) or not ("." in path or path.endswith("/")):
        return True
    directory, _, leaf = path.rpartition("/")
    module = f"{directory}/{leaf.split('.')[0]}.py"
    return any((root / candidate).exists() for root in (REPO, PACKAGE)
               for candidate in (path, module))


def _module_exists(dotted: str) -> bool:
    if dotted.split(".")[0] not in _ROOTS:
        return True                             # pytest, json.tool: not ours
    base = REPO / dotted.replace(".", "/")
    return base.with_suffix(".py").exists() or (base / "__main__.py").exists()


def _sources() -> str:
    files = [*REPO.glob("*.py"), *PACKAGE.rglob("*.py")]
    for root in ("tools", "tests", "benchmark"):
        files += (REPO / root).rglob("*.py")
    return "\n".join(p.read_text() for p in files if p != Path(__file__))


@pytest.fixture(scope="module")
def is_read():
    """name -> whether some ``*.py`` of the tree reads that variable."""
    sources = _sources()
    overrides = {_ENV_PREFIX + f.name.upper()
                 for f in dataclasses.fields(ServeConfig)}

    def check(name: str) -> bool:
        return (name in overrides
                or re.search(r"\b%s\b" % name, sources) is not None)
    return check


@pytest.fixture(scope="module")
def subcommands():
    parser = build_parser()
    action, = (a for a in parser._actions
               if isinstance(a, argparse._SubParsersAction))
    return set(action.choices)


@pytest.mark.parametrize("document", DOCUMENTS,
                         ids=[str(p.relative_to(REPO)) for p in DOCUMENTS])
def test_document_names_what_is_in_the_tree(document, is_read, subcommands):
    stale = []
    for number, code in code_of(document.read_text()):
        for path in _PATH.findall(code):
            if not _exists(path):
                stale.append(f"{number}: no such path: {path}")
        for module, target in _PYTHON.findall(code):
            if module and not _module_exists(target):
                stale.append(f"{number}: python -m {target}: no such module")
            elif not module and target.endswith(".py") \
                    and not _exists(target):
                stale.append(f"{number}: python {target}: no such script")
        for name in _ASSIGNMENT.findall(code):
            if not is_read(name):
                stale.append(f"{number}: {name}= is read by no *.py here")
        for sub in _SUBCOMMAND.findall(code):
            if sub not in subcommands:
                stale.append(f"{number}: tpuserve {sub}: no such subcommand")
    assert not stale, f"{document.relative_to(REPO)}:\n" + "\n".join(stale)
