"""The documents name things that exist: every backticked path, every
``python -m tez_tpu.…`` module and every backticked ``make <target>`` in
README.md, PERF.md, the verify skill and docs/*.md resolves in this
checkout."""
import glob
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "PERF.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_ROOTS = ("tez_tpu", "tools", "tests", "benchmarks", "docs")
#: shorthand the documents use for packages under tez_tpu/
_SHORTHAND = ("ops", "library", "parallel")
_PATH = re.compile(
    r"(?:%s)/[\w./*<>-]*" % "|".join(_ROOTS + _SHORTHAND))
_MODULE = re.compile(r"python3? (?:-u )?-m (tez_tpu(?:\.\w+)+)")
_MAKE = re.compile(r"^make ([a-z][\w-]*)")


with open(os.path.join(REPO, "Makefile")) as _fh:
    _MAKE_TARGETS = set(re.findall(r"^([a-z][\w-]*):", _fh.read(), re.M))


def _exists(path: str) -> bool:
    path = path.rstrip(".")
    head = path.split("/", 1)[0]
    if head in _SHORTHAND:
        path = "tez_tpu/" + path
    if "<" in path:           # `benchmarks/layer_metrics/<name>.json`
        path = re.sub(r"<[^>]*>", "*", path)
    return bool(glob.glob(os.path.join(REPO, path)))


def _missing(text: str):
    for code in re.findall(r"`([^`\n]+)`", text):
        # a path is named by a span that starts with it: the match ends
        # where `a/b.py:12`, `a/b.py::test_x` or `a/b.py make_corpus` go
        # on to name a place inside the file
        m = _PATH.match(code)
        if m and not _exists(m.group(0)):
            yield code
        m = _MAKE.match(code)
        if m and m.group(1) not in _MAKE_TARGETS:
            yield code
    for mod in _MODULE.findall(text):
        if importlib.util.find_spec(mod) is None:
            yield "python -m " + mod


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(REPO, doc)) as fh:
        missing = sorted(set(_missing(fh.read())))
    assert not missing, f"{doc} names what is not in the tree: {missing}"
