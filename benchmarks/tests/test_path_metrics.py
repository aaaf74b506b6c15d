"""The seven readers of the critical path (benchmarks/path_metrics.py) over a
recorded span fixture; run by hand, `python -m pytest benchmarks/tests`.

path_spans.json: three small traced OrderedWordCount DAGs in one session on
the CPU backend (the first a warm-up, as in a run), every finished span with
its ids, thread, times and the arguments the walk reads.  How it was
recorded is in its ``recorded`` key."""
from __future__ import annotations

import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import path_metrics  # noqa: E402
import run as bench_run  # noqa: E402
from tez_tpu.common import tracing  # noqa: E402
from tez_tpu.common.tracing import Span  # noqa: E402

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
PATH = ["path_host_work_s_per_dag", "path_device_wait_s_per_dag",
        "path_control_s_per_dag", "path_unnamed_s_per_dag",
        "path_stall_s_per_dag"]
SEVEN = PATH + ["dag_turnaround_s_per_dag", "dag_head_s_per_dag"]


@pytest.fixture()
def window():
    """The fixture's spans in the plane, and the harness's observations of
    its window: the two DAGs after the warm-up."""
    tracing.clear_all()
    fixture = json.load(open(os.path.join(TESTS, "path_spans.json")))
    tracing.arm(scope="fixture", capacity=65536)
    for name, cat, trace, sid, parent, start, end, args, thread in \
            fixture["spans"]:
        sp = Span(name, cat, trace, parent, dict(args))
        sp.span_id, sp.start, sp.end, sp.thread = sid, start, end, thread
        tracing.plane().record(sp)
    yield {"dags": fixture["dags"][1:]}
    tracing.clear_all()


def test_each_reader_file_says_what_benchmark_json_says():
    entries = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert [m["name"] for m in BENCHMARK["per_layer"]][-7:] == SEVEN
    for name in SEVEN:
        entry = entries[name]
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           f"{name}.json")))
        assert spec["layer"] == entry["layer"]
        assert spec["moves"] == entry["moves"] == "dag_wall_s"
        assert "workloads" not in entry and "workloads" not in spec
        assert (entry["unit"], entry["better"], entry["source"]) == \
            ("s", "lower", "program_span")
        assert spec["kind"] == "module" and os.path.exists(
            os.path.join(BENCH, "layer_metrics", spec["module"]))


def test_the_five_path_metrics_sum_to_the_windows_dag_wall(window):
    values = {name: bench_run.read_layer_metric(name, window)
              for name in SEVEN}
    assert all(v is not None for v in values.values()), values
    dags = window["dags"]
    dag_wall = (dags[-1]["t_done"] - dags[0]["t_submit"]) / len(dags)
    assert sum(values[name] for name in PATH) == pytest.approx(dag_wall,
                                                               rel=1e-9)
    assert values["path_stall_s_per_dag"] == 0.0      # walked, none met
    assert values["path_host_work_s_per_dag"] > 0
    assert values["path_device_wait_s_per_dag"] > 0
    assert values["path_control_s_per_dag"] > 0
    assert values["path_unnamed_s_per_dag"] < 0.10 * dag_wall
    path = path_metrics.window_path(window)
    assert path["periods"] == 2 and path["miss"] == 0
    assert path["steps"]["guess"] <= 0.10 * sum(path["steps"].values())
    # between two DAGs: the sorter's tail, commit, the client's turn, the
    # next head up to its first attempt; a head: submit -> first launch
    assert 0 < values["dag_turnaround_s_per_dag"] < dag_wall
    assert 0 < values["dag_head_s_per_dag"] < dag_wall


def test_a_buffer_that_dropped_spans_gives_no_number(window):
    spans = tracing.snapshot()
    tracing.clear_all()
    tracing.arm(scope="small", capacity=len(spans) - 10)
    for sp in spans:
        tracing.plane().record(sp)
    assert tracing.dropped() == 10
    for name in SEVEN:
        assert bench_run.read_layer_metric(name, window) is None, name


def test_a_walk_that_misses_gives_no_number(window, monkeypatch):
    from tez_tpu.tools import trace_export
    monkeypatch.setattr(trace_export, "_MAX_STEPS", 5)   # the walk gives up
    for name in PATH:
        assert bench_run.read_layer_metric(name, window) is None, name


def test_a_program_without_links_gives_no_path_and_does_not_raise(
        window, monkeypatch):
    """The parent of the PR that brought the links: no ``tracing.here``."""
    monkeypatch.delattr(tracing, "here")
    for name in PATH:
        assert bench_run.read_layer_metric(name, dict(window)) is None, name
    # the two that read spans the parent has still read them
    assert bench_run.read_layer_metric("dag_turnaround_s_per_dag",
                                       dict(window)) > 0
