"""span_metrics on planted spans, and the new per-layer metrics through a
traced rehearsal; run by hand, `python -m pytest benchmarks/tests`."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import span_metrics  # noqa: E402

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
OLD = {"control_s_per_dag", "dag_wall_max_s", "task_wall_s_per_dag",
       "spill_bytes_per_input_byte", "merge_wait_s_per_dag",
       "exchange_round_s_per_dag", "sort_merge_roofline", "device_idle_pct",
       "peak_hbm_gb", "compiles_in_window"}
NEW = [m for m in BENCHMARK["per_layer"] if m["name"] not in OLD]


def test_thirteen_new_metrics_each_with_its_reader_files():
    assert len(NEW) == 13
    for m in NEW:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           f"{m['name']}.json")))
        assert spec["layer"] == m["layer"] and spec["moves"] == "dag_wall_s"
        assert spec.get("workloads") == m.get("workloads")
        if spec["kind"] == "module":
            assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                               spec["module"]))


def test_self_time_with_nested_and_cross_thread_spans():
    # thread A: run [0, 10] holds merge.readback [2, 5] and merge.gather
    # [5, 6]; thread B, SAME NAME but its own key: merge.readback [3, 9]
    spans = [("task.run", 0.0, 10.0, "runner#1", "t"),
             ("merge.readback", 2.0, 5.0, "runner#1", "t"),
             ("merge.gather", 5.0, 6.0, "runner#1", "t"),
             ("kernel.merge_path_pair", 2.0, 2.5, "runner#1", "t"),
             ("merge.readback", 3.0, 9.0, "runner#2", "t")]
    # readback on A loses the kernel span nested in it: 3 - 0.5, plus B's 6
    assert span_metrics.self_seconds(spans, ["merge.readback"]) == \
        pytest.approx(2.5 + 6.0)
    assert span_metrics.self_seconds(spans, ["task.run"]) == \
        pytest.approx(10.0 - 3.0 - 1.0)
    assert span_metrics.self_seconds(
        spans, ["merge.gather", "merge.readback"]) == pytest.approx(9.5)
    assert span_metrics.self_seconds(spans, ["no.such"]) == 0


def test_untasked_max_finds_a_planted_hole():
    spans = [("dag.dag", 100.0, 110.0, "am#d1", "t1"),
             ("task.attempt", 100.5, 103.0, "c1#1", "t1"),
             ("task.attempt", 102.0, 104.0, "c2#2", "t1"),
             # the hole: 104 -> 106, no attempt open
             ("task.attempt", 106.0, 109.8, "c1#1", "t1"),
             ("dag.dag", 200.0, 203.0, "am#d2", "t2"),
             ("task.attempt", 200.1, 202.9, "c1#1", "t2")]
    assert span_metrics.untasked_max_s(spans) == pytest.approx(2.0)
    # attempts of another DAG do not fill it
    spans.append(("task.attempt", 104.0, 106.0, "c9#9", "t2"))
    assert span_metrics.untasked_max_s(spans) == pytest.approx(2.0)
    assert span_metrics.untasked_max_s(
        [("task.attempt", 0.0, 1.0, "c#1", "t")]) is None


class _Span:
    def __init__(self, name, cat, start, end, thread, trace_id, **args):
        self.name, self.cat, self.start, self.end = name, cat, start, end
        self.thread, self.trace_id, self.args = thread, trace_id, args


def _obs():
    return {"dags": [{"state": "SUCCEEDED", "dag_id": "dag_1"},
                     {"state": "SUCCEEDED", "dag_id": "dag_2"}],
            "histogram_ms": {"device.d2h": 500.0,
                             "device.dispatch_wait": 1500.0}}


def test_readers_take_the_windows_dags_and_refuse_a_ring_that_dropped(
        monkeypatch):
    from tez_tpu.common import tracing
    held = [_Span("dag:x", "dag", 0.0, 10.0, "am#dag_0", "warm",
                  dag_id="dag_0"),
            _Span("merge.readback", "merge", 1.0, 9.0, "c#1", "warm"),
            _Span("dag:x", "dag", 10.0, 20.0, "am#dag_1", "t1",
                  dag_id="dag_1"),
            _Span("attempt:a", "task", 10.5, 19.0, "c#1", "t1"),
            _Span("merge.readback", "merge", 11.0, 14.0, "c#1", "t1"),
            _Span("dag:x", "dag", 20.0, 30.0, "am#dag_2", "t2",
                  dag_id="dag_2"),
            _Span("attempt:b", "task", 20.5, 29.5, "c#1", "t2"),
            _Span("merge.readback", "merge", 21.0, 22.0, "c#1", "t2")]
    monkeypatch.setattr(tracing, "snapshot", lambda: held)
    monkeypatch.setattr(tracing, "dropped", lambda: 0, raising=False)
    obs = _obs()
    # the warm-up's 8 s are not the window's
    assert span_metrics.self_s_per_dag(obs, ("merge.readback",)) == \
        pytest.approx((3.0 + 1.0) / 2)
    assert span_metrics.self_s_per_dag(obs, ("exchange.plan",)) is None
    assert span_metrics.dag_untasked_max_s(obs) == pytest.approx(1.0)
    assert span_metrics.histograms_s_per_dag(
        obs, ("device.dispatch_wait", "device.d2h")) == pytest.approx(1.0)
    monkeypatch.setattr(tracing, "dropped", lambda: 3, raising=False)
    assert span_metrics.self_s_per_dag(obs, ("merge.readback",)) is None
    assert span_metrics.dag_untasked_max_s(obs) is None


@pytest.mark.parametrize("workload", ["owc_session_small", "owc_mesh4_zipf"])
def test_traced_rehearsal_finds_every_new_metric_of_the_cell(workload):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", "2147483659", "--seconds", "1", "--rehearse",
         "2", "--trace", "1"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 4, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    listed = {m["name"] for m in NEW
              if workload in m.get("workloads", [workload])}
    assert listed <= set(line["layer_metrics_found"]), \
        listed - set(line["layer_metrics_found"])
