"""The TPC-H Q3 cell under the harness on a CPU backend: run by hand with the
rest, `python -m pytest benchmarks/tests`.  Every test drives
benchmarks/run.py as a benchmark run does, in a process of its own; the
controls at the cell's own size (SF1), the rehearsals and the planted faults
at 2 MiB (SF 0.01)."""
from __future__ import annotations

import json
import os

import pytest

from test_harness import BENCH, BENCHMARK, TESTS, drive, rehearse

CELL = "tpch_q3_sf1"
NEW_ON_CPU = {"scan_s_per_dag", "kfk_join_s_per_dag"}
NUMBERS = {"rows_wrong", "rows_missing_or_extra", "lines_malformed",
           "commits_missing"}


def test_the_cells_own_metrics_each_with_its_reader_files():
    own = [m for m in BENCHMARK["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in own} == NEW_ON_CPU | {"kfk_probe_roofline"}
    for m in own:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           f"{m['name']}.json")))
        assert spec["layer"] == m["layer"] and spec["moves"] == "dag_wall_s"
        assert spec.get("workloads") == [CELL]
        assert spec["kind"] == "module"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           spec["module"]))


def test_rehearsal_is_correct_and_finds_the_new_layer_metrics():
    proc, line = rehearse(CELL, "--trace", "1")
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert line["rehearsal"] is True and line["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert "metrics" not in line and "device" not in line
    assert set(line["compared"]) == NUMBERS | {"dags_without_answer"}
    assert all(v["value"] == 0 == v["limit"]
               for v in line["compared"].values())
    found = set(line["layer_metrics_found"])
    assert NEW_ON_CPU <= found
    assert {"task_wall_s_per_dag", "commit_s_per_dag", "control_s_per_dag",
            "dag_head_s_per_dag", "path_device_wait_s_per_dag"} <= found
    # nothing to read without a device trace, or listed for other cells
    assert not {"kfk_probe_roofline", "hashjoin_probe_roofline",
                "hashjoin_probe_s_per_dag", "agg_s_per_dag",
                "sort_merge_roofline", "tokenize_s_per_dag"} & found


CONTROLS = {"float32_revenue": "rows_wrong",
            "lineitem_dropped": "rows_wrong",
            "segment_ignored": "rows_wrong",
            "committed_twice": "rows_missing_or_extra"}


def test_every_control_at_the_cells_size_is_not_correct():
    proc, line = drive(os.path.join(TESTS, "faulty_run.py"), "control",
                       "--workload", CELL, "--seed", "2147483659")
    assert proc.returncode == 0, proc.stderr[-2000:]
    controls = line["controls"]
    sound = controls.pop("None")
    assert sound["correct"] is True and not any(sound["compared"].values())
    assert set(controls) == set(CONTROLS)
    for broken, reading in controls.items():
        assert reading["correct"] is False, broken
        assert reading["compared"][CONTROLS[broken]] > 0, broken
        assert reading["compared"]["lines_malformed"] == 0, broken
        assert reading["compared"]["commits_missing"] == 0, broken
    assert controls["committed_twice"]["compared"] == {
        "rows_wrong": 0, "rows_missing_or_extra": 10, "lines_malformed": 0,
        "commits_missing": 0}
    # one line fewer moves the first order down, or out of the ten
    assert controls["lineitem_dropped"]["compared"]["rows_wrong"] >= 1


@pytest.mark.parametrize("fault", ["carry_off_by_one", "int32_sum",
                                   "half_block"])
def test_a_broken_timed_path_is_not_correct(fault):
    proc, line = rehearse(CELL, "--trace", "0", first=(fault,),
                          script=os.path.join(TESTS, "faulty_tpch.py"))
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert line["correct"] is False
    wrong = line["compared"]["rows_wrong"]
    assert wrong["value"] > wrong["limit"]
    # what does come out is ten well-formed lines, committed once
    for number in ("rows_missing_or_extra", "lines_malformed",
                   "commits_missing"):
        assert line["compared"][number]["value"] == 0, number
