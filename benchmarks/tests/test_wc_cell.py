"""The WordCount cell under the harness on a CPU backend: run by hand with
the rest, `python -m pytest benchmarks/tests`.  Every test drives
benchmarks/run.py as a benchmark run does, in a process of its own, at 2 MiB
(229,376 words)."""
from __future__ import annotations

import json
import os

import pytest

from test_harness import BENCH, BENCHMARK, TESTS, drive, rehearse

CELL = "wc_unordered_zipf"
NUMBERS = {"words_wrong_count", "words_repeated", "lines_malformed",
           "commits_missing"}


def test_the_cells_own_metrics_each_with_its_reader_files():
    own = [m for m in BENCHMARK["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in own} == {"agg_s_per_dag", "agg_fold_roofline"}
    for m in own:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           f"{m['name']}.json")))
        assert spec["layer"] == m["layer"] and spec["moves"] == "dag_wall_s"
        assert spec.get("workloads") == [CELL]
        assert spec["kind"] == "module"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           spec["module"]))


def test_rehearsal_is_correct_and_finds_the_new_layer_metric():
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
    assert "agg_s_per_dag" in found
    assert {"task_wall_s_per_dag", "commit_s_per_dag", "control_s_per_dag",
            "dag_head_s_per_dag", "path_device_wait_s_per_dag"} <= found
    # nothing to read without a device trace, or listed for other cells
    assert not {"agg_fold_roofline", "sort_merge_roofline",
                "merge_wait_s_per_dag", "hashjoin_partition_s_per_dag",
                "tokenize_s_per_dag"} & found


CONTROLS = {"approximate_counts": "words_wrong_count",
            "committed_twice": "words_repeated",
            "partition_left_out": "words_wrong_count"}


def test_every_control_is_not_correct():
    proc, line = drive(os.path.join(TESTS, "faulty_run.py"), "control",
                       "--workload", CELL, "--seed", "2147483659",
                       "--rehearse", "2")
    assert proc.returncode == 0, proc.stderr[-2000:]
    controls = line["controls"]
    sound = controls.pop("None")
    assert sound["correct"] is True and not any(sound["compared"].values())
    assert set(controls) == set(CONTROLS)
    for broken, reading in controls.items():
        assert reading["correct"] is False, broken
        assert reading["compared"][CONTROLS[broken]] > 0, broken
        assert reading["compared"]["lines_malformed"] == 0
        assert reading["compared"]["commits_missing"] == 0
    assert controls["approximate_counts"]["compared"] == {
        "words_wrong_count": 1, "words_repeated": 0, "lines_malformed": 0,
        "commits_missing": 0}
    assert controls["partition_left_out"]["compared"]["words_repeated"] == 0


def _faulty(fault):
    return rehearse(CELL, "--trace", "0", first=(fault,),
                    script=os.path.join(TESTS, "faulty_wc.py"))


@pytest.mark.parametrize("fault", ["half_block", "table_dropped",
                                   "values_ignored"])
def test_a_broken_timed_path_is_not_correct(fault):
    proc, line = _faulty(fault)
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert line["correct"] is False
    wrong = line["compared"]["words_wrong_count"]
    assert wrong["value"] > wrong["limit"]
    # what does come out is a word a line, committed once
    for number in ("words_repeated", "lines_malformed", "commits_missing"):
        assert line["compared"][number]["value"] == 0, number


def test_the_plant_of_twos_alone_is_correct():
    """values_ignored's corpus and reference with a sound fold."""
    proc, line = _faulty("twos")
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert line["correct"] is True, line["compared"]
