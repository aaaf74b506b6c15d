"""The TeraSort cell under the harness on a CPU backend: run by hand with
the rest, `python -m pytest benchmarks/tests`.  Every test drives
benchmarks/run.py as the driver does, in a process of its own, at 2 MiB
(20,960 records)."""
from __future__ import annotations

import json
import os

import pytest

from test_harness import BENCH, BENCHMARK, TESTS, drive, rehearse

CELL = "terasort_spill_uniform"
#: the new per-layer metrics a CPU run can find; range_sort_roofline reads
#: the device trace
NEW_ON_CPU = {"partition_sample_s_per_dag", "payload_gather_s_per_dag",
              "payload_gather_bytes_per_input_byte"}
NUMBERS = {"records_lost_or_invented", "records_out_of_order",
           "partitions_out_of_order", "records_malformed", "commits_missing"}


def test_the_cells_own_metrics_each_with_its_reader_files():
    own = [m for m in BENCHMARK["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in own} == NEW_ON_CPU | {"range_sort_roofline"}
    for m in own:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           f"{m['name']}.json")))
        assert spec["layer"] == m["layer"] and spec["moves"] == "dag_wall_s"
        assert spec.get("workloads") == [CELL]
        if spec["kind"] == "module":
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
    assert {"task_wall_s_per_dag", "commit_s_per_dag",
            "merge_wait_s_per_dag"} <= found
    # nothing to read without a device trace; listed for other cells only
    assert not {"range_sort_roofline", "sort_merge_roofline",
                "tokenize_s_per_dag", "spill_bytes_per_input_byte"} & found


CONTROLS = {"record_dropped": "records_lost_or_invented",
            "payload_swapped": "records_lost_or_invented",
            "unordered": "records_out_of_order",
            "hash_partitioned": "partitions_out_of_order",
            "committed_twice": "records_lost_or_invented"}


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
    # these four break one guarantee and no other
    for broken in ("record_dropped", "payload_swapped", "unordered",
                   "hash_partitioned"):
        others = {k: v for k, v in controls[broken]["compared"].items()
                  if k != CONTROLS[broken]}
        assert not any(others.values()), (broken, others)


@pytest.mark.parametrize("fault,number", [
    ("half_batch", "records_lost_or_invented"),
    ("hash_put_back", "partitions_out_of_order")])
def test_a_broken_timed_path_is_not_correct(fault, number):
    proc, line = rehearse(CELL, "--trace", "0", first=(fault,),
                          script=os.path.join(TESTS, "faulty_terasort.py"))
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert line["correct"] is False
    assert line["compared"][number]["value"] > line["compared"][number]["limit"]
    if fault == "hash_put_back":
        # every record is there and every part sorted: only the ranges lie
        assert line["compared"]["records_lost_or_invented"]["value"] == 0
        assert line["compared"]["records_out_of_order"]["value"] == 0
