"""The hash-join cell under the harness on a CPU backend: run by hand with
the rest, `python -m pytest benchmarks/tests`.  Every test drives
benchmarks/run.py as the driver does, in a process of its own, at 2 MiB
(95,316 keys: a rehearsal's sides are two to one, as the generator cuts
them)."""
from __future__ import annotations

import json
import os

import pytest

from test_harness import BENCH, BENCHMARK, TESTS, drive, rehearse

CELL = "hashjoin_broadcast_stream_large"
#: the new per-layer metrics a CPU run can find; hashjoin_probe_roofline
#: reads the device trace
NEW_ON_CPU = {"hashjoin_partition_s_per_dag", "hashjoin_build_s_per_dag",
              "hashjoin_probe_s_per_dag"}
NUMBERS = {"keys_missing", "keys_invented", "keys_repeated",
           "lines_malformed", "commits_missing"}


def test_the_cells_own_metrics_each_with_its_reader_files():
    own = [m for m in BENCHMARK["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in own} == NEW_ON_CPU | {"hashjoin_probe_roofline"}
    for m in own:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           f"{m['name']}.json")))
        assert spec["layer"] == m["layer"] and spec["moves"] == "dag_wall_s"
        assert spec.get("workloads") == [CELL]
        assert spec["kind"] == "module"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           spec["module"]))


def test_the_accepted_cells_keep_the_metrics_this_cell_cannot_read():
    """Nothing is sorted or merged here: the two unlisted metrics that read
    a sort or a merge are listed for the five cells that have them."""
    five = [w["name"] for w in BENCHMARK["workloads"] if w["name"] != CELL]
    by_name = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in ("sort_merge_roofline", "merge_wait_s_per_dag"):
        assert by_name[name]["workloads"] == five


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
            "control_s_per_dag"} <= found
    # nothing to read without a device trace, or listed for other cells
    assert not {"hashjoin_probe_roofline", "sort_merge_roofline",
                "merge_wait_s_per_dag", "join_s_per_dag",
                "tokenize_s_per_dag"} & found


CONTROLS = {"match_dropped": "keys_missing",
            "left_side_only": "keys_invented",
            "committed_twice": "keys_repeated"}


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
        # each breaks one guarantee and no other
        others = {k: v for k, v in reading["compared"].items()
                  if k != CONTROLS[broken]}
        assert not any(others.values()), (broken, others)
    assert controls["match_dropped"]["compared"]["keys_missing"] == 1


@pytest.mark.parametrize("fault", ["half_batch", "hash_side_partitioned"])
def test_a_broken_timed_path_is_not_correct(fault):
    proc, line = rehearse(CELL, "--trace", "0", first=(fault,),
                          script=os.path.join(TESTS, "faulty_hashjoin.py"))
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert line["correct"] is False
    missing = line["compared"]["keys_missing"]
    assert missing["value"] > missing["limit"]
    # what does come out is right: a key of both sides, once
    for number in ("keys_invented", "keys_repeated", "lines_malformed",
                   "commits_missing"):
        assert line["compared"][number]["value"] == 0, number
