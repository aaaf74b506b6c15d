"""The harness on a CPU backend: run by hand, `python -m pytest benchmarks/tests`.

Every test drives benchmarks/run.py as the driver does, in a process of its
own (one process a backend), at 2 MiB.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
ENV.pop("BENCH_RUN", None)


def drive(script, *argv, cwd=ROOT, env=ENV):
    proc = subprocess.run([sys.executable, script, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines else None)


def rehearse(workload, *extra, script=os.path.join(BENCH, "run.py"),
             first=(), **kw):
    return drive(script, *first, *extra, "--workload", workload, "--seed",
                 "2147483659", "--seconds", "1", "--rehearse", "2", **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct_and_is_no_measurement(workload):
    proc, line = rehearse(workload, "--trace", "0")
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert line["rehearsal"] is True and line["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert "metrics" not in line and "device" not in line
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())
    # the numbers compared are the last lines of standard error too
    assert proc.stderr.strip().splitlines()[-1].split("compared: ")[1] == \
        json.dumps(line["compared"])


def test_refuses_to_measure_without_a_tpu():
    proc, line = drive(os.path.join(BENCH, "run.py"), "--workload", CELLS[0],
                       "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode not in (0, 4) and line is None
    assert "platform=cpu" in proc.stderr and "device_kind=" in proc.stderr \
        and "count=" in proc.stderr


def copy_benchmark(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    return tmp_path / "benchmarks"


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    copy_benchmark(tmp_path)
    proc, line = drive(str(tmp_path / "benchmarks" / "run.py"), "--workload",
                       CELLS[0], "--seed", "1", "--seconds", "1", "--trace",
                       "0", "--rehearse", "2", cwd=tmp_path)
    assert proc.returncode != 0 and line is None


def test_layer_metric_files_say_what_benchmark_json_says():
    for m in BENCHMARK["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           f"{m['name']}.json")))
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert spec.get("workloads") == m.get("workloads")


def test_new_cell_traffic_and_metrics_are_found_with_no_edit(tmp_path):
    """What a later PR may do: add files and entries, edit nothing."""
    new = copy_benchmark(tmp_path)
    os.symlink(os.path.join(ROOT, "tez_tpu"), tmp_path / "tez_tpu")
    bench = json.loads(json.dumps(BENCHMARK))
    config = json.load(open(new / "configs" / "owc-1chip.json"))
    config["name"] = "owc-combine-1chip"
    config["dag_kwargs"]["combine"] = True
    config["evidence"] = {"zero_groups": ["DeviceFailover"]}
    json.dump(config, open(new / "configs" / "owc-combine-1chip.json", "w"))
    json.dump({"why": "uniform keys", "warmup_dags": 1, "loop": "closed",
               "clients": 1, "data": {"corpus_mib": 2,
                                      "distribution": "uniform",
                                      "data_seed": 7},
               "conf": {"tez.runtime.io.sort.mb": 1}},
              open(new / "traffic" / "uniform_tiny.json", "w"))
    json.dump({"kind": "counter_ratio", "counter": "SHUFFLE_BYTES",
               "per": "input_bytes", "layer": "transport", "moves":
               "dag_wall_s", "workloads": ["owc_combine_uniform"]},
              open(new / "layer_metrics" / "shuffle_bytes_per_input_byte.json",
                   "w"))
    json.dump({"kind": "histogram_sum_per_dag", "histogram": "device.d2h",
               "scale": 0.001, "layer": "span sort", "moves": "dag_wall_s",
               "workloads": ["owc_combine_uniform"]},
              open(new / "layer_metrics" / "d2h_wait_s_per_dag.json", "w"))
    (new / "layer_metrics" / "dags_in_window.py").write_text(
        "def read(obs):\n    return float(len(obs['dags']))\n")
    bench["configs"].append({
        "name": "owc-combine-1chip", "source": "test", "reduced": [],
        "file": "benchmarks/configs/owc-combine-1chip.json", "why": "test"})
    bench["workloads"].append({
        "name": "owc_combine_uniform", "config": "owc-combine-1chip",
        "traffic": "uniform_tiny", "chips": 1, "why": "test"})
    for name, unit in (("shuffle_bytes_per_input_byte", "B/B"),
                       ("d2h_wait_s_per_dag", "s"), ("dags_in_window", "n")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source":
            "program_counter", "layer": "test", "moves": "dag_wall_s",
            "workloads": ["owc_combine_uniform"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    proc, line = rehearse("owc_combine_uniform", "--trace", "1",
                          script=str(new / "run.py"), cwd=tmp_path)
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert line["correct"] is True
    found = set(line["layer_metrics_found"])
    assert {"shuffle_bytes_per_input_byte", "d2h_wait_s_per_dag",
            "dags_in_window", "task_wall_s_per_dag",
            "dag_wall_max_s"} <= found
    # listed for other cells only, or nothing to read on a CPU: left out
    assert not {"exchange_round_s_per_dag", "spill_bytes_per_input_byte",
                "sort_merge_roofline", "device_idle_pct"} & found


FAULTS = [("half_batch", "owc_session_small", "words_wrong_count"),
          ("answer_altered", "owc_session_small", "words_wrong_count"),
          ("half_batch", "owc_mesh4_zipf", "words_wrong_count"),
          ("answer_altered", "owc_mesh4_zipf", "words_wrong_count"),
          ("exchange_left_out", "owc_mesh4_zipf", "words_wrong_count")]


@pytest.mark.parametrize("fault,workload,number", FAULTS)
def test_a_broken_timed_path_is_not_correct(fault, workload, number):
    if workload not in CELLS:
        pytest.skip(f"{workload} is not a cell of BENCHMARK.json")
    proc, line = rehearse(workload, "--trace", "0", first=(fault,),
                          script=os.path.join(TESTS, "faulty_run.py"))
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert line["correct"] is False
    assert line["compared"][number]["value"] > line["compared"][number]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_every_control_is_not_correct(workload):
    proc, line = drive(os.path.join(TESTS, "faulty_run.py"), "control",
                       "--workload", workload, "--seed", "2147483659",
                       "--rehearse", "2")
    assert proc.returncode == 0, proc.stderr[-2000:]
    controls = line["controls"]
    assert controls.pop("None")["correct"] is True
    assert len(controls) == 3
    failing = {"approximate_counts": "words_wrong_count",
               "unordered": "lines_out_of_order",
               "committed_twice": "words_repeated"}
    for broken, reading in controls.items():
        assert reading["correct"] is False
        assert reading["compared"][failing[broken]] > 0
