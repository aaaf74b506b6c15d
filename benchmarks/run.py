#!/usr/bin/env python3
"""benchmarks/run.py: one cell of BENCHMARK.json, one process, one result line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The runner knows no cell, configuration, traffic mix or per-layer metric by
name.  ``BENCHMARK.json`` ``workloads[*]`` names a ``config`` and a
``traffic``; the configuration's ``file`` holds the DAG builder
(``module:function``), its keyword arguments, conf keys, the generator and
the device evidence a DAG has to show; ``traffic/<traffic>.json`` holds the
corpus size, the conf keys cut with it and the loop; ``generators/<name>.py``
makes inputs and reference from ``--seed`` and decides `correct`;
``layer_metrics/<name>.json`` (or ``.py`` with a ``read(obs)``) says how a
per-layer metric is read.  A later PR adds files and a ``workloads`` entry.

Set-up (all of it in ``setup_s``): backend up, native library built if stale,
compile cache at ``JAX_COMPILATION_CACHE_DIR`` or ``benchmarks/.jax_cache``,
corpus and reference from the seed, one TezClient session pre-warmed, the
cell's warm-up DAGs run and compared.  Window: DAGs of the cell's one shape
submitted back to back through that session, closed loop, one client, until
``--seconds`` have passed; the DAG in flight finishes.  After the window,
outside every clock: the session is stopped and every output directory of
the window is compared with the reference.

It measures on a TPU only.  ``--rehearse`` drives the same code on a CPU
backend for tests: it names the engine, stamps ``platform: cpu``, prints no
metric and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_CHIP = 3
EXIT_REHEARSAL = 4


class BenchFailure(Exception):
    """The run cannot give a result line."""


def say(msg: str) -> None:
    print(f"[bench +{time.time() - T_START:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> Dict[str, Any]:
    """Everything the cell is, found by the names in BENCHMARK.json."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json "
                           f"(has: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")

    def listed(metric: Dict[str, Any]) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
            "per_layer": [m for m in bench["per_layer"] if listed(m)]}


# ---------------------------------------------------------------------------
# the system under test: one session, DAGs back to back
# ---------------------------------------------------------------------------

def counter_total(counters: Dict[str, Dict[str, int]], name: str) -> int:
    return sum(group.get(name, 0) for group in counters.values())


def evidence_failures(rule: Dict[str, Any], counters: Dict[str, Any],
                      records: int) -> List[str]:
    """Why this DAG was not the device's work (chip_smoke.py
    check_device_work, as data): a host run is a different result."""
    why = []
    for group in rule.get("zero_groups", []):
        nonzero = {k: v for k, v in counters.get(group, {}).items() if v}
        if nonzero:
            why.append(f"{group} {nonzero}")
    for name, share in rule.get("min_share_of_records", {}).items():
        got = counter_total(counters, name)
        if got < share * records:
            why.append(f"{name} {got} < {share} x {records} records")
    for name in rule.get("positive", []):
        if counter_total(counters, name) <= 0:
            why.append(f"{name} is 0")
    return why


def breaker_state() -> str:
    from tez_tpu.ops import async_stage
    return async_stage.process_breaker().state


def am_dag_seconds(client: Any) -> Dict[str, float]:
    """The AM's own time per DAG (DAG_FINISHED ``time_taken``), from the
    session's in-memory history service; {} where that is not the service."""
    from tez_tpu.am.history import HistoryEventType
    am = getattr(client.framework_client, "am", None)
    service = getattr(am, "logging_service", None)
    if not hasattr(service, "of_type"):
        return {}
    return {e.dag_id: float(e.data["time_taken"])
            for e in service.of_type(HistoryEventType.DAG_FINISHED)}


def run_dag(client: Any, build: Callable, inputs: List[str], out_dir: str,
            kwargs: Dict[str, Any]) -> Dict[str, Any]:
    t_submit = time.time()
    handle = client.submit_dag(build(inputs, out_dir, **kwargs))
    status = handle.wait_for_completion()
    t_done = time.time()
    counters = status.counters.to_dict() if status.counters else {}
    return {"t_submit": t_submit, "t_done": t_done, "out_dir": out_dir,
            "dag_id": str(handle.dag_id), "state": status.state.name,
            "diagnostics": list(status.diagnostics), "counters": counters}


def histogram_sums() -> Dict[str, float]:
    from tez_tpu.common import metrics
    return {name: h.sum_ms for name, h in
            metrics.registry().histograms().items()}


# ---------------------------------------------------------------------------
# per-layer readers, by ``kind``; each returns None where it finds nothing
# ---------------------------------------------------------------------------

def _completed(obs: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [d for d in obs["dags"] if d["state"] == "SUCCEEDED"]


def read_counter(spec, obs):
    """``counter_sum_per_dag``: the counter over the DAGs, scaled;
    ``counter_ratio``: the same per unit of ``spec["per"]`` (input_bytes)."""
    dags = _completed(obs)
    top = sum(counter_total(d["counters"], spec["counter"]) for d in dags)
    if not dags or not top:
        return None
    per = obs[spec["per"]] if "per" in spec else 1
    return top * spec.get("scale", 1.0) / (per * len(dags))


def read_histogram_sum_per_dag(spec, obs):
    dags = _completed(obs)
    total = obs["histogram_ms"].get(spec["histogram"], 0.0)
    if not dags or not total:
        return None
    return total * spec.get("scale", 1.0) / len(dags)


def read_client_clock(spec, obs):
    dags = _completed(obs)
    if not dags:
        return None
    walls = [d["t_done"] - d["t_submit"] for d in dags]
    if spec["stat"] == "max":
        return max(walls)
    if spec["stat"] == "minus_am_mean":
        am = [obs["am_seconds"].get(d["dag_id"]) for d in dags]
        if any(a is None for a in am):
            return None
        return sum(w - a for w, a in zip(walls, am)) / len(dags)
    raise BenchFailure(f"client_clock stat {spec['stat']!r} unknown")


def read_process(spec, obs):
    if spec["stat"] == "compiles_in_window":
        return float(obs["compiles_in_window"])
    if spec["stat"] == "peak_hbm_gb":
        return obs["memory_peak_bytes"] / 1e9 if obs["memory_peak_bytes"] \
            else None
    raise BenchFailure(f"process stat {spec['stat']!r} unknown")


def read_trace(spec, obs):
    trace = obs.get("trace")
    if not trace or not trace["busy_s_fullest"]:
        return None
    if spec["stat"] == "idle_pct":
        return 100.0 * (1.0 - trace["busy_s_fullest"] / trace["window_s"])
    if spec["stat"] == "hbm_roofline_pct":
        from trace_reduce import hbm_roofline_pct
        rows = sum(counter_total(d["counters"], name)
                   for d in _completed(obs) for name in spec["row_counters"])
        lanes = obs["config"][spec["lanes_key"]] + spec["extra_lanes"]
        return hbm_roofline_pct(rows, lanes, trace["busy_s_fullest"],
                                obs["device_kind"])
    raise BenchFailure(f"trace stat {spec['stat']!r} unknown")


READERS = {"counter_ratio": read_counter,
           "counter_sum_per_dag": read_counter,
           "histogram_sum_per_dag": read_histogram_sum_per_dag,
           "client_clock": read_client_clock,
           "process": read_process,
           "trace": read_trace}


def read_layer_metric(name: str, obs: Dict[str, Any]) -> Optional[float]:
    if os.path.exists(os.path.join(HERE, "layer_metrics", f"{name}.py")):
        return load_module("layer_metrics", name).read(obs)
    spec = load_json(HERE, "layer_metrics", f"{name}.json")
    if spec["kind"] not in READERS:
        raise BenchFailure(f"layer metric {name}: kind {spec['kind']!r} "
                           f"unknown (has: {sorted(READERS)})")
    return READERS[spec["kind"]](spec, obs)


def end_to_end(obs: Dict[str, Any]) -> Dict[str, float]:
    """Over all the work and all the time of the window."""
    dags = _completed(obs)
    if not dags:
        return {"setup_s": obs["setup_s"]}
    elapsed = dags[-1]["t_done"] - obs["dags"][0]["t_submit"]
    return {"dag_wall_s": elapsed / len(dags),
            "input_mb_per_s": obs["input_bytes"] * len(dags) / 1e6 / elapsed,
            "setup_s": obs["setup_s"]}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def backend(chips: int, rehearse: bool):
    """JAX's devices, or a BenchFailure where this is no machine to measure
    on.  Every line names platform, device_kind and count."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(HERE, ".jax_cache"))
    import jax
    devices = jax.devices()
    dev = devices[0]
    where = (f"platform={dev.platform} device_kind={dev.device_kind!r} "
             f"count={len(devices)}")
    if rehearse:
        if dev.platform != "cpu":
            raise BenchFailure(f"--rehearse is for a CPU backend ({where})")
        if len(devices) < chips:
            raise BenchFailure(
                f"the rehearsal needs {chips} CPU devices ({where}): set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={chips}")
    elif dev.platform != "tpu" or len(devices) < chips:
        raise BenchFailure(f"no measurement without {chips} TPU chip(s): "
                           f"{where}")
    say(f"device: {where} (backend up)")
    return devices


def corpus_params(spec: Dict[str, Any], rehearse_mib: int) -> Dict[str, Any]:
    """The generator's parameters: the configuration's shapes, the traffic
    mix's scale, and a rehearsal's own corpus size."""
    params = {**spec["config"]["data"], **spec["traffic"]["data"]}
    if rehearse_mib:
        params["corpus_mib"] = rehearse_mib
    return params


def run_cell(args: argparse.Namespace, spec: Dict[str, Any], devices,
             workdir: str) -> Dict[str, Any]:
    """Set-up, window, comparison.  Returns the result line's content and
    the observations the per-layer readers take their numbers from."""
    config, traffic = spec["config"], spec["traffic"]
    platform = devices[0].platform
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.ops import device as device_ops
    from tez_tpu.ops import native
    say(f"[{platform}] native library: {native.loaded_path()}")

    generator = load_module("generators", config["generator"])
    t0 = time.time()
    made = generator.generate(os.path.join(workdir, "corpus"),
                              corpus_params(spec, args.rehearse), args.seed)
    say(f"[{platform}] corpus: {made['input_bytes']} bytes, "
        f"{made['records']} records from seed {args.seed} in "
        f"{time.time() - t0:.2f}s")

    module, function = config["dag_builder"].split(":")
    build = getattr(importlib.import_module(module), function)
    conf = {**config["conf"], **traffic.get("conf", {}),
            "tez.staging-dir": os.path.join(workdir, "staging"),
            "tez.runtime.tpu.host.spill.dir": os.path.join(workdir, "spill")}
    if args.rehearse:
        conf.update(config["rehearse_conf"])
    if args.trace:
        conf.update(config.get("trace_conf", {}))
    kwargs = config["dag_kwargs"]
    rule = config["evidence"]

    def check(dag: Dict[str, Any]) -> List[str]:
        if dag["state"] != "SUCCEEDED":
            return [f"state {dag['state']}: {dag['diagnostics']}"]
        why = evidence_failures(rule, dag["counters"], made["records"])
        if breaker_state() != "closed":
            why.append(f"process breaker {breaker_state()}")
        return why

    numbers = {k: 0 for k in generator.LIMITS}
    numbers["dags_without_answer"] = 0

    def compare_into(numbers: Dict[str, int], dag: Dict[str, Any]) -> None:
        if dag["state"] != "SUCCEEDED":
            numbers["dags_without_answer"] += 1
            return
        for k, v in generator.compare(dag["out_dir"],
                                      made["reference"]).items():
            numbers[k] += v

    obs: Dict[str, Any] = {
        "config": config,
        "input_bytes": made["input_bytes"], "records": made["records"],
        "device_kind": devices[0].device_kind, "dags": [], "trace": None}
    client = TezClient.create(f"bench-{args.workload}", conf,
                              session=True).start()
    try:
        client.pre_warm()
        for n in range(int(traffic["warmup_dags"])):
            dag = run_dag(client, build, made["inputs"],
                          os.path.join(workdir, f"warm-{n}"), kwargs)
            why = check(dag)
            say(f"[{platform}] warm-up DAG {n}: {dag['state']} in "
                f"{dag['t_done'] - dag['t_submit']:.2f}s, "
                f"{len(device_ops.COMPILE_LOG)} compiles so far"
                f"{' NOT THE DEVICE: ' + str(why) if why else ''}")
            # a wrong warm-up answer is compared like the window's: the run
            # goes on and reports correct false rather than dying here
            compare_into(numbers, dag)
            shutil.rmtree(dag["out_dir"])

        if args.trace:
            import trace_reduce
            tracer = trace_reduce.Tracer(os.path.join(workdir, "trace"))
        hist_before = histogram_sums()
        t_window = time.time()
        obs["setup_s"] = t_window - T_START
        if args.trace:
            tracer.start()
        while True:
            n = len(obs["dags"])
            dag = run_dag(client, build, made["inputs"],
                          os.path.join(workdir, f"out-{n}"), kwargs)
            dag["why_failed"] = check(dag)
            obs["dags"].append(dag)
            if time.time() - t_window >= args.seconds:
                break
        t_close = time.time()
        if args.trace:
            tracer.stop()
        hist_after = histogram_sums()
        obs["histogram_ms"] = {k: v - hist_before.get(k, 0.0)
                               for k, v in hist_after.items()}
        obs["compiles_in_window"] = sum(
            1 for *_x, t_done in device_ops.COMPILE_LOG if t_done >= t_window)
        obs["am_seconds"] = am_dag_seconds(client)
        obs["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices)
        if args.trace:
            spans = trace_reduce.program_spans()
    finally:
        client.stop()
    say(f"[{platform}] window: {len(obs['dags'])} DAGs in "
        f"{t_close - t_window:.2f}s, "
        f"{obs['compiles_in_window']} compiles inside it; each, s: "
        f"{[round(d['t_done'] - d['t_submit'], 3) for d in obs['dags']]}")

    # the comparison, outside every clock, the program's session stopped
    t0 = time.time()
    for dag in obs["dags"]:
        compare_into(numbers, dag)
    limits = {**generator.LIMITS, "dags_without_answer": 0}
    compared = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(numbers[k] <= limits[k] for k in limits)
    say(f"[{platform}] compared {len(obs['dags'])} outputs in "
        f"{time.time() - t0:.2f}s")

    if args.trace:
        obs["trace"] = trace_reduce.reduce_trace(
            tracer.xplane_path(), n_devices=spec["cell"]["chips"],
            spans=spans, marks=tracer.marks)
    failed = [d for d in obs["dags"] if d["why_failed"]]
    for d in failed:
        say(f"[{platform}] FAILED {d['dag_id']}: {d['why_failed']}")
    return {"correct": correct, "attempted": len(obs["dags"]),
            "failed": len(failed), "compared": compared, "obs": obs}


def result_line(args, spec, devices, res) -> Dict[str, Any]:
    obs = res["obs"]
    metrics: Dict[str, Any] = {}
    if args.trace:
        for m in spec["per_layer"]:
            value = read_layer_metric(m["name"], obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(obs)
        for m in spec["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": obs["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and obs["trace"]:
        device["busy_s"] = obs["trace"]["busy_s_mean"]
        device["window_s"] = obs["trace"]["window_s"]
        line["breakdown"] = obs["trace"]["breakdown"]
    line["compiles_in_window"] = obs["compiles_in_window"]
    line["compared"] = res["compared"]
    return line


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="MIB",
                    help="CPU dry run for tests on a corpus of MIB MiB: no "
                         "metric, non-zero exit")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "tez_tpu")):
        print(f"benchmarks/run.py: no tez_tpu package beside {HERE}: "
              f"nothing to measure", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, HERE]
    workdir = None
    try:
        spec = load_cell(args.workload)
        devices = backend(spec["cell"]["chips"], args.rehearse)
        workdir = tempfile.mkdtemp(prefix="tez_bench_")
        res = run_cell(args, spec, devices, workdir)
        compared = json.dumps(res["compared"])
        if args.rehearse:
            line = {"rehearsal": True, "platform": devices[0].platform,
                    "device_kind": devices[0].device_kind,
                    "count": len(devices), "correct": res["correct"],
                    "attempted": res["attempted"], "failed": res["failed"]}
            if args.trace:
                # names only: a CPU run's number never stands under a
                # device metric's name
                line["layer_metrics_found"] = [
                    m["name"] for m in spec["per_layer"]
                    if read_layer_metric(m["name"], res["obs"]) is not None]
            line["compared"] = res["compared"]
            print(json.dumps(line), flush=True)
            say(f"compared: {compared}")
            return EXIT_REHEARSAL
        line = result_line(args, spec, devices, res)
        print(json.dumps(line), flush=True)
        say(f"compared: {compared}")
        return 0
    except BenchFailure as e:
        print(f"benchmarks/run.py FAILED: {e}", file=sys.stderr, flush=True)
        return EXIT_NO_CHIP
    finally:
        if workdir is not None:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
