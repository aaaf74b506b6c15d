#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that tez_tpu's main path runs on the chip.

One process, nothing on the side: the north-star workload at the size
BASELINE.md's "100 GB protocol, stage 1" calls real for one chip —
OrderedWordCount over a 1 GB zipfian corpus, 2,000,000-word vocabulary,
combine off, ``tez.runtime.io.sort.mb=64``, 4 tokenizers x 4 summation tasks
x 1 sorter, ``tez.runtime.sorter.class=auto`` — through the entry points a
user calls (``TezClient.create`` -> ``build_dag`` -> ``submit_dag`` ->
``wait_for_completion``), output compared with the streamed golden.

It fails (non-zero exit, reason on stderr, no JSON line) unless

* JAX's first device is a TPU (``--allow-cpu`` relaxes this one check, for a
  tiny dry run before chip time is spent; it also has to name the engine,
  because ``auto`` means the host engine on a CPU backend);
* ``libtezhost.so`` was built from the committed sources in this run;
* the DAG SUCCEEDED and the output equals the golden;
* the device did the work: rows counted under the device engine for both the
  span sort and the merges, and device memory actually held a span's lanes;
* every ``DeviceFailover`` counter is zero and the process breaker is closed.

Where the machine caps the size of a file (``RLIMIT_FSIZE``; the driver's chip
machine does) the corpus, written as parts, and ``io.sort.mb`` are cut to what
a span spill and a tokenizer's final run can fit, and the header line says so.

With four or more TPU devices it also runs the mesh leg (the tokenizer ->
summation edge over the ICI exchange, one summation task per chip).

The last stdout line is ``{"ok": true, "device": {...}}``.  Everything before
it is information, labelled with the platform — not a metric.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
T_START = time.time()
# Largest file the DAG writes, per byte of one tokenizer's input: its final
# merged run (all partitions, keys + values + offsets).  2.2 measured at
# 128 MB, io.sort.mb=8 on the CPU dry run; rounded up.
FINAL_RUN_BYTES_PER_INPUT_BYTE = 2.3
# ... and a span spill per byte of tez.runtime.io.sort.mb (2.0 measured there)
SPILL_BYTES_PER_SORT_BYTE = 2.2


class SmokeFailure(Exception):
    """A phase did not meet its check."""


def say(msg: str) -> None:
    print(f"[smoke +{time.time() - T_START:7.1f}s] {msg}", flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    say(f"ok: {what}")


def build_native() -> None:
    """`make -B all` from the committed sources, then prove the library
    the package loads is that fresh build.  Not `clean all`: the Makefile
    puts a build in place by rename, so a process that is loading or
    packing the library meanwhile (the tier-1 suite runs this script beside
    tests/test_packaging.py) never finds it gone."""
    native_dir = os.path.join(HERE, "tez_tpu", "native")
    t0 = time.time()
    subprocess.run(["make", "-s", "-C", native_dir, "-B", "all"],
                   check=True)
    from tez_tpu.ops import native
    so = native.loaded_path()
    require(os.path.dirname(so) == native_dir and
            os.path.getmtime(so) >= t0 - 1.0,
            f"libtezhost.so built from source in this run "
            f"({time.time() - t0:.1f}s, {so})")


def counter_total(counters, name: str) -> int:
    return sum(group.get(name, 0) for group in counters.values())


def run_wordcount(td: str, args, exchange: str, mb: int, tag: str) -> dict:
    """One OrderedWordCount through TezClient; returns what was observed."""
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.examples import ordered_wordcount
    from tez_tpu.ops import device
    from tez_tpu.tools.spill_bench import make_corpus, verify_output

    # a directory of parts, four to a tokenizer, never one 1 GB file: the
    # machine may cap the size of a file (RLIMIT_FSIZE)
    corpus = os.path.join(td, f"corpus_{tag}")
    t0 = time.time()
    nbytes, golden = make_corpus(
        corpus, mb, args.vocab_size, seed=args.seed,
        part_bytes=(mb << 20) // (4 * args.parallelism))
    words = int(golden.sum())
    say(f"{tag}: corpus {nbytes / 1e6:.0f} MB, {words} words, "
        f"{int((golden > 0).sum())} distinct, made in {time.time() - t0:.1f}s")
    conf = {"tez.staging-dir": os.path.join(td, f"stg_{tag}"),
            "tez.runner.mode": "threads",
            "tez.runtime.sorter.class": args.engine,
            "tez.runtime.io.sort.mb": args.sort_mb,
            "tez.runtime.tpu.host.spill.dir": os.path.join(td, f"spill_{tag}")}
    out_dir = os.path.join(td, f"out_{tag}")
    compiles_before = len(device.COMPILE_LOG)
    t_submit = time.time()
    with TezClient.create(f"chip-smoke-{tag}", conf) as client:
        dag = ordered_wordcount.build_dag(
            [corpus], out_dir, tokenizer_parallelism=args.parallelism,
            summation_parallelism=args.parallelism, sorter_parallelism=1,
            combine=False, tokenizer_mode="vector", exchange=exchange)
        dag_client = client.submit_dag(dag)
        status = dag_client.wait_for_completion()
        final = dag_client.get_dag_status(with_counters=True)
    wall = time.time() - t_submit
    if status.state.name != "SUCCEEDED":
        raise SmokeFailure(f"{tag}: DAG state {status.state.name}: "
                           f"{status.diagnostics}")
    say(f"ok: {tag}: DAG SUCCEEDED through TezClient in {wall:.1f}s wall")
    t0 = time.time()
    try:
        distinct = verify_output(out_dir, golden)
    except ValueError as e:
        raise SmokeFailure(f"{tag}: {e}") from e
    say(f"ok: {tag}: output equals the streamed golden ({distinct} lines, "
        f"checked in {time.time() - t0:.1f}s)")
    compiles = device.COMPILE_LOG[compiles_before:]
    shutil.rmtree(corpus)
    shutil.rmtree(out_dir, ignore_errors=True)
    return {"wall_s": wall, "words": words, "corpus_bytes": nbytes,
            "counters": final.counters.to_dict(),
            "compiles": compiles, "t_submit": t_submit}


def report_compiles(tag: str, compiles, wall: float) -> None:
    per_kernel: dict = {}
    for name, _sig, secs, _sort_ops, _t_done in compiles:
        n, tot, mx = per_kernel.get(name, (0, 0.0, 0.0))
        per_kernel[name] = (n + 1, tot + secs, max(mx, secs))
    total = sum(t for _, t, _ in per_kernel.values())
    say(f"{tag}: set-up: {len(compiles)} kernel compiles, {total:.1f}s "
        f"summed (they overlap across task threads) inside {wall:.1f}s wall")
    for name, (n, tot, mx) in sorted(per_kernel.items()):
        say(f"    compile {name}: {n} signature(s), {tot:.1f}s total, "
            f"slowest {mx:.1f}s")


def check_device_work(tag: str, res: dict, args, platform: str,
                      dev) -> dict:
    """Evidence a host-routed run cannot produce (module docstring)."""
    from tez_tpu.common import metrics
    from tez_tpu.ops import async_stage
    from tez_tpu.ops.device import _bucket
    c = res["counters"]
    rows = {k: counter_total(c, k) for k in (
        "DEVICE_SORT_RECORDS", "HOST_SORT_RECORDS", "DEVICE_MERGE_RECORDS",
        "HOST_MERGE_RECORDS", "OUTPUT_RECORDS", "SPILLED_RECORDS",
        "ADDITIONAL_SPILLS_BYTES_WRITTEN", "SHUFFLE_BYTES")}
    say(f"{tag}: counters {json.dumps(rows)}")
    hists = metrics.registry().histograms()
    stage_names = ("device.encode", "device.h2d", "device.dispatch_wait",
                   "device.d2h", "device.sort", "device.merge",
                   "device.failover.host_sort")
    hist_counts = {h: (hists[h].count if h in hists else 0)
                   for h in stage_names}
    say(f"{tag}: stage histogram counts {json.dumps(hist_counts)}")
    say(f"{tag}: stage histogram wall, summed over threads, s: " +
        json.dumps({h: round(hists[h].sum_ms / 1000.0, 1)
                    for h in stage_names if h in hists}))
    require(rows["DEVICE_SORT_RECORDS"] > 0,
            f"{tag}: span sorts ran on the device engine "
            f"({rows['DEVICE_SORT_RECORDS']} rows device, "
            f"{rows['HOST_SORT_RECORDS']} rows host)")
    # every tokenizer word must have been sorted by SOME engine; the device
    # must have taken the full-size spans (only tails below the routing
    # floors may go to the host)
    require(rows["DEVICE_SORT_RECORDS"] >= 0.9 * res["words"],
            f"{tag}: device-sorted rows cover >= 90% of the corpus's "
            f"{res['words']} words")
    require(rows["DEVICE_MERGE_RECORDS"] > 0,
            f"{tag}: merges ran on the device engine "
            f"({rows['DEVICE_MERGE_RECORDS']} rows device, "
            f"{rows['HOST_MERGE_RECORDS']} rows host)")
    require(hist_counts["device.h2d"] > 0 and
            hist_counts["device.dispatch_wait"] > 0 and
            hist_counts["device.merge"] > 0,
            f"{tag}: device.h2d / device.dispatch_wait / device.merge "
            f"histograms moved")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    # one full span's key lanes + lengths, as staged for the sort
    span_rows = min(res["words"] // args.parallelism,
                    (args.sort_mb << 20) // 32)
    key_bytes = 1 + len(str(args.vocab_size - 1))
    lane_bytes = _bucket(span_rows // 2) * (4 * ((key_bytes + 3) // 4) + 4)
    say(f"{tag}: device memory peak_bytes_in_use={peak} "
        f"(one span's lanes need >= {lane_bytes})")
    if platform == "tpu":
        require(peak >= lane_bytes,
                f"{tag}: device memory held at least one span's key lanes")
    failover = c.get(async_stage.COUNTER_GROUP, {})
    say(f"{tag}: DeviceFailover counters {json.dumps(failover)}")
    require(not any(failover.values()),
            f"{tag}: every DeviceFailover counter is zero")
    state = async_stage.process_breaker().state
    require(state == "closed", f"{tag}: process breaker is {state}")
    return {"rows": rows, "hist_counts": hist_counts, "peak_bytes": peak}


def mesh_leg(td: str, args, devices) -> dict:
    """OrderedWordCount with the tokenizer->summation edge on the ICI
    exchange, one summation task per chip."""
    from tez_tpu.parallel import exchange
    from tez_tpu.parallel.coordinator import mesh_coordinator
    width = args.parallelism
    res = run_wordcount(td, args, "mesh", args.mesh_mb, "mesh")
    report_compiles("mesh", res["compiles"], res["wall_s"])
    coord = mesh_coordinator()
    mesh = coord.mesh_for(width)
    ragged_ok, reason = exchange.probe_ragged_support(mesh)
    info = {"lane_rows": dict(coord.lane_rows),
            "exchanges_run": coord.exchanges_run,
            "multi_round_exchanges": coord.multi_round_exchanges,
            "rows_exchanged": coord.rows_exchanged,
            "last_engine": coord.last_engine,
            "ragged_probe": [ragged_ok, reason],
            "output_shard_device_ids": dict(coord.last_shard_devices),
            "mesh_device_ids": [d.id for d in mesh.devices.flat]}
    say(f"mesh: {json.dumps(info)}")
    require(coord.exchanges_run >= 1 and
            coord.rows_exchanged == res["words"],
            f"mesh: {coord.rows_exchanged} rows crossed the exchange "
            f"(= {res['words']} words) in {coord.exchanges_run} exchange(s)")
    require(sorted(coord.lane_rows) == list(range(width)) and
            all(coord.lane_rows[d] > 0 for d in range(width)),
            f"mesh: rows landed on all {width} lanes")
    require(sorted(coord.last_shard_devices.values()) ==
            sorted(d.id for d in devices[:width]),
            f"mesh: output shards live on {width} distinct chips")
    failover = res["counters"].get("DeviceFailover", {})
    require(not any(failover.values()),
            "mesh: every DeviceFailover counter is zero")
    return info


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mb", type=int, default=1024,
                    help="corpus size (default: the 1 GB protocol size)")
    ap.add_argument("--vocab-size", type=int, default=2_000_000)
    ap.add_argument("--sort-mb", type=int, default=64)
    ap.add_argument("--parallelism", type=int, default=4)
    ap.add_argument("--mesh-mb", type=int, default=128,
                    help="mesh-leg corpus (runs with >= 4 devices only)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="dry run: accept a CPU backend.  Relaxes only the "
                         "platform check, and sets the sorter engine to "
                         "'device' because 'auto' means host on CPU")
    args = ap.parse_args()
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    cut = ""
    if fsize != resource.RLIM_INFINITY:
        # forced cuts, stated: a span spill and a tokenizer's final run must
        # each fit a file (make_corpus overshoots its target by up to one
        # 10 MB chunk)
        fit_sort = int(fsize / SPILL_BYTES_PER_SORT_BYTE) >> 20
        fit_mb = (int(fsize * args.parallelism /
                      FINAL_RUN_BYTES_PER_INPUT_BYTE) >> 20) - 10
        if args.sort_mb > fit_sort or args.mb > fit_mb:
            cut = (f" (cut from {args.mb} MB, io.sort.mb={args.sort_mb}: "
                   f"RLIMIT_FSIZE={fsize} caps spills and final runs)")
            args.sort_mb = min(args.sort_mb, fit_sort)
            args.mb = min(args.mb, fit_mb)
    span_rows = (args.sort_mb << 20) // 32
    say(f"chip_smoke: {args.mb} MB corpus{cut}, vocab {args.vocab_size}, "
        f"io.sort.mb={args.sort_mb}, {args.parallelism}x{args.parallelism}x1"
        f", combine off, seed {args.seed}")
    say("file-size limit (RLIMIT_FSIZE): " +
        ("none" if fsize == resource.RLIM_INFINITY else f"{fsize} bytes"))
    words_est = (args.mb << 20) // (2 + len(str(args.vocab_size - 1)))
    if args.sort_mb < 1 or words_est // args.parallelism < 2 * span_rows:
        raise SmokeFailure(
            f"corpus too small{cut}: ~{words_est // args.parallelism} words "
            f"per tokenizer is under 2 spans of {span_rows}; raise --mb or "
            f"lower --sort-mb")

    import jax
    import jaxlib
    devices = jax.devices()
    dev = devices[0]
    platform = dev.platform
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    from tez_tpu.ops import compile_cache
    say(f"device: platform={platform} device_kind={dev.device_kind!r} "
        f"count={len(devices)} (backend ready after "
        f"{time.time() - T_START:.1f}s)")
    say(f"versions: jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version} python={sys.version.split()[0]}")
    entries_before = compile_cache.entry_count()
    placed_by = "JAX_COMPILATION_CACHE_DIR" \
        if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "checkout default"
    say(f"compile cache: {compile_cache.cache_dir()} ({placed_by}), "
        f"{entries_before} entries at start")
    if platform != "tpu":
        if not args.allow_cpu:
            raise SmokeFailure(
                f"jax.devices()[0].platform is {platform!r}, not 'tpu' "
                f"(--allow-cpu for a dry run)")
        say("DRY RUN on a CPU backend (--allow-cpu): nothing below is a "
            "device result")
    args.engine = "auto" if platform == "tpu" else "device"

    build_native()
    device_info = {"platform": platform, "kind": dev.device_kind,
                   "count": len(devices)}
    summary: dict = {"device": device_info, "args": vars(args)}
    td = tempfile.mkdtemp(prefix="tez_smoke_")
    try:
        res = run_wordcount(td, args, "host", args.mb, "main")
        say(f"main [{platform}]: {res['corpus_bytes'] / 1e6:.0f} MB in "
            f"{res['wall_s']:.1f}s wall, compiles included "
            f"(information, not a metric)")
        report_compiles("main", res["compiles"], res["wall_s"])
        if res["compiles"]:
            first = min(t for *_x, t in res["compiles"])
            say(f"main: first kernel ready {first - res['t_submit']:.1f}s "
                f"after submit")
        summary["main"] = check_device_work("main", res, args, platform,
                                            dev)
        summary["main"]["wall_s"] = res["wall_s"]
        summary["main"]["compiles"] = [list(c[:3]) for c in res["compiles"]]
        per_dev_peak = {d.id: int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devices}
        say(f"main: peak_bytes_in_use per device id {per_dev_peak} "
            f"(span sorts take JAX's default device)")
        summary["main"]["per_device_peak"] = per_dev_peak

        if len(devices) >= 4:
            summary["mesh"] = mesh_leg(td, args, devices)
        else:
            say(f"mesh leg: skipped ({len(devices)} device"
                f"{'' if len(devices) == 1 else 's'})")
    finally:
        shutil.rmtree(td, ignore_errors=True)
    written = compile_cache.entry_count() - entries_before
    say(f"compile cache: {written} entries written this run")
    summary["cache_entries_written"] = written
    summary["total_s"] = time.time() - T_START
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "a") as fh:
        fh.write(json.dumps(summary, default=str) + "\n")
    say(f"done in {time.time() - T_START:.1f}s")
    print(json.dumps({"ok": True, "device": device_info}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
