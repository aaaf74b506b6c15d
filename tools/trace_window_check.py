"""One traced benchmark window, with the checks a result line does not carry.

    python3 tools/trace_window_check.py --workload <cell> --seed <n> \
        --seconds 45 --trace 1

Runs ``benchmarks/run.py`` in this process with the same arguments (its
result line goes to standard output as always).  The reduction stays the
benchmark's: this tool listens at two of ``trace_reduce``'s own calls and
adds nothing of its own to what they compute.

at ``reduce_trace``  the profiler's file is still there (``run.py`` removes
    its working directory afterwards): its size, the clock check, and the
    ``XLA Modules`` events of the merge programs (what
    ``merge_launches_per_dag`` has to agree with) from the planes
    ``load_xplane`` returns, and every program's events and device seconds
    inside the window (``program_device_s``)
at ``label_gap``     for each idle gap the reducer labels, every span name's
    cover of it and not only the largest (``breakdown.idle_gaps`` keeps
    that one)
at ``result_line``   the window's DAGs as the harness observed them: the
    ``MeshExchange`` counters a DAG (``exchange_counters_a_dag``; empty in
    a cell with no mesh edge), which no metric reports one by one, and
    ``event_delivery_a_dag``: histogram ``am.task.event_wait`` (events a
    DAG, p50 / p95 / highest bucket in ms: the AM had the event -> the
    runner handed it to the input) beside the beats a DAG
    (``am.heartbeat.rtt``'s count) and how many of them a wake sent
    (counter ``am.heartbeat.woken``), and ``am.task.event_wake``: the same
    wait for the events that came after their attempt had started alone
    (the wake, with no ``initialize`` in it); and ``critical_path_s_a_dag``:
    the walk of ``trace_export.critical_path`` over the window's periods
    (one DAG's client-side submit to the next one's), the one the
    ``path_*`` metrics read -- seconds a period by span name and by class,
    the hand-over stretches by the span that began after them, the steps
    between threads by link / thread / guess, the stalls met with their
    times, the share of a period the walk missed (0 unless it gave up), and
    the last period's path as it ran (``last_period_chain``)

and, after the run, reads the span buffer for

orphans      spans whose ``trace_id`` is no DAG's root span's (the client's
             own spans and ``host.stall`` are roots by design and not
             counted), and spans whose ``parent_id`` resolves to nothing
             recorded
dropped      ``tracing.dropped()``
spans_a_dag  spans of the window over the DAGs that started in it, by name
self_s_a_dag ``trace_reduce.self_intervals`` over the window's
             ``program_spans()``, by name, over those DAGs: what a task's
             wall is made of
programs     per compiled program, the window's ``kernel.<Kernel.name>``
             spans of the kernels that trace it a DAG (``launches_a_dag``)
             and the program's device time in the trace over that count
             (``device_ms_a_launch``): what one launch costs the chip
compiled     every signature this process compiled (``COMPILE_LOG``): the
             kernel, the signature, the seconds, and ``sort_ops``, the sort
             operations in its lowered module -- the witness of which sort
             body was traced
exchange_self_s_a_dag  the same self time for the ``exchange.*`` spans
             alone, each named with the argument that says which of its
             sites it is (``stage``, else ``what``, else ``device``: a
             shard on a reader thread, else ``round``: the executing
             thread), so ``exchange.decode`` reads as shards and
             ``assemble`` and ``exchange.pack`` as producers and the round
agg_a_dag    the group-by-sum's counters a DAG, beside ``programs``:
             ``AGG_LAUNCHES`` (device folds), ``AGG_FOLD_ROWS`` (a block's
             and the table's rows a fold, unpadded), ``AGG_INPUT_ROWS``
             (rows folded on the device) and ``AGG_GROUPS`` (rows of the
             final tables); zeros where no task aggregated
scan_a_dag   the typed columnar scans a DAG, by table: rows and bytes the
             ``scan.read`` spans read, rows the ``scan.filter`` spans kept
             (a filter is its thread's last read's); empty where no task
             scanned columns
kfk_a_dag    the key-foreign-key join's counters a DAG: ``KFK_PROBE_LAUNCHES``
             (device probes), ``KFK_PROBE_ROWS`` (a block's and the build's
             rows a launch, unpadded), ``KFK_PROBE_STREAM_ROWS``,
             ``KFK_OUTPUT_ROWS`` (the hits), and ``carried_rows``: the rows
             the ``join.carry`` spans gave a build row's value
group_rows_a_dag  the rows the window's ``input.group`` spans grouped, a
             DAG, by the path their ``width`` argument names: ``fixed`` (the
             one width of every key of the block, compared a word at a
             time) and ``ragged`` (-1: lengths differ, or keys wider than
             ``runformat.MAX_FIXED_WIDTH``; a span with no ``width`` is a
             tree from before PR 37 and counts here)

The clock check: for every span with a ``tez.<name>`` twin in the profiler's
file, |(annotation start - marker offset) - span start| — the reducer's
marker arithmetic, checked at every span instead of at the marker alone.

The summary is one JSON object on standard error (prefix ``trace_check:``)
and in ``chiprun_out/trace_check_<workload>_<seed>.json``.
"""
from __future__ import annotations

import bisect
import collections
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

MERGE_PROGRAMS = ("_merge_sort_impl", "_slice_to_bucket_impl",
                  "_fused_resident_merge_impl")
MATCH_WINDOW_S = 0.005


def clock_check(path, spans, marks, mark_name):
    """Spans against their annotation twins on the host planes."""
    from jax.profiler import ProfileData
    twins = collections.defaultdict(list)
    mark_ns = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == mark_name:
                    mark_ns.append(e.start_ns)
                elif e.name.startswith("tez."):
                    twins[e.name[4:]].append(e.start_ns / 1e9)
    offset = min(mark_ns) / 1e9 - marks["mark"]
    for starts in twins.values():
        starts.sort()
    errors = []
    for sp in spans:
        starts = twins.get(sp.name)
        if not starts or not marks["start"] <= sp.start <= marks["stop"]:
            continue
        want = sp.start + offset
        i = bisect.bisect_left(starts, want)
        near = min(abs(starts[j] - want) for j in (i - 1, i)
                   if 0 <= j < len(starts))
        if near <= MATCH_WINDOW_S:
            errors.append(near)
    out = {"twins": len(errors),
           "annotations": sum(map(len, twins.values()))}
    if errors:
        errors.sort()
        out.update(median_us=statistics.median(errors) * 1e6,
                   p99_us=errors[int(0.99 * (len(errors) - 1))] * 1e6,
                   max_us=errors[-1] * 1e6)
    return out


def exchange_site(span) -> str:
    """``exchange.<phase>[<site>]``: which of a phase's sites a span is."""
    for key in ("stage", "what"):
        if key in span.args:
            return f"{span.name}[{span.args[key]}]"
    for key in ("device", "round"):
        if key in span.args:
            return f"{span.name}[{key}]"
    return span.name


def kernel_programs() -> dict:
    """``Kernel.name`` -> the program it traces, of every kernel of
    ``ops/device.py`` (a donating flavor traces its plain twin's)."""
    from tez_tpu.ops import device
    return {k.name: k.program
            for value in vars(device).values()
            for k in (value if isinstance(value, tuple) else (value,))
            if isinstance(k, device.Kernel)}


def program_table(device_s, kernel_spans, programs, dags) -> dict:
    """Per program: launches a DAG, by the ``kernel.<name>`` spans of the
    kernels that trace it, and device ms a launch, its device seconds in
    the trace over that count.  A program nothing launched in the window is
    left out; one the trace did not see reads no device time."""
    launches = collections.Counter()
    for name, count in kernel_spans.items():
        program = programs.get(name[len("kernel."):])
        if program is not None:
            launches[program] += count
    return {program: {
        "launches_a_dag": round(count / dags, 2),
        "device_ms_a_launch": round(
            device_s[program] * 1e3 / count, 3) if program in device_s
        else None}
        for program, count in launches.most_common()}


def event_delivery(dags) -> dict:
    """The window's ``am.task.event_wait`` and heartbeat counts, a DAG."""
    from tez_tpu.common import metrics
    groups = collections.defaultdict(collections.Counter)
    for dag in dags:
        for group, counters in dag["counters"].items():
            if group == "TaskUmbilical" or group.startswith(
                    metrics.HIST_GROUP_PREFIX + "am."):
                groups[group].update(counters)
    hists = metrics.histograms_from_counters(groups)
    wait = hists.get("am.task.event_wait", {})
    wake = hists.get("am.task.event_wake", {})
    n = len(dags)
    return {
        "events": wait.get("count", 0) / n,
        "event_wait_ms": {k: wait.get(k) for k in ("p50", "p95", "max_ms")},
        "event_wait_mean_ms": wait.get("sum_us", 0) / 1e3 / max(
            1, wait.get("count", 0)),
        "events_after_start": wake.get("count", 0) / n,
        "event_wake_ms": {k: wake.get(k) for k in ("p50", "p95", "max_ms")},
        "event_wake_mean_ms": wake.get("sum_us", 0) / 1e3 / max(
            1, wake.get("count", 0)),
        "beats": hists.get("am.heartbeat.rtt", {}).get("count", 0) / n,
        "woken": groups["TaskUmbilical"].get("am.heartbeat.woken", 0) / n}


def group_rows(spans, dags) -> dict:
    """``group_rows_a_dag``: rows grouped a DAG, by the path taken."""
    rows = collections.Counter(fixed=0, ragged=0)
    for s in spans:
        if s.name == "input.group":
            fixed = s.args.get("width", -1) >= 0
            rows["fixed" if fixed else "ragged"] += s.args.get("rows", 0)
    return {k: v / dags for k, v in rows.items()}


AGG_COUNTERS = ("AGG_LAUNCHES", "AGG_FOLD_ROWS", "AGG_INPUT_ROWS",
                "AGG_GROUPS")


def agg_counters(dags) -> dict:
    """``agg_a_dag``: the group-by-sum's counters over the DAGs, a DAG."""
    totals = collections.Counter()
    for dag in dags:
        for counters in dag["counters"].values():
            totals.update({k: counters.get(k, 0) for k in AGG_COUNTERS})
    return {k: totals[k] / max(1, len(dags)) for k in AGG_COUNTERS}


KFK_COUNTERS = ("KFK_PROBE_LAUNCHES", "KFK_PROBE_ROWS",
                "KFK_PROBE_STREAM_ROWS", "KFK_OUTPUT_ROWS")


def kfk_counters(dags) -> dict:
    """``kfk_a_dag`` less ``carried_rows``: the join's counters a DAG."""
    totals = collections.Counter()
    for dag in dags:
        for counters in dag["counters"].values():
            totals.update({k: counters.get(k, 0) for k in KFK_COUNTERS})
    return {k: totals[k] / max(1, len(dags)) for k in KFK_COUNTERS}


def scan_tables(spans, dags) -> dict:
    """``scan_a_dag``: rows read and kept and bytes read a DAG, by table."""
    out = collections.defaultdict(collections.Counter)
    last_read = {}
    for s in sorted(spans, key=lambda s: s.start):
        if s.name == "scan.read":
            table = s.args.get("table", "")
            out[table]["rows_read"] += s.args.get("rows", 0)
            out[table]["bytes_read"] += s.args.get("bytes", 0)
            last_read[s.thread] = table
        elif s.name == "scan.filter":
            out[last_read.get(s.thread, "")]["rows_kept"] += \
                s.args.get("rows_out", 0)
    return {table: {k: v / dags for k, v in sorted(c.items())}
            for table, c in sorted(out.items())}


def path_table(obs) -> dict:
    """``critical_path_s_a_dag``: the window's walk, a period."""
    import path_metrics
    path = path_metrics.window_path(obs)
    if path is None:
        return {"walked": False}
    n = path["periods"]
    steps = path["steps"]
    return {
        "walked": True, "periods": n, "path_s": path["seconds"] / n,
        "miss": path["miss"],
        "by_class": {k: round(v / n, 5) for k, v in path["by_class"].items()},
        "by_name": {k: round(v / n, 5) for k, v in sorted(
            path["by_name"].items(), key=lambda kv: -kv[1]) if v / n >= 5e-5},
        "handoff_s": {k: round(v / n, 5) for k, v in sorted(
            path["handoff_s"].items(), key=lambda kv: -kv[1])
            if v / n >= 5e-5},
        "steps_a_dag": {k: round(v / n, 2) for k, v in steps.items()},
        "guess_share": steps["guess"] / max(1, sum(steps.values())),
        "stalls": [[round(a - obs["dags"][0]["t_submit"], 3), round(d, 3)]
                   for a, d in path["stalls"]],
        # the last period's path as it ran, oldest first: [name, thread,
        # ms from the period's start, ms], stretches of 1 ms and more
        "last_period_chain": [
            [c["name"], c["thread"].split("#")[0][-28:],
             round((c["start"] - path["chain"][0]["start"]) * 1e3, 1),
             round(c["seconds"] * 1e3, 1)]
            for c in path["chain"] if c["seconds"] >= 1e-3]}


def main() -> int:
    import run as bench_run
    import trace_reduce
    from tez_tpu.common import tracing

    found = {"gaps": []}
    label_gap = trace_reduce.label_gap

    def reduce_and_keep(path, n_devices, spans, marks):
        found["marks"] = dict(marks)
        found["trace_bytes"] = os.path.getsize(path)
        found["clock"] = clock_check(path, tracing.snapshot(), marks,
                                     trace_reduce.MARK)
        planes = trace_reduce.load_xplane(path)
        found["merge_program_events"] = collections.Counter(
            trace_reduce.program_name(e[0])
            for p in planes["planes"] if trace_reduce.DEVICE_PLANE.match(
                p["name"])
            for line in p["lines"] if line["name"] == trace_reduce.MODULES_LINE
            for e in line["events"]
            if trace_reduce.program_name(e[0]) in MERGE_PROGRAMS)
        res = trace_reduce.reduce_planes(planes, n_devices, spans, marks)
        # the reducer's own per-program sums (its ten largest)
        found["program_device_s"] = dict(
            res["breakdown"]["device_ops"]) if res else {}
        return res

    def label_and_keep(lo, hi, selfs):
        cover = collections.Counter()
        for name, a, b in selfs:
            if min(b, hi) > max(a, lo):
                cover[name] += (min(b, hi) - max(a, lo)) / (hi - lo)
        found["gaps"].append({
            "seconds": hi - lo,
            "threads_in": {k: round(v, 2) for k, v in cover.most_common()
                           if v >= 0.05}})
        return label_gap(lo, hi, selfs)

    result_line = bench_run.result_line

    def line_and_keep(args, spec, devices, res):
        dags = res["obs"]["dags"]
        totals = collections.Counter()
        for dag in dags:
            totals.update(dag["counters"].get("MeshExchange", {}))
        found["exchange_counters_a_dag"] = {
            k: v / len(dags) for k, v in sorted(totals.items())}
        found["event_delivery_a_dag"] = event_delivery(dags)
        found["critical_path_s_a_dag"] = path_table(res["obs"])
        found["agg_a_dag"] = agg_counters(dags)
        found["kfk_a_dag"] = kfk_counters(dags)
        return result_line(args, spec, devices, res)

    trace_reduce.reduce_trace = reduce_and_keep
    trace_reduce.label_gap = label_and_keep
    bench_run.result_line = line_and_keep
    rc = bench_run.main(sys.argv[1:])
    if "marks" not in found:
        return rc

    marks = found.pop("marks")
    spans = [s for s in tracing.snapshot() if s.end is not None]
    roots = {s.trace_id: s for s in spans if s.cat == "dag"}
    ids = {s.span_id for s in spans}
    # the client's spans and the stall witness's are roots of their own,
    # found by the window's clock: no orphans
    orphans = collections.Counter(
        s.name.split(":")[0] for s in spans if s.trace_id not in roots
        and s.cat not in ("client", "host"))
    unresolved = collections.Counter(
        s.name.split(":")[0] for s in spans
        if s.parent_id and s.parent_id not in ids)
    dags = sum(marks["start"] <= r.start <= marks["stop"]
               for r in roots.values()) or 1
    window = [row for row in trace_reduce.program_spans()
              if marks["start"] <= row[1] <= marks["stop"]]
    self_s = collections.Counter()
    for name, a, b in trace_reduce.self_intervals(window):
        self_s[name] += b - a
    # the exchange's spans by site: same rows, same self time, other names
    # (a span's self time depends on every span of its thread, so all go in)
    by_site = collections.Counter()
    for name, a, b in trace_reduce.self_intervals(
            [(exchange_site(s) if s.cat == "exchange" else s.name,
              s.start, s.end, s.thread) for s in spans
             if marks["start"] <= s.start <= marks["stop"]]):
        if name.startswith("exchange."):
            by_site[name] += b - a
    events = found.pop("merge_program_events")
    from tez_tpu.ops import device
    kernel_spans = collections.Counter(
        row[0] for row in window if row[0].startswith("kernel.")
        and row[0] != "kernel.compile")
    args = dict(zip(sys.argv[1::2], sys.argv[2::2]))
    in_window = [s for s in spans
                 if marks["start"] <= s.start <= marks["stop"]]
    summary = {
        "workload": args.get("--workload"), "seed": args.get("--seed"),
        "spans": len(spans), "dropped": tracing.dropped(),
        "dag_roots": len(roots), "window_dags": dags,
        "orphan_spans": dict(orphans), "unresolved_parents": dict(unresolved),
        "spans_a_dag": len(window) / dags,
        "spans_a_dag_by_name": {k: round(v / dags, 1) for k, v in
                                collections.Counter(
                                    row[0] for row in window).most_common()},
        "self_s_a_dag": {k: round(v / dags, 4)
                         for k, v in self_s.most_common()},
        "exchange_self_s_a_dag": {k: round(v / dags, 4)
                                  for k, v in by_site.most_common()},
        "group_rows_a_dag": group_rows(in_window, dags),
        "programs": program_table(found.pop("program_device_s"),
                                  kernel_spans, kernel_programs(), dags),
        "agg_a_dag": found.pop("agg_a_dag", {}),
        "scan_a_dag": scan_tables(in_window, dags),
        "kfk_a_dag": {**found.pop("kfk_a_dag", {}), "carried_rows": sum(
            s.args.get("rows", 0) for s in in_window
            if s.name == "join.carry") / dags},
        "compiled": [{"kernel": name, "signature": sig,
                      "seconds": round(secs, 2), "sort_ops": sort_ops}
                     for name, sig, secs, sort_ops, _t in
                     device.COMPILE_LOG],
        "merge_program_events": dict(events),
        "merge_program_events_a_dag": sum(events.values()) / dags,
        **found}
    print("trace_check: " + json.dumps(summary), file=sys.stderr, flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"trace_check_{summary['workload']}_"
                                f"{summary['seed']}.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
