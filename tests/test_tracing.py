"""Tracing-plane tests: span runtime, carrier propagation, Perfetto export,
latency histograms, /metrics surface, and the span critical-path analyzer."""
import collections
import json
import threading
import typing
import urllib.request

import pytest

from tests.trace_schema import check_trace
from tez_tpu.common import metrics, tracing
from tez_tpu.common.counters import TezCounters


# ------------------------------------------------------------- span runtime

def test_disarmed_is_noop():
    """The disarmed fast path: no spans, no allocations, NOOP singleton."""
    assert not tracing.armed()
    s = tracing.span("anything", cat="x", k=1)
    assert s is tracing.NOOP_SPAN
    with s as inner:
        inner.annotate(a=1)
        inner.event("e")
    tracing.event("standalone")
    assert tracing.snapshot() == []
    assert tracing.current_span() is None
    assert tracing.current_carrier() == ""


def test_armed_records_nested_spans():
    tracing.arm(scope="t")
    with tracing.span("outer", cat="task", vertex="v1") as outer:
        assert tracing.current_span() is outer
        with tracing.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            inner.event("tick", n=1)
    spans = tracing.snapshot()
    assert [s.name for s in spans] == ["inner", "outer"]  # finish order
    assert all(s.end is not None and s.end >= s.start for s in spans)
    assert spans[0].events and spans[0].events[0][1] == "tick"


def test_span_error_capture():
    tracing.arm(scope="t")
    with pytest.raises(ValueError):
        with tracing.span("boom"):
            raise ValueError("no")
    (sp,) = tracing.snapshot()
    assert sp.args.get("error", "").startswith("ValueError")


def test_carrier_round_trip_and_attach():
    tracing.arm(scope="t")
    with tracing.span("root") as root:
        carrier = tracing.current_carrier()
    ctx = tracing.parse_carrier(carrier)
    assert ctx == (root.trace_id, root.span_id)
    assert tracing.parse_carrier("") is None
    assert tracing.parse_carrier("00-zz-xx-01") is None
    # a "remote" worker attaches the carrier and parents off it
    with tracing.attached(carrier):
        with tracing.span("remote") as rm:
            assert rm.trace_id == root.trace_id
            assert rm.parent_id == root.span_id


def test_cross_thread_explicit_parent():
    """Fetch-style spans: parent captured on one thread, span on another."""
    tracing.arm(scope="t")
    captured = {}
    with tracing.span("attempt") as att:
        captured["ctx"] = tracing.current_context()

    def fetcher():
        with tracing.span("shuffle.fetch", parent=captured["ctx"]) as f:
            captured["fetch"] = (f.trace_id, f.parent_id)

    th = threading.Thread(target=fetcher)
    th.start()
    th.join()
    assert captured["fetch"] == (att.trace_id, att.span_id)


def test_buffer_survives_disarm_and_is_bounded():
    tracing.arm(scope="t", capacity=8)
    for i in range(20):
        with tracing.span(f"s{i}"):
            pass
    assert len(tracing.snapshot()) == 8              # ring buffer bound
    tracing.clear("t")
    assert not tracing.armed()
    assert len(tracing.snapshot()) == 8              # survives disarm
    assert tracing.span("late") is tracing.NOOP_SPAN  # but records nothing
    tracing.clear_all()
    assert tracing.snapshot() == []


def test_install_from_conf_refcounted():
    from tez_tpu.common import config as C
    conf = C.TezConfiguration({"tez.trace.enabled": True})
    assert tracing.install_from_conf(conf, scope="dag1")
    assert tracing.install_from_conf(conf, scope="dag2")
    tracing.clear("dag1")
    assert tracing.armed()                            # dag2 still holds it
    tracing.clear("dag2")
    assert not tracing.armed()
    off = C.TezConfiguration({})
    assert not tracing.install_from_conf(off, scope="dag3")
    assert not tracing.armed()


# ---------------------------------------------------------- perfetto export

def test_spans_export_valid_trace_event_json():
    from tez_tpu.tools import trace_export
    tracing.arm(scope="t")
    with tracing.span("outer", cat="task", vertex="v"):
        with tracing.span("inner"):
            pass
        tracing.event("fence.stale_epoch", seam="umbilical")
    trace = trace_export.spans_to_trace(tracing.snapshot())
    n = check_trace(json.loads(json.dumps(trace)))
    assert n >= 4  # 2 X spans + 1 instant + >=1 thread_name metadata
    names = [e["name"] for e in trace["traceEvents"]]
    assert {"outer", "inner", "fence.stale_epoch", "thread_name"} <= set(names)
    x = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert all(e["args"]["trace_id"] for e in x)


def test_critical_path_picks_dominant():
    """The walk of a one-thread buffer: every second of the period under one
    name, the envelope's own time `unnamed`, the dominant member named."""
    from tez_tpu.tools.trace_export import (critical_path,
                                            critical_path_report,
                                            dominant_span)
    tracing.arm(scope="t")
    with tracing.span("dag", cat="dag") as root:
        with tracing.span("fast", vertex="a"):
            pass
        with tracing.span("slow", vertex="b") as slow:
            slow.start -= 0.5                          # fake 500ms of work
        root.start -= 0.6                              # 100ms of its own
    spans = tracing.snapshot()
    path = critical_path(spans)
    assert path["seconds"] == pytest.approx(root.end - root.start)
    assert sum(path["by_class"].values()) == pytest.approx(path["seconds"])
    assert path["by_name"]["slow"] == pytest.approx(0.5, abs=1e-3)
    assert path["by_class"]["unnamed"] == pytest.approx(0.1, abs=1e-3)
    assert path["miss"] == 0 and path["steps"]["guess"] == 0
    chain = [c["name"] for c in path["chain"]]
    assert chain[0] == chain[-1] == "dag.dag" and "fast" in chain
    assert dominant_span(path)["span_id"] == slow.span_id
    rep = critical_path_report(spans)
    assert rep["dominant"]["name"] == "slow"
    assert rep["dominant"]["vertex"] == "b"
    assert rep["chain"][0]["name"] == "dag.dag"


# ------------------------------------------------------- latency histograms

def test_histogram_buckets_and_quantiles():
    h = metrics.Histogram("x")
    for ms in (0.5, 3, 3, 700, 1e9):
        h.observe(ms)
    d = h.to_dict()
    assert d["count"] == 5
    assert sum(d["counts"]) == 5
    assert d["counts"][-1] == 1                       # 1e9 ms -> overflow
    assert metrics.bucket_index(0.5) == 0
    assert metrics.bucket_index(1.0) == 0
    assert metrics.bucket_index(1.5) == 1
    assert metrics.bucket_index(65536.0) == 16
    assert metrics.bucket_index(65537.0) == 17
    assert 0 < h.quantile(0.5) <= 4.0
    assert h.quantile(0.95) >= 512.0


def test_observe_mirrors_into_counters_and_aggregates():
    """Bucket counters roll up task->vertex->DAG through plain aggregate()."""
    c1, c2 = TezCounters(), TezCounters()
    metrics.observe("shuffle.fetch.rtt", 3.0, counters=c1)
    metrics.observe("shuffle.fetch.rtt", 100.0, counters=c2)
    agg = TezCounters()
    agg.aggregate(c1)
    agg.aggregate(c2)
    hists = metrics.histograms_from_counters(agg.to_dict())
    h = hists["shuffle.fetch.rtt"]
    assert h["count"] == 2
    assert h["sum_us"] == 103000
    assert h["max_ms"] == 128.0


def test_prometheus_render_is_well_formed():
    metrics.observe("spill.write", 12.0)
    metrics.set_gauge("running_tasks", 3)
    text = metrics.render_prometheus(metrics.registry().histograms(),
                                     metrics.registry().gauges())
    lines = text.splitlines()
    assert text.endswith("\n")
    hist = [ln for ln in lines if ln.startswith("tez_latency_spill_write_ms")]
    assert any('le="+Inf"' in ln for ln in hist)
    assert any(ln.startswith("tez_latency_spill_write_ms_sum") for ln in hist)
    assert any(ln.startswith("tez_latency_spill_write_ms_count 1") for ln in hist)
    # cumulative buckets never decrease
    vals = [int(ln.rsplit(" ", 1)[1]) for ln in hist if "_bucket" in ln]
    assert vals == sorted(vals)
    assert "tez_running_tasks 3" in text
    # every sample line is "name{labels} value" or "name value"
    for ln in lines:
        if ln.startswith("#") or not ln:
            continue
        assert len(ln.rsplit(" ", 1)) == 2, ln


def test_counter_diff_histogram_regression():
    from tez_tpu.tools.counter_diff import diff_histograms, flatten
    a, b = TezCounters(), TezCounters()
    for _ in range(20):
        metrics.observe("shuffle.fetch.rtt", 10.0, counters=a)
        metrics.observe("shuffle.fetch.rtt", 300.0, counters=b)
    rows = diff_histograms(a.to_dict(), b.to_dict())
    (name, sa, sb, regressed) = rows[0]
    assert name == "shuffle.fetch.rtt" and regressed
    assert sb["p95"] > sa["p95"]
    # same distribution -> no regression flag
    rows = diff_histograms(a.to_dict(), a.to_dict())
    assert not rows[0][3]
    # histogram groups are kept out of the plain counter diff
    assert flatten(a.to_dict()) == {}


def test_limits_configure_annotations_resolve():
    """Regression: Limits.configure used 'Any' without importing it, which
    blew up only when annotations were evaluated."""
    from tez_tpu.common import counters as counters_mod
    hints = typing.get_type_hints(counters_mod.Limits.configure.__func__,
                                  vars(counters_mod))
    assert hints["conf"] is typing.Any


# --------------------------------------------------- swimlane / history r-t

def test_swimlane_history_round_trip(tmp_path):
    """History JSONL -> DagInfo -> swimlane SVG: lane count matches the
    containers used, every attempt renders one bar, bar geometry is
    monotonic with attempt duration."""
    import re
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.common.payload import ProcessorDescriptor
    from tez_tpu.dag.dag import DAG, Vertex
    from tez_tpu.tools.history_parser import parse_jsonl_files
    from tez_tpu.tools.swimlane import LEFT, render_svg
    hist = str(tmp_path / "hist")
    c = TezClient.create("lane", {
        "tez.staging-dir": str(tmp_path / "s"),
        "tez.history.logging.service.class":
            "tez_tpu.am.history:JsonlHistoryLoggingService",
        "tez.history.logging.log-dir": hist}).start()
    try:
        dag = DAG.create("lanedag").add_vertex(Vertex.create(
            "v", ProcessorDescriptor.create(
                "tez_tpu.library.processors:SleepProcessor",
                payload={"sleep_ms": 5}), 3))
        st = c.submit_dag(dag).wait_for_completion(timeout=30)
        assert st.state.name == "SUCCEEDED"
    finally:
        c.stop()
    dag_info = list(parse_jsonl_files([hist]).values())[0]
    attempts = [a for a in dag_info.all_attempts() if a.start_time]
    assert len(attempts) == 3
    containers = {a.container_id for a in attempts}
    svg = render_svg(dag_info)
    bars = re.findall(r'<rect x="([\d.]+)" y="\d+" width="([\d.]+)"[^>]*>'
                      r'<title>(attempt_\S+)', svg)
    assert len(bars) == len(attempts)                 # one bar per attempt
    assert len(re.findall(r'<text x="4" y="\d+">', svg)) - 1 \
        == len(containers)                            # one label per lane
    by_id = {a.attempt_id: a for a in attempts}
    for x, w, aid in bars:
        a = by_id[aid]
        assert float(x) >= LEFT                        # bars start in-lane
        assert float(w) >= 2.0                         # min visible width
        # longer attempts never render narrower than much-shorter ones
    durs = sorted((by_id[aid].duration, float(w)) for x, w, aid in bars)
    for (d0, w0), (d1, w1) in zip(durs, durs[1:]):
        if d1 - d0 > 0.05:                             # beyond min-width blur
            assert w1 >= w0


# ----------------------------------------------------------- e2e trace plane

def test_e2e_trace_and_metrics(tmp_path):
    """A real DAG with tez.trace.enabled: one trace id links dag, attempt,
    and shuffle spans; /metrics and /trace serve from the same run; the
    span critical-path analyzer names the dominant vertex."""
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.tools import trace_export
    from tez_tpu.tools.analyzers import SpanCriticalPathAnalyzer
    from tez_tpu.tools.chaos import _build_dag
    result = str(tmp_path / "result.txt")
    c = TezClient.create("traced", {
        "tez.staging-dir": str(tmp_path / "s"),
        "tez.am.web.enabled": True}).start()
    try:
        dag = _build_dag("traced", result, trace=True)
        st = c.submit_dag(dag).wait_for_completion(timeout=60)
        assert st.state.name == "SUCCEEDED"
        url = c.framework_client.am.web_ui.url
        prom = urllib.request.urlopen(url + "metrics").read().decode()
        trace_json = json.loads(
            urllib.request.urlopen(url + "trace").read())
        dag_impl = c.framework_client.am.current_dag
    finally:
        c.stop()

    spans = tracing.snapshot()
    assert spans, "no spans recorded with tez.trace.enabled"
    by_cat = {}
    for s in spans:
        by_cat.setdefault(s.cat, []).append(s)
    (dag_span,) = by_cat["dag"]
    assert dag_span.end is not None                    # finished on dag end
    attempts = by_cat["task"]
    assert any(s.name.startswith("attempt:") for s in attempts)
    fetches = [s for s in by_cat.get("shuffle", [])
               if s.name == "shuffle.fetch"]
    assert fetches, "no shuffle.fetch spans"
    # causality: every attempt and fetch span shares the DAG's trace id
    for s in attempts + fetches:
        assert s.trace_id == dag_span.trace_id, s.name

    # exported trace validates against the trace_event schema
    check_trace(trace_export.spans_to_trace(spans))
    assert trace_json["traceEvents"], "GET /trace returned an empty trace"
    check_trace(trace_json)

    # /metrics: valid-ish prometheus with the two acceptance histograms
    assert "# TYPE tez_latency_shuffle_fetch_rtt_ms histogram" in prom
    assert "# TYPE tez_latency_spill_write_ms histogram" in prom
    assert "tez_running_tasks" in prom
    assert "tez_am_epoch" in prom

    # analyzer names the dominant vertex of the scatter-gather DAG
    res = SpanCriticalPathAnalyzer().analyze(dag_impl)
    assert "dominant vertex:" in res.headline, res.headline
    assert ("producer" in res.headline) or ("consumer" in res.headline), \
        res.headline


# ------------------------------------------- the plane's repairs (ISSUE 26)

def test_same_named_threads_get_distinct_keys_and_self_times():
    """Thread NAMES repeat (every sorter has a sortmaster_0); Span.thread
    does not, so a reader that nests spans by thread keeps two threads'
    self times apart."""
    import os
    import sys
    import time
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench)
    try:
        import trace_reduce
    finally:
        sys.path.remove(bench)
    tracing.arm(scope="t")
    gate = threading.Barrier(2)

    def worker():
        gate.wait()                       # both alive at once: two idents
        with tracing.span("outer", cat="x"):
            with tracing.span("inner", cat="x"):
                time.sleep(0.02)
        gate.wait()

    threads = [threading.Thread(target=worker, name="sortmaster_0")
               for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tracing.snapshot()
    keys = {s.thread for s in spans}
    assert len(keys) == 2
    assert all(k.startswith("sortmaster_0#") for k in keys)
    selfs = trace_reduce.self_intervals(
        [(s.name, s.start, s.end, s.thread) for s in spans])
    inner = sum(b - a for n, a, b in selfs if n == "inner")
    outer = sum(b - a for n, a, b in selfs if n == "outer")
    assert inner >= 0.04 - 1e-3           # two threads' worth, not one
    # under ONE key the second thread's spans would nest in the first's and
    # 'outer' would lose or gain a whole 'inner'; apart, outer's self time
    # is only the few microseconds around each inner
    assert outer < 0.01
    # the export shows the readable part
    from tez_tpu.tools import trace_export
    names = {e["args"]["name"]
             for e in trace_export.spans_to_events(spans)
             if e["name"] == "thread_name"}
    assert names == {"sortmaster_0"}


def test_ring_counts_what_it_evicts():
    tracing.arm(scope="t", capacity=8)
    assert tracing.dropped() == 0
    for i in range(20):
        with tracing.span(f"s{i}"):
            pass
    assert len(tracing.snapshot()) == 8
    assert tracing.dropped() == 12
    tracing.clear_all()
    assert tracing.dropped() == 0


NEW_SPAN_SITES = [
    ("am.task.queue", "am"), ("am.task.done", "am"),
    ("am.dag.commit", "am"),
    ("input.wait_splits", "task"), ("input.open", "task"),
    ("input.read", "task"), ("input.group", "task"),
    ("processor.tokenize", "task"), ("processor.sum", "task"),
    ("processor.format", "task"),
    ("output.write", "task"), ("output.close", "task"),
    ("output.commit", "task"),
    ("sort.collect", "sort"), ("sort.flush", "sort"),
    ("sort.final_merge", "sort"),
    ("spill.write", "spill"), ("spill.read", "spill"),
    ("merge.stage", "merge"), ("merge.launch", "merge"),
    ("merge.readback", "merge"), ("merge.gather", "merge"),
    ("kernel.merge_sort", "kernel"), ("kernel.compile", "kernel"),
    ("exchange.wait_peers", "exchange"), ("exchange.plan", "exchange"),
    ("exchange.pack", "exchange"), ("exchange.launch", "exchange"),
    ("exchange.readback", "exchange"), ("exchange.decode", "exchange"),
    # PR 36: the DAG boundary, the envelopes' children, the stall witness
    ("submit_dag", "client"), ("wake", "client"), ("status", "client"),
    ("build", "client"), ("am.dag.admit", "am"), ("am.dag.init", "am"),
    ("task.instantiate", "task"), ("processor.initialize", "task"),
    ("input.initialize", "task"), ("output.initialize", "task"),
    ("input.start", "task"), ("task.events", "task"),
    ("input.close", "task"), ("processor.close", "task"),
    ("finish", "task"), ("host.stall", "host"),
]


@pytest.mark.parametrize("name,cat", NEW_SPAN_SITES)
def test_disarmed_new_sites_are_noop(name, cat):
    """Every new site goes through span()/start_span()/metrics.timer():
    disarmed, each is the shared NOOP singleton and records nothing."""
    assert not tracing.armed()
    assert tracing.span(name, cat=cat, rows=1) is tracing.NOOP_SPAN
    assert tracing.start_span(name, cat=cat, lane="am#dag_1") \
        is tracing.NOOP_SPAN
    with metrics.timer(name):
        pass
    tracing.event(name, rows=1)
    assert tracing.here() == ""          # the link helper: one flag load
    assert tracing.snapshot() == []


def test_disarmed_sorter_and_merge_record_nothing():
    """The sites themselves, disarmed: a device sort, a flush and a device
    merge leave the buffer empty (and the always-on counters count)."""
    from tez_tpu.common.counters import TaskCounter
    counters, merged = _four_run_merge()
    assert not tracing.armed()
    assert tracing.snapshot() == []
    assert merged.batch.num_records == 1200
    assert counters.find_counter(TaskCounter.DEVICE_MERGE_LAUNCHES).value > 0


def test_timer_opens_a_span_when_armed():
    tracing.arm(scope="t")
    with metrics.timer("spill.write"):
        pass
    (sp,) = tracing.snapshot()
    assert (sp.name, sp.cat) == ("spill.write", "spill")


def test_span_enters_a_profiler_annotation_once_jax_is_loaded(monkeypatch):
    """Armed and jax imported: a with-span enters TraceAnnotation("tez." +
    name); a start_span (no thread, no annotation) does not."""
    import jax
    entered = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    tracing.arm(scope="t")
    with tracing.span("merge.readback", cat="merge"):
        pass
    tracing.start_span("am.task.queue", cat="am", lane="am#d").finish()
    assert entered == [("enter", "tez.merge.readback"),
                       ("exit", "tez.merge.readback")]


def test_start_span_lane_overrides_the_thread_key():
    tracing.arm(scope="t")
    sp = tracing.start_span("am.task.queue", cat="am", lane="am#dag_1")
    sp.finish()
    assert sp.thread == "am#dag_1"
    assert tracing.start_span("x").thread == tracing.thread_key()


def test_bound_carries_the_callers_context_to_another_thread():
    """The hand-off rule in one call: a thread started on bound(fn) opens
    its spans under the caller's; with nothing to carry it is fn itself."""
    import threading

    def work():
        with tracing.span("merge.readback", cat="merge"):
            pass

    assert tracing.bound(work) is work
    tracing.arm(scope="t")
    with tracing.span("run", cat="task") as parent:
        t = threading.Thread(target=tracing.bound(work))
        t.start()
        t.join()
    child = next(s for s in tracing.snapshot() if s.name == "merge.readback")
    assert (child.trace_id, child.parent_id) == (parent.trace_id,
                                                 parent.span_id)
    assert child.thread != parent.thread


def test_start_span_can_open_in_the_past():
    """A wait known only once it is over (exchange.wait_peers): ``start``
    goes in at the call, armed or not, and never onto NOOP_SPAN."""
    assert tracing.start_span("exchange.wait_peers", start=1.0) \
        is tracing.NOOP_SPAN
    tracing.arm(scope="t")
    sp = tracing.start_span("exchange.wait_peers", cat="exchange", start=1.0)
    sp.finish()
    assert sp.start == 1.0 and sp.end > 1.0
    assert tracing.snapshot() == [sp]


# ------------------------------------------------ launch counters (ISSUE 26)

def _four_run_merge():
    """Four sorted runs of 300 rows through the host-fed device merge."""
    import numpy as np
    from tez_tpu.ops.runformat import KVBatch, Run
    from tez_tpu.ops.sorter import merge_sorted_runs
    runs = []
    for r in range(4):
        keys = sorted(f"k{r}{i:05d}".encode() for i in range(300))
        batch = KVBatch.from_pairs([(k, b"v") for k in keys])
        runs.append(Run(batch, np.array([0, 300], dtype=np.int64)))
    counters = TezCounters()
    merged = merge_sorted_runs(runs, 1, 16, counters=counters,
                               engine="device", device_min_records=0)
    return counters, merged


def test_merge_launch_rows_are_the_padded_concatenation():
    """Worked by hand: 4 runs x 300 rows are 1200 records, sorted in ONE
    launch on the bucket of their sum, 2048 rows: padding 2048 / 1200, no
    levels (the ladder this replaced launched 7 programs on 4096 rows)."""
    from tez_tpu.common.counters import TaskCounter
    counters, merged = _four_run_merge()
    c = {t: counters.find_counter(t).value for t in (
        TaskCounter.DEVICE_MERGE_RECORDS, TaskCounter.DEVICE_MERGE_LAUNCHES,
        TaskCounter.DEVICE_MERGE_LAUNCH_ROWS)}
    assert c[TaskCounter.DEVICE_MERGE_RECORDS] == 1200
    assert c[TaskCounter.DEVICE_MERGE_LAUNCH_ROWS] == 2048
    assert c[TaskCounter.DEVICE_MERGE_LAUNCHES] == 1
    keys = [merged.batch.key(i) for i in range(merged.batch.num_records)]
    assert keys == sorted(keys)


# ------------------------------------------- cause across threads (ISSUE 26)

def _chains_end_in(spans, root):
    """Spans whose parent chain does not end in `root`; the client's own
    spans and the stall witness's are roots by design and left out."""
    by_id = {s.span_id: s for s in spans}
    bad = []
    for s in spans:
        if s.cat in ("client", "host"):
            continue
        cur, hops = s, 0
        while cur.parent_id is not None and hops < 64:
            if cur.parent_id not in by_id:
                bad.append((s.name, "unresolved parent"))
                break
            cur, hops = by_id[cur.parent_id], hops + 1
        else:
            if cur is not root:
                bad.append((s.name, f"chain ends in {cur.name}"))
    return bad


@pytest.mark.parametrize("pipeline_depth,sort_threads", [(2, 0), (0, 1)])
def test_sorter_worker_threads_inherit_the_submitters_context(
        pipeline_depth, sort_threads):
    """The async pipeline's staging/readback threads and the sortmaster
    executor: spans they record hang under the span that was current where
    the work was handed over."""
    from tez_tpu.ops.sorter import DeviceSorter
    tracing.arm(scope="t")
    with tracing.span("attempt:x", cat="task") as root:
        sorter = DeviceSorter(num_partitions=2, engine="device",
                              device_min_records=0,
                              span_budget_bytes=4096,
                              pipeline_depth=pipeline_depth,
                              sort_threads=sort_threads)
        for i in range(600):
            sorter.write(f"k{i % 97:05d}".encode(), b"v")
        sorter.flush()
    spans = tracing.snapshot()
    assert {s.trace_id for s in spans} == {root.trace_id}
    assert _chains_end_in(spans, root) == []
    off_thread = {s.name for s in spans if s.thread != root.thread}
    if pipeline_depth:
        assert {"device.encode", "device.h2d", "device.dispatch",
                "device.d2h"} <= off_thread
    else:
        assert any(n.startswith("kernel.") for n in off_thread)
    assert any(s.thread.startswith(("sorter-pipeline", "sortmaster"))
               for s in spans)


def _owc_corpus(tmp_path, words_per_file=100_000, files=4):
    import random
    rng = random.Random(26)
    paths = []
    for i in range(files):
        p = tmp_path / f"in{i}.txt"
        with open(p, "w") as fh:
            for _ in range(words_per_file // 100):
                fh.write(" ".join(f"w{rng.randrange(50_000):07d}"
                                  for _ in range(100)) + "\n")
        paths.append(str(p))
    return paths


@pytest.fixture(scope="module")
def traced_owc(tmp_path_factory):
    """One OrderedWordCount DAG, device engine forced, spans small enough
    that tokenizers sort several, spill and final-merge; traced."""
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.examples.ordered_wordcount import build_dag
    from tez_tpu.am.history import HistoryEventType
    tmp_path = tmp_path_factory.mktemp("owc")
    tracing.clear_all()
    conf = {"tez.staging-dir": str(tmp_path / "s"),
            "tez.runner.mode": "threads",
            "tez.runtime.sorter.class": "device",
            "tez.runtime.tpu.device.sort.min.records": 0,
            "tez.runtime.io.sort.mb": 1,
            "tez.runtime.tpu.host.spill.dir": str(tmp_path / "spill"),
            "tez.trace.enabled": True, "tez.trace.buffer.spans": 262144}
    client = TezClient.create("traced-owc", conf, session=True).start()
    try:
        dag = build_dag(_owc_corpus(tmp_path), str(tmp_path / "out"),
                        tokenizer_parallelism=4, summation_parallelism=4,
                        sorter_parallelism=1, combine=False,
                        tokenizer_mode="vector", exchange="host")
        status = client.submit_dag(dag).wait_for_completion(timeout=300)
        finished = client.framework_client.am.logging_service.of_type(
            HistoryEventType.DAG_FINISHED)
    finally:
        client.stop()
    spans = tracing.snapshot()
    dropped = tracing.dropped()
    tracing.clear_all()
    return status, finished, spans, dropped


def test_owc_every_span_hangs_under_the_dag_root(traced_owc):
    status, _finished, spans, dropped = traced_owc
    assert status.state.name == "SUCCEEDED" and dropped == 0
    (root,) = [s for s in spans if s.cat == "dag"]
    assert root.name == "dag:OrderedWordCount"
    # one trace a DAG; the client's own spans (submit_dag, wake, status:
    # the root opens inside the first) and the stall witness's are roots
    # of their own, found by the clock
    own = [s for s in spans if s.cat in ("client", "host")]
    assert {s.name for s in own if s.cat == "client"} == {
        "build", "submit_dag", "wake", "status"}
    assert all(s.parent_id is None for s in own)
    assert {s.trace_id for s in spans if s not in own} == {root.trace_id}
    assert _chains_end_in(spans, root) == []
    names = {s.name.split(":")[0] for s in spans}
    # the worker-thread spans are the point: staging/readback threads,
    # merges on reduce-side threads, the AM's lane
    assert {"device.encode", "device.h2d", "device.dispatch", "device.d2h",
            "sort.collect", "sort.flush", "sort.final_merge",
            "merge.stage", "merge.launch", "merge.readback", "merge.gather",
            "spill.write", "spill.read", "kernel.merge_sort",
            "input.open", "input.read", "input.group",
            "processor.tokenize", "processor.sum", "processor.format",
            "output.write", "output.close", "output.commit",
            "am.task.queue", "am.task.done", "am.dag.commit",
            "am.dag.admit", "am.dag.init",
            "task.instantiate", "processor.initialize", "input.initialize",
            "output.initialize", "input.start", "task.events",
            "input.close", "processor.close", "finish",
            "am.vertex", "am.dag.finish"} <= names
    worker = {s.name for s in spans
              if s.thread.startswith("sorter-pipeline")}
    assert {"device.encode", "device.d2h"} <= worker
    # the AM's open spans stand on the DAG's lane, the commit on its thread
    lanes = {s.name.split(":")[0]: s.thread for s in spans if s.cat in
             ("am", "dag")}
    assert lanes["dag"] == lanes["am.task.queue"] \
        == f"am#{root.args['dag_id']}"
    # what brackets work without doing any is a point, not a span: a
    # reader that asks what the threads did must not answer with it
    points = [s for s in spans if s.name in ("am.vertex", "am.dag.finish")]
    assert {s.cat for s in points} == {"instant"}
    assert [s.args["state"] for s in points
            if s.args.get("vertex") == "tokenizer"] == ["STARTED",
                                                        "SUCCEEDED"]
    assert sum(s.name == "am.dag.finish" for s in points) == 1
    (commit,) = [s for s in spans if s.name == "am.dag.commit"]
    assert all(s.thread == commit.thread and s.parent_id == commit.span_id
               for s in spans if s.name == "output.commit")
    # the budget: nowhere near one span a record
    assert len(spans) < 4000


def test_owc_span_names_are_the_documented_vocabulary(traced_owc):
    import os
    from tests.trace_schema import undocumented_spans
    _status, _finished, spans, _dropped = traced_owc
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "observability.md")).read()
    assert undocumented_spans({s.name for s in spans}, doc) == set()
    assert undocumented_spans({"made.up"}, doc) == {"made.up"}


def test_owc_groups_every_block_by_the_fixed_width_compare(traced_owc):
    """OrderedWordCount's keys are one width (`w%07d` words, then 8-byte
    counts): every `input.group` span says the fixed compare took its
    block, with arguments its vocabulary row names."""
    import os
    from tests.trace_schema import undocumented_span_args
    _status, _finished, spans, _dropped = traced_owc
    groups = [s for s in spans if s.name == "input.group"]
    assert groups and {s.args["width"] for s in groups} == {8}
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "observability.md")).read()
    assert undocumented_span_args("input.group", groups[0].args, doc) \
        == set()


def test_dag_status_time_taken_is_the_events_local(traced_owc):
    status, finished, _spans, _dropped = traced_owc
    (event,) = finished
    assert status.time_taken == event.data["time_taken"]
    assert status.time_taken > 0


def test_mesh_exchange_spans_hang_under_the_dag_root(tmp_path):
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.examples.ordered_wordcount import build_dag
    conf = {"tez.staging-dir": str(tmp_path / "s"),
            "tez.runner.mode": "threads",
            "tez.runtime.sorter.class": "device",
            "tez.runtime.tpu.device.sort.min.records": 0,
            "tez.trace.enabled": True}
    client = TezClient.create("traced-mesh", conf, session=True).start()
    try:
        dag = build_dag(_owc_corpus(tmp_path, words_per_file=5000),
                        str(tmp_path / "out"), tokenizer_parallelism=4,
                        summation_parallelism=4, sorter_parallelism=1,
                        combine=False, tokenizer_mode="vector",
                        exchange="mesh")
        status = client.submit_dag(dag).wait_for_completion(timeout=300)
    finally:
        client.stop()
    assert status.state.name == "SUCCEEDED"
    spans = tracing.snapshot()
    (root,) = [s for s in spans if s.cat == "dag"]
    assert _chains_end_in(spans, root) == []
    names = collections.Counter(s.name for s in spans)
    assert {"exchange.wait_peers", "exchange.plan", "exchange.pack",
            "exchange.launch", "exchange.readback", "exchange.decode",
            "shuffle.wait"} <= set(names)
    assert names["exchange.wait_peers"] == 4       # one a producer
    readers = [s for s in spans if s.name == "exchange.readback"
               and s.thread.startswith("mesh-exchange-read-")]
    assert len(readers) >= 4 and \
        all(s.trace_id == root.trace_id for s in readers)
    waits = [s for s in spans if s.name == "exchange.wait_peers"]
    assert len({s.thread for s in waits}) == 4     # a lane each
    assert all(s.end >= s.start for s in waits)


def test_dag_status_time_taken_over_the_wire(tmp_path):
    """The remote client reads the number the AM's DAG_FINISHED event holds,
    through the DAGClientServer socket protocol."""
    from tez_tpu.am.app_master import DAGAppMaster
    from tez_tpu.am.client_server import DAGClientServer
    from tez_tpu.am.history import HistoryEventType
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.common import config as C
    from tez_tpu.common.ids import new_app_id
    from tez_tpu.common.payload import ProcessorDescriptor
    from tez_tpu.common.security import JobTokenSecretManager
    from tez_tpu.dag.dag import DAG, Vertex
    token = JobTokenSecretManager().secret.hex()
    am = DAGAppMaster(new_app_id(), C.TezConfiguration({
        "tez.staging-dir": str(tmp_path / "stg"),
        "tez.runner.mode": "threads", "tez.am.local.num-containers": 2,
        "tez.job.token": token}))
    am.start()
    server = DAGClientServer(am, am.secrets, host="127.0.0.1",
                             port=0).start()
    try:
        client = TezClient.create("remote", {
            "tez.framework.mode": "remote",
            "tez.am.address": f"127.0.0.1:{server.port}",
            "tez.job.token": token}).start()
        try:
            dag = DAG.create("wire").add_vertex(Vertex.create(
                "v", ProcessorDescriptor.create(
                    "tez_tpu.library.processors:SleepProcessor",
                    payload={"sleep_ms": 20}), 2))
            status = client.submit_dag(dag).wait_for_completion(timeout=60)
        finally:
            client.stop()
        (event,) = am.logging_service.of_type(HistoryEventType.DAG_FINISHED)
    finally:
        server.stop()
        am.stop()
    assert status.state.name == "SUCCEEDED"
    assert status.time_taken == event.data["time_taken"]
    assert status.time_taken >= 0.02
