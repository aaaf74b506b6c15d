"""Phase-9 tests: history parser, analyzers, swimlane over a real run."""
import os

import pytest

from tez_tpu.examples import ordered_wordcount
from tez_tpu.tools.analyzers import (ALL_ANALYZERS, analyze_dag,
                                     CriticalPathAnalyzer)
from tez_tpu.tools.history_parser import parse_jsonl_files
from tez_tpu.tools.swimlane import render_svg


@pytest.fixture(scope="module")
def history_dir(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("hist")
    corpus = tmp / "in.txt"
    corpus.write_text("alpha beta gamma alpha\n" * 200)
    hist = str(tmp / "history")
    state = ordered_wordcount.run(
        [str(corpus)], str(tmp / "out"),
        conf={"tez.staging-dir": str(tmp / "s"),
              "tez.history.logging.service.class":
                  "tez_tpu.am.history:JsonlHistoryLoggingService",
              "tez.history.logging.log-dir": hist},
        tokenizer_parallelism=2)
    assert state == "SUCCEEDED"
    return hist


def test_parse_history(history_dir):
    dags = parse_jsonl_files([history_dir])
    assert len(dags) == 1
    dag = list(dags.values())[0]
    assert dag.name == "OrderedWordCount"
    assert dag.state == "SUCCEEDED"
    assert {v.name for v in dag.vertices.values()} == \
        {"tokenizer", "summation", "sorter"}
    assert dag.duration > 0
    tok = dag.vertex("tokenizer")
    assert tok.num_tasks == 2 and len(tok.tasks) == 2
    for t in tok.tasks.values():
        att = t.successful_attempt
        assert att is not None and att.container_id
        assert att.counters  # per-attempt counters recorded


def test_analyzers_produce_results(history_dir):
    dags = parse_jsonl_files([history_dir])
    dag = list(dags.values())[0]
    results = analyze_dag(dag)
    assert len(results) == len(ALL_ANALYZERS)
    by_name = {r.analyzer: r for r in results}
    assert "tokenizer" in str(by_name["critical_path"].rows)
    shuffled = by_name["shuffle_time"].rows
    assert any(r["shuffle_bytes"] > 0 for r in shuffled)
    assert by_name["hung_tasks"].rows == []
    reuse = by_name["container_reuse"]
    assert sum(r.get("tasks_run", 0) for r in reuse.rows) >= 5
    # full reference plugin set
    overview = by_name["dag_overview"]
    assert {r["vertex"] for r in overview.rows} == \
        {"tokenizer", "summation", "sorter"}
    assert all(r["task_states"].get("SUCCEEDED") for r in overview.rows)
    assert by_name["input_read_errors"].rows == []
    loc = by_name["locality"].rows
    assert loc and all(r["local_fraction"] == 1.0 for r in loc)  # single host
    crit = by_name["vertex_critical_path"]
    assert [r["vertex"] for r in crit.rows] == \
        ["tokenizer", "summation", "sorter"]
    assert by_name["task_assignment"].rows
    assert by_name["attempt_result_stats"].rows
    assert by_name["slow_nodes"].rows
    assert by_name["one_on_one_edges"].rows == []  # no 1-1 edges in this DAG


from tez_tpu.library.processors import SimpleProcessor  # noqa: E402


class OneToOneEmitter(SimpleProcessor):
    """Module-level so descriptors can resolve tests.test_tools:OneToOneEmitter."""

    def run(self, inputs, outputs):
        outputs["b"].get_writer().write(b"k", b"v")


class OneToOneReader(SimpleProcessor):
    def run(self, inputs, outputs):
        list(inputs["a"].get_reader())


def test_one_on_one_edge_analyzer(tmp_path):
    """ONE_TO_ONE edge placement analysis over a real 1-1 DAG run."""
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.common.payload import (InputDescriptor, OutputDescriptor,
                                        ProcessorDescriptor)
    from tez_tpu.dag.dag import DAG, Edge, Vertex
    from tez_tpu.dag.edge_property import (DataMovementType, DataSourceType,
                                           EdgeProperty, SchedulingType)
    from tez_tpu.tools.analyzers import OneOnOneEdgeAnalyzer
    hist = str(tmp_path / "hist")
    c = TezClient.create("oo", {
        "tez.staging-dir": str(tmp_path / "s"),
        "tez.history.logging.service.class":
            "tez_tpu.am.history:JsonlHistoryLoggingService",
        "tez.history.logging.log-dir": hist}).start()
    try:
        kv = {"tez.runtime.key.class": "bytes",
              "tez.runtime.value.class": "bytes"}
        a = Vertex.create("a", ProcessorDescriptor.create(OneToOneEmitter), 2)
        b = Vertex.create("b", ProcessorDescriptor.create(OneToOneReader), 2)
        prop = EdgeProperty.create(
            DataMovementType.ONE_TO_ONE, DataSourceType.PERSISTED,
            SchedulingType.SEQUENTIAL,
            OutputDescriptor.create(
                "tez_tpu.library.unordered:UnorderedKVOutput", payload=kv),
            InputDescriptor.create(
                "tez_tpu.library.unordered:UnorderedKVInput", payload=kv))
        dag = DAG.create("oodag").add_vertex(a).add_vertex(b)
        dag.add_edge(Edge.create(a, b, prop))
        st = c.submit_dag(dag).wait_for_completion(timeout=30)
        assert st.state.name == "SUCCEEDED"
    finally:
        c.stop()
    dags = parse_jsonl_files([hist])
    dag_info = list(dags.values())[0]
    assert dag_info.edges and dag_info.edges[0]["movement"] == "ONE_TO_ONE"
    res = OneOnOneEdgeAnalyzer().analyze(dag_info)
    assert res.rows == [{"edge": "a->b", "pairs": 2, "colocated": 2}]


def test_swimlane_svg(history_dir):
    dags = parse_jsonl_files([history_dir])
    dag = list(dags.values())[0]
    svg = render_svg(dag)
    assert svg.startswith("<svg") and svg.endswith("</svg>")
    assert "tokenizer" in svg and "attempt_" in svg


def test_analyzer_cli(history_dir, capsys):
    import sys
    from tez_tpu.tools import analyzers
    old = sys.argv
    try:
        sys.argv = ["analyzers", history_dir]
        assert analyzers.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "critical_path" in out and "OrderedWordCount" in out


def test_native_gather_matches_numpy():
    """native/ragged.cpp gather == numpy fallback (skips if no toolchain)."""
    import numpy as np
    from tez_tpu.ops.native import gather_ragged_native, native_available
    if not native_available():
        import pytest
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(1)
    n = 5000
    lens = rng.integers(0, 30, n)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    data = rng.integers(0, 256, int(offsets[-1])).astype(np.uint8)
    perm = rng.permutation(n)
    out, oo = gather_ragged_native(data, offsets, perm)
    # golden via pure-numpy path
    from tez_tpu.ops.runformat import _ranges
    nl = lens[perm]
    golden_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(nl, out=golden_off[1:])
    idx = np.repeat(offsets[:-1][perm], nl) + _ranges(nl)
    assert np.array_equal(out, data[idx])
    assert np.array_equal(oo, golden_off)


def test_am_web_endpoint(tmp_path):
    """AM web UI serves live status (AMWebController analog)."""
    import json
    import urllib.request
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.common.payload import ProcessorDescriptor
    from tez_tpu.dag.dag import DAG, Vertex
    c = TezClient.create("web", {"tez.staging-dir": str(tmp_path / "s"),
                                 "tez.fake.access.token": "hunter2",
                                 "tez.am.web.enabled": True}).start()
    try:
        dag = DAG.create("webdag").add_vertex(Vertex.create(
            "v", ProcessorDescriptor.create(
                "tez_tpu.library.processors:SleepProcessor",
                payload={"sleep_ms": 1}), 2))
        c.submit_dag(dag).wait_for_completion(timeout=30)
        url = c.framework_client.am.web_ui.url
        status = json.loads(urllib.request.urlopen(url + "status").read())
        assert status["name"] == "webdag"
        assert status["state"] == "SUCCEEDED"
        assert status["vertices"]["v"]["succeeded"] == 2
        counters = json.loads(urllib.request.urlopen(
            url + "counters").read())
        assert "TaskCounter" in counters
        page = urllib.request.urlopen(url).read()
        assert b"<html" in page
        # SPA REST surface (tez-ui feature set)
        graph = json.loads(urllib.request.urlopen(url + "graph").read())
        assert [v["name"] for v in graph["vertices"]] == ["v"]
        assert graph["vertices"][0]["state"] == "SUCCEEDED"
        tasks = json.loads(urllib.request.urlopen(
            url + "tasks?vertex=v").read())
        assert len(tasks) == 2
        assert all(t["attempts"][0]["state"] == "SUCCEEDED" for t in tasks)
        dags = json.loads(urllib.request.urlopen(url + "dags").read())
        assert any(d["state"] == "SUCCEEDED" for d in dags)
        res = json.loads(urllib.request.urlopen(url + "analyzers").read())
        assert {"critical_path", "dag_overview"} <= \
            {r["analyzer"] for r in res}
        # attempt drill-down: counters + diagnostics + timing per attempt
        aid = tasks[0]["attempts"][0]["id"]
        att = json.loads(urllib.request.urlopen(
            url + "attempt?id=" + urllib.parse.quote(aid)).read())
        assert att["state"] == "SUCCEEDED" and att["vertex"] == "v"
        assert "TaskCounter" in att["counters"]
        assert json.loads(urllib.request.urlopen(
            url + "attempt?id=bogus").read())["error"]
        # per-vertex counter aggregation
        vc = json.loads(urllib.request.urlopen(
            url + "counters?vertex=v").read())
        assert "TaskCounter" in vc
        # effective conf with secrets redacted
        conf = json.loads(urllib.request.urlopen(url + "conf").read())
        assert conf.get("tez.am.web.enabled") in (True, "True")
        assert conf["tez.fake.access.token"] == "<redacted>"
        assert "hunter2" not in json.dumps(conf)
    finally:
        c.stop()


def test_host_sorter_engine_byte_exact():
    """'host' sorter engine (np.lexsort) output == device engine output."""
    import random
    from tez_tpu.ops.sorter import DeviceSorter
    rng = random.Random(11)
    pairs = [(bytes(rng.randrange(256) for _ in range(rng.randrange(1, 20))),
              bytes(rng.randrange(256) for _ in range(4)))
             for _ in range(800)]
    runs = []
    for engine in ("device", "host"):
        s = DeviceSorter(num_partitions=3, engine=engine)
        for k, v in pairs:
            s.write(k, v)
        runs.append(s.flush())
    assert list(runs[0].batch.iter_pairs()) == list(runs[1].batch.iter_pairs())
    import numpy as np
    np.testing.assert_array_equal(runs[0].row_index, runs[1].row_index)


def test_thread_dump_and_stats():
    from io import StringIO
    from tez_tpu.runtime.diagnostics import (RuntimeStatsUpdater,
                                             dump_thread_stacks)
    from tez_tpu.common.counters import TaskCounter, TezCounters
    text = dump_thread_stacks()
    assert "MainThread" in text
    c = TezCounters()
    u = RuntimeStatsUpdater(c)
    sum(i * i for i in range(100000))
    u.update()
    assert c.find_counter(TaskCounter.CPU_MILLISECONDS).value >= 0
    assert c.find_counter(TaskCounter.PHYSICAL_MEMORY_BYTES).value > 0


def test_counter_diff_cli(history_dir, capsys):
    import sys
    from tez_tpu.tools import counter_diff
    import glob as g
    from tez_tpu.am.history import scan_history_store
    f = scan_history_store(history_dir)[0]
    old = sys.argv
    try:
        sys.argv = ["counter_diff", f, f]
        assert counter_diff.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "wall delta" in out


def test_log_split(tmp_path):
    """tez-log-split analog: interleaved attempt logs carve into per-attempt
    files, continuation lines follow their record."""
    from tez_tpu.tools.log_split import split_log
    a1 = "attempt_1785290000_0001_1_00_000000_0"
    a2 = "attempt_1785290000_0001_1_00_000001_0"
    combined = [
        "2026-07-29 01:00:00 INFO am: dag submitted\n",
        f"2026-07-29 01:00:01 INFO [{a1}] task: starting\n",
        f"2026-07-29 01:00:01 ERROR [{a2}] task: boom\n",
        "Traceback (most recent call last):\n",
        "  File \"x.py\", line 1\n",
        f"2026-07-29 01:00:02 INFO [{a1}] task: done\n",
        "2026-07-29 01:00:03 INFO am: dag finished\n",
    ]
    out = str(tmp_path / "split")
    counts = split_log(combined, out)
    assert counts == {"main.log": 2, f"{a1}.log": 2, f"{a2}.log": 3}
    body = open(os.path.join(out, f"{a2}.log")).read()
    assert "Traceback" in body and "File" in body   # continuation followed


def test_client_session_expiry(tmp_path):
    """Standalone session AM shuts down when the client stops talking
    (reference: tez.am.client.heartbeat.timeout.secs)."""
    import time as _time
    from tests.test_standalone_am import spawn_am
    from tez_tpu.client.tez_client import TezClient
    proc, port, token = spawn_am(
        tmp_path, "--num-containers", "1",
        "--client-heartbeat-timeout-secs", "1.5")
    try:
        c = TezClient.create("exp", {
            "tez.framework.mode": "remote",
            "tez.am.address": f"127.0.0.1:{port}",
            "tez.job.token": token,
            "tez.client.am.heartbeat.interval.secs": 0.5}).start()
        _time.sleep(4)               # idle but alive: keepalive holds the
        assert proc.poll() is None   # session open past the 1.5s timeout
        c.stop()                     # client goes away without shutdown
        deadline = _time.time() + 15
        while proc.poll() is None and _time.time() < deadline:
            _time.sleep(0.2)
        assert proc.poll() is not None, "session AM outlived its client"
    finally:
        if proc.poll() is None:
            proc.terminate()
        proc.wait(timeout=10)


# -------------------------------------------- tools/trace_window_check.py
@pytest.fixture()
def window_check():
    """The trace tool as a module; it puts the checkout and benchmarks/ at
    the head of ``sys.path`` as it is imported, which is undone here."""
    import importlib.util
    import sys
    saved = list(sys.path)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_window_check.py")
    spec = importlib.util.spec_from_file_location("trace_window_check", path)
    module = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(module)
        yield module
    finally:
        sys.path[:] = saved


def test_trace_tool_puts_device_time_beside_launches(window_check):
    import collections
    programs = window_check.kernel_programs()
    # the six programs the benchmark's readers find by name, and the
    # donating flavors beside their plain twins
    assert {programs[k] for k in (
        "resident_hash_sort", "fused_resident_range_sort",
        "resident_merge_sort", "merge_sort", "join_match", "join_probe")} == {
        "_fused_resident_hash_sort_impl", "_fused_resident_range_sort_impl",
        "_fused_resident_merge_impl", "_merge_sort_impl", "_join_match_impl",
        "_join_probe_impl"}
    assert programs["resident_hash_sort_donated"] == \
        programs["resident_hash_sort"]
    table = window_check.program_table(
        {"_join_probe_impl": 0.8, "_slice_to_bucket_impl": 0.1},
        collections.Counter({"kernel.join_probe": 16,
                             "kernel.resident_hash_sort": 3,
                             "kernel.resident_hash_sort_donated": 5,
                             "kernel.no_such_kernel": 1}),
        programs, dags=2)
    assert table == {
        "_join_probe_impl": {"launches_a_dag": 8.0,
                             "device_ms_a_launch": 50.0},
        # launched, but not among the programs the trace gave a time for
        "_fused_resident_hash_sort_impl": {"launches_a_dag": 4.0,
                                           "device_ms_a_launch": None}}


def test_trace_tool_counts_grouped_rows_by_path(window_check):
    from tez_tpu.common.tracing import Span
    spans = [Span("input.group", "task", "t", None, args)
             for args in ({"rows": 300, "width": 8}, {"rows": 100, "width": 0},
                          {"rows": 40, "width": -1},
                          {"rows": 20})]        # a tree from before `width`
    spans.append(Span("input.read", "task", "t", None, {"rows": 999}))
    assert window_check.group_rows(spans, dags=2) == {"fixed": 200.0,
                                                      "ragged": 30.0}
