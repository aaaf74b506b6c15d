"""The critical path of a DAG period (tools/trace_export.py ``critical_path``):
the walk on a hand-made span set whose path is known to the millisecond, and
on a real traced two-DAG OrderedWordCount session."""
import os
import threading
import time

import pytest

from tez_tpu.common import tracing
from tez_tpu.common.tracing import Span
from tez_tpu.tools.trace_export import (CLASSES, critical_path,
                                        critical_path_report)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _span(name, cat, thread, start, end, after=None, **args):
    sp = Span(name, cat, "t" * 32, None, dict(args))
    sp.thread, sp.start, sp.end = thread, start, end
    if after is not None:
        sp.args["after"] = after.span_id
    return sp


def _two_stages(link_the_fetch=True, stall=None):
    """A period [0, 1]: the client submits, stage A (runner r#2) tokenizes
    and closes its output, a fetcher thread fetches it, stage B (r#3) waits
    for the fetch, reads a merge back and sums; the client wakes, reads the
    status and takes 50 ms of its own before the next submit."""
    main, lane = "MainThread#1", "am#dag_1"
    submit = _span("submit_dag", "client", main, 0.000, 0.050)
    q_a = _span("am.task.queue", "am", lane, 0.050, 0.060, after=submit)
    q_b = _span("am.task.queue", "am", lane, 0.055, 0.065, after=submit)
    a = _span("attempt:a", "task", "r#2", 0.060, 0.400, after=q_a, vertex="A")
    a_run = _span("run", "task", "r#2", 0.070, 0.390)
    tok = _span("processor.tokenize", "task", "r#2", 0.080, 0.300)
    close = _span("output.close", "task", "r#2", 0.300, 0.390)
    fetch = _span("shuffle.fetch", "shuffle", "f#4", 0.400, 0.420,
                  after=close if link_the_fetch else None)
    b = _span("attempt:b", "task", "r#3", 0.065, 0.900, after=q_b, vertex="B")
    b_run = _span("run", "task", "r#3", 0.070, 0.890)
    wait = _span("shuffle.wait", "shuffle", "r#3", 0.100, 0.420, after=fetch)
    readback = _span("merge.readback", "merge", "r#3", 0.500, 0.700)
    summed = _span("processor.sum", "task", "r#3", 0.700, 0.880)
    wake = _span("wake", "client", main, 0.900, 0.920, after=b)
    status = _span("status", "client", main, 0.920, 0.950)
    root = _span("dag:x", "dag", lane, 0.040, 0.900, dag_id="dag_1")
    spans = [submit, q_a, q_b, a, a_run, tok, close, fetch, b, b_run, wait,
             readback, summed, wake, status, root]
    if stall is not None:
        spans.append(_span("host.stall", "host", "host.stall", *stall))
    return spans, main


def test_hand_made_two_stage_path_is_known_to_the_millisecond():
    spans, main = _two_stages()
    path = critical_path(spans, [(0.0, 1.0)], thread=main)
    assert path["seconds"] == pytest.approx(1.0) and path["miss"] == 0
    assert set(path["by_class"]) == set(CLASSES)
    assert sum(path["by_class"].values()) == pytest.approx(1.0)
    want = {"control": 0.110, "device wait": 0.200, "host work": 0.520,
            "unnamed": 0.170, "stall": 0.0}
    for cls, seconds in want.items():
        assert path["by_class"][cls] == pytest.approx(seconds, abs=1e-6), cls
    names = {"client.submit_dag": 0.050, "am.task.queue": 0.010,
             "processor.tokenize": 0.220, "output.close": 0.090,
             "shuffle.fetch": 0.030, "merge.readback": 0.200,
             "processor.sum": 0.180, "client.wake": 0.020,
             "client.status": 0.030, "(no span)": 0.050,
             "task.run": 0.100, "task.attempt": 0.020}
    for name, seconds in names.items():
        assert path["by_name"][name] == pytest.approx(seconds, abs=1e-6), name
    # the wait crossed to the fetch that ended it and took no second itself;
    # the root span and the second queue span are off the path
    assert path["by_name"].get("shuffle.wait", 0.0) == pytest.approx(0.0)
    assert "dag.dag" not in path["by_name"]
    # the fetch began 10 ms after the close that made its event
    assert path["handoff_s"] == {"shuffle.fetch": pytest.approx(0.010)}
    assert path["steps"] == {"link": 5, "thread": 1, "guess": 0}
    chain = [c["name"] for c in path["chain"]]
    assert chain[0] == "client.submit_dag" and chain[-1] == "(no span)"
    assert chain.index("output.close") < chain.index("shuffle.fetch") \
        < chain.index("merge.readback") < chain.index("client.wake")


def test_a_missing_link_is_a_guess_and_is_counted():
    spans, main = _two_stages(link_the_fetch=False)
    path = critical_path(spans, [(0.0, 1.0)], thread=main)
    assert path["steps"] == {"link": 4, "thread": 1, "guess": 1}
    assert sum(path["by_class"].values()) == pytest.approx(1.0)
    # the guess went to what ended last before the fetch began: stage A's
    # attempt, so the 10 ms between are nobody's and A's tail is walked
    assert path["handoff_s"] == {}
    assert path["by_name"]["shuffle.fetch"] == pytest.approx(0.020)
    assert path["by_name"]["task.attempt"] == pytest.approx(0.030)


def test_a_stall_takes_its_seconds_from_the_span_under_it():
    spans, main = _two_stages(stall=(0.550, 0.650))
    path = critical_path(spans, [(0.0, 1.0)], thread=main)
    assert path["by_class"]["stall"] == pytest.approx(0.100)
    assert path["by_class"]["device wait"] == pytest.approx(0.100)
    assert path["by_name"]["host.stall"] == pytest.approx(0.100)
    assert path["by_name"]["merge.readback"] == pytest.approx(0.100)
    assert sum(path["by_class"].values()) == pytest.approx(1.0)
    assert path["stalls"] == [[0.550, pytest.approx(0.100)]]


def test_two_periods_add_up_and_a_period_without_spans_is_unnamed():
    spans, main = _two_stages()
    path = critical_path(spans, [(0.0, 1.0), (1.0, 1.5)], thread=main)
    assert path["periods"] == 2 and path["seconds"] == pytest.approx(1.5)
    assert sum(path["by_class"].values()) == pytest.approx(1.5)
    assert path["by_class"]["unnamed"] == pytest.approx(0.170 + 0.5)


def test_the_witness_lives_with_the_plane_and_records_a_late_wake(
        monkeypatch):
    def witnesses():
        return [t for t in threading.enumerate()
                if t.name == "trace-stall-witness"]
    assert not tracing.armed() and witnesses() == []
    assert tracing.here() == ""
    monkeypatch.setattr(tracing, "STALL_LATE_S", 0.0)   # every wake is late
    tracing.arm(scope="plain")
    assert witnesses() == []                # a test's arm() asks for none
    tracing.arm(scope="t", witness=True)    # as install_from_conf does
    tracing.arm(scope="u", witness=True)
    tracing.clear("plain")
    assert len(witnesses()) == 1
    deadline = time.time() + 5
    while time.time() < deadline and not any(
            s.name == "host.stall" for s in tracing.snapshot()):
        time.sleep(0.01)
    (stall, *_more) = [s for s in tracing.snapshot()
                       if s.name == "host.stall"]
    assert stall.cat == "host" and stall.thread == "host.stall"
    assert stall.parent_id is None and stall.end >= stall.start
    tracing.clear("t")
    assert len(witnesses()) == 1            # "u" still holds the plane
    tracing.clear("u")
    deadline = time.time() + 5
    while time.time() < deadline and witnesses():
        time.sleep(0.01)
    assert witnesses() == []


def test_here_is_the_open_span_then_the_last_one_finished():
    tracing.arm(scope="t")
    with tracing.span("outer") as outer:
        with tracing.span("inner") as inner:
            assert tracing.here() == inner.span_id
        assert tracing.here() == outer.span_id
    assert tracing.here() == outer.span_id
    seen = {}
    th = threading.Thread(target=lambda: seen.update(id=tracing.here()))
    th.start()
    th.join()
    assert seen["id"] == ""                 # another thread: its own place


# ------------------------------------------------ a real two-DAG session

@pytest.fixture(scope="module")
def traced_session(tmp_path_factory):
    """Two OrderedWordCount DAGs back to back in one traced session, device
    engine forced, as the benchmark's loop submits them: t_submit before
    the build, t_done after the final status."""
    from tests.test_tracing import _owc_corpus
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.examples.ordered_wordcount import build_dag
    tmp_path = tmp_path_factory.mktemp("path")
    tracing.clear_all()
    conf = {"tez.staging-dir": str(tmp_path / "s"),
            "tez.runner.mode": "threads",
            "tez.runtime.sorter.class": "device",
            "tez.runtime.tpu.device.sort.min.records": 0,
            "tez.runtime.io.sort.mb": 1,
            "tez.runtime.tpu.host.spill.dir": str(tmp_path / "spill"),
            "tez.trace.enabled": True, "tez.trace.buffer.spans": 262144}
    corpus = _owc_corpus(tmp_path)
    client = TezClient.create("traced-path", conf, session=True).start()
    dags = []
    try:
        for n in range(3):                  # the first one warms up
            t_submit = time.time()
            handle = client.submit_dag(build_dag(
                corpus, str(tmp_path / f"out{n}"), tokenizer_parallelism=4,
                summation_parallelism=4, sorter_parallelism=1, combine=False,
                tokenizer_mode="vector", exchange="host"))
            status = handle.wait_for_completion(timeout=300)
            dags.append({"t_submit": t_submit, "t_done": time.time(),
                         "dag_id": str(handle.dag_id), "status": status})
    finally:
        client.stop()
    spans = [s for s in tracing.snapshot() if s.end is not None]
    dropped = tracing.dropped()
    tracing.clear_all()
    return dags, spans, dropped


def test_session_spans_are_documented_and_none_is_an_orphan(traced_session):
    from tests.trace_schema import undocumented_spans
    dags, spans, dropped = traced_session
    assert dropped == 0
    assert all(d["status"].state.name == "SUCCEEDED" for d in dags)
    doc = open(os.path.join(ROOT, "docs", "observability.md")).read()
    assert undocumented_spans({s.name for s in spans}, doc) == set()
    by_id = {s.span_id: s for s in spans}
    roots = {s.trace_id for s in spans if s.cat == "dag"}
    assert len(roots) == 3
    for s in spans:
        if s.parent_id is None:
            # a root: a DAG's, the client's own, or the stall witness's
            assert s.cat in ("dag", "client", "host"), s
        else:
            assert s.parent_id in by_id, s
            assert s.trace_id in roots, s
    # every link a span carries resolves to a recorded span
    links = [s.args["after"] for s in spans if s.args.get("after")]
    assert len(links) > 100
    assert [a for a in links if a not in by_id] == []


def test_session_path_names_nearly_every_second(traced_session):
    dags, spans, _dropped = traced_session
    window = dags[1:]
    periods = [(window[0]["t_submit"], window[1]["t_submit"]),
               (window[1]["t_submit"], window[1]["t_done"])]
    thread = next(s.thread for s in spans if s.name == "submit_dag")
    path = critical_path(spans, periods, thread=thread)
    total = window[1]["t_done"] - window[0]["t_submit"]
    assert path["miss"] == 0
    assert path["seconds"] == pytest.approx(total)
    assert sum(path["by_class"].values()) == pytest.approx(total)
    assert path["by_class"]["unnamed"] < 0.10 * total, path["by_name"]
    steps = path["steps"]
    assert steps["guess"] <= 0.10 * sum(steps.values()), steps
    assert steps["link"] >= 10
    # the path runs through all three stages and the boundary
    assert {"client.submit_dag", "client.status", "am.dag.init",
            "am.task.queue", "am.dag.commit"} <= set(path["by_name"])


def test_analyzer_and_report_print_the_same_path(traced_session):
    from tez_tpu.tools.analyzers import SpanCriticalPathAnalyzer
    from tez_tpu.tools.history_parser import DagInfo
    from tez_tpu.tools.trace_export import dag_period
    dags, spans, _dropped = traced_session
    dag_id = dags[2]["dag_id"]
    lo, hi, thread = dag_period(spans, dag_id)
    assert thread is not None and lo < hi
    path = critical_path(spans, [(lo, hi)], thread=thread)
    report = critical_path_report(spans, dag_id)
    assert [c["span_id"] for c in report["chain"]] == \
        [c["span_id"] for c in path["chain"]]
    tracing.arm(scope="replay")
    try:
        for s in spans:
            tracing.plane().record(s)
        result = SpanCriticalPathAnalyzer().analyze(DagInfo(dag_id=dag_id))
    finally:
        tracing.clear_all()
    assert [r["span_id"] for r in result.rows] == \
        [c["span_id"] for c in path["chain"]]
    assert "critical path of" in result.headline


def test_event_wake_counts_only_events_after_the_attempt_started(
        traced_session):
    from tez_tpu.common import metrics
    dags, _spans, _dropped = traced_session
    counters = dags[2]["status"].counters.to_dict()
    hists = metrics.histograms_from_counters(
        {g: c for g, c in counters.items()
         if g.startswith(metrics.HIST_GROUP_PREFIX + "am.task.event")})
    wait, wake = hists["am.task.event_wait"], hists.get(
        "am.task.event_wake", {"count": 0})
    assert 0 <= wake["count"] <= wait["count"] and wait["count"] > 0
    doc = open(os.path.join(ROOT, "docs", "observability.md")).read()
    from tests.trace_schema import undocumented_metrics
    assert undocumented_metrics(
        ["am.task.event_wait", "am.task.event_wake"], doc) == set()


def test_untraced_session_starts_no_witness_and_records_nothing(tmp_path):
    from tests.test_tracing import _owc_corpus
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.examples.ordered_wordcount import build_dag
    tracing.clear_all()
    conf = {"tez.staging-dir": str(tmp_path / "s"),
            "tez.runner.mode": "threads"}
    started = []
    real_start = threading.Thread.start

    def start(self):
        started.append(self.name)
        return real_start(self)
    threading.Thread.start = start
    try:
        with TezClient.create("untraced", conf, session=True) as client:
            status = client.submit_dag(build_dag(
                _owc_corpus(tmp_path, words_per_file=2000, files=2),
                str(tmp_path / "out"), tokenizer_parallelism=2,
                summation_parallelism=2)).wait_for_completion(timeout=300)
    finally:
        threading.Thread.start = real_start
    assert status.state.name == "SUCCEEDED"
    assert "trace-stall-witness" not in started
    assert not tracing.armed() and tracing.snapshot() == []
    assert tracing.here() == ""
