"""State-machine unit tests driving DAG/Vertex/Task/Attempt directly with a
DrainDispatcher (the reference's TestDAGImpl/TestVertexImpl/TestTaskAttempt
style — no runners, injected events only)."""
import enum
import os
from typing import Any

import pytest

from tez_tpu.am.dag_impl import DAGImpl, DAGState
from tez_tpu.am.events import (DAGEvent, DAGEventType, SchedulerEventType,
                               TaskAttemptEvent, TaskAttemptEventType,
                               TaskEvent, TaskEventType, VertexEvent,
                               VertexEventType)
from tez_tpu.am.history import HistoryEvent, InMemoryHistoryLoggingService
from tez_tpu.am.task_impl import TaskAttemptState, TaskState
from tez_tpu.am.vertex_impl import VertexState
from tez_tpu.common import config as C
from tez_tpu.common.counters import TezCounters
from tez_tpu.common.dispatcher import DrainDispatcher
from tez_tpu.common.ids import DAGId
from tez_tpu.common.payload import ProcessorDescriptor
from tez_tpu.dag.dag import DAG, Edge, Vertex
from tez_tpu.dag.edge_property import (DataMovementType, DataSourceType,
                                       EdgeProperty, SchedulingType)
from tez_tpu.common.payload import InputDescriptor, OutputDescriptor


class FakeAM:
    """Minimal AMContext: everything flows through a DrainDispatcher; no
    runners exist, so attempts only move when the test injects events."""

    def __init__(self):
        self.dispatcher = DrainDispatcher()
        self.conf = C.TezConfiguration({"tez.am.task.max.failed.attempts": 3})
        self.dag_counters = TezCounters()
        self.logging_service = InMemoryHistoryLoggingService()
        self.current_dag = None
        self.finished = []
        self.launch_requests = []
        from tez_tpu.am.events import (DAGEventType, SchedulerEventType,
                                       TaskAttemptEventType, TaskEventType,
                                       VertexEventType)
        d = self.dispatcher
        d.register(DAGEventType, lambda e: self.current_dag.handle(e))
        d.register(VertexEventType, self._vertex)
        d.register(TaskEventType, self._task)
        d.register(TaskAttemptEventType, self._attempt)
        d.register(SchedulerEventType, self._scheduler)

    # handlers
    def _vertex(self, e):
        v = self.current_dag.vertex_by_id(e.vertex_id)
        if v:
            v.handle(e)

    def _task(self, e):
        v = self.current_dag.vertex_by_id(e.task_id.vertex_id)
        t = v.tasks.get(e.task_id.id) if v else None
        if t:
            t.handle(e)

    def _attempt(self, e):
        v = self.current_dag.vertex_by_id(e.attempt_id.vertex_id)
        t = v.tasks.get(e.attempt_id.task_id.id) if v else None
        a = t.attempt(e.attempt_id) if t else None
        if a:
            a.handle(e)

    def _scheduler(self, e):
        if e.event_type is SchedulerEventType.S_TA_LAUNCH_REQUEST:
            self.launch_requests.append(e.attempt_id)

    # AMContext surface
    def dispatch(self, e):
        self.dispatcher.dispatch(e)

    def history(self, e: HistoryEvent):
        self.logging_service.handle(e)

    def history_vertex_configured(self, v):
        pass

    def submit_to_executor(self, fn):
        fn()   # synchronous: commits/initializers run inline

    def total_slots(self):
        return 4

    def ensure_runners(self, backlog):
        pass

    def kill_attempt_in_runner(self, attempt_id):
        pass

    def wake_vertex_tasks(self, vertex_id):
        pass

    def deliver_processor_events(self, v, events, idx):
        pass

    def on_dag_finished(self, dag, final):
        self.finished.append(final)


def build_dag(am: FakeAM, vertices=(("a", 2), ("b", 2)), edges=(("a", "b"),)):
    dag = DAG.create("t")
    vs = {}
    for name, par in vertices:
        vs[name] = Vertex.create(name, ProcessorDescriptor.create(
            "tez_tpu.library.processors:SimpleProcessor"), par)
        dag.add_vertex(vs[name])
    for s, d in edges:
        dag.add_edge(Edge.create(vs[s], vs[d], EdgeProperty.create(
            DataMovementType.SCATTER_GATHER, DataSourceType.PERSISTED,
            SchedulingType.SEQUENTIAL, OutputDescriptor.create("x:O"),
            InputDescriptor.create("x:I"))))
    plan = dag.create_dag_plan()
    impl = DAGImpl(DAGId("app_0_t", 1), plan, am)
    am.current_dag = impl
    return impl


def start_dag(am, impl):
    am.dispatch(DAGEvent(DAGEventType.DAG_INIT, impl.dag_id))
    am.dispatch(DAGEvent(DAGEventType.DAG_START, impl.dag_id))
    am.dispatcher.drain()


def finish_attempt(am, attempt_id, state="done"):
    am.dispatch(TaskAttemptEvent(
        TaskAttemptEventType.TA_STARTED_REMOTELY, attempt_id,
        container_id="c0"))
    am.dispatcher.drain()
    t = {"done": TaskAttemptEventType.TA_DONE,
         "failed": TaskAttemptEventType.TA_FAILED}[state]
    am.dispatch(TaskAttemptEvent(t, attempt_id, diagnostics="injected"))
    am.dispatcher.drain()


def test_happy_path_to_succeeded():
    am = FakeAM()
    impl = build_dag(am)
    start_dag(am, impl)
    assert impl.state is DAGState.RUNNING
    a = impl.vertex_by_name("a")
    assert a.state is VertexState.RUNNING
    # ImmediateStart on source vertex 'a' launches both tasks
    assert len(am.launch_requests) == 2
    for att in list(am.launch_requests):
        finish_attempt(am, att)
    assert a.state is VertexState.SUCCEEDED
    # slow-start released consumer tasks once sources completed
    b = impl.vertex_by_name("b")
    assert b.state is VertexState.RUNNING
    b_attempts = am.launch_requests[2:]
    assert len(b_attempts) == 2
    for att in b_attempts:
        finish_attempt(am, att)
    assert impl.state is DAGState.SUCCEEDED
    assert am.finished == [DAGState.SUCCEEDED]


def test_can_commit_does_not_wait_for_the_dispatcher_to_see_the_start():
    """A runner asks from its own thread; an attempt that is done within
    milliseconds asks while TA_STARTED_REMOTELY is still queued.  A refusal
    there left a leaf output unpublished.  An attempt the AM has ended may
    not commit, and the first to ask keeps the right."""
    from tez_tpu.am.task_impl import TaskAttemptState
    am = FakeAM()
    impl = build_dag(am, vertices=(("a", 1),), edges=())
    start_dag(am, impl)
    first = am.launch_requests[0]
    task = impl.vertex_by_name("a").tasks[0]
    assert task.attempt(first).state is TaskAttemptState.SUBMITTED
    assert task.can_commit(first)
    finish_attempt(am, first, state="failed")
    assert not task.can_commit(first)
    second = am.launch_requests[-1]
    assert second != first and task.can_commit(second)


def test_task_retries_until_limit_then_fails_dag():
    am = FakeAM()
    impl = build_dag(am, vertices=(("a", 1),), edges=())
    start_dag(am, impl)
    att = am.launch_requests[0]
    task = impl.vertex_by_name("a").tasks[0]
    # max.failed.attempts = 3: two retries after the first failure
    for i in range(3):
        finish_attempt(am, am.launch_requests[-1], state="failed")
        if i < 2:
            assert task.state is TaskState.RUNNING
            assert len(am.launch_requests) == i + 2  # replacement spawned
    assert task.state is TaskState.FAILED
    assert impl.vertex_by_name("a").state is VertexState.FAILED
    assert impl.state is DAGState.FAILED


def test_output_loss_reruns_succeeded_task():
    am = FakeAM()
    impl = build_dag(am)
    start_dag(am, impl)
    for att in list(am.launch_requests):
        finish_attempt(am, att)
    a = impl.vertex_by_name("a")
    assert a.state is VertexState.SUCCEEDED
    # consumer reports the producer's output as lost (local fetch error)
    lost = a.tasks[0].successful_attempt
    am.dispatch(TaskAttemptEvent(
        TaskAttemptEventType.TA_OUTPUT_FAILED, lost,
        consumer_task_index=0, is_local_fetch=True, diagnostics="lost"))
    am.dispatcher.drain()
    assert a.tasks[0].state is TaskState.RUNNING     # re-running
    assert a.state is VertexState.RUNNING            # vertex pulled back
    # the rerun completes; vertex succeeds again
    finish_attempt(am, am.launch_requests[-1])
    assert a.state is VertexState.SUCCEEDED


def test_kill_running_dag():
    am = FakeAM()
    impl = build_dag(am, vertices=(("a", 2),), edges=())
    start_dag(am, impl)
    am.dispatch(DAGEvent(DAGEventType.DAG_KILL, impl.dag_id,
                         diagnostics="test kill"))
    am.dispatcher.drain()
    # attempts were told to die; inject their kill confirmations
    a = impl.vertex_by_name("a")
    for t in a.tasks.values():
        for att in t.live_attempts():
            am.dispatch(TaskAttemptEvent(
                TaskAttemptEventType.TA_KILL_REQUEST, att.attempt_id,
                diagnostics="killed"))
    am.dispatcher.drain()
    assert impl.state is DAGState.KILLED
    assert am.finished == [DAGState.KILLED]


def test_vertex_manager_error_fails_dag():
    am = FakeAM()
    impl = build_dag(am, vertices=(("a", 1),), edges=())
    start_dag(am, impl)
    am.dispatch(VertexEvent(
        VertexEventType.V_MANAGER_USER_CODE_ERROR,
        impl.vertex_by_name("a").vertex_id, diagnostics="boom"))
    am.dispatcher.drain()
    # terminate in-flight attempts
    for t in impl.vertex_by_name("a").tasks.values():
        for att in t.live_attempts():
            am.dispatch(TaskAttemptEvent(
                TaskAttemptEventType.TA_KILL_REQUEST, att.attempt_id))
    am.dispatcher.drain()
    assert impl.vertex_by_name("a").state is VertexState.FAILED
    assert impl.state is DAGState.FAILED
    assert any("boom" in d for d in impl.vertex_by_name("a").diagnostics)


def test_speculative_attempt_loser_killed():
    am = FakeAM()
    impl = build_dag(am, vertices=(("a", 1),), edges=())
    start_dag(am, impl)
    task = impl.vertex_by_name("a").tasks[0]
    first = am.launch_requests[0]
    am.dispatch(TaskAttemptEvent(TaskAttemptEventType.TA_STARTED_REMOTELY,
                                 first, container_id="c0"))
    am.dispatcher.drain()
    am.dispatch(TaskEvent(TaskEventType.T_ADD_SPEC_ATTEMPT, task.task_id))
    am.dispatcher.drain()
    assert len(am.launch_requests) == 2
    second = am.launch_requests[1]
    am.dispatch(TaskAttemptEvent(TaskAttemptEventType.TA_STARTED_REMOTELY,
                                 second, container_id="c1"))
    am.dispatcher.drain()
    # the speculative attempt wins
    am.dispatch(TaskAttemptEvent(TaskAttemptEventType.TA_DONE, second))
    am.dispatcher.drain()
    assert task.state is TaskState.SUCCEEDED
    assert task.successful_attempt == second
    loser = task.attempt(first)
    assert loser.state is TaskAttemptState.KILLED


def test_container_blacklisted_after_repeated_failures():
    """A container accumulating failures stops receiving work (AMNode
    blacklisting analog)."""
    from tez_tpu.am.task_scheduler import LocalTaskSchedulerService

    class Ctx:
        def ensure_runners(self, backlog):
            pass

    sched = LocalTaskSchedulerService(Ctx(), 2)
    from tez_tpu.common.ids import DAGId
    vid = DAGId("app_0_bl", 1).vertex(0)
    cid = "container-x"
    for i in range(3):
        att = vid.task(i).attempt(0)
        sched.schedule(att, object(), priority=1)
        got = sched.get_task(cid, timeout=0.1)
        assert got is not None
        sched.deallocate(att, failed=True)
    assert sched.is_blacklisted(cid)
    # further pulls from the bad container are refused...
    att = vid.task(9).attempt(0)
    sched.schedule(att, object(), priority=1)
    assert sched.get_task(cid, timeout=0.1) is None
    # ...but a healthy container still gets the work
    assert sched.get_task("container-y", timeout=0.1) is not None


class CountingCommitter:
    """A leaf output's committer that counts its commits (class-wide: the
    AM instantiates it from the plan)."""

    commits = 0

    def __init__(self, context: Any) -> None:
        self.context = context

    def initialize(self) -> None:
        pass

    def setup_output(self) -> None:
        pass

    def commit_output(self) -> None:
        CountingCommitter.commits += 1

    def abort_output(self, final_state: str) -> None:
        pass


def test_vertices_that_finish_together_commit_the_dag_once():
    """Two last vertices that complete before the DAG has seen the first
    completion's event: the counters run ahead of the events, so the second
    event finds every vertex counted too, and the DAG was committing
    already.  It commits once (two commits of one output race each other:
    one lists the staged files as the other removes them)."""
    from tez_tpu.common.payload import OutputCommitterDescriptor
    from tez_tpu.dag.dag import DataSinkDescriptor
    am = FakeAM()
    dag = DAG.create("t")
    for name in ("a", "b"):
        v = Vertex.create(name, ProcessorDescriptor.create(
            "tez_tpu.library.processors:SimpleProcessor"), 1)
        dag.add_vertex(v)
    v.add_data_sink("out", DataSinkDescriptor.create(
        OutputDescriptor.create("x:O"), OutputCommitterDescriptor.create(
            f"{__name__}:CountingCommitter")))
    impl = DAGImpl(DAGId("app_0_t", 1), dag.create_dag_plan(), am)
    am.current_dag = impl
    CountingCommitter.commits = 0
    start_dag(am, impl)
    attempts = list(am.launch_requests)
    assert len(attempts) == 2
    for att in attempts:
        am.dispatch(TaskAttemptEvent(
            TaskAttemptEventType.TA_STARTED_REMOTELY, att, container_id="c0"))
    am.dispatcher.drain()
    for att in attempts:                # both done before either is seen
        am.dispatch(TaskAttemptEvent(TaskAttemptEventType.TA_DONE, att))
    am.dispatcher.drain()
    assert impl.state is DAGState.SUCCEEDED
    assert CountingCommitter.commits == 1
    assert am.finished == [DAGState.SUCCEEDED]
