"""An event reaches a running task when the AM has it, not at the task's
next heartbeat.

Every case runs with ``tez.task.am.heartbeat.interval-ms`` = 1000, so a timer
cannot pass it: whatever arrives inside a fraction of a second came by the
first beat or by a wake.  Two harnesses: whole DAGs through ``TezClient``
(local mode, runner threads), and one ``TaskRunner`` against the real
``TaskCommunicatorManager`` with a stub context, where a case needs to hold
the AM's side still.
"""
import os
import threading
import time

import pytest

from tez_tpu.am.edge import EdgeImpl
from tez_tpu.am.task_comm import TaskCommunicatorManager
from tez_tpu.am.vertex_impl import VertexImpl
from tez_tpu.api.events import (CustomProcessorEvent, DataMovementEvent,
                                InputDataInformationEvent)
from tez_tpu.api.runtime import (KeyValueReader, LogicalIOProcessor,
                                 LogicalInput, LogicalOutput)
from tez_tpu.client.dag_client import DAGStatusState
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common import config as C
from tez_tpu.common import faults, metrics
from tez_tpu.common.counters import TaskCounter
from tez_tpu.common.ids import DAGId
from tez_tpu.common.payload import (InputDescriptor, OutputDescriptor,
                                    ProcessorDescriptor)
from tez_tpu.dag.dag import DAG, Edge, Vertex
from tez_tpu.dag.edge_property import (DataMovementType, DataSourceType,
                                       EdgeProperty, SchedulingType)
from tez_tpu.runtime.contexts import TaskKilledError
from tez_tpu.runtime.task_runner import TaskRunner
from tez_tpu.runtime.task_spec import InputSpec, TaskSpec

INTERVAL_MS = 1000
HERE = __name__

#: what the doubles below saw, by a name the payload gives (runner threads
#: share this process, so a module global is the shortest wire)
SEEN = {}
GATES = {}


def _seen(name):
    return SEEN.setdefault(name, {"events": [], "at": [],
                                  "arrived": threading.Condition()})


class RecordingInput(LogicalInput):
    """Keeps every event with the second it was handed over; its reader is
    ready once ``want`` (payload, default: one a physical input) came."""

    def initialize(self):
        payload = self.context.user_payload.load() or {}
        self._rec = _seen(payload.get("name", "in"))
        self._want = payload.get("want", self.num_physical_inputs)
        return []

    def handle_events(self, events):
        with self._rec["arrived"]:
            for ev in events:
                self._rec["events"].append(ev)
                self._rec["at"].append(time.time())
            self._rec["arrived"].notify_all()

    def get_reader(self):
        with self._rec["arrived"]:
            while len(self._rec["events"]) < self._want:
                self._rec["arrived"].wait(0.05)
                self.context.notify_progress()
        return _Empty()

    def close(self):
        return []


class _Empty(KeyValueReader):
    def __iter__(self):
        return iter(())


class EventOutput(LogicalOutput):
    """One DataMovementEvent at close, as a sorted output's."""

    def initialize(self):
        return []

    def get_writer(self):
        return None

    def handle_events(self, events):
        pass

    def close(self):
        return [DataMovementEvent(source_index=0,
                                  user_payload=self.context.task_index)]


class GateProcessor(LogicalIOProcessor):
    """Payload ``{"gate": {task_index: name}}``: that task waits for
    ``GATES[name]`` before it ends; every task drives its inputs first.
    Custom events land in ``SEEN[payload["name"]]``."""

    def initialize(self):
        self._payload = self.context.user_payload.load() or {}
        self._rec = _seen(self._payload.get("name", "proc"))

    def handle_events(self, events):
        with self._rec["arrived"]:
            self._rec["events"].extend(events)
            self._rec["at"].extend([time.time()] * len(events))
            self._rec["arrived"].notify_all()

    def run(self, inputs, outputs):
        for inp in inputs.values():
            inp.get_reader()
        gate = self._payload.get("gate", {}).get(self.context.task_index)
        if gate is not None:
            while not GATES[gate].wait(0.05):
                self.context.notify_progress()

    def close(self):
        pass


class ReadingProcessor(GateProcessor):
    """Asks its one input for a reader; a kill that reaches it there is
    kept in ``SEEN["killed"]``."""

    def run(self, inputs, outputs):
        try:
            inputs["src"].get_reader()
        except TaskKilledError as e:
            _seen("killed")["events"].append(e)
            raise


@pytest.fixture(autouse=True)
def _fresh_doubles():
    SEEN.clear()
    GATES.clear()
    yield
    for gate in GATES.values():
        gate.set()


def _wait_for(cond, timeout=5.0):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, "timed out"
        time.sleep(0.005)


def _hist(counters, name):
    return metrics.histograms_from_counters(counters).get(
        name, {"count": 0, "max_ms": 0.0})


def _woken(counters):
    return counters.get("TaskUmbilical", {}).get("am.heartbeat.woken", 0)


# ---------------------------------------------------------------- whole DAGs
@pytest.fixture()
def client(tmp_staging):
    c = TezClient.create("events", {
        "tez.staging-dir": tmp_staging,
        "tez.am.local.num-containers": 4,
        "tez.task.am.heartbeat.interval-ms": INTERVAL_MS}).start()
    yield c
    c.stop()


def _gate_vertex(name, parallelism, **payload):
    payload["name"] = name
    return Vertex.create(name, ProcessorDescriptor.create(
        f"{HERE}:GateProcessor", payload=payload), parallelism)


def _edge(a, b):
    return Edge.create(a, b, EdgeProperty.create(
        DataMovementType.SCATTER_GATHER, DataSourceType.PERSISTED,
        SchedulingType.SEQUENTIAL,
        OutputDescriptor.create(f"{HERE}:EventOutput"),
        InputDescriptor.create(f"{HERE}:RecordingInput",
                               payload={"name": b.name + ".in"})))


def test_running_consumer_has_a_late_producers_event_within_100ms(client):
    """The consumer is up and asleep in its 1 s reporter wait when the last
    producer ends: the event still reaches its input at once."""
    GATES["late"] = threading.Event()
    a = _gate_vertex("a", 2, gate={1: "late"})
    b = _gate_vertex("b", 1)
    # release the consumer with the first producer: it must be RUNNING
    # while the second is still held
    for v in (a, b):
        v.set_conf("tez.shuffle-vertex-manager.min-src-fraction", 0.0)
        v.set_conf("tez.shuffle-vertex-manager.max-src-fraction", 0.0)
    dag = DAG.create("late").add_vertex(a).add_vertex(b).add_edge(_edge(a, b))
    dag_client = client.submit_dag(dag)
    rec = _seen("b.in")
    _wait_for(lambda: len(rec["events"]) == 1)
    time.sleep(0.15)        # the consumer's first beat is long gone
    released = time.time()
    GATES["late"].set()
    _wait_for(lambda: len(rec["events"]) == 2)
    assert rec["at"][1] - released < 0.25
    status = dag_client.wait_for_completion(timeout=30)
    assert status.state is DAGStatusState.SUCCEEDED
    counters = status.counters.to_dict()
    waits = _hist(counters, "am.task.event_wait")
    assert waits["count"] == 2 and waits["max_ms"] <= 128
    assert _woken(counters) >= 1


def test_an_idle_attempt_beats_at_once_then_every_interval(client):
    """Liveness is what it was: nothing wakes a task with no events, and it
    still beats as it starts and one interval later."""
    v = Vertex.create("v", ProcessorDescriptor.create(
        "tez_tpu.library.processors:SleepProcessor",
        payload={"sleep_ms": 1200}), 1)
    status = client.submit_dag(DAG.create("idle").add_vertex(v))\
        .wait_for_completion(timeout=30)
    assert status.state is DAGStatusState.SUCCEEDED
    counters = status.counters.to_dict()
    wall_ms = status.counters.find_counter(
        TaskCounter.WALL_CLOCK_MILLISECONDS).value
    # one as it starts and one an interval the task lived through
    assert 2 <= _hist(counters, "am.heartbeat.rtt")["count"] \
        <= 2 + wall_ms // INTERVAL_MS
    assert _woken(counters) == 0


def _corpus(tmp_path, files=4, lines=300):
    import random
    rng = random.Random(33)
    paths = []
    for i in range(files):
        path = tmp_path / f"in{i}.txt"
        path.write_text("".join(
            " ".join(f"w{rng.randrange(200)}" for _ in range(8)) + "\n"
            for _ in range(lines)))
        paths.append(str(path))
    return paths


@pytest.fixture()
def owc(tmp_path):
    """A warm three-vertex OrderedWordCount (4 x 4 x 1) with the plane
    armed: (status, seconds, spans) of the second DAG of a session."""
    from tez_tpu.common import tracing
    from tez_tpu.examples.ordered_wordcount import build_dag
    client = TezClient.create("owc-events", {
        "tez.staging-dir": str(tmp_path / "s"), "tez.runner.mode": "threads",
        "tez.trace.enabled": True,
        "tez.task.am.heartbeat.interval-ms": INTERVAL_MS},
        session=True).start()
    try:
        paths = _corpus(tmp_path)
        for k in range(2):
            dag = build_dag(paths, str(tmp_path / f"out{k}"),
                            tokenizer_parallelism=4, summation_parallelism=4,
                            sorter_parallelism=1, combine=False,
                            tokenizer_mode="vector")
            t0 = time.time()
            status = client.submit_dag(dag).wait_for_completion(timeout=120)
            seconds = time.time() - t0
        root = [s for s in tracing.snapshot() if s.cat == "dag"][-1]
        spans = [s for s in tracing.snapshot()
                 if s.trace_id == root.trace_id]
    finally:
        client.stop()
    assert status.state is DAGStatusState.SUCCEEDED
    return status, seconds, spans


def test_three_vertex_owc_finishes_in_under_one_interval(owc):
    _status, seconds, _spans = owc
    assert seconds < INTERVAL_MS / 1000.0


def test_root_input_tasks_have_their_splits_within_100ms(owc):
    status, _seconds, spans = owc
    counters = status.counters.to_dict()
    waits = _hist(counters, "am.task.event_wait")
    # 4 split events, 4 x 4 tokenizer events, 4 summation events
    assert waits["count"] == 24 and waits["max_ms"] <= 128
    assert all(s.end - s.start < 0.1 for s in spans
               if s.name == "input.wait_splits")
    assert len([s for s in spans if s.name.startswith("attempt:")]) == 9


# ------------------------------------------------- one runner, the real comm
class _Ctx:
    """What TaskCommunicatorManager reads of the AM."""
    attempt = 0
    app_id = "app_0_events"
    node_id = "local"

    def __init__(self, **conf):
        self.conf = C.TezConfiguration(conf)
        self.dag = None
        self.dispatched = []

    def find_dag(self, dag_id):
        return self.dag

    def dispatch(self, event):
        self.dispatched.append(event)


class _RouteToZero:
    def route_data_movement_event_to_destination(self, src, idx, dest):
        class _M:
            target_indices = [0]
        return _M()


class _StubDag:
    def __init__(self, vertex):
        self.vertex = vertex

    def vertex_by_id(self, vertex_id):
        return self.vertex


def _stub_vertex():
    """A VertexImpl as far as the event pull reads it: one in-edge ``src``
    with the real log, and the root-input table."""
    class _Named:
        name = "src"
    edge = EdgeImpl("e0", None, _Named(), None)
    edge.edge_manager = _RouteToZero()
    vertex = VertexImpl.__new__(VertexImpl)
    vertex.in_edges = {"src": edge}
    vertex.root_input_events = {}
    return vertex, edge


VERTEX_ID = DAGId("app_0_events", 1).vertex(0)


def _spec(inputs=(), processor=None, **conf):
    conf.setdefault("tez.task.am.heartbeat.interval-ms", INTERVAL_MS)
    return TaskSpec(
        attempt_id=VERTEX_ID.task(0).attempt(0), dag_name="d",
        vertex_name="v", vertex_parallelism=1,
        processor_descriptor=processor or ProcessorDescriptor.create(
            f"{HERE}:GateProcessor", payload={"name": "proc"}),
        inputs=tuple(inputs), outputs=(), conf=conf)


def _recording_input(want):
    return InputSpec("src", InputDescriptor.create(
        f"{HERE}:RecordingInput", payload={"name": "in", "want": want}), want)


class _Running:
    """A TaskRunner on a thread against ``comm``; every response the comm
    gave it, with the second it left."""

    def __init__(self, comm, spec, umbilical=None):
        self.comm = comm
        self.responses = []
        heartbeat = comm.heartbeat

        def recorded(request):
            response = heartbeat(request)
            self.responses.append((time.time(), response))
            return response
        comm.heartbeat = recorded
        comm._session(spec.attempt_id)      # what get_task would have made
        self.runner = TaskRunner(spec, umbilical or comm)
        self.state = None
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.started = time.time()
        self.thread.start()

    def _run(self):
        self.state = self.runner.run()

    def join(self, timeout=5.0):
        self.thread.join(timeout)
        assert not self.thread.is_alive()
        return self.state


def test_backlog_bound_streams_seven_events_in_order_over_four_responses():
    ctx = _Ctx(**{"tez.task.max-event-backlog": 2})
    vertex, edge = _stub_vertex()
    ctx.dag = _StubDag(vertex)
    for i in range(7):
        edge.add_source_event(i, 0, DataMovementEvent(
            source_index=0, user_payload=i))
    run = _Running(TaskCommunicatorManager(ctx), _spec([_recording_input(7)]))
    assert run.join() == "SUCCEEDED"
    assert [ev.user_payload for ev in SEEN["in"]["events"]] == list(range(7))
    carried = [len(r.events) for _, r in run.responses if r.events]
    assert carried == [2, 2, 2, 1]
    assert [r.more for _, r in run.responses if r.events] == \
        [True, True, True, False]
    # a cut pull is followed at once, not an interval later
    assert SEEN["in"]["at"][-1] - run.started < 0.5


def test_kill_reaches_a_task_waiting_for_its_splits(tmp_path):
    """``kill_attempt`` wakes the reporter; the beat brings should_die;
    the turn of ``_wait_splits`` that follows raises."""
    ctx = _Ctx()
    comm = TaskCommunicatorManager(ctx)
    run = _Running(comm, _spec(
        [InputSpec("src", InputDescriptor.create(
            "tez_tpu.io.formats:MRInput", payload={"format": "text"}), 1,
            is_root_input=True)],
        processor=ProcessorDescriptor.create(f"{HERE}:ReadingProcessor")))
    _wait_for(lambda: run.responses)
    time.sleep(0.1)                     # it sits in _wait_splits by now
    assert run.thread.is_alive()
    killed = time.time()
    comm.kill_attempt(run.runner.spec.attempt_id)
    assert run.join() == "KILLED"
    assert time.time() - killed < 0.5
    assert len(SEEN["killed"]["events"]) == 1
    assert run.responses[-1][1].should_die


def test_custom_events_wake_the_processor():
    ctx = _Ctx()
    ctx.dag = _StubDag(_stub_vertex()[0])
    comm = TaskCommunicatorManager(ctx)
    GATES["hold"] = threading.Event()
    run = _Running(comm, _spec(processor=ProcessorDescriptor.create(
        f"{HERE}:GateProcessor", payload={"name": "proc",
                                          "gate": {0: "hold"}})))
    _wait_for(lambda: run.responses)
    time.sleep(0.1)
    sent = time.time()
    comm.deliver_custom_events(run.runner.spec.attempt_id,
                               [CustomProcessorEvent(user_payload=b"x")])
    _wait_for(lambda: SEEN["proc"]["events"])
    assert SEEN["proc"]["at"][0] - sent < 0.25
    GATES["hold"].set()
    assert run.join() == "SUCCEEDED"
    counters = run.runner.counters.to_dict()
    assert _woken(counters) == 1
    assert _hist(counters, "am.task.event_wait")["max_ms"] <= 128


def test_heartbeat_delay_fault_still_delays_delivery():
    """A wake is a heartbeat like any other: it goes through the
    ``am.heartbeat`` fault point, and a delay rule holds the event back."""
    ctx = _Ctx()
    vertex, edge = _stub_vertex()
    ctx.dag = _StubDag(vertex)
    comm = TaskCommunicatorManager(ctx)
    run = _Running(comm, _spec([_recording_input(1)]))
    _wait_for(lambda: run.responses)
    faults.install("delay-test", faults.parse_spec(
        "am.heartbeat:delay:ms=300,n=1"))
    try:
        added = time.time()
        edge.add_source_event(0, 0, DataMovementEvent(source_index=0,
                                                      user_payload=0))
        comm.wake_vertex(VERTEX_ID)
        assert run.join() == "SUCCEEDED"
    finally:
        faults.clear("delay-test")
    assert 0.3 <= SEEN["in"]["at"][0] - added < 0.9
    waits = _hist(run.runner.counters.to_dict(), "am.task.event_wait")
    assert waits["count"] == 1 and waits["max_ms"] >= 512


def test_wakes_during_a_beat_coalesce_into_one():
    """A 10,000-source fan-in ends in a burst: the wakes that arrive while
    a beat is in flight cost one more beat, not one each."""
    ctx = _Ctx()
    comm = TaskCommunicatorManager(ctx)
    GATES["hold"] = threading.Event()
    in_flight = threading.Event()
    proceed = threading.Event()
    heartbeat = comm.heartbeat

    def slow(request):
        if in_flight.is_set() and not proceed.is_set():
            proceed.wait(2)
        return heartbeat(request)
    comm.heartbeat = slow
    run = _Running(comm, _spec(processor=ProcessorDescriptor.create(
        f"{HERE}:GateProcessor", payload={"name": "proc",
                                          "gate": {0: "hold"}})))
    _wait_for(lambda: run.responses)
    in_flight.set()
    comm.wake_vertex(VERTEX_ID)         # this beat blocks in `slow`
    time.sleep(0.05)
    for _ in range(10_000):
        comm.wake_vertex(VERTEX_ID)
    proceed.set()
    time.sleep(0.2)
    GATES["hold"].set()
    assert run.join() == "SUCCEEDED"
    assert len(run.responses) == 3      # the first, the held one, one more
    assert _woken(run.runner.counters.to_dict()) == 2


def test_no_wake_is_lost_under_a_storm_of_producers():
    """More threads than cores add events and wake while the reporter
    beats, at a switch interval that cuts every check-then-act in two: each
    event is handed over once, in the log's order, and the last one does not
    wait for the timer."""
    import sys
    ctx = _Ctx()
    vertex, edge = _stub_vertex()
    ctx.dag = _StubDag(vertex)
    comm = TaskCommunicatorManager(ctx)
    producers, each = 16, 125
    run = _Running(comm, _spec([_recording_input(producers * each)]))
    order = []
    order_lock = threading.Lock()

    def produce(p):
        for i in range(each):
            with order_lock:        # the log's order, as the test knows it
                edge.add_source_event(p, 0, DataMovementEvent(
                    source_index=0, user_payload=(p, i)))
                order.append((p, i))
            comm.wake_vertex(VERTEX_ID)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=produce, args=(p,))
                   for p in range(producers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20)
        last_added = time.time()
        assert run.join(10) == "SUCCEEDED"
    finally:
        sys.setswitchinterval(interval)
    assert [ev.user_payload for ev in SEEN["in"]["events"]] == order
    assert SEEN["in"]["at"][-1] - last_added < 0.5
    assert len(run.responses) <= 1 + producers * each


def test_wake_vertex_reaches_only_that_vertexs_live_attempts():
    comm = TaskCommunicatorManager(_Ctx())
    woken = []
    mine = VERTEX_ID.task(0).attempt(0)
    other = DAGId("app_0_events", 1).vertex(1).task(0).attempt(0)
    for attempt in (mine, other):
        comm._session(attempt)
        comm.register_waker(attempt, lambda a=attempt: woken.append(a))
    gone = VERTEX_ID.task(1).attempt(0)
    comm.register_waker(gone, lambda: woken.append(gone))   # no session
    comm.wake_vertex(VERTEX_ID)
    assert woken == [mine]
    comm.kill_attempt(other)
    assert woken == [mine, other]
    comm._drop_session(mine)
    comm.wake_vertex(VERTEX_ID)
    assert woken == [mine, other]


def test_root_events_added_while_a_task_runs_are_pulled_once_each():
    vertex, _edge_unused = _stub_vertex()
    first = InputDataInformationEvent(source_index=0, user_payload="a",
                                      target_index=0)
    elsewhere = InputDataInformationEvent(source_index=1, user_payload="b",
                                          target_index=1)
    vertex.root_input_events["in"] = [first, elsewhere]
    seqs, stamps = {}, []
    assert vertex.get_task_events(0, seqs, stamps=stamps) == [("in", first)]
    assert stamps == [0.0]
    assert vertex.get_task_events(0, seqs) == []
    late = InputDataInformationEvent(source_index=2, user_payload="c",
                                     target_index=0)
    vertex.root_input_events["in"].append(late)
    assert vertex.get_task_events(0, seqs) == [("in", late)]
    assert vertex.get_task_events(0, seqs) == []
    assert not vertex.has_task_events(seqs)


def test_remote_umbilical_beats_at_once_and_then_by_interval():
    """One framed connection, shared with can_commit and task_done: no
    waker, no long poll.  The first beat still goes out as the reporter
    starts; an event the AM gets meanwhile waits for the interval."""
    from tez_tpu.am.umbilical_server import RemoteUmbilical, UmbilicalServer
    from tez_tpu.common.security import JobTokenSecretManager
    ctx = _Ctx()
    vertex, edge = _stub_vertex()
    ctx.dag = _StubDag(vertex)
    comm = TaskCommunicatorManager(ctx)
    secrets = JobTokenSecretManager()
    server = UmbilicalServer(comm, secrets).start()
    umbilical = RemoteUmbilical("127.0.0.1", server.port, secrets)
    try:
        assert not hasattr(umbilical, "register_waker")
        run = _Running(comm, _spec([_recording_input(1)]), umbilical)
        _wait_for(lambda: run.responses)
        assert run.responses[0][0] - run.started < 0.5
        added = time.time()
        edge.add_source_event(0, 0, DataMovementEvent(source_index=0,
                                                      user_payload=0))
        comm.wake_vertex(VERTEX_ID)     # nobody to wake
        assert run.join() == "SUCCEEDED"
        beats = [t for t, _ in run.responses]
        assert len(beats) == 2
        assert 0.9 <= beats[1] - beats[0] < 1.5
        assert SEEN["in"]["at"][0] - added > 0.5
        assert _woken(run.runner.counters.to_dict()) == 0
    finally:
        umbilical.close()
        server.stop()


# ------------------------------------------------------------------ the docs
def test_observability_doc_names_the_histogram_and_the_counter():
    from tests.trace_schema import undocumented_metrics
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "observability.md")).read()
    assert undocumented_metrics(
        {"am.task.event_wait", "am.heartbeat.woken", "am.heartbeat.rtt"},
        doc) == set()
    assert undocumented_metrics({"am.made.up"}, doc) == {"am.made.up"}
    assert "am.task.event_wait" in metrics.WELL_KNOWN_HISTOGRAMS
