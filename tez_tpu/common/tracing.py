"""Low-overhead distributed tracing plane: causal spans across AM/runtime/shuffle.

Reference parity: there is no tracing subsystem in Apache Tez itself — the
reference profiling surface is counters plus ATS history (SURVEY.md §5.1).
This module supplies the span substrate the history plane cannot: causal
links across threads and seams (DAG submit -> TaskSpec -> task body ->
umbilical -> shuffle fetch), with per-event timestamps fine enough to see a
single penalty-box hold or fence rejection.

Design rules (mirroring common/faults.py):

- Process-global plane, armed per-DAG via ``install_from_conf(conf, scope)``
  from the AM submit path and released in ``on_dag_finished``.  Arming is
  reference-counted by scope; the span buffer SURVIVES disarm so post-run
  exporters (chaos --trace-out, GET /trace) can read it.
- Single-boolean disarmed fast path: every entry point checks the module
  flag ``_armed`` first and returns a shared no-op singleton, so a
  production run that never arms tracing pays one attribute load per call
  and allocates nothing.
- Bounded in-memory ring buffer (``collections.deque(maxlen=...)``) —
  a runaway DAG evicts its oldest spans instead of eating the heap, and
  ``dropped()`` says how many it evicted: a reader that needs every span
  of a window (benchmarks/span_metrics.py) refuses a buffer that lost any.
- Cause crosses threads by hand: whoever gives work to another thread
  captures ``current_context()`` at the hand-off and the worker runs under
  ``attached(ctx)`` (or opens its spans with ``parent=ctx``).  A span opened
  on a thread with no context is a root with a fresh trace id, tied to no
  DAG — docs/observability.md "Starting a thread".
- Cause crosses a WAIT by a link: whoever ends a wait (completes a fetch
  table, a pipeline's last span, an exchange) leaves ``here()`` -- where
  its thread has got to, as a span id -- where the waiter already looks,
  and the waiter puts it on its span as ``after=<id>``; a span that could
  not begin before another ended carries the same.  The critical path
  (tools/trace_export.py) crosses threads on these and nowhere else.
- A stall witness thread lives while the plane is armed, and only then: it
  records ``host.stall`` whenever a 10 ms sleep ends more than 50 ms late.
- One clock: spans stamp ``time.time()`` (epoch seconds, the realtime clock
  the XLA profiler stamps with).  A span used as a context manager also
  enters a ``jax.profiler.TraceAnnotation("tez." + name)`` when jax is
  already imported, so the program's spans stand in the profiler's own
  file, on the profiler's clock, beside ``XLA Modules``.

Carrier format is W3C trace-context shaped (``00-<trace_id>-<span_id>-01``)
so the strings stamped into TaskSpec / heartbeats stay greppable and could
interop with a real OTLP exporter later.
"""
from __future__ import annotations

import functools
import itertools
import random
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

from tez_tpu.obs import flight as _flight

DEFAULT_BUFFER_SPANS = 32768

_armed = False          # single-boolean fast path (see common/faults.py)
_TLS = threading.local()


# --------------------------------------------------------------------------
# Trace context + carrier
# --------------------------------------------------------------------------

class TraceContext(NamedTuple):
    """Immutable causal coordinate: which trace, and which span is parent."""
    trace_id: str
    span_id: str

    def carrier(self) -> str:
        """W3C traceparent-style wire string for TaskSpec/heartbeat fields."""
        return f"00-{self.trace_id}-{self.span_id}-01"


def parse_carrier(s: Optional[str]) -> Optional[TraceContext]:
    """Parse a carrier string; malformed/empty carriers yield None (the
    receiver simply starts a fresh root trace — never an error)."""
    if not s:
        return None
    parts = s.split("-")
    if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    return TraceContext(parts[1], parts[2])


# ids have to be unique, not unguessable: the generator's state, no system
# call a span (os.urandom is one, and slow in a sandboxed kernel)
def _gen_trace_id() -> str:
    return "%032x" % random.getrandbits(128)


def _gen_span_id() -> str:
    return "%016x" % random.getrandbits(64)


def thread_key() -> str:
    """``Span.thread`` of a span opened on this thread now."""
    return f"{threading.current_thread().name}#{threading.get_ident()}"


# --------------------------------------------------------------------------
# Spans
# --------------------------------------------------------------------------

class Span:
    """One timed unit of work.  start/end are epoch seconds (time.time) so
    spans recorded on different threads/processes align on one axis.
    ``thread`` is ``<thread name>#<ident>``: names repeat (every sorter has
    a ``sortmaster_0``), and a reader that nests spans by thread needs a
    key that does not."""

    __slots__ = ("name", "cat", "trace_id", "span_id", "parent_id",
                 "start", "end", "args", "events", "thread", "_recorded",
                 "_annotation", "_seq")

    def __init__(self, name: str, cat: str, trace_id: str,
                 parent_id: Optional[str], args: Dict[str, Any]) -> None:
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.span_id = _gen_span_id()
        self.parent_id = parent_id
        self.start = time.time()
        self.end: Optional[float] = None
        self.args = args
        self.events: List[Tuple[float, str, Dict[str, Any]]] = []
        self.thread = thread_key()
        self._recorded = False
        self._annotation: Any = None
        self._seq = -1            # order of recording (TracePlane.record)

    # -- annotation -------------------------------------------------------
    def annotate(self, **kv: Any) -> "Span":
        self.args.update(kv)
        return self

    def event(self, name: str, **attrs: Any) -> None:
        """Timestamped point annotation inside this span (fault firings,
        fence rejections, penalty-box holds...)."""
        self.events.append((time.time(), name, attrs))

    # -- lifecycle --------------------------------------------------------
    @property
    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id)

    @property
    def duration(self) -> float:
        return (self.end if self.end is not None else time.time()) - self.start

    def finish(self, error: Optional[BaseException] = None) -> None:
        if self._recorded:
            return
        self._recorded = True
        self.end = time.time()
        if error is not None:
            self.args["error"] = f"{type(error).__name__}: {error}"
        _PLANE.record(self)
        if _flight.armed():
            _flight.span_edge(self.name, self.start, self.end - self.start,
                              cat=self.cat)

    # -- context-manager protocol (pushes onto the thread-local stack) ----
    def __enter__(self) -> "Span":
        _stack().append(self)
        # the twin in the profiler's own trace: only once jax is loaded
        # (the AM process must not import it), a no-op TraceMe while no
        # profiler session is active
        jax = sys.modules.get("jax")
        if jax is not None:
            self._annotation = jax.profiler.TraceAnnotation(
                "tez." + self.name)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        _TLS.last = self.span_id          # where this thread got to: here()
        self.finish(error=exc if isinstance(exc, BaseException) else None)
        return False

    def __repr__(self) -> str:  # debugging aid only
        return (f"Span({self.name!r}, trace={self.trace_id[:8]}, "
                f"span={self.span_id}, parent={self.parent_id}, "
                f"dur={self.duration * 1000:.2f}ms)")


class _NoopSpan:
    """Shared disarmed singleton: every method is a no-op and ``with``
    support returns the same object, so the disarmed path allocates zero
    objects per call."""

    __slots__ = ()

    name = ""
    trace_id = ""
    span_id = ""
    parent_id = None
    start = 0.0
    end = 0.0
    duration = 0.0
    context = None
    events: List[Any] = []
    args: Dict[str, Any] = {}

    def annotate(self, **kv: Any) -> "_NoopSpan":
        return self

    def event(self, name: str, **attrs: Any) -> None:
        pass

    def finish(self, error: Optional[BaseException] = None) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


def _stack() -> List[Span]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


#: ``parent=NEW_TRACE``: a root with a fresh trace id whatever span is open
#: on the calling thread (a DAG's root opens inside the client's submit)
NEW_TRACE: Any = object()


def _resolve_parent(parent: Any) -> Tuple[str, Optional[str]]:
    """Return (trace_id, parent_span_id) honoring: explicit parent >
    thread-local current span > thread-attached ambient context > new root."""
    if parent is NEW_TRACE:
        return _gen_trace_id(), None
    if parent is None:
        st = _stack()
        if st:
            ctx = st[-1].context
            return ctx.trace_id, ctx.span_id
        ambient = getattr(_TLS, "ambient", None)
        if ambient is not None:
            return ambient.trace_id, ambient.span_id
        return _gen_trace_id(), None
    if isinstance(parent, Span):
        return parent.trace_id, parent.span_id
    if isinstance(parent, TraceContext):
        return parent.trace_id, parent.span_id
    if isinstance(parent, str):
        ctx = parse_carrier(parent)
        if ctx is not None:
            return ctx.trace_id, ctx.span_id
        return _gen_trace_id(), None
    raise TypeError(f"unsupported span parent: {parent!r}")


# --------------------------------------------------------------------------
# Public span API
# --------------------------------------------------------------------------

def span(name: str, cat: str = "", parent: Any = None, **args: Any):
    """Start a span intended for ``with`` use on the current thread:
    it becomes the thread's current span until the block exits."""
    if not _armed:
        return NOOP_SPAN
    trace_id, parent_id = _resolve_parent(parent)
    return Span(name, cat, trace_id, parent_id, args)


def start_span(name: str, cat: str = "", parent: Any = None,
               lane: Optional[str] = None, start: Optional[float] = None,
               **args: Any):
    """Start a span WITHOUT touching the thread-local stack — for
    long-lived / cross-thread spans (e.g. the DAG root span the AM holds
    open until on_dag_finished).  Caller must invoke .finish().

    Such a span belongs to no thread, and a reader that nests spans by
    ``Span.thread`` would bill it against whatever its starting thread did
    meanwhile: ``lane`` names the row it stands on instead (the AM keeps the
    root, queue and task.done spans of one DAG on ``am#<dag_id>``, so they
    nest among themselves).  ``start`` (epoch seconds) opens it in the
    past: a wait that is known only once it is over."""
    if not _armed:
        return NOOP_SPAN
    trace_id, parent_id = _resolve_parent(parent)
    sp = Span(name, cat, trace_id, parent_id, args)
    if lane is not None:
        sp.thread = lane
    if start is not None:
        sp.start = start
    return sp


def event(name: str, parent: Any = None, **attrs: Any) -> None:
    """Record a point event.  Attached to the current span when one is
    active on this thread; otherwise recorded as a standalone zero-duration
    span (the common case for fence rejections and penalty-box holds that
    fire on dispatcher/fetcher threads)."""
    if not _armed:
        return
    st = _stack()
    if parent is None and st:
        st[-1].event(name, **attrs)
        return
    trace_id, parent_id = _resolve_parent(parent)
    sp = Span(name, "instant", trace_id, parent_id, dict(attrs))
    sp.finish()


def current_span() -> Optional[Span]:
    if not _armed:
        return None
    st = _stack()
    return st[-1] if st else None


def here() -> str:
    """Where this thread has got to, as a span id: the span open on it now,
    else the last one it finished, else ``""`` -- and ``""`` after one flag
    load while disarmed.  What a waker leaves where its waiter looks (a
    fetch table, a future, a queue item); the waiter puts it on its own
    span as ``after=<id>`` as it leaves the wait, and a reader can then
    step from the wait to the work that ended it
    (tools/trace_export.py ``critical_path``)."""
    if not _armed:
        return ""
    st = _stack()
    if st:
        return st[-1].span_id
    return getattr(_TLS, "last", "")


def current_context() -> Optional[TraceContext]:
    """The causal coordinate a child started *now* on this thread would
    inherit — current span, else the thread-attached ambient context."""
    st = _stack()
    if st:
        return st[-1].context
    return getattr(_TLS, "ambient", None)


def current_carrier() -> str:
    ctx = current_context()
    return ctx.carrier() if ctx is not None else ""


@contextmanager
def attached(parent: Any) -> Iterator[Optional[TraceContext]]:
    """Attach an ambient trace context to this thread for the duration of
    the block: spans started with no explicit parent and no active span
    will parent under it.  ``parent`` may be a carrier string, TraceContext,
    or Span; falsy/unparseable values attach nothing (no-op)."""
    ctx: Optional[TraceContext] = None
    if isinstance(parent, TraceContext):
        ctx = parent
    elif isinstance(parent, Span):
        ctx = parent.context
    elif isinstance(parent, str):
        ctx = parse_carrier(parent)
    prev = getattr(_TLS, "ambient", None)
    _TLS.ambient = ctx if ctx is not None else prev
    try:
        yield ctx
    finally:
        _TLS.ambient = prev


def came_after(span_id: str) -> None:
    """Put ``after=span_id`` on the span open on this thread: what a wait
    does, as it returns, with the ``here()`` its waker left it."""
    if _armed and span_id:
        st = _stack()
        if st:
            st[-1].args["after"] = span_id


def traced(name: str, cat: str = "") -> Any:
    """Decorator: the call is a span `name` of this thread (the examples'
    ``build_dag``: the client's ``build``)."""
    def wrap(fn: Any) -> Any:
        @functools.wraps(fn)
        def run(*args: Any, **kwargs: Any) -> Any:
            with span(name, cat=cat):
                return fn(*args, **kwargs)
        return run
    return wrap


def bound(fn: Any, ctx: Optional[TraceContext] = None) -> Any:
    """`fn`, to be run on another thread under `ctx` (this thread's context
    of now, unless one captured earlier is given): what a hand-off to an
    executor or a new thread passes in place of `fn`
    (docs/observability.md "Starting a thread").  `fn` itself where there
    is no context to carry — always, while nothing is traced."""
    if ctx is None:
        ctx = current_context()
    if ctx is None:
        return fn

    def run(*args: Any, **kwargs: Any) -> Any:
        with attached(ctx):
            return fn(*args, **kwargs)
    return run


# --------------------------------------------------------------------------
# The stall witness
# --------------------------------------------------------------------------

STALL_NAME = "host.stall"
STALL_PERIOD_S = 0.010
STALL_LATE_S = 0.050


class _StallWitness(threading.Thread):
    """Sleeps 10 ms at a time on the monotonic clock and records a
    ``host.stall`` span (a root of its own, on a lane of its own) whenever
    it wakes more than 50 ms late: the machine stood still, or one thread
    held the GIL that long -- either way no thread of this process could
    have run.  Started by a configuration's arming (``install_from_conf``:
    a traced DAG or session), stopped when the plane is disarmed."""

    def __init__(self) -> None:
        super().__init__(name="trace-stall-witness", daemon=True)
        self.stop = threading.Event()

    def run(self) -> None:
        while True:
            due = time.monotonic() + STALL_PERIOD_S
            if self.stop.wait(STALL_PERIOD_S):
                return
            late = time.monotonic() - due
            if late > STALL_LATE_S:
                now = time.time()
                sp = Span(STALL_NAME, "host", _gen_trace_id(), None,
                          {"late_ms": round(late * 1000.0, 1)})
                sp.thread = STALL_NAME
                sp.start = now - late
                sp.finish()


# --------------------------------------------------------------------------
# The plane (arming + ring buffer)
# --------------------------------------------------------------------------

class TracePlane:
    """Scope-refcounted arming + bounded span ring buffer."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._scopes: set = set()
        self._buf: Optional[deque] = None
        self._seq = itertools.count()    # next() is atomic: no lock to record
        self._witness: Optional[_StallWitness] = None   # armed, and only then

    def install(self, scope: str, capacity: int = DEFAULT_BUFFER_SPANS,
                witness: bool = False) -> None:
        """``witness``: have the stall witness run until the plane is
        disarmed (a configuration's arming asks for it; a test's or a
        tool's ``arm()`` does not, unless it says so)."""
        global _armed
        with self._lock:
            self._scopes.add(scope)
            if self._buf is None or (self._buf.maxlen or 0) != capacity:
                old = list(self._buf) if self._buf is not None else []
                self._buf = deque(old, maxlen=max(1, int(capacity)))
            _armed = True
            if witness and self._witness is None:
                self._witness = _StallWitness()
                self._witness.start()

    def clear(self, scope: str) -> None:
        """Release one scope.  The buffer is deliberately retained so
        post-run exporters can still read the spans."""
        global _armed
        with self._lock:
            self._scopes.discard(scope)
            if not self._scopes:
                _armed = False
                self._stop_witness_locked()

    def clear_all(self) -> None:
        global _armed
        with self._lock:
            self._scopes.clear()
            self._buf = None
            self._seq = itertools.count()
            _armed = False
            self._stop_witness_locked()

    def _stop_witness_locked(self) -> None:
        if self._witness is not None:
            self._witness.stop.set()
            self._witness = None

    def record(self, sp: Span) -> None:
        # lock-free: spans finish under other modules' locks, and deque
        # appends and count steps are atomic
        buf = self._buf
        if buf is not None:
            sp._seq = next(self._seq)
            buf.append(sp)

    def dropped(self) -> int:
        """Spans evicted from the ring since clear_all: those recorded
        (the highest sequence number held, plus one) less those held."""
        held = self.snapshot()
        if not held:
            return 0
        return max(sp._seq for sp in held) + 1 - len(held)

    def snapshot(self) -> List[Span]:
        buf = self._buf
        return list(buf) if buf is not None else []

    @property
    def scopes(self) -> set:
        with self._lock:
            return set(self._scopes)


_PLANE = TracePlane()


def plane() -> TracePlane:
    return _PLANE


def armed() -> bool:
    return _armed


def arm(scope: str = "manual", capacity: int = DEFAULT_BUFFER_SPANS,
        witness: bool = False) -> None:
    _PLANE.install(scope, capacity, witness)


def clear(scope: str) -> None:
    _PLANE.clear(scope)


def clear_all() -> None:
    _PLANE.clear_all()


def snapshot() -> List[Span]:
    return _PLANE.snapshot()


def dropped() -> int:
    """How many spans the ring evicted: 0 means snapshot() is complete."""
    return _PLANE.dropped()


def install_from_conf(conf: Any, scope: str) -> bool:
    """Arm the plane for one DAG when ``tez.trace.enabled`` is set.
    Mirrors faults.install_from_conf: called from app_master.submit_dag
    with scope=str(dag_id); the matching clear() happens in
    on_dag_finished."""
    from tez_tpu.common import config as C
    enabled = conf.get(C.TRACE_ENABLED)
    if not (enabled is True or str(enabled) == "True"):
        return False
    capacity = int(conf.get(C.TRACE_BUFFER_SPANS))
    _PLANE.install(scope, capacity, witness=True)
    return True
