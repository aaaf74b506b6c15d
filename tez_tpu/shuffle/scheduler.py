"""Fetch scheduling for the DCN shuffle path.

Reference parity: tez-runtime-library/.../shuffle/orderedgrouped/
ShuffleScheduler.java:91 — per-host queues (MapHost), a bounded fetcher
pool (:295), multi-output coalescing per connection (keep-alive batching),
a penalty DelayQueue with backoff Referee (:179-180), per-input retry
accounting, and speculative refetch of stalled connections.

TPU-first deltas: this scheduler only runs for inter-host (DCN) fetches —
same-host handoffs short-circuit through tez_tpu.shuffle.service and
intra-slice scatter-gather rides the ICI mesh exchange instead
(parallel/coordinator.py), so the pool is sized for cross-slice stragglers,
not the common path.
"""
from __future__ import annotations

import heapq
import logging
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from tez_tpu.common import metrics, tracing
from tez_tpu.shuffle.service import ShuffleDataNotFound
from tez_tpu.utils.backoff import ExponentialBackoff

log = logging.getLogger(__name__)

HostKey = Tuple[str, int]


@dataclass
class FetchRequest:
    """One (source output, partition) to pull from one host."""
    host: str
    port: int
    path: str
    spill: int
    partition: int
    #: opaque caller cookie handed back on delivery
    cookie: Any = None
    attempts: int = 0
    speculative: bool = False
    #: caller's trace context (tracing.TraceContext | None): fetch spans,
    #: penalty-box holds and retry events parent under the consuming task
    trace: Any = None
    #: span id the producer's event carried: the fetch span is ``after`` it
    after: str = ""
    #: measured wire RTT of the successful fetch, stamped before delivery
    rtt_ms: float = 0.0

    @property
    def key(self) -> Tuple[str, int, int]:
        return (self.path, self.spill, self.partition)


class _Host:
    """MapHost analog: pending queue + penalty/busy state.  ``active`` is a
    count, not a flag: a speculative refetch legitimately opens a second
    concurrent connection, and serialization must resume only when BOTH are
    done."""
    __slots__ = ("key", "pending", "active", "penalized", "failures")

    def __init__(self, key: HostKey) -> None:
        self.key = key
        self.pending: deque = deque()
        self.active = 0
        self.penalized = False
        self.failures = 0


class _Inflight:
    __slots__ = ("host_key", "requests", "started")

    def __init__(self, host_key: HostKey, requests: List[FetchRequest],
                 started: float):
        self.host_key = host_key
        self.requests = requests
        self.started = started


def TcpFetchSession(secrets: Any, host: str, port: int,
                    connect_timeout: float = 5.0, ssl_context: Any = None,
                    read_timeout: float = 30.0, epoch: int = 0,
                    app_id: str = ""):
    """Real transport session: ONE TCP connect + nonce handshake, many
    fetches (shuffle/server.py FetchSession — the server's handler loops
    per connection).  epoch/app_id stamp each request so the server can
    fence consumers from a superseded AM incarnation."""
    from tez_tpu.shuffle.server import FetchSession
    return FetchSession(secrets, host, port, connect_timeout,
                        ssl_context=ssl_context, read_timeout=read_timeout,
                        epoch=epoch, app_id=app_id)


class FetchScheduler:
    """Bounded fetcher pool over per-host queues with penalty-box backoff.

    ``deliver(request, batch, error)`` is invoked exactly once per enqueued
    request key — batch on success (or ``None`` for a speculative duplicate
    that lost the race... those are swallowed, not delivered), error after
    the retry budget or on a definitive miss.
    """

    def __init__(self, deliver: Callable[[FetchRequest, Any, Optional[Exception]], None],
                 session_factory: Callable[[str, int], Any],
                 num_fetchers: int = 8,
                 max_per_fetch: int = 20,
                 penalty_base: float = 0.25,
                 penalty_cap: float = 10.0,
                 max_attempts: int = 4,
                 stall_timeout: float = 15.0,
                 name: str = "shuffle",
                 penalty_rng: Optional[random.Random] = None,
                 session_ttl: float = 30.0,
                 clock: Callable[[], float] = time.time,
                 local_probe: Optional[Callable[[str, int, int], Any]] = None):
        self.deliver = deliver
        self.session_factory = session_factory
        # injectable clock drives every TTL/penalty/stall decision so tests
        # can step time deterministically (never touches perf_counter RTTs)
        self._clock = clock
        # store short-circuit: a probe that returns the batch when this
        # host already holds the data (same-host buffer store) — probed
        # requests never open a connection
        self.local_probe = local_probe
        self.num_fetchers = max(1, num_fetchers)
        self.max_per_fetch = max(1, max_per_fetch)
        self.penalty_base = penalty_base
        self.penalty_cap = penalty_cap
        # full jitter so fetchers penalized by the same bad host don't
        # reconnect in lockstep when the box opens; penalty_rng pins the
        # draw for deterministic tests
        self._penalty = ExponentialBackoff(penalty_base, penalty_cap,
                                           jitter=True, rng=penalty_rng)
        self.max_attempts = max_attempts
        self.stall_timeout = stall_timeout
        self.session_ttl = session_ttl

        self.lock = threading.Condition()
        # per-host keep-alive cache: a healthy session is checked back in
        # after its batch instead of closed, so the next batch to the same
        # host skips the TCP connect + nonce handshake.  Bounded: OPEN
        # sessions (cached + checked out) never exceed num_fetchers — the
        # cache yields (oldest idle first) before a new connect.  The
        # referee closes entries idle past session_ttl.
        self._session_cache: Dict[HostKey, Tuple[Any, float]] = {}
        self._open_sessions = 0
        self.hosts: Dict[HostKey, _Host] = {}
        self.ready: deque = deque()            # host keys with runnable work
        self.penalties: List[Tuple[float, HostKey]] = []   # heap
        self.inflight: Dict[int, _Inflight] = {}           # worker id -> batch
        self.done_keys: Set[Tuple[str, int, int]] = set()  # delivered once
        self.speculated: Set[Tuple[str, int, int]] = set()
        self._outstanding = 0      # enqueued keys not yet delivered (gauge)
        self._stopped = False
        self._workers = [
            threading.Thread(target=self._worker, args=(i,), daemon=True,
                             name=f"{name}-fetcher-{i}")
            for i in range(self.num_fetchers)]
        self._referee = threading.Thread(target=self._referee_loop,
                                         daemon=True, name=f"{name}-referee")
        for t in self._workers:
            t.start()
        self._referee.start()

    # ------------------------------------------------------------------ API
    def enqueue(self, req: FetchRequest) -> None:
        key = (req.host, req.port)
        with self.lock:
            if self._stopped or req.key in self.done_keys:
                return
            host = self.hosts.get(key)
            if host is None:
                host = self.hosts[key] = _Host(key)
            host.pending.append(req)
            if not req.speculative:
                self._outstanding += 1
                metrics.set_gauge("shuffle.queued_fetches",
                                  self._outstanding)
            self._make_ready(host)
            self.lock.notify()

    def stop(self) -> None:
        with self.lock:
            self._stopped = True
            for sess, _ in self._session_cache.values():
                self._close_session(sess)
            self._session_cache.clear()
            self.lock.notify_all()

    # ------------------------------------------------------------ internals
    def _close_session(self, session: Any) -> None:
        """Caller holds the lock (Condition wraps an RLock, so re-entry from
        checkout eviction is fine).  close() is a socket close — it never
        calls deliver, so the no-two-locks rule holds."""
        self._open_sessions -= 1
        try:
            session.close()
        except Exception:  # noqa: BLE001
            pass

    def _checkout_session(self, host: _Host) -> Any:
        """Reuse the host's cached session, or connect a new one.  The
        connect happens OUTSIDE the lock (it can block for seconds); the
        open-session slot is reserved first so the bound can't be raced.

        TTL is validated HERE, not only in the referee sweep: a session
        that idled past session_ttl may already be half-closed by the
        server, and the referee may simply not have woken yet — reusing
        it would fail the whole batch and penalize a healthy host.  The
        expired session is closed and a fresh connect replaces it (the
        slot count carries over 1:1)."""
        with self.lock:
            cached = self._session_cache.pop(host.key, None)
            if cached is not None:
                sess, last = cached
                if self.session_ttl > 0 and \
                        self._clock() - last >= self.session_ttl:
                    # stale: close (releases its slot) and fall through to
                    # the fresh-connect path, which re-reserves a slot
                    self._close_session(sess)
                else:
                    return sess       # already counted in _open_sessions
            while self._open_sessions >= self.num_fetchers and \
                    self._session_cache:
                oldest = min(self._session_cache,
                             key=lambda k: self._session_cache[k][1])
                sess, _ = self._session_cache.pop(oldest)
                self._close_session(sess)
            self._open_sessions += 1
        try:
            return self.session_factory(*host.key)
        except BaseException:
            with self.lock:
                self._open_sessions -= 1
            raise

    def _checkin_session(self, host: _Host, session: Any,
                         healthy: bool) -> None:
        with self.lock:
            if not healthy or self._stopped or self.session_ttl <= 0 or \
                    host.key in self._session_cache:
                # close-on-error (the connection is suspect), on shutdown,
                # or when a concurrent speculative batch already cached one
                self._close_session(session)
            else:
                self._session_cache[host.key] = (session, self._clock())
                self.lock.notify_all()    # referee recomputes TTL deadline

    def _make_ready(self, host: _Host) -> None:
        """Caller holds the lock."""
        if host.active == 0 and not host.penalized and host.pending and \
                host.key not in self.ready:
            self.ready.append(host.key)

    def _worker(self, worker_id: int) -> None:
        while True:
            with self.lock:
                while not self.ready and not self._stopped:
                    self.lock.wait(0.5)
                if self._stopped:
                    return
                host = self.hosts[self.ready.popleft()]
                batch_reqs: List[FetchRequest] = []
                while host.pending and len(batch_reqs) < self.max_per_fetch:
                    r = host.pending.popleft()
                    if r.key in self.done_keys:
                        continue
                    batch_reqs.append(r)
                if not batch_reqs:
                    self._make_ready(host)
                    continue
                host.active += 1
                self.inflight[worker_id] = _Inflight(host.key, batch_reqs,
                                                     self._clock())
                self.lock.notify_all()   # referee recomputes its deadline
            self._fetch_batch(worker_id, host, batch_reqs)

    def _fetch_batch(self, worker_id: int, host: _Host,
                     reqs: List[FetchRequest]) -> None:
        """ONE session fetches every request (coalescing); reused from the
        per-host cache across batches when the last one ended healthy."""
        if self.local_probe is not None:
            # store short-circuit: serve what this host already holds and
            # connect only for the remainder (zero-copy same-host path)
            remaining: List[FetchRequest] = []
            for req in reqs:
                try:
                    batch = self.local_probe(req.path, req.spill,
                                             req.partition)
                except BaseException:  # noqa: BLE001 — probe is best-effort
                    batch = None
                if batch is None:
                    remaining.append(req)
                    continue
                metrics.observe("shuffle.fetch.short_circuit", 0.0)
                tracing.event("shuffle.fetch.short_circuit",
                              parent=req.trace, src=req.path,
                              spill=req.spill, partition=req.partition)
                self._deliver_once(req, batch, None)
            reqs = remaining
            if not reqs:
                with self.lock:
                    self.inflight.pop(worker_id, None)
                    host.active -= 1
                    self._make_ready(host)
                    self.lock.notify()
                return
        session = None
        completed = 0
        failed_conn: Optional[Exception] = None
        try:
            session = self._checkout_session(host)
            for i, req in enumerate(reqs):
                sp = tracing.span(
                    "shuffle.fetch", cat="shuffle", parent=req.trace,
                    mode="remote", host=f"{req.host}:{req.port}",
                    src=req.path, spill=req.spill, partition=req.partition,
                    attempt=req.attempts, speculative=req.speculative,
                    after=req.after)
                t0 = time.perf_counter()
                try:
                    with sp:
                        batch = session.fetch(req.path, req.spill,
                                              req.partition)
                except (ShuffleDataNotFound, PermissionError) as e:
                    # definitive per-input miss: deliver, connection is fine
                    self._deliver_once(req, None, e)
                    completed = i + 1
                    continue
                except BaseException as e:  # noqa: BLE001 — conn-level fault
                    failed_conn = e
                    completed = i
                    break
                req.rtt_ms = (time.perf_counter() - t0) * 1000.0
                metrics.observe("shuffle.fetch.rtt", req.rtt_ms)
                self._deliver_once(req, batch, None)
                completed = i + 1
        except BaseException as e:  # noqa: BLE001 — session open failed
            failed_conn = e
        finally:
            if session is not None:
                self._checkin_session(host, session, failed_conn is None)
        failed_out: List[Tuple[FetchRequest, Exception]] = []
        with self.lock:
            self.inflight.pop(worker_id, None)
            host.active -= 1
            if failed_conn is not None:
                failed_out = self._host_failed(host, reqs[completed:],
                                               failed_conn)
            else:
                host.failures = 0
            self._make_ready(host)
            self.lock.notify()
        # outside the scheduler lock: delivery takes the caller's lock and
        # the caller's threads take ours via enqueue — never hold both
        for req, err in failed_out:
            self._deliver_once(req, None, err)

    def _deliver_once(self, req: FetchRequest, batch: Any,
                      error: Optional[Exception]) -> None:
        with self.lock:
            if req.key in self.done_keys:
                return      # speculative duplicate lost the race
            self.done_keys.add(req.key)
            self._outstanding = max(0, self._outstanding - 1)
            metrics.set_gauge("shuffle.queued_fetches", self._outstanding)
        try:
            self.deliver(req, batch, error)
        except BaseException:  # noqa: BLE001 — a callback fault must not
            log.exception("fetch delivery failed for %s", req.key)

    def _host_failed(self, host: _Host, rest: List[FetchRequest],
                     error: Exception
                     ) -> List[Tuple[FetchRequest, Exception]]:
        """Caller holds the lock.  Penalize the host with exponential
        backoff; requeue the unfetched requests; return the ones whose
        retry budget is exhausted (caller delivers them lock-free)."""
        host.failures += 1
        penalty = self._penalty.delay(host.failures - 1)
        failed_out: List[Tuple[FetchRequest, Exception]] = []
        for req in rest:
            req.attempts += 1
            if req.attempts >= self.max_attempts:
                failed_out.append((req, ConnectionError(
                    f"fetch {req.key} from {host.key[0]}:{host.key[1]} "
                    f"failed after {req.attempts} attempts: {error!r}")))
            else:
                # speculative dups requeue too: the original may be stalled
                # forever, so dropping the dup could mean NOTHING delivers
                # this key (done_keys still dedups if both complete)
                host.pending.appendleft(req)
        if host.pending:
            host.penalized = True
            heapq.heappush(self.penalties,
                           (self._clock() + penalty, host.key))
            tracing.event("shuffle.penalty_box",
                          parent=rest[0].trace if rest else None,
                          host=f"{host.key[0]}:{host.key[1]}",
                          penalty_s=round(penalty, 4),
                          failures=host.failures,
                          error=f"{type(error).__name__}: {error}")
            log.info("penalty box: %s:%s for %.2fs (%d failures)",
                     host.key[0], host.key[1], penalty, host.failures)
        return failed_out

    def _referee_loop(self) -> None:
        """Releases penalized hosts when their penalty expires and issues
        speculative duplicates for stalled in-flight fetches.  Sleeps until
        the earliest deadline (penalty expiry or stall) rather than polling."""
        with self.lock:
            while not self._stopped:
                now = self._clock()
                # keep-alive TTL sweep: cached sessions idle past
                # session_ttl are closed so quiesced hosts don't pin
                # sockets (and server-side handler threads) forever
                for key in [k for k, (_, last) in
                            self._session_cache.items()
                            if now - last >= self.session_ttl]:
                    sess, _ = self._session_cache.pop(key)
                    self._close_session(sess)
                while self.penalties and self.penalties[0][0] <= now:
                    _, key = heapq.heappop(self.penalties)
                    host = self.hosts.get(key)
                    if host is not None:
                        host.penalized = False
                        self._make_ready(host)
                        self.lock.notify()
                # speculative refetch: an in-flight batch older than the
                # stall timeout gets duplicate requests on a NEW connection
                # (the stuck one may be a dead socket, not a dead host);
                # first completed delivery wins via done_keys
                for infl in list(self.inflight.values()):
                    if now - infl.started < self.stall_timeout:
                        continue
                    host = self.hosts.get(infl.host_key)
                    if host is None:
                        continue
                    added = 0
                    for req in infl.requests:
                        if req.key in self.done_keys or \
                                req.key in self.speculated:
                            continue
                        self.speculated.add(req.key)
                        dup = FetchRequest(req.host, req.port, req.path,
                                           req.spill, req.partition,
                                           cookie=req.cookie,
                                           attempts=req.attempts,
                                           speculative=True,
                                           trace=req.trace)
                        tracing.event("shuffle.speculative_refetch",
                                      parent=req.trace, key=str(req.key),
                                      host=f"{req.host}:{req.port}")
                        host.pending.append(dup)
                        added += 1
                        log.info("speculative refetch of %s from %s:%s",
                                 req.key, req.host, req.port)
                    # the stalled connection still counts in host.active;
                    # allow ONE concurrent speculative connection — only
                    # when this pass actually issued new duplicates
                    if added and not host.penalized and \
                            host.key not in self.ready:
                        self.ready.append(host.key)
                        self.lock.notify()
                deadline = self.penalties[0][0] if self.penalties else None
                for infl in self.inflight.values():
                    if all(r.key in self.speculated or r.key in self.done_keys
                           for r in infl.requests):
                        continue   # fully handled: its stall deadline is
                        # moot — never a reason to wake (avoids a 100Hz spin
                        # while a slow-but-alive batch drains)
                    stall_at = infl.started + self.stall_timeout
                    if deadline is None or stall_at < deadline:
                        deadline = stall_at
                if self._session_cache:
                    ttl_at = min(last for _, last in
                                 self._session_cache.values()) + \
                        self.session_ttl
                    if deadline is None or ttl_at < deadline:
                        deadline = ttl_at
                wait = 5.0 if deadline is None else \
                    max(0.01, deadline - self._clock())
                self.lock.wait(wait)
