"""Async double-buffered device plane (ops/async_stage.py, DeviceSorter
pipeline integration).

The scheduler's contract is asserted against a FAKE clock and thread
events, never wall time: overlap (span k+1's encode starts before span k
completes), the dispatch-ahead depth bound, deterministic coalescing, and
out-of-order completion under the device.dispatch.delay fault point.
"""
import threading

import numpy as np
import pytest

from tez_tpu.common import faults
from tez_tpu.common.faults import parse_spec
from tez_tpu.ops.async_stage import AsyncSpanPipeline, overlap_pairs


class LogicalClock:
    """Thread-safe monotone counter: every _mark gets a unique tick, so
    event ordering is exact and wall-time free."""

    def __init__(self):
        self._t = 0
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            self._t += 1
            return self._t


def test_overlap_witness_fake_clock():
    """span 1's encode must start while span 0 is still in flight: span 0's
    readback is held on an event that only span 1's encode sets."""
    span1_encoding = threading.Event()

    def encode(p):
        if p == 1:
            span1_encoding.set()
        return p

    def readback(inflight, ids):
        if ids == (0,):
            assert span1_encoding.wait(timeout=10.0), \
                "span 1 never started encoding while span 0 was in flight"
        return inflight

    pipe = AsyncSpanPipeline(
        dispatch_fn=lambda s: s, readback_fn=readback, encode_fn=encode,
        depth=2, readback_workers=2, clock=LogicalClock(), instrument=True)
    for i in range(3):
        pipe.submit(i, i)
    res = pipe.drain()
    assert res == {0: 0, 1: 1, 2: 2}
    pairs = overlap_pairs(pipe.events)
    assert ((0,), (1,)) in pairs, f"no overlap witnessed: {pipe.events}"
    assert pipe.stats.max_in_flight <= 2


def test_depth_bound_never_exceeded():
    """depth=1 serializes groups: in-flight never exceeds the bound and no
    encode starts while an earlier group is in flight."""
    release = threading.Event()
    seen = []

    def readback(inflight, ids):
        seen.append(ids)
        if len(seen) == 1:
            release.wait(timeout=10.0)
        return inflight

    pipe = AsyncSpanPipeline(
        dispatch_fn=lambda s: s, readback_fn=readback,
        depth=1, readback_workers=2, clock=LogicalClock(), instrument=True)
    for i in range(4):
        pipe.submit(i, i)
    release.set()
    pipe.drain()
    assert pipe.stats.max_in_flight == 1
    assert overlap_pairs(pipe.events) == []   # depth=1: no overlap possible


def test_paused_coalesce_deterministic():
    dispatched = []

    def dispatch(staged):
        dispatched.append(staged)
        return staged

    pipe = AsyncSpanPipeline(
        dispatch_fn=dispatch, readback_fn=lambda s, ids: sum(s),
        coalesce_fn=lambda staged: [x for s in staged for x in s],
        records_fn=len, coalesce_records=100, paused=True)
    for i in range(4):
        pipe.submit(i, [i] * 10, coalesce=True)
    pipe.resume()
    res = pipe.drain()
    assert len(dispatched) == 1          # every span in ONE dispatch
    assert pipe.stats.coalesced_groups == 1
    assert res == {i: sum([0] * 10 + [1] * 10 + [2] * 10 + [3] * 10)
                   for i in range(4)}


def test_coalesce_budget_respected():
    pipe = AsyncSpanPipeline(
        dispatch_fn=lambda s: s, readback_fn=lambda s, ids: len(ids),
        coalesce_fn=lambda staged: staged, records_fn=len,
        coalesce_records=20, paused=True)
    for i in range(4):
        pipe.submit(i, [i] * 10, coalesce=True)
    pipe.resume()
    pipe.drain()
    assert pipe.stats.dispatched == 2    # 4 x 10 records under a 20 budget
    assert pipe.stats.coalesced_groups == 2


def test_stage_error_propagates_and_poisons():
    def dispatch(staged):
        raise ValueError("boom at dispatch")

    pipe = AsyncSpanPipeline(dispatch_fn=dispatch,
                             readback_fn=lambda s, ids: s)
    pipe.submit(0, 0)
    with pytest.raises(ValueError, match="boom at dispatch"):
        pipe.drain()
    with pytest.raises(RuntimeError, match="pipeline failed"):
        pipe.submit(1, 1)


# -- DeviceSorter on the plane (needs jax; tier-1 runs with JAX_PLATFORMS=cpu)

def test_resident_span_sort_compiles_once_per_bucket():
    """Spans of different sizes inside one power-of-two bucket launch ONE
    compiled span sort; the next bucket compiles once more.  The kernels'
    caches are process-wide, so the partition count is one no other test
    sorts by."""
    from tez_tpu.ops import device
    from tez_tpu.ops.runformat import KVBatch
    from tez_tpu.ops.sorter import DeviceSorter

    def compiles_for(n):
        rng = np.random.default_rng(n)
        kb = rng.integers(0, 256, n * 8, dtype=np.int64).astype(np.uint8)
        off = np.arange(n + 1, dtype=np.int64) * 8
        sorter = DeviceSorter(num_partitions=11, engine="device",
                              device_min_records=0)
        sorter.write_batch(KVBatch(kb, off, kb.copy(), off))
        started = []
        with device.compile_listener(
                lambda begins: started.append(1) if begins else None):
            run = sorter.flush_run()
        assert run.batch.num_records == n
        return len(started)

    assert compiles_for(600) == 1                 # first span of (512, 1024]
    assert [compiles_for(n) for n in (520, 700, 1000, 1024)] == [0, 0, 0, 0]
    assert compiles_for(1025) == 1                # the next bucket
    assert compiles_for(2048) == 0


def _mk_batch(n, seed):
    from tez_tpu.ops.runformat import KVBatch
    rng = np.random.default_rng(seed)
    keys = [b"k%08d" % i for i in rng.integers(0, 500, n)]
    vals = [b"v%06d" % i for i in rng.integers(0, 999999, n)]
    kb = np.frombuffer(b"".join(keys), dtype=np.uint8)
    ko = np.cumsum([0] + [len(k) for k in keys]).astype(np.int64)
    vb = np.frombuffer(b"".join(vals), dtype=np.uint8)
    vo = np.cumsum([0] + [len(v) for v in vals]).astype(np.int64)
    return KVBatch(kb, ko, vb, vo)


def _spill_sorter(depth):
    from tez_tpu.ops.sorter import DeviceSorter
    spills = {}
    s = DeviceSorter(num_partitions=4, engine="device",
                     device_min_records=0, key_width=16,
                     span_budget_bytes=20_000, pipeline_depth=depth)
    s.on_spill = lambda run, sid: spills.update(
        {sid: (run.batch.key_bytes.tobytes(), run.batch.val_bytes.tobytes(),
               run.row_index.tobytes())})
    return s, spills


def test_out_of_order_completion_spills_bit_exact():
    """device.dispatch.delay holds span 0's completion while later spans
    drain past it: completion is out of order, yet every spill carries its
    correct spill id and payload — bit-exact vs the fault-free sync engine."""
    sync, sync_spills = _spill_sorter(depth=0)
    for i in range(4):
        sync.write_batch(_mk_batch(1000, i))
    assert sync.flush_run() is None
    assert sorted(sync_spills) == [0, 1, 2, 3]

    faults.install("t", parse_spec(
        "device.dispatch.delay:delay:ms=400,n=1,match=span=0"))
    try:
        apipe, aspills = _spill_sorter(depth=2)
        for i in range(4):
            apipe.write_batch(_mk_batch(1000, i))
        assert apipe.flush_run() is None
        # on_spill fires in completion order; dict insertion order keeps it
        order = list(aspills)
    finally:
        faults.install("t", [])
    assert order[-1] == 0, f"span 0 was not delayed past the rest: {order}"
    assert aspills == sync_spills


def test_flush_reassembles_async_runs_in_spill_order():
    """Non-pipelined flush: runs complete out of order under the delay
    fault but the final merged output is bit-exact vs the sync engine."""
    from tez_tpu.ops.sorter import DeviceSorter

    def flush(depth, with_fault):
        if with_fault:
            faults.install("t", parse_spec(
                "device.dispatch.delay:delay:ms=400,n=1,match=span=0"))
        try:
            s = DeviceSorter(num_partitions=4, engine="device",
                             device_min_records=0, key_width=16,
                             span_budget_bytes=20_000, pipeline_depth=depth,
                             pipeline_coalesce_records=0)
            for i in range(4):
                s.write_batch(_mk_batch(1000, i))
            r = s.flush_run()
        finally:
            if with_fault:
                faults.install("t", [])
        return (r.batch.key_bytes.tobytes(), r.batch.val_bytes.tobytes(),
                r.row_index.tobytes())

    assert flush(2, True) == flush(0, False)


# -- failure containment: watchdog / failover / breaker / OOM ladder --------

import time  # noqa: E402

from tez_tpu.common.counters import TezCounters  # noqa: E402
from tez_tpu.ops.async_stage import (COUNTER_GROUP,  # noqa: E402
                                     CircuitBreaker)


class SettableClock:
    """Manually-advanced fake clock: watchdog deadlines are compared on the
    pipeline's injectable clock, so tests blow a deadline by advancing it —
    never by sleeping it out."""

    def __init__(self):
        self._t = 0.0
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            return self._t

    def advance(self, dt):
        with self._lock:
            self._t += dt


def test_failover_on_device_exception():
    """A device exception mid-dispatch re-routes JUST that group through
    failover_fn; the other spans stay on the device path and the pipeline
    never poisons."""
    def dispatch(staged):
        if staged == 1:
            raise ValueError("chip fault on span 1")
        return staged

    counters = TezCounters()
    pipe = AsyncSpanPipeline(
        dispatch_fn=dispatch, readback_fn=lambda s, ids: ("device", s),
        failover_fn=lambda ids, payloads: ("host", payloads[0]),
        breaker=CircuitBreaker(failures=100), counters=counters)
    for i in range(3):
        pipe.submit(i, i)
    res = pipe.drain()
    assert res == {0: ("device", 0), 1: ("host", 1), 2: ("device", 2)}
    assert pipe.stats.failovers == 1
    fo = counters.group(COUNTER_GROUP)
    assert fo.find_counter("device.failover.spans").value == 1
    assert fo.find_counter("device.failover.groups").value == 1


def test_watchdog_abandons_hung_readback_fake_clock():
    """A readback that never returns: the watchdog (deadline on the FAKE
    clock) abandons the attempt, fails the span over, and drain() returns
    in bounded wall time with every result present."""
    clock = SettableClock()
    hang = threading.Event()
    in_hang = threading.Event()
    failed_over = threading.Event()

    def readback(inflight, ids):
        if ids == (0,):
            in_hang.set()
            hang.wait(timeout=30.0)   # a hung D2H nobody will release
        return ("device", inflight)

    def failover(ids, payloads):
        failed_over.set()
        return ("host", payloads[0])

    pipe = AsyncSpanPipeline(
        dispatch_fn=lambda s: s, readback_fn=readback,
        failover_fn=failover, breaker=CircuitBreaker(failures=100),
        clock=clock, watchdog_readback_ms=1000)
    t_wall = time.monotonic()
    pipe.submit(0, 0)
    assert in_hang.wait(timeout=10.0)
    clock.advance(2.0)                # blow the 1000ms readback deadline
    assert failed_over.wait(timeout=10.0), "watchdog never fired"
    pipe.submit(1, 1)
    pipe.submit(2, 2)
    res = pipe.drain()
    wall = time.monotonic() - t_wall
    try:
        assert res == {0: ("host", 0), 1: ("device", 1), 2: ("device", 2)}
        assert pipe.stats.watchdog_fires == 1
        assert wall < 15.0, f"flush() not bounded by the watchdog: {wall:.1f}s"
    finally:
        hang.set()                    # release the abandoned daemon worker


def test_watchdog_abandons_hung_dispatch_and_drains_pending():
    """A dispatch that never returns wedges the staging thread itself: the
    watchdog must claim the hung group AND take over the queue, draining
    every not-yet-staged span through failover — drain() stays bounded."""
    clock = SettableClock()
    hang = threading.Event()
    in_hang = threading.Event()

    def dispatch(staged):
        if staged == 0:
            in_hang.set()
            hang.wait(timeout=30.0)   # staging thread stuck inside XLA
        return staged

    counters = TezCounters()
    pipe = AsyncSpanPipeline(
        dispatch_fn=dispatch, readback_fn=lambda s, ids: ("device", s),
        failover_fn=lambda ids, payloads: ("host", payloads[0]),
        breaker=CircuitBreaker(failures=100), counters=counters,
        clock=clock, watchdog_dispatch_ms=1000, paused=True)
    t_wall = time.monotonic()
    for i in range(4):
        pipe.submit(i, i)
    pipe.resume()
    assert in_hang.wait(timeout=10.0)
    clock.advance(2.0)                # blow the 1000ms dispatch deadline
    res = pipe.drain()
    wall = time.monotonic() - t_wall
    try:
        assert res == {i: ("host", i) for i in range(4)}
        assert pipe.stats.watchdog_fires == 1
        fo = counters.group(COUNTER_GROUP)
        assert fo.find_counter("device.watchdog.dispatch_fires").value == 1
        assert fo.find_counter("device.failover.drained").value == 3
        assert wall < 15.0, f"flush() not bounded when wedged: {wall:.1f}s"
    finally:
        hang.set()                    # release the abandoned staging thread


def test_breaker_trips_and_half_open_recovers_fake_clock():
    clock = SettableClock()
    br = CircuitBreaker(failures=2, cooldown_ms=1000, clock=clock)
    assert br.allow_device() and br.state == "closed"
    br.record_failure()
    assert br.state == "closed"       # below the consecutive threshold
    br.record_failure()
    assert br.state == "open" and br.trips == 1
    assert not br.allow_device()      # cooldown not elapsed
    clock.advance(1.1)
    assert br.allow_device()          # the half-open probe slot
    assert br.state == "half-open"
    assert not br.allow_device()      # only ONE probe at a time
    br.record_success()
    assert br.state == "closed" and br.recoveries == 1
    # a probe FAILURE re-opens immediately for another full cooldown
    br.record_failure()
    br.record_failure()
    assert br.state == "open" and br.trips == 2
    clock.advance(1.1)
    assert br.allow_device()
    br.record_failure()
    assert br.state == "open" and br.trips == 3
    assert not br.allow_device()


def test_breaker_open_short_circuits_before_device():
    """With the breaker open every group routes straight to the host
    engine — the dispatch fn (the chip) is never touched."""
    br = CircuitBreaker(failures=1, cooldown_ms=10_000,
                        clock=SettableClock())
    br.record_failure()               # open; fake clock never elapses it
    dispatched = []
    counters = TezCounters()
    pipe = AsyncSpanPipeline(
        dispatch_fn=lambda s: dispatched.append(s) or s,
        readback_fn=lambda s, ids: ("device", s),
        failover_fn=lambda ids, payloads: ("host", payloads[0]),
        breaker=br, counters=counters)
    for i in range(3):
        pipe.submit(i, i)
    res = pipe.drain()
    assert dispatched == []
    assert res == {i: ("host", i) for i in range(3)}
    assert counters.group(COUNTER_GROUP).find_counter(
        "device.breaker.short_circuits").value == 3


def test_oom_split_retry_before_host_failover():
    """RESOURCE_EXHAUSTED takes the split ladder FIRST: oom_retry_fn's
    (on-device) result completes the group, failover_fn is never called,
    and the split success re-arms the breaker."""
    failover_calls = []

    def dispatch(staged):
        if staged == 0:
            raise MemoryError("RESOURCE_EXHAUSTED: span too large")
        return staged

    br = CircuitBreaker(failures=2)
    counters = TezCounters()
    pipe = AsyncSpanPipeline(
        dispatch_fn=dispatch, readback_fn=lambda s, ids: ("device", s),
        failover_fn=lambda ids, payloads:
            failover_calls.append(ids) or ("host", payloads[0]),
        oom_retry_fn=lambda ids, payloads: ("split", payloads[0]),
        breaker=br, counters=counters)
    pipe.submit(0, 0)
    pipe.submit(1, 1)
    res = pipe.drain()
    assert res == {0: ("split", 0), 1: ("device", 1)}
    assert failover_calls == []       # the ladder stopped on-device
    assert pipe.stats.oom_splits == 1
    fo = counters.group(COUNTER_GROUP)
    assert fo.find_counter("device.oom.split_attempts").value == 1
    assert fo.find_counter("device.oom.split_success").value == 1
    assert br.state == "closed" and br.trips == 0


def test_oom_split_floor_falls_back_to_host():
    """When the split retry declines (floor reached — it raises), the
    group continues down the ladder to host failover."""
    def retry(ids, payloads):
        raise MemoryError("split floor reached")

    counters = TezCounters()
    pipe = AsyncSpanPipeline(
        dispatch_fn=lambda s: (_ for _ in ()).throw(
            MemoryError("RESOURCE_EXHAUSTED")),
        readback_fn=lambda s, ids: s,
        failover_fn=lambda ids, payloads: ("host", payloads[0]),
        oom_retry_fn=retry, breaker=CircuitBreaker(failures=100),
        counters=counters)
    pipe.submit(0, 0)
    res = pipe.drain()
    assert res == {0: ("host", 0)}
    fo = counters.group(COUNTER_GROUP)
    assert fo.find_counter("device.oom.split_attempts").value == 1
    assert fo.find_counter("device.oom.split_success").value == 0
    assert fo.find_counter("device.failover.spans").value == 1


def _flush_merged(depth, spec, **sorter_kw):
    """flush_run() a 4-span DeviceSorter under an optional fault spec;
    returns (merged-run bytes, counters)."""
    from tez_tpu.ops.sorter import DeviceSorter
    if spec:
        faults.install("t", parse_spec(spec))
    try:
        s = DeviceSorter(num_partitions=4, engine="device",
                         device_min_records=0, key_width=16,
                         span_budget_bytes=20_000, pipeline_depth=depth,
                         pipeline_coalesce_records=0, **sorter_kw)
        for i in range(4):
            s.write_batch(_mk_batch(1000, i))
        r = s.flush_run()
    finally:
        if spec:
            faults.install("t", [])
    return (r.batch.key_bytes.tobytes(), r.batch.val_bytes.tobytes(),
            r.row_index.tobytes()), s.counters


def test_sorter_oom_split_on_device_bit_exact():
    """One injected RESOURCE_EXHAUSTED dispatch (budget n=1): the span
    retries split in half ON DEVICE (the budget is spent, so the halves
    sort clean), the stable split-merge is bit-exact vs the fault-free
    sync engine, and host failover is never taken."""
    base, _ = _flush_merged(0, "")
    br = CircuitBreaker(failures=100)
    got, counters = _flush_merged(
        2, "device.dispatch.oom:fail:n=1,exc=runtime,match=span=0",
        split_min_bytes=1_000, breaker=br)
    assert got == base
    fo = counters.group(COUNTER_GROUP)
    assert fo.find_counter("device.oom.split_attempts").value == 1
    assert fo.find_counter("device.oom.split_success").value == 1
    assert fo.find_counter("device.failover.spans").value == 0
    assert br.trips == 0


def test_sorter_readback_failure_fails_over_bit_exact():
    """An injected readback crash re-sorts that span through the host
    engine; the merged flush stays bit-exact vs the sync engine."""
    base, _ = _flush_merged(0, "")
    br = CircuitBreaker(failures=100)
    got, counters = _flush_merged(
        2, "device.readback.fail:fail:n=1,exc=io,match=span=0", breaker=br)
    assert got == base
    fo = counters.group(COUNTER_GROUP)
    assert fo.find_counter("device.failover.spans").value == 1
    assert br.trips == 0


def test_engine_auto_width_routing():
    from tez_tpu.ops.sorter import _route_engine
    # narrow spans fall back to host ONLY when the caller opted in by
    # passing key bytes (auto engines)
    assert _route_engine("device", 10_000, 0, key_nbytes=100,
                         min_key_bytes=1 << 20) == "host"
    assert _route_engine("device", 10_000, 0, key_nbytes=1 << 21,
                         min_key_bytes=1 << 20) == "device"
    # explicit device engine never passes key_nbytes: no width rerouting
    assert _route_engine("device", 10_000, 0, key_nbytes=-1,
                         min_key_bytes=1 << 20) == "device"
    # record floor still applies first
    assert _route_engine("device", 10, 100, key_nbytes=1 << 21,
                         min_key_bytes=1 << 20) == "host"
    assert _route_engine("host", 10_000, 0) == "host"


# -- compile is set-up, and a compile error is not a sick chip (PR 21) -------

def test_compile_is_outside_the_dispatch_watchdog_fake_clock():
    """A kernel that compiles for 100 s against a 1 s dispatch deadline: the
    watchdog's clock is stopped while ops.device.Kernel compiles and
    restarts at launch, so nothing fires and nothing fails over.  (The same
    100 s spent INSIDE the launch is the hung-dispatch test above.)"""
    from tez_tpu.ops.device import Kernel
    clock = SettableClock()

    def slow_to_trace(x):
        clock.advance(100.0)     # the compile, on the pipeline's clock
        time.sleep(0.4)          # wall time for several monitor polls
        return x + 1

    kernel = Kernel(slow_to_trace, "slow_compile")
    counters = TezCounters()
    pipe = AsyncSpanPipeline(
        dispatch_fn=lambda s: kernel(np.int32(s)),
        readback_fn=lambda s, ids: int(s),
        failover_fn=lambda ids, payloads: "host",
        breaker=CircuitBreaker(failures=100), counters=counters,
        clock=clock, watchdog_dispatch_ms=1000)
    pipe.submit(0, 0)
    pipe.submit(1, 1)
    assert pipe.drain() == {0: 1, 1: 2}
    assert kernel.cache_size() == 1
    assert pipe.stats.watchdog_fires == 0 and pipe.stats.failovers == 0
    assert not any(c.value for c in counters.group(COUNTER_GROUP))


def test_compile_error_fails_the_attempt_and_is_never_failed_over():
    """The rule of docs/device_pipeline.md: a kernel that cannot compile
    poisons the pipeline with its name — no host re-sort, no breaker hit."""
    from tez_tpu.ops.device import Kernel, KernelCompileError

    def untraceable(x):
        raise NotImplementedError("Unimplemented primitive in lowering")

    kernel = Kernel(untraceable, "broken_kernel")
    failed_over = []
    counters = TezCounters()
    br = CircuitBreaker(failures=1)
    pipe = AsyncSpanPipeline(
        dispatch_fn=lambda s: kernel(np.zeros((s + 4, 2), np.uint32)),
        readback_fn=lambda s, ids: s,
        failover_fn=lambda ids, payloads: failed_over.append(ids) or "host",
        breaker=br, counters=counters)
    pipe.submit(0, 0)
    with pytest.raises(KernelCompileError,
                       match=r"broken_kernel\[4x2\].*Unimplemented"):
        pipe.drain()
    assert failed_over == []
    assert br.state == "closed" and br.trips == 0
    fo = counters.group(COUNTER_GROUP)
    assert fo.find_counter("device.compile.errors").value == 1
    assert fo.find_counter("device.failover.spans").value == 0


def test_sorter_compile_error_surfaces_but_injected_oom_fails_over(
        monkeypatch):
    """Same DeviceSorter, two failures: an injected RESOURCE_EXHAUSTED at
    the split floor still re-sorts on the host bit-exactly; a span-sort
    kernel that cannot compile fails flush_run() instead."""
    from tez_tpu.ops import device
    from tez_tpu.ops.device import Kernel, KernelCompileError
    base, _ = _flush_merged(0, "")
    got, counters = _flush_merged(
        2, "device.dispatch.oom:fail:n=1,exc=runtime,match=span=0",
        breaker=CircuitBreaker(failures=100))
    assert got == base
    assert counters.group(COUNTER_GROUP).find_counter(
        "device.failover.spans").value == 1

    def refused(lanes, lengths, num_partitions):
        raise ValueError("Shape mismatch in input, indices and output")
    broken = Kernel(refused, "resident_hash_sort",
                    static_argnames=("num_partitions",))
    monkeypatch.setattr(device, "_HASH_SORTS", (broken, broken))
    # the poison surfaces at whichever comes first: the next submit (which
    # wraps it) or the drain (which re-raises it)
    with pytest.raises(RuntimeError) as err:
        _flush_merged(2, "", breaker=CircuitBreaker(failures=100))
    cause = err.value if isinstance(err.value, KernelCompileError) \
        else err.value.__cause__
    assert isinstance(cause, KernelCompileError)
    assert "resident_hash_sort" in str(cause)
