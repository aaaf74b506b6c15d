"""Device sorter: PipelinedSorter semantics on TPU kernels.

Reference parity: tez-runtime-library/.../common/sort/impl/PipelinedSorter.java:75
— records collect into spans; full spans sort independently (there: background
threads, here: device kernels while the host keeps collecting); flush merges
spans (or, pipelined, emits each span as its own spill).  Spill-to-host-disk
replaces spill-to-local-FS.

Exactness: the device sorts by (partition, fixed-width key prefix) stably;
rows whose keys exceed the prefix width get a host tie-break pass so final
order equals full raw-byte order for ANY key length (SURVEY.md §7
"byte-identical ordered output").
"""
from __future__ import annotations

import contextlib
import logging
import os
import time
import uuid
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from tez_tpu.common import tracing
from tez_tpu.common.counters import TaskCounter, TezCounters
from tez_tpu.ops import device
from tez_tpu.ops.keycodec import (encode_keys, encode_split_keys,
                                  pad_to_matrix, range_partitions)
from tez_tpu.ops.runformat import (FileRun, KVBatch, PartitionedRunWriter,
                                   Run, adjacent_equal_rows, gather_ragged,
                                   save_run_partitioned)

log = logging.getLogger(__name__)


def _exact_tiebreak(lengths: np.ndarray, partitions: np.ndarray,
                    lanes: np.ndarray, width: int,
                    keyfn: Callable[[int], bytes]) -> Optional[np.ndarray]:
    """Return a refinement permutation for rows whose sorted (partition,
    prefix) group contains a SORT key longer than `width`, or None if exact
    already.  `lengths`/`keyfn` describe the sort keys in sorted order (the
    normalized keys when a comparator is configured).  Host cost is
    proportional to colliding rows only."""
    if len(lengths) == 0 or lengths.max(initial=0) <= width:
        return None
    clamped = np.minimum(lengths, width + 1)
    same_as_prev = np.zeros(len(lengths), dtype=bool)
    if len(lengths) > 1:
        same_as_prev[1:] = (partitions[1:] == partitions[:-1]) & \
            (clamped[1:] == clamped[:-1]) & \
            np.all(lanes[1:] == lanes[:-1], axis=1)
    # group starts
    starts = np.flatnonzero(~same_as_prev)
    ends = np.append(starts[1:], len(lengths))
    perm = np.arange(len(lengths), dtype=np.int64)
    changed = False
    for s, e in zip(starts, ends):
        if e - s <= 1:
            continue
        if int(lengths[s:e].max()) <= width:
            continue  # prefix fully determined the order
        keys = [keyfn(i) for i in range(s, e)]
        order = sorted(range(e - s), key=lambda j: keys[j])
        if order != list(range(e - s)):
            perm[s:e] = s + np.asarray(order, dtype=np.int64)
            changed = True
    return perm if changed else None


def _sorted_key_view(sort_bytes: np.ndarray, sort_offsets: np.ndarray,
                     perm: np.ndarray
                     ) -> Tuple[np.ndarray, Callable[[int], bytes]]:
    """(lengths, keyfn) over the sort keys in sorted order, slicing the
    already-materialized ragged arrays (no re-normalization)."""
    starts = sort_offsets[:-1][perm]
    lengths = (sort_offsets[1:] - sort_offsets[:-1])[perm]

    def keyfn(i: int) -> bytes:
        s = int(starts[i])
        return sort_bytes[s:s + int(lengths[i])].tobytes()

    return lengths, keyfn


def normalize_batch_keys(batch: KVBatch,
                         normalizer: Callable[[bytes], bytes]
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Materialize normalized sort keys as ragged (bytes, offsets) arrays.
    Per-record host cost — paid only when a custom comparator is configured
    (the reference's RawComparator pays per-COMPARISON, which is worse)."""
    n = batch.num_records
    keys = [normalizer(batch.key(i)) for i in range(n)]
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    data = np.frombuffer(b"".join(keys), dtype=np.uint8)
    return data, offsets


def _take(batch: KVBatch, perm: np.ndarray,
          counters: Optional[TezCounters], lock=None) -> KVBatch:
    """batch.take(perm) where a sort's or a merge's permutation moves the
    records themselves: the one named site of the payload's gather.  `lock`
    where other threads write the same counters (a sorter's two readback
    workers)."""
    with tracing.span("payload.gather", cat="sort", rows=len(perm)):
        out = batch.take(perm)
    if counters is not None:
        with lock if lock is not None else contextlib.nullcontext():
            counters.increment(TaskCounter.PAYLOAD_GATHER_BYTES,
                               out.key_bytes.nbytes + out.val_bytes.nbytes)
    return out


class SpanBuffer:
    """Collect-side buffer: raw bytes accumulated until the span budget."""

    def __init__(self) -> None:
        self.keys: List[bytes] = []
        self.vals: List[bytes] = []
        self.parts: List[int] = []     # only when a custom partitioner runs
        self.nbytes = 0
        self.batches: List[KVBatch] = []
        self._partitioned: Optional[bool] = None   # set by the first add
        self.all_pre_combined = True   # every added batch promised unique keys

    def _set_mode(self, partitioned: bool) -> None:
        if self._partitioned is None:
            self._partitioned = partitioned
        elif self._partitioned != partitioned:
            raise ValueError(
                "cannot mix partitioned and unpartitioned writes in one "
                "span (custom Partitioner output must cover every record)")

    def add(self, key: bytes, value: bytes,
            partition: Optional[int] = None) -> None:
        self._set_mode(partition is not None)
        self.all_pre_combined = False
        self.keys.append(key)
        self.vals.append(value)
        if partition is not None:
            self.parts.append(partition)
        self.nbytes += len(key) + len(value) + 16

    def add_batch(self, batch: KVBatch) -> None:
        self._set_mode(False)
        if not batch.pre_combined:
            self.all_pre_combined = False
        self.batches.append(batch)
        self.nbytes += batch.nbytes

    @property
    def num_records(self) -> int:
        return len(self.keys) + sum(b.num_records for b in self.batches)

    def to_batch(self) -> KVBatch:
        parts = list(self.batches)
        if self.keys:
            parts.append(KVBatch.from_pairs(list(zip(self.keys, self.vals))))
        if not parts:
            return KVBatch.empty()
        return parts[0] if len(parts) == 1 else KVBatch.concat(parts)


Combiner = Callable[[Run], Run]

#: Below this many records a device dispatch (trace/compile-cache lookup +
#: H2D/D2H) costs more than the host sort itself; the device engine routes
#: smaller spans to the host sorter.  The TPU-native framework pattern:
#: accelerate the big batches, keep the chatter off the chip.
DEVICE_SORT_MIN_RECORDS = 1 << 16

#: Auto-engine floor on a span's total SORT-KEY bytes for the device path
#: (tez.runtime.sort.engine.min-bytes).  The device sorts key lanes only —
#: wide-VALUE spans clear the record-count bar while carrying few key bytes,
#: so the dispatch+transfer overhead buys almost no device work and the
#: host gather of the wide values dominates either way.  Only consulted
#: when the engine was requested as `auto`; an explicit engine=device is
#: never silently rerouted by width.
ENGINE_MIN_KEY_BYTES = 1 << 20

#: Failure-containment defaults for the async device plane (overridden by
#: the tez.runtime.device.* knobs via library/outputs.py).
DEVICE_WATCHDOG_DISPATCH_MS = 60_000.0
DEVICE_WATCHDOG_READBACK_MS = 60_000.0
DEVICE_BREAKER_FAILURES = 3
DEVICE_BREAKER_COOLDOWN_MS = 5_000.0
DEVICE_SPLIT_MIN_BYTES = 1 << 20


def resolve_engine(engine: str) -> str:
    """Resolve the `auto` engine: device kernels on an accelerator backend,
    host kernels when the backend is the CPU because that was asked for
    (an XLA:CPU sort + dispatch round-trip loses to the native engine
    outright).  A backend that cannot initialise raises — `auto` never
    turns a chip that could not be claimed into a host run
    (ops.device.backend_platform).  Per-span width/count routing happens
    later (DeviceSorter._span_engine)."""
    if engine == "auto":
        return "device" if device.accelerator_present() else "host"
    return engine


def _route_engine(engine: str, n: int, min_records: int,
                  key_nbytes: int = -1, min_key_bytes: int = 0) -> str:
    """Per-span engine routing: host below the record-count floor and —
    when the caller opts in by passing key_nbytes >= 0 (auto engines) —
    host below the key-byte floor too."""
    if engine != "device":
        return engine
    if n < min_records:
        return "host"
    if min_key_bytes > 0 and 0 <= key_nbytes < min_key_bytes:
        return "host"
    return engine


class DeviceSorter:
    """The OrderedPartitionedKVOutput engine."""

    def __init__(self, num_partitions: int, key_width: int = 16,
                 span_budget_bytes: int = 256 << 20,
                 spill_dir: Optional[str] = None,
                 counters: Optional[TezCounters] = None,
                 combiner: Optional[Combiner] = None,
                 partitioner: str = "hash",
                 split_points: Sequence[bytes] = (),
                 mem_budget_bytes: Optional[int] = None,
                 engine: str = "device",
                 sort_threads: int = 0,
                 merge_factor: int = 64,
                 key_normalizer: Optional[Callable[[bytes], bytes]] = None,
                 spill_codec: Optional[str] = None,
                 resident_keys: bool = True,
                 device_min_records: int = DEVICE_SORT_MIN_RECORDS,
                 engine_min_bytes: int = ENGINE_MIN_KEY_BYTES,
                 pipeline_depth: int = 0,
                 pipeline_coalesce_records: int = -1,
                 watchdog_dispatch_ms: float = DEVICE_WATCHDOG_DISPATCH_MS,
                 watchdog_readback_ms: float = DEVICE_WATCHDOG_READBACK_MS,
                 breaker_failures: int = DEVICE_BREAKER_FAILURES,
                 breaker_cooldown_ms: float = DEVICE_BREAKER_COOLDOWN_MS,
                 split_min_bytes: int = DEVICE_SPLIT_MIN_BYTES,
                 breaker=None):
        self.num_partitions = num_partitions
        self.key_width = max(4, key_width)
        # 'device' (TPU kernels) | 'host' (np.lexsort/native) | 'auto'
        self.engine = resolve_engine(engine)
        #: width-aware auto routing: a span only takes the device path when
        #: its total key bytes clear this floor TOO (never applied to an
        #: explicitly requested device engine)
        self._auto_engine = engine == "auto"
        self.engine_min_bytes = engine_min_bytes
        self.device_min_records = device_min_records
        #: async double-buffered device plane (ops/async_stage.py): spans
        #: submit to a bounded dispatch-ahead pipeline — span k+1's host
        #: encode/H2D overlaps span k's in-flight sort while span k-1's
        #: readback drains; completed runs collect out-of-order and are
        #: reassembled in spill-id order at flush (bit-exact vs sync).
        #: 0 = synchronous spans (host engines: the pipeline only helps
        #: when a dispatch actually leaves the host, so it stays off).
        self.pipeline_depth = pipeline_depth if self.engine == "device" else 0
        #: span-batching budget (records): small adjacent spans coalesce
        #: into ONE bucketed dispatch while their sum fits.  -1 = auto
        #: (device_min_records: exactly the spans too small to be worth a
        #: dispatch each), 0 = off.
        self.pipeline_coalesce_records = (
            device_min_records if pipeline_coalesce_records < 0
            else pipeline_coalesce_records)
        self._pipeline = None
        self._async_store_ids: List[int] = []
        #: failure containment for the async plane (ops/async_stage.py):
        #: watchdog deadlines, host-engine failover via the circuit
        #: breaker, and the OOM split floor.  breaker=None = the sticky
        #: per-process breaker (a sick chip is a process property).
        self.watchdog_dispatch_ms = watchdog_dispatch_ms
        self.watchdog_readback_ms = watchdog_readback_ms
        self.breaker_failures = breaker_failures
        self.breaker_cooldown_ms = breaker_cooldown_ms
        self.split_min_bytes = split_min_bytes
        self._breaker = breaker
        #: keep sorted key lanes in HBM for downstream device merges.  The
        #: pinned HBM (~(key width + 4) B/row per registered output, freed
        #: at DAG deletion) is OUTSIDE the host memory budgets — operators
        #: of long many-output DAGs can turn it off
        #: (tez.runtime.tpu.resident.keys).
        self.resident_keys = resident_keys
        #: custom comparator as key normalization (library/comparators.py);
        #: None = sort by raw key bytes (zero-cost default)
        self.key_normalizer = key_normalizer
        #: host-spill compression (reference: tez.runtime.compress on IFile)
        self.spill_codec = spill_codec
        self.span_budget = span_budget_bytes
        self.spill_dir = spill_dir
        self.counters = counters or TezCounters()
        # per-record hot path: resolve the counter ONCE (find_counter takes
        # a registry lock per call)
        self._out_records_ctr = self.counters.find_counter(
            TaskCounter.OUTPUT_RECORDS)
        self.combiner = combiner
        #: 'hash' (FNV of the key, fused into the span sort), 'range'
        #: (total order over `split_points`, fused likewise), anything else:
        #: one partition unless write() is given one a record
        self.partitioner = partitioner
        self.split_points: List[bytes] = list(split_points)
        if partitioner == "range" and \
                len(self.split_points) != num_partitions - 1:
            raise ValueError(
                f"{len(self.split_points)} split points for "
                f"{num_partitions} partitions")
        self._split_lanes: dict = {}     # lane count -> encoded split rows
        self.mem_budget = mem_budget_bytes or (span_budget_bytes * 2)
        #: bounded k-way merge width (reference: io.sort.factor)
        self.merge_factor = merge_factor
        #: background span sorting ("sortmaster" analog: collection
        #: continues while a full span sorts; PipelinedSorter.java:326).
        #: Capped at ONE worker: counters follow a single-writer-per-counter
        #: rule (the collector thread owns OUTPUT_*, the sortmaster owns the
        #: sort/merge/spill counters) and on_spill consumers are not
        #: required to be re-entrant.
        self._executor = None
        if sort_threads > 0:
            import concurrent.futures
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="sortmaster")
        self._pending = []
        self._last_bg = ""      # tracing.here() of the last sortmaster span
        import threading as _threading
        self._store_lock = _threading.Lock()
        self._span = SpanBuffer()
        self._runs: List[Run | str] = []   # Run (in RAM) or path (spilled)
        self._runs_nbytes = 0
        self._closed = False
        self.num_spills = 0
        self.on_spill: Optional[Callable[[Run, int], None]] = None  # pipelined

    # -- write side ----------------------------------------------------------
    def write(self, key: bytes, value: bytes,
              partition: Optional[int] = None) -> None:
        """partition: pre-computed by a custom Partitioner over the LOGICAL
        key/value (the serde runs before this layer); None = device hash."""
        if partition is not None and not 0 <= partition < self.num_partitions:
            raise ValueError(
                f"partitioner returned {partition}, valid range is "
                f"[0, {self.num_partitions})")
        self._span.add(key, value, partition)
        self._out_records_ctr.increment()
        if self._span.nbytes >= self.span_budget:
            self._sort_span()

    def write_batch(self, batch: KVBatch) -> None:
        with tracing.span("sort.collect", cat="sort",
                          rows=batch.num_records):
            self._span.add_batch(batch)
            self._out_records_ctr.increment(batch.num_records)
            if self._span.nbytes >= self.span_budget:
                self._sort_span()

    # -- span sort (device) --------------------------------------------------
    def _precombine(self, batch: KVBatch,
                    custom_parts: Optional[np.ndarray],
                    skip: bool = False) -> KVBatch:
        """Hash-combine BEFORE the sort when the combiner allows it.

        The reference combines after each spill sort
        (PipelinedSorter.java:559 -> combiner on the sorted stream); on TPU
        the sort is the expensive device step, so collapsing duplicate keys
        first shrinks pad/lanes/sort/gather by the duplication factor.  The
        post-sort combiner still runs (idempotent for sum) and covers the
        paths this fast path declines."""
        if skip or self.combiner is not sum_long_combiner or \
                custom_parts is not None:
            return batch
        n = batch.num_records
        if n < 2:
            return batch
        if not bool(np.all(np.diff(batch.val_offsets) == 8)):
            return batch   # long-serde fixed-8 values only
        from tez_tpu.ops.native import hash_sum_native
        from tez_tpu.ops.serde import decode_longs_be, encode_longs_be
        decoded = decode_longs_be(batch.val_bytes, n)
        first_idx, sums = hash_sum_native(batch.key_bytes,
                                          batch.key_offsets, decoded)
        kb2, ko2 = gather_ragged(batch.key_bytes, batch.key_offsets,
                                 first_idx)
        vb = encode_longs_be(sums)
        vo = np.arange(len(sums) + 1, dtype=np.int64) * 8
        self.counters.increment(TaskCounter.COMBINE_INPUT_RECORDS, n)
        self.counters.increment(TaskCounter.COMBINE_OUTPUT_RECORDS,
                                len(sums))
        return KVBatch(kb2, ko2, vb, vo)

    def _finalize_span(self) -> Run:
        """Sort + combine the current span (shared by spill and flush)."""
        batch = self._span.to_batch()
        custom_parts = np.asarray(self._span.parts, dtype=np.int32) \
            if self._span.parts else None
        # a span made entirely of pre-combined batches (e.g. ONE fused
        # tokenizer emission) has nothing for the hash pass to collapse
        skip_pre = self._span.all_pre_combined and \
            len(self._span.batches) == 1
        self._span = SpanBuffer()
        batch = self._precombine(batch, custom_parts, skip=skip_pre)
        run = self.sort_batch(batch, custom_partitions=custom_parts)
        if self.combiner is not None:
            run = self.combiner(run)
        self.num_spills += 1
        return run

    # -- async double-buffered span plane ------------------------------------
    def _ensure_pipeline(self):
        if self._pipeline is None:
            from tez_tpu.ops.async_stage import (AsyncSpanPipeline,
                                                 process_breaker)
            breaker = self._breaker
            if breaker is None:
                breaker = process_breaker()
                breaker.configure(failures=self.breaker_failures,
                                  cooldown_ms=self.breaker_cooldown_ms)
            self._pipeline = AsyncSpanPipeline(
                encode_fn=self._async_encode,
                stage_fn=self._async_h2d,
                dispatch_fn=self._async_dispatch,
                readback_fn=self._async_readback,
                coalesce_fn=self._async_coalesce,
                records_fn=lambda p: p["batch"].num_records,
                on_complete=self._async_complete,
                depth=self.pipeline_depth,
                coalesce_records=self.pipeline_coalesce_records,
                counters=self.counters,
                name="sorter-pipeline",
                failover_fn=self._async_failover,
                oom_retry_fn=self._async_oom_retry,
                breaker=breaker,
                watchdog_dispatch_ms=self.watchdog_dispatch_ms,
                watchdog_readback_ms=self.watchdog_readback_ms)
        return self._pipeline

    def _group_batch(self, ids, payloads) -> Tuple[KVBatch,
                                                   Optional[np.ndarray]]:
        """Rebuild one dispatch group's span from its RAW payloads (the
        failover/retry paths re-run precombine — the device attempt's
        encode results died with the attempt)."""
        batches = [self._precombine(p["batch"], p["custom_parts"],
                                    skip=p["skip_pre"]) for p in payloads]
        batch = batches[0] if len(batches) == 1 else KVBatch.concat(batches)
        # coalesced groups never carry custom partitions (_submit_span_async
        # excludes them from coalescing)
        custom_parts = payloads[0]["custom_parts"] if len(payloads) == 1 \
            else None
        return batch, custom_parts

    def _async_failover(self, ids, payloads) -> Run:
        """Host-engine failover for a failed device attempt (watchdog fire,
        device exception, breaker short-circuit): bit-exact with the device
        path by the host/device golden contract (tests/test_device_parity)."""
        batch, custom_parts = self._group_batch(ids, payloads)
        run = self.sort_batch(batch, custom_partitions=custom_parts,
                              engine="host")
        if self.combiner is not None:
            run = self.combiner(run)
        return run

    def _async_oom_retry(self, ids, payloads) -> Run:
        """RESOURCE_EXHAUSTED ladder: EVICT then split.  First ask the
        buffer store's pressure hooks to reclaim HBM (cold resident key
        lanes demote to the host tier) and retry the WHOLE span on
        device; only when nothing was evictable — or the whole-span
        retry OOMs again — fall to the halving split (recursively, down
        to split_min_bytes) before the host engine takes over.  Merging
        the stably-sorted halves with run-age tie order equals the
        stable sort of the whole span — bit-exact."""
        from tez_tpu.ops import async_stage
        from tez_tpu.ops.device import is_resource_exhausted
        batch, custom_parts = self._group_batch(ids, payloads)
        freed = async_stage.relieve_pressure(batch.nbytes, self.counters)
        if freed > 0:
            try:
                run = self.sort_batch(batch,
                                      custom_partitions=custom_parts,
                                      engine="device")
                if self.combiner is not None:
                    run = self.combiner(run)
                return run
            except BaseException as e:  # noqa: BLE001 — ladder continues
                if not is_resource_exhausted(e):
                    raise
        run = self._split_device_sort(batch, custom_parts,
                                      detail=f"span={min(ids)}")
        if self.combiner is not None:
            run = self.combiner(run)
        return run

    def _split_device_sort(self, batch: KVBatch,
                           custom_parts: Optional[np.ndarray],
                           detail: str) -> Run:
        from tez_tpu.common import faults
        from tez_tpu.ops.device import is_resource_exhausted
        n = batch.num_records
        nbytes = int(batch.key_offsets[-1]) + int(batch.val_offsets[-1])
        if n < 2 or nbytes <= self.split_min_bytes:
            # at the floor: decline the retry — the caller's ladder sends
            # the span to the host engine
            raise MemoryError(
                f"span at OOM-split floor ({nbytes}B <= "
                f"{self.split_min_bytes}B, n={n})")
        h = n // 2
        runs: List[Run] = []
        for lo, hi in ((0, h), (h, n)):
            half = batch.take(np.arange(lo, hi, dtype=np.int64))
            parts_half = custom_parts[lo:hi] if custom_parts is not None \
                else None
            try:
                if faults.armed():
                    faults.fire("device.dispatch.oom",
                                f"{detail}:split[{lo}:{hi})")
                runs.append(self.sort_batch(half,
                                            custom_partitions=parts_half,
                                            engine="device"))
            except BaseException as e:  # noqa: BLE001 — recurse on OOM only
                if not is_resource_exhausted(e):
                    raise
                runs.append(self._split_device_sort(half, parts_half,
                                                    detail))
        # run-age tie order makes the merge of the stably-sorted halves
        # identical to the stable sort of the concatenated span
        return merge_sorted_runs(runs, self.num_partitions, self.key_width,
                                 counters=self.counters, engine="device",
                                 key_normalizer=self.key_normalizer,
                                 device_min_records=self.device_min_records)

    def _submit_span_async(self) -> None:
        batch = self._span.to_batch()
        custom_parts = np.asarray(self._span.parts, dtype=np.int32) \
            if self._span.parts else None
        skip_pre = self._span.all_pre_combined and \
            len(self._span.batches) == 1
        self._span = SpanBuffer()
        spill_id = self.num_spills
        self.num_spills += 1
        # pipelined mode keeps one span per spill_id (consumers track spill
        # ids); store mode may coalesce — the joint stable sort of adjacent
        # spans equals the merge of their individual sorts (ties keep
        # arrival order), so the flush-time merge output is unchanged
        coalesce = self.on_spill is None and custom_parts is None
        self._ensure_pipeline().submit(
            spill_id,
            {"batch": batch, "custom_parts": custom_parts,
             "skip_pre": skip_pre},
            coalesce=coalesce)

    def _async_encode(self, payload: dict) -> dict:
        """Staging thread: precombine + host ragged->lane encode (the
        resident fast path's host work), overlapped with in-flight sorts."""
        batch = self._precombine(payload["batch"], payload["custom_parts"],
                                 skip=payload["skip_pre"])
        custom_parts = payload["custom_parts"]
        resident = None
        if custom_parts is None and batch.num_records > 0:
            resident = self._resident_encode(batch, self._span_engine(batch))
        if resident is not None:
            return {"kind": "resident", "batch": batch,
                    "lanes": resident[0], "lengths": resident[1]}
        return {"kind": "generic", "batch": batch,
                "custom_parts": custom_parts}

    def _resident_encode(self, batch: KVBatch, engine: str
                         ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(lanes, lengths) where the span takes the device-resident fast
        path: a partitioner the span sort fuses (hash, range), the device
        engine, raw-byte order, and every key (and split point) inside the
        lane width.  Lanes are sized to the ACTUAL longest key (fewer upload
        bytes); whole keys fit them, so the partition derives from lanes ON
        DEVICE, prefix order IS exact byte order (no tie-break), and the
        sorted key columns stay in HBM for the consumer merge."""
        if self.partitioner not in ("hash", "range") or engine == "host" or \
                self.key_normalizer is not None or not self.resident_keys:
            return None
        klens = batch.key_offsets[1:] - batch.key_offsets[:-1]
        wmax = max([int(klens.max(initial=1))] +
                   [len(s) for s in self.split_points])
        if wmax > self.key_width:
            return None
        eff = ((max(wmax, 1) + 3) // 4) * 4
        return encode_keys(batch.key_bytes, batch.key_offsets, eff)

    def _splits_for(self, lanes: np.ndarray):
        """The range partitioner's split rows at these lanes' width (None:
        the hash kernel)."""
        if self.partitioner != "range":
            return None
        width = lanes.shape[1]
        if width not in self._split_lanes:
            self._split_lanes[width] = encode_split_keys(self.split_points,
                                                         width * 4)
        return self._split_lanes[width]

    def _async_coalesce(self, staged_list: List[dict]) -> dict:
        batch = KVBatch.concat([s["batch"] for s in staged_list])
        if all(s["kind"] == "resident" for s in staged_list):
            width = max(s["lanes"].shape[1] for s in staged_list)
            # widening narrower views with ZERO lanes preserves order:
            # bytes beyond a key's length are zero in the lane encoding
            lanes = np.concatenate([
                s["lanes"] if s["lanes"].shape[1] == width else
                np.pad(s["lanes"], ((0, 0), (0, width - s["lanes"].shape[1])))
                for s in staged_list])
            lengths = np.concatenate([s["lengths"] for s in staged_list])
            return {"kind": "resident", "batch": batch,
                    "lanes": lanes, "lengths": lengths}
        return {"kind": "generic", "batch": batch, "custom_parts": None}

    def _async_h2d(self, staged: dict) -> dict:
        if staged["kind"] == "resident":
            staged["staged_dev"] = device.stage_resident_span(
                staged["lanes"], staged["lengths"])
        return staged

    def _async_dispatch(self, staged: dict) -> dict:
        t0 = time.time()
        if staged["kind"] == "resident":
            inflight = device.dispatch_resident_span(
                staged["staged_dev"], self.num_partitions,
                self._splits_for(staged["lanes"]))
            return {"kind": "resident", "batch": staged["batch"],
                    "inflight": inflight, "t0": t0}
        # generic spans (normalizer / custom partitioner / host-routed /
        # over-width keys): the full sync span sort runs here on the staging
        # thread — still overlapped against other spans' readback
        run = self.sort_batch(staged["batch"],
                              custom_partitions=staged["custom_parts"])
        return {"kind": "generic", "run": run, "t0": t0}

    def _async_readback(self, inflight: dict, ids) -> Run:
        if inflight["kind"] == "resident":
            sp, perm, dev = device.readback_resident_span(
                inflight["inflight"])
            sorted_batch = _take(inflight["batch"], perm, self.counters,
                                 self._store_lock)
            sorted_batch.dev_keys = dev
            self._record_sort(inflight["t0"], "device",
                              sorted_batch.num_records)
            run = Run.from_sorted_batch(sorted_batch, sp,
                                        self.num_partitions)
        else:
            run = inflight["run"]
        if self.combiner is not None:
            run = self.combiner(run)
        return run

    def _async_complete(self, ids, run: Run) -> None:
        """Completion callback — fires in COMPLETION order (out-of-order
        under delays); coalesced groups complete under their first spill
        id."""
        sid = min(ids)
        if self.on_spill is not None:
            self.on_spill(run, sid)
        else:
            with self._store_lock:
                self._store_run(run)
                self._async_store_ids.append(sid)

    def _drain_async(self) -> None:
        """Block until every submitted span completed, then restore spill-id
        order over the stored runs so the flush merge sees the same run
        sequence as the synchronous engine (stable ties = run order)."""
        pipe, self._pipeline = self._pipeline, None
        if pipe is not None:
            pipe.drain()
            tracing.came_after(pipe.last_done)     # the caller's sort.flush
        if self._async_store_ids:
            order = sorted(range(len(self._async_store_ids)),
                           key=lambda i: self._async_store_ids[i])
            self._runs = [self._runs[i] for i in order]
            self._async_store_ids = []

    def _sort_span(self) -> None:
        if self._span.num_records == 0:
            return
        if self.pipeline_depth > 0:
            self._submit_span_async()
            return
        if self._executor is not None:
            # hand the full span to the sortmaster; keep collecting
            batch = self._span.to_batch()
            custom_parts = np.asarray(self._span.parts, dtype=np.int32) \
                if self._span.parts else None
            skip_pre = self._span.all_pre_combined and \
                len(self._span.batches) == 1
            self._span = SpanBuffer()
            spill_id = self.num_spills
            self.num_spills += 1

            def _bg() -> None:
                pre = self._precombine(batch, custom_parts, skip=skip_pre)
                run = self.sort_batch(pre, custom_partitions=custom_parts)
                if self.combiner is not None:
                    run = self.combiner(run)
                if self.on_spill is not None:
                    self.on_spill(run, spill_id)
                else:
                    # store (and possibly disk-spill) AS spans finish so RAM
                    # stays bounded by mem_budget, same as the sync path
                    with self._store_lock:
                        self._store_run(run)
                self._last_bg = tracing.here()

            self._pending.append(self._executor.submit(tracing.bound(_bg)))
            return
        run = self._finalize_span()
        if self.on_spill is not None:
            # pipelined shuffle: each span ships immediately
            self.on_spill(run, self.num_spills - 1)
        else:
            self._store_run(run)

    def _span_engine(self, batch: KVBatch) -> str:
        """Per-span routing: record-count floor always; key-byte floor only
        for auto-resolved device engines (wide-value small-key spans carry
        too little device work to pay a dispatch)."""
        key_nbytes = int(batch.key_offsets[-1]) if self._auto_engine else -1
        return _route_engine(self.engine, batch.num_records,
                             self.device_min_records,
                             key_nbytes=key_nbytes,
                             min_key_bytes=self.engine_min_bytes)

    def _record_sort(self, t0: float, engine: str, rows: int) -> None:
        """One span sorted by `engine` ('device' | 'host')."""
        ms = (time.time() - t0) * 1000.0
        with self._store_lock:   # two readback workers land here at once
            self.counters.find_counter(TaskCounter.DEVICE_SORT_MILLIS)\
                .increment(int(ms))
            self.counters.increment(
                TaskCounter.DEVICE_SORT_RECORDS if engine == "device"
                else TaskCounter.HOST_SORT_RECORDS, rows)
        from tez_tpu.common import metrics
        metrics.observe("device.sort", ms, counters=self.counters)

    def sort_batch(self, batch: KVBatch,
                   custom_partitions: Optional[np.ndarray] = None,
                   engine: Optional[str] = None) -> Run:
        """engine overrides the per-span routing: the containment plane
        forces 'host' (failover re-sort) or 'device' (OOM split retry);
        None = normal routing."""
        t0 = time.time()
        if custom_partitions is not None:
            # validate ONCE for every engine path: a short array would read
            # past the buffer inside the native comparator and an
            # out-of-range id would index past num_partitions-sized native
            # buffers (heap corruption, not a python error)
            if len(custom_partitions) != batch.num_records:
                raise ValueError(
                    "custom partitions must cover every record in the span")
            if batch.num_records and (
                    int(custom_partitions.min()) < 0 or
                    int(custom_partitions.max()) >= self.num_partitions):
                raise ValueError(
                    f"partitioner returned ids outside "
                    f"[0, {self.num_partitions})")
        # hybrid routing: tiny spans sort faster on host than a device
        # round-trip, even under the device engine
        if engine is None:
            engine = self._span_engine(batch)
        resident = self._resident_encode(batch, engine) \
            if custom_partitions is None else None
        if resident is not None:
            lanes, lengths = resident
            sorted_partitions, perm, dev = device.sort_span_resident(
                lanes, lengths, self.num_partitions, self._splits_for(lanes))
            sorted_batch = _take(batch, perm, self.counters, self._store_lock)
            sorted_batch.dev_keys = dev
            self._record_sort(t0, "device", batch.num_records)
            return Run.from_sorted_batch(sorted_batch, sorted_partitions,
                                         self.num_partitions)
        if custom_partitions is None and self.partitioner == "range":
            # off the fused path (host engine, failover, a comparator, keys
            # over the lane width): the same partition ids from the host
            custom_partitions = range_partitions(
                batch.key_bytes, batch.key_offsets, self.split_points)
        if self.key_normalizer is not None:
            sort_bytes, sort_offsets = normalize_batch_keys(
                batch, self.key_normalizer)
        else:
            sort_bytes, sort_offsets = batch.key_bytes, batch.key_offsets
        if engine == "host":
            return self._native_host_sort(batch, sort_bytes, sort_offsets,
                                          custom_partitions, t0)
        lanes, lengths = encode_keys(sort_bytes, sort_offsets, self.key_width)
        if custom_partitions is not None:
            sorted_partitions, perm = device.sort_run(custom_partitions,
                                                      lanes, lengths)
        elif self.partitioner == "hash":
            # fused single-dispatch kernel: full-key FNV hash (matrix padded
            # to the longest key so every byte is hashed — host-partitioner
            # parity) + (partition, key) LSD sort
            klens = batch.key_offsets[1:] - batch.key_offsets[:-1]
            wmax = int(klens.max(initial=1))
            hash_w = 1 << max(2, (wmax - 1).bit_length())
            hmat, hlens = pad_to_matrix(batch.key_bytes, batch.key_offsets,
                                        hash_w)
            sorted_partitions, perm = device.hash_sort_span(
                hmat, hlens, lanes, lengths, self.num_partitions)
        else:
            sorted_partitions, perm = device.sort_run(
                np.zeros(batch.num_records, dtype=np.int32), lanes, lengths)
        sorted_batch = _take(batch, perm, self.counters, self._store_lock)
        sort_lengths, keyfn = _sorted_key_view(sort_bytes, sort_offsets, perm)
        refinement = _exact_tiebreak(
            sort_lengths, sorted_partitions, lanes[perm], self.key_width,
            keyfn)
        if refinement is not None:
            sorted_batch = _take(sorted_batch, refinement, self.counters,
                                 self._store_lock)
        self._record_sort(t0, "device", batch.num_records)
        return Run.from_sorted_batch(sorted_batch, sorted_partitions,
                                     self.num_partitions)

    def _native_host_sort(self, batch: KVBatch, sort_bytes: np.ndarray,
                          sort_offsets: np.ndarray,
                          custom_parts: Optional[np.ndarray],
                          t0: float) -> Run:
        """C-speed host span sort: threaded FNV partition + stable parallel
        index sort over the ragged sort keys (full-key compares — no padded
        matrix, no tie-break pass), GIL released so concurrent tasks
        overlap."""
        from tez_tpu.ops.native import (fnv32_partition_native,
                                        sort_partition_keys_native,
                                        span_sort_emit_native)
        if self.key_normalizer is None:
            # fused fast path: partition + stable sort + materialization in
            # ONE native call — sorted key bytes emit sequentially (dedup
            # path repeats each unique key in place), values follow the
            # stable permutation; no Python-side take().  custom_parts
            # length/range were validated at the sort_batch boundary.
            fused = span_sort_emit_native(
                batch.key_bytes, batch.key_offsets,
                batch.val_bytes, batch.val_offsets,
                self.num_partitions, custom_parts,
                compute_hash=(custom_parts is None and
                              self.partitioner == "hash"))
            if fused is not None:
                out_kb, out_ko, out_vb, out_vo, row_index = fused
                self._record_sort(t0, "host", batch.num_records)
                return Run(KVBatch(out_kb, out_ko, out_vb, out_vo),
                           row_index)
        parts: Optional[np.ndarray]
        if custom_parts is not None:
            parts = custom_parts
        elif self.partitioner == "hash" and self.num_partitions > 1:
            parts = fnv32_partition_native(batch.key_bytes,
                                           batch.key_offsets,
                                           self.num_partitions)
        else:
            parts = None    # everything lands in partition 0
        perm = sort_partition_keys_native(sort_bytes, sort_offsets, parts)
        sorted_batch = _take(batch, perm, self.counters, self._store_lock)
        if parts is None:
            sorted_partitions = np.zeros(batch.num_records, dtype=np.int32)
        else:
            sorted_partitions = parts[perm]
        self._record_sort(t0, "host", batch.num_records)
        return Run.from_sorted_batch(sorted_batch, sorted_partitions,
                                     self.num_partitions)

    def _store_run(self, run: Run) -> None:
        self.counters.increment(TaskCounter.SPILLED_RECORDS,
                                run.batch.num_records)
        if self.spill_dir is not None and \
                self._runs_nbytes + run.nbytes > self.mem_budget:
            path = os.path.join(self.spill_dir,
                                f"spill_{uuid.uuid4().hex}.prun")
            save_run_partitioned(run, path, codec=self.spill_codec)
            # count bytes actually written: with compression on, disk I/O
            # is what these counters exist to report
            written = os.path.getsize(path)
            self.counters.increment(TaskCounter.ADDITIONAL_SPILLS_BYTES_WRITTEN,
                                    written)
            self.counters.increment(TaskCounter.ADDITIONAL_SPILL_COUNT)
            self.counters.increment(TaskCounter.HOST_SPILL_BYTES, written)
            self._runs.append(path)
        else:
            self._runs.append(run)
            self._runs_nbytes += run.nbytes

    def _drain_pending(self, store: bool) -> None:
        """Join the sortmaster (workers stored/shipped their runs already).
        Exception-safe: the executor always shuts down, then the first
        worker error re-raises."""
        error: Optional[BaseException] = None
        try:
            for fut in self._pending:
                try:
                    fut.result()
                except BaseException as e:  # noqa: BLE001
                    if error is None:
                        error = e
        finally:
            self._pending = []
            if self._executor is not None:
                self._executor.shutdown(wait=True)
                self._executor = None
        if error is not None:
            raise error
        tracing.came_after(self._last_bg)          # the caller's sort.flush

    # -- flush ---------------------------------------------------------------
    def flush(self) -> Optional[Run]:
        """Final merge of all spans, fully materialized (compat surface for
        in-RAM callers/tests).  Returns None in pipelined mode.  Spill-scale
        callers want flush_run(), which keeps disk-resident data on disk."""
        result = self.flush_run()
        if isinstance(result, FileRun):
            run = result.to_run()
            result.delete()
            return run
        return result

    def flush_run(self):
        """Final merge of all spans.  Returns None in pipelined mode (spans
        already shipped via on_spill; a trailing partial span ships here).

        In-RAM cases return a `Run` exactly as before (single-span fast
        path; all-RAM multi-span device merge with HBM-resident keys).  When
        any span spilled to disk, the merge instead STREAMS: a partition-
        major blockwise k-way merge (ops/block_merge.py) over the
        partition-indexed span files, written incrementally to one final
        partition-indexed file — no second full sort, no full
        materialization; resident memory is one block per span.  Returns a
        disk-backed `FileRun` (reference: the final IFile + TezSpillRecord
        a PipelinedSorter task publishes, PipelinedSorter.java:559 final
        merge -> TezMerger.java:76)."""
        assert not self._closed
        self._closed = True
        if self.pipeline_depth > 0:
            # async plane: the trailing span submits like any other, then
            # the drain barrier collects out-of-order completions and
            # restores spill-id order
            self._sort_span()
            with tracing.span("sort.flush", cat="sort"):
                self._drain_async()
                self._drain_pending(store=True)  # no-op unless sortmaster ran
            if self.on_spill is not None:
                return None
        elif self.on_spill is not None:
            if self._span.num_records > 0:
                self._sort_span()
            with tracing.span("sort.flush", cat="sort"):
                self._drain_pending(store=False)
            return None
        else:
            if self._span.num_records > 0 and not self._runs and \
                    not self._pending:
                # common fast path: everything fit one span
                return self._finalize_span()
            self._sort_span()
            with tracing.span("sort.flush", cat="sort"):
                self._drain_pending(store=True)
        runs = list(self._runs)
        self._runs = []
        if not runs:
            return Run(KVBatch.empty(),
                       np.zeros(self.num_partitions + 1, dtype=np.int64))
        if len(runs) == 1 and not isinstance(runs[0], str):
            return runs[0]
        with tracing.span("sort.final_merge", cat="sort", runs=len(runs)):
            if not any(isinstance(r, str) for r in runs):
                merged = merge_sorted_runs(
                    runs, self.num_partitions, self.key_width,
                    counters=self.counters, engine=self.engine,
                    merge_factor=self.merge_factor,
                    key_normalizer=self.key_normalizer,
                    device_min_records=self.device_min_records)
                if self.combiner is not None:
                    merged = self.combiner(merged)
                return merged
            return self._stream_final_merge(runs)

    def _stream_final_merge(self, runs: List["Run | str"]) -> "FileRun":
        """Blockwise partition-major merge of spilled + resident spans into
        one partition-indexed file."""
        from tez_tpu.common import metrics
        from tez_tpu.ops.block_merge import iter_merged_blocks
        sources: List["Run | FileRun"] = []
        for r in runs:
            if isinstance(r, str):
                self.counters.increment(
                    TaskCounter.ADDITIONAL_SPILLS_BYTES_READ,
                    os.path.getsize(r))
                sources.append(FileRun(r))
            else:
                sources.append(r)
        path = os.path.join(self.spill_dir,
                            f"final_{uuid.uuid4().hex}.prun")
        writer = PartitionedRunWriter(path, self.num_partitions,
                                      codec=self.spill_codec)
        self.counters.increment(TaskCounter.MERGED_MAP_OUTPUTS, len(sources))
        try:
            for p in range(self.num_partitions):
                srcs = []
                for s in sources:
                    if s.partition_row_count(p) == 0:
                        continue
                    srcs.append(s.iter_partition_blocks(p)
                                if isinstance(s, FileRun)
                                else iter([s.partition(p)]))
                for block in iter_merged_blocks(
                        srcs, self.key_width, engine=self.engine,
                        key_normalizer=self.key_normalizer,
                        merge_factor=self.merge_factor,
                        device_min_records=self.device_min_records,
                        counters=self.counters):
                    if self.combiner is not None:
                        # block-local combine: legal for the (associative)
                        # combiner contract; a key split across block edges
                        # keeps at most one extra record per edge, and the
                        # consumer's grouped reader re-unifies it
                        combined = self.combiner(Run(
                            block, np.array([0, block.num_records],
                                            dtype=np.int64)))
                        block = combined.batch
                    with metrics.timer("spill.write"):
                        writer.append(block, p)
            writer.close()
        except BaseException:
            writer.abort()
            raise
        self.counters.increment(TaskCounter.ADDITIONAL_SPILLS_BYTES_WRITTEN,
                                writer.bytes_written)
        # span spill files are dead now
        for r in runs:
            if isinstance(r, str):
                try:
                    os.remove(r)
                except OSError:
                    pass
        return FileRun(path)


def _record_merge_ms(counters: Optional[TezCounters], t0: float) -> None:
    """device.merge latency histogram: wall of one device merge dispatch
    (host-fed or resident), the reduce-side twin of device.sort."""
    from tez_tpu.common import metrics
    metrics.observe("device.merge", (time.time() - t0) * 1000.0,
                    counters=counters)


def _record_merge(counters: Optional[TezCounters], t0: float, engine: str,
                  rows: int, num_runs: int, final: bool) -> None:
    """One merge pass done by `engine` ('device' | 'host'): rows always
    count toward that engine; wall and MERGED_MAP_OUTPUTS only when this
    pass is the one the task reports (`final`)."""
    if counters is None:
        return
    counters.increment(
        TaskCounter.DEVICE_MERGE_RECORDS if engine == "device"
        else TaskCounter.HOST_MERGE_RECORDS, rows)
    if final:
        counters.find_counter(TaskCounter.DEVICE_MERGE_MILLIS)\
            .increment(int((time.time() - t0) * 1000))
        counters.increment(TaskCounter.MERGED_MAP_OUTPUTS, num_runs)


def _record_launches(counters: Optional[TezCounters], tally: dict) -> None:
    """Kernel launches of one merge (device.launch_tally) into the task's
    counters.  DEVICE_MERGE_LAUNCHES counts every program the merge
    launched, staging ones too; DEVICE_MERGE_LAUNCH_ROWS the rows of the
    programs that compare (device.MERGE_LEVEL_KERNELS), so that over
    DEVICE_MERGE_RECORDS it is the padding."""
    if counters is None:
        return
    counters.increment(TaskCounter.DEVICE_MERGE_LAUNCHES,
                       sum(n for n, _r in tally.values()))
    counters.increment(TaskCounter.DEVICE_MERGE_LAUNCH_ROWS,
                       sum(tally[k][1] for k in device.MERGE_LEVEL_KERNELS
                           if k in tally))


def _merge_resident_partitioned(live: Sequence[Run], num_partitions: int
                                ) -> Tuple[np.ndarray, np.ndarray]:
    """Multi-partition device-resident merge: each run's HBM key columns are
    (partition, key)-sorted, so partition p occupies the contiguous rows
    [row_index[p], row_index[p+1]) of its device view — merge those slices
    per partition and emit partitions in order.  Within a partition, slices
    merge in run order (stable ties = MergeQueue age semantics), so the
    result is bit-identical to the generic concat+sort merge.  Returns
    (permutation into the concat of live runs' batches, row_index)."""
    offs = np.zeros(len(live), dtype=np.int64)
    if len(live) > 1:
        np.cumsum([r.batch.num_records for r in live[:-1]], out=offs[1:])
    pieces: List[np.ndarray] = []
    counts = np.zeros(num_partitions, dtype=np.int64)
    for p in range(num_partitions):
        slices, bases = [], []
        for r, off in zip(live, offs):
            lo, hi = int(r.row_index[p]), int(r.row_index[p + 1])
            if hi > lo:
                lanes_dev, lens_dev, _lo0, _n = r.batch.dev_keys
                slices.append((lanes_dev, lens_dev, lo, hi))
                bases.append(off + lo)
        if not slices:
            continue
        perm = device.merge_resident_slices(slices)
        with tracing.span("merge.gather", cat="merge", rows=len(perm)):
            cnts = np.asarray([hi - lo for (_l, _n, lo, hi) in slices],
                              dtype=np.int64)
            bounds = np.zeros(len(cnts) + 1, dtype=np.int64)
            np.cumsum(cnts, out=bounds[1:])
            sl = np.searchsorted(bounds[1:], perm, side="right")
            pieces.append(np.asarray(bases, dtype=np.int64)[sl] +
                          (perm - bounds[sl]))
        counts[p] = len(perm)
    row_index = np.zeros(num_partitions + 1, dtype=np.int64)
    np.cumsum(counts, out=row_index[1:])
    total = np.concatenate(pieces) if pieces else np.zeros(0, np.int64)
    return total, row_index


def merge_sorted_runs(runs: Sequence[Run], num_partitions: int,
                      key_width: int,
                      counters: Optional[TezCounters] = None,
                      engine: str = "device",
                      merge_factor: int = 0,
                      key_normalizer: Optional[Callable[[bytes], bytes]]
                      = None,
                      device_min_records: int = DEVICE_SORT_MIN_RECORDS,
                      final: bool = True) -> Run:
    """k-way merge of partition-sorted runs (TezMerger analog): concatenate,
    stable device sort by (partition, key prefix), host tie-break.

    `counters` always receives the rows under the engine that merged them;
    final=False marks a pass the task does not report as its merge (inner
    cascade levels, block-merge rounds, background mem->disk merges), which
    skips MERGED_MAP_OUTPUTS and the merge wall.

    merge_factor > 0 bounds how many runs merge per pass (io.sort.factor):
    each device sort then works on at most factor runs' worth of rows, which
    bounds the PER-MERGE device working set (HBM buffers + sort scratch);
    host-side runs still coexist — the host-spill path in DeviceSorter is
    what bounds host RAM (SURVEY.md §5.7 multi-pass external merge)."""
    if merge_factor > 1 and len(runs) > merge_factor:
        level = list(runs)
        while len(level) > merge_factor:
            nxt = []
            for i in range(0, len(level), merge_factor):
                chunk = level[i:i + merge_factor]
                # only the final pass reports MERGED_MAP_OUTPUTS / millis
                nxt.append(chunk[0] if len(chunk) == 1 else
                           merge_sorted_runs(
                               chunk, num_partitions, key_width, counters,
                               engine, key_normalizer=key_normalizer,
                               device_min_records=device_min_records,
                               final=False))
            level = nxt
        runs = level
    t0 = time.time()
    if engine != "host" and key_normalizer is None:
        live = [r for r in runs if r.batch.num_records > 0]
        views = [r.batch.dev_keys for r in live]
        if live and all(v is not None for v in views):
            # mixed lane widths are fine: narrower views widen with zero
            # lanes on device (zero = absent bytes in the lane encoding)
            # device-resident merge: key columns are already in HBM from
            # the producers' span sorts — only the permutation comes back
            # (VERDICT r1 item 4; TezMerger semantics preserved)
            with device.launch_tally() as tally:
                if num_partitions == 1:
                    perm = device.merge_resident_slices(views)
                    row_index = None
                else:
                    perm, row_index = _merge_resident_partitioned(
                        live, num_partitions)
            _record_merge_ms(counters, t0)
            _record_launches(counters, tally)
            with tracing.span("merge.gather", cat="merge", rows=len(perm)):
                batch = KVBatch.concat([r.batch for r in live])
            sorted_batch = _take(batch, perm, counters)
            _record_merge(counters, t0, "device", batch.num_records,
                          len(runs), final)
            if row_index is None:
                row_index = np.array([0, sorted_batch.num_records], np.int64)
            return Run(sorted_batch, row_index)
    # hybrid routing for the generic path only — when producer key lanes
    # are already device-resident the resident merge above is cheaper than
    # any host sort regardless of size
    engine = _route_engine(engine, sum(r.batch.num_records for r in runs),
                           device_min_records)
    if engine == "host" and key_normalizer is None:
        # fused fast path: group-scan each sorted run, k-way merge group
        # heads, emit contiguous segment copies — no concatenation and no
        # per-row gather.  Equal (partition, key) groups emit in `runs`
        # order (MergeQueue age semantics).
        live = [r for r in runs if r.batch.num_records > 0]
        if live and all(r.num_partitions == num_partitions for r in live):
            from tez_tpu.ops.native import merge_emit_native
            fused = merge_emit_native(
                [(r.batch.key_bytes, r.batch.key_offsets,
                  r.batch.val_bytes, r.batch.val_offsets, r.row_index)
                 for r in live], num_partitions)
            if fused is not None:
                out_kb, out_ko, out_vb, out_vo, row_index = fused
                _record_merge(counters, t0, "host", len(out_ko) - 1,
                              len(runs), final)
                return Run(KVBatch(out_kb, out_ko, out_vb, out_vo),
                           row_index)
    # the host's preparation of a merge of runs that are not on the device:
    # the runs joined into one batch, then (device engine) its keys as lanes
    with tracing.span("merge.encode", cat="merge", stage="concat",
                      runs=len(runs)):
        batch = KVBatch.concat([r.batch for r in runs])
        partitions = np.concatenate([
            np.repeat(np.arange(r.num_partitions, dtype=np.int32),
                      np.diff(r.row_index)) for r in runs]) \
            if runs else np.zeros(0, np.int32)
        if key_normalizer is not None:
            sort_bytes, sort_offsets = normalize_batch_keys(batch,
                                                            key_normalizer)
        else:
            sort_bytes, sort_offsets = batch.key_bytes, batch.key_offsets
    if engine == "host":
        # native merge: the runs are ALREADY (partition, key)-sorted, so a
        # ladder of in-place merges (O(n log k)) replaces a full re-sort;
        # full-key compares, run-order ties (= MergeQueue age order via the
        # concat index), GIL released
        from tez_tpu.ops.native import merge_runs_native
        run_bounds = np.zeros(len(runs) + 1, dtype=np.int64)
        np.cumsum([r.batch.num_records for r in runs], out=run_bounds[1:])
        perm_n = merge_runs_native(
            sort_bytes, sort_offsets,
            partitions if num_partitions > 1 else None, run_bounds)
        sorted_batch = _take(batch, perm_n, counters)
        sorted_partitions = partitions[perm_n]
        _record_merge(counters, t0, "host", batch.num_records,
                      len(runs), final)
        return Run.from_sorted_batch(sorted_batch, sorted_partitions,
                                     num_partitions)
    with tracing.span("merge.encode", cat="merge", stage="lanes",
                      rows=batch.num_records):
        lanes, lengths = encode_keys(sort_bytes, sort_offsets, key_width)
    # the concatenation is in run-arrival order: one stable sort of it IS
    # the merge (equal keys keep run order); prefix-equal beyond-cap keys
    # still fall to the host tie-break below
    t_dev = time.time()
    with device.launch_tally() as tally:
        perm = device.merge_runs(partitions, lanes, lengths)
    _record_merge_ms(counters, t_dev)
    _record_launches(counters, tally)
    sorted_batch = _take(batch, perm, counters)
    with tracing.span("merge.gather", cat="merge", rows=len(perm)):
        sorted_partitions = partitions[perm]
        sort_lengths, keyfn = _sorted_key_view(sort_bytes, sort_offsets,
                                               perm)
        refinement = _exact_tiebreak(sort_lengths, sorted_partitions,
                                     lanes[perm], key_width, keyfn)
    if refinement is not None:
        sorted_batch = _take(sorted_batch, refinement, counters)
    _record_merge(counters, t0, "device", batch.num_records, len(runs),
                  final)
    with tracing.span("merge.encode", cat="merge", stage="index"):
        return Run.from_sorted_batch(sorted_batch, sorted_partitions,
                                     num_partitions)


# ---------------------------------------------------------------------------
# combiners
# ---------------------------------------------------------------------------
def sum_long_combiner(run: Run) -> Run:
    """Vectorized combine for 8-byte big-endian-long values: sums values of
    equal (partition, key) groups (the WordCount/OrderedWordCount combiner)."""
    from tez_tpu.ops.serde import VarLongSerde
    batch = run.batch
    n = batch.num_records
    if n == 0:
        return run
    ko, kb = batch.key_offsets, batch.key_bytes
    lengths = ko[1:] - ko[:-1]
    partitions = np.repeat(np.arange(run.num_partitions, dtype=np.int32),
                           np.diff(run.row_index))
    # adjacent-equal detection (sorted within partition): same partition,
    # same length, same bytes
    same = np.zeros(n, dtype=bool)
    if n > 1:
        cand = (partitions[1:] == partitions[:-1]) & \
            (lengths[1:] == lengths[:-1])
        idx = np.flatnonzero(cand)
        same[idx + 1] = adjacent_equal_rows(kb, ko, idx)
    group_starts = np.flatnonzero(~same)
    # decode values (8-byte BE unsigned with sign-flip encoding); the fast
    # path requires every value to be exactly 8 bytes (long serde), not just
    # the right total
    uniform_long = bool(np.all(np.diff(batch.val_offsets) == 8))
    vals = batch.val_bytes.reshape(n, 8) if uniform_long else None
    serde = VarLongSerde()
    if vals is not None:
        nums = vals.astype(np.uint64)
        weights = (256 ** np.arange(7, -1, -1)).astype(np.uint64)
        unsigned = (nums * weights).sum(axis=1, dtype=np.uint64)
        # encoding is val + 2^63 (mod 2^64) == top-bit flip of two's complement
        decoded = (unsigned ^ np.uint64(1 << 63)).view(np.int64)
        sums = np.add.reduceat(decoded, group_starts)
        out_vals = b"".join(serde.to_bytes(int(s)) for s in sums)
        vb = np.frombuffer(out_vals, dtype=np.uint8).copy()
        vo = np.arange(len(group_starts) + 1, dtype=np.int64) * 8
    else:
        # ragged fallback
        sums = []
        bounds = np.append(group_starts, n)
        for s, e in zip(bounds[:-1], bounds[1:]):
            sums.append(sum(serde.from_bytes(batch.value(i))
                            for i in range(s, e)))
        out_vals = b"".join(serde.to_bytes(s) for s in sums)
        vb = np.frombuffer(out_vals, dtype=np.uint8).copy()
        vo = np.arange(len(group_starts) + 1, dtype=np.int64) * 8
    kb2, ko2 = gather_ragged(kb, ko, group_starts)
    new_counts = np.bincount(partitions[group_starts],
                             minlength=run.num_partitions).astype(np.int64)
    row_index = np.zeros(run.num_partitions + 1, dtype=np.int64)
    np.cumsum(new_counts, out=row_index[1:])
    return Run(KVBatch(kb2, ko2, vb, vo), row_index)
