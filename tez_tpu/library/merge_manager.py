"""Consumer-side bounded-memory shuffle merge (the MergeManager analog).

Reference parity: tez-runtime-library/.../common/shuffle/orderedgrouped/
MergeManager.java:83 — `reserve()` admission with stall (:404), the
commitMemory >= mergeThreshold mem->disk merge trigger (:387), the on-disk
merge cascade, and a final merge over leftover memory + disk segments —
re-thought for this framework's vectorized data plane:

- Fetched batches are already partition-sorted runs (the producer ships
  sorted slices), so a "mem->disk merge" is one vectorized k-way merge of
  the committed batches written out as a block-chunked sorted file
  (ops.runformat.ChunkedRunWriter), and the DISK admission target just
  streams the oversized batch to its own chunked file — no record-at-a-time
  byte crunching anywhere.
- The final merge is vectorized + in-RAM when everything fits the budget
  (the common case, byte-for-byte the old fast path), and otherwise a
  streaming heap-merge over block-buffered disk runs whose resident set is
  one block per run — a partition far larger than host RAM reduces with
  peak memory ~ budget + num_runs * block_bytes.

Equal keys across different source runs emerge in run-arrival order (the
reference's MergeQueue makes the same arrival-dependent choice; within one
source the producer's sorted order is preserved exactly).
"""
from __future__ import annotations

import logging
import os
import threading
import uuid
from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from tez_tpu.common import tracing
from tez_tpu.common.counters import TaskCounter, TezCounters
from tez_tpu.ops.block_merge import iter_merged_blocks
from tez_tpu.ops.runformat import (ChunkedRunWriter, KVBatch, Run,
                                   iter_chunked_run)
from tez_tpu.ops.sorter import merge_sorted_runs, normalize_batch_keys

log = logging.getLogger(__name__)


def _as_run(batch: KVBatch) -> Run:
    return Run(batch, np.array([0, batch.num_records], dtype=np.int64))


class _FileSource:
    """Disk-direct shuffle source: one partition of a producer's
    partition-indexed output file, merged straight off the producer's disk
    (LocalDiskFetchedInput analog) — never copied into this consumer's
    memory budget or spill dir."""

    __slots__ = ("path", "partition", "nbytes")

    def __init__(self, path: str, partition: int, nbytes: int):
        self.path = path
        self.partition = partition
        self.nbytes = nbytes


class ShuffleMergeManager:
    """Admission + background mem->disk merging for one consumer input.

    Thread model: fetch threads call `commit()` (which may stall on the
    memory budget); one background merger thread frees memory by merging
    committed batches to disk; `finish()` joins the merger and hands back
    either a fully-merged in-RAM batch or a streaming plan.
    """

    def __init__(self, counters: TezCounters, budget_bytes: int,
                 spill_dir: str,
                 key_width: int = 16,
                 engine: str = "device",
                 device_min_records: "int | None" = None,
                 merge_factor: int = 64,
                 merge_threshold: float = 0.9,
                 eager_threshold: float = 0.0,
                 max_single_fraction: float = 0.25,
                 key_normalizer: Optional[Callable[[bytes], bytes]] = None,
                 codec: Optional[str] = None,
                 block_records: int = 65536,
                 async_depth: int = 0,
                 instrument: bool = False,
                 breaker: Any = None,
                 watchdog_dispatch_ms: Optional[float] = None,
                 watchdog_readback_ms: Optional[float] = None):
        self.counters = counters
        self.budget = int(budget_bytes)
        self.spill_dir = spill_dir
        self.key_width = key_width
        from tez_tpu.ops.sorter import resolve_engine
        self.engine = resolve_engine(engine)
        from tez_tpu.ops.sorter import DEVICE_SORT_MIN_RECORDS
        self.device_min_records = DEVICE_SORT_MIN_RECORDS \
            if device_min_records is None else device_min_records
        self.merge_factor = max(2, merge_factor)
        self.merge_threshold = merge_threshold
        # push-based shuffle's merge-wave overlap: > 0 lets the background
        # merger start a mem->disk merge once committed memory crosses
        # eager_threshold * budget — well before the admission-pressure
        # threshold above — so merge work runs WHILE the map wave is still
        # pushing spills instead of serializing after it.  0 = historical
        # behavior (merge only under admission pressure).
        self.eager_threshold = max(0.0, float(eager_threshold))
        self.max_single = int(self.budget * max_single_fraction) \
            if self.budget > 0 else 0
        self.key_normalizer = key_normalizer
        self.codec = codec
        self.block_records = block_records

        self.lock = threading.Condition()
        # committed in-memory batches: (slot, seq, batch) — slot-major
        # order keeps the no-spill final merge byte-identical to the
        # historical slot-ordered merge; seq is global arrival order
        self._mem: List[Tuple[int, int, KVBatch]] = []
        self._mem_bytes = 0
        self._seq = 0
        self._disk_runs: List[str] = []          # chunked run paths, by age
        self._disk_slots: set = set()            # slots with data on disk
        # disk-direct sources (producer-owned files; never merged by the
        # background merger — they cost no memory and no consumer disk)
        self._file_sources: List[Tuple[int, int, _FileSource]] = []
        self._merging: List[Tuple[int, int, KVBatch]] = []  # claimed by merger
        self._stalled = 0                        # fetchers waiting in commit
        self._slot_gen: dict = {}                # slot -> reset generation
        self._mem_to_disk = 0
        self._disk_to_disk = 0
        self.peak_mem_bytes = 0
        self._poisoned: Optional[str] = None
        self._closed = False
        self._error: Optional[BaseException] = None
        # --- async merge plane (tez.runtime.merge.async.depth > 0) ---
        # background merges submit through an AsyncSpanPipeline instead of
        # running inline on the merger thread: the chunked-run disk write of
        # merge k (readback stage) overlaps the device dispatch of merge
        # k+1, and in-flight fetch commits overlap both.  async_depth=0 is
        # byte-for-byte the historical synchronous merger.
        self.async_depth = max(0, int(async_depth))
        self._instrument = instrument
        self._pipe_seq = 0              # submission order (= fold order)
        self._pending_out: dict = {}    # seq -> completed, not yet folded
        self._next_out = 0
        self._disk_claim: Optional[List[str]] = None
        self._pipeline = None
        if self.budget > 0 and self.async_depth > 0:
            self._pipeline = self._build_pipeline(
                breaker, watchdog_dispatch_ms, watchdog_readback_ms)
        self._merger: Optional[threading.Thread] = None
        if self.budget > 0:
            # the merger thread (and, through it, the async merge lane)
            # works for the task that built this manager
            self._merger = threading.Thread(target=tracing.bound(
                                                self._merge_loop),
                                            daemon=True,
                                            name="shuffle-merger")
            self._merger.start()

    def _build_pipeline(self, breaker: Any,
                        watchdog_dispatch_ms: Optional[float],
                        watchdog_readback_ms: Optional[float]):
        """The merge dispatch lane: same AsyncSpanPipeline (and the same
        PR-5 containment ladder — watchdog, circuit breaker, OOM
        span-halving, host failover from raw payloads) that serves the
        producer sort side, pointed at merge work.  Dispatch-wait latency
        lands in the "device.merge" histogram instead of the sort plane's
        device.dispatch_wait."""
        from tez_tpu.ops import async_stage
        from tez_tpu.ops import sorter as _sorter
        return async_stage.AsyncSpanPipeline(
            dispatch_fn=self._pipe_dispatch,
            readback_fn=self._pipe_readback,
            on_complete=self._pipe_complete,
            depth=self.async_depth,
            readback_workers=1,
            counters=self.counters,
            instrument=self._instrument,
            name="merge-pipeline",
            failover_fn=self._pipe_failover,
            oom_retry_fn=self._pipe_oom_retry,
            breaker=breaker,
            watchdog_dispatch_ms=_sorter.DEVICE_WATCHDOG_DISPATCH_MS
            if watchdog_dispatch_ms is None else watchdog_dispatch_ms,
            watchdog_readback_ms=_sorter.DEVICE_WATCHDOG_READBACK_MS
            if watchdog_readback_ms is None else watchdog_readback_ms,
            dispatch_wait_hist="device.merge")

    # ------------------------------------------------------------- admission
    def slot_generation(self, slot: int) -> int:
        """Current reset-generation of a slot.  Fetchers capture this BEFORE
        fetching and pass it to commit(): a commit whose generation is stale
        (the slot reset mid-fetch) is dropped instead of stored, so a new
        producer attempt's data can never be discarded by the old attempt's
        late-arriving fetch."""
        with self.lock:
            return self._slot_gen.get(slot, 0)

    def commit(self, slot: int, batch: KVBatch, generation: int = 0) -> bool:
        """Account a fetched (sorted) batch.  MEM target when it fits the
        budget — stalling while the merger frees memory (reserve():404
        semantics) — DISK target for oversized batches (maxSingleShuffleLimit
        analog): streamed straight to its own chunked run.  Returns False if
        the batch was dropped as stale (slot reset since `generation`)."""
        if self.budget <= 0:
            with self.lock:
                if self._slot_gen.get(slot, 0) != generation:
                    return False
                self._mem.append((slot, self._seq, batch))
                self._seq += 1
                self._mem_bytes += batch.nbytes
                self.peak_mem_bytes = max(self.peak_mem_bytes, self._mem_bytes)
            self.counters.increment(TaskCounter.SHUFFLE_BYTES_TO_MEM,
                                    batch.nbytes)
            return True
        if batch.nbytes > self.max_single:
            path = self._write_chunked([_as_run(batch)])
            with self.lock:
                if self._slot_gen.get(slot, 0) != generation:
                    try:
                        os.remove(path)
                    except OSError:
                        pass
                    return False
                self._disk_runs.append(path)
                self._disk_slots.add(slot)
                if len(self._disk_runs) >= self.merge_factor:
                    # wake the merger the moment the cascade trigger
                    # crosses instead of up to a poll period later
                    self.lock.notify_all()
            self.counters.increment(TaskCounter.SHUFFLE_BYTES_TO_DISK,
                                    batch.nbytes)
            return True
        with self.lock:
            while self._mem_bytes + batch.nbytes > self.budget and \
                    self._error is None and self._poisoned is None:
                if not self._mem and not self._merging:
                    # nothing the merger could free: the batch itself is
                    # what's over budget (many stalled fetchers, tiny
                    # budget).  Fall through and admit anyway — peak memory
                    # then exceeds the budget by at most one sub-max_single
                    # batch, which beats deadlocking the fetch forever.
                    break
                self._stalled += 1           # merger merges on our behalf
                self.lock.notify_all()
                try:
                    self.lock.wait(0.1)
                finally:
                    self._stalled -= 1
            self._raise_if_broken()
            if self._slot_gen.get(slot, 0) != generation:
                return False
            self._mem.append((slot, self._seq, batch))
            self._seq += 1
            self._mem_bytes += batch.nbytes
            self.peak_mem_bytes = max(self.peak_mem_bytes, self._mem_bytes)
            if self._mem_bytes >= self.budget * self._wake_threshold():
                self.lock.notify_all()
        self.counters.increment(TaskCounter.SHUFFLE_BYTES_TO_MEM, batch.nbytes)
        return True

    def commit_local_file(self, slot: int, path: str, partition: int,
                          nbytes: int, generation: int = 0) -> bool:
        """Admit a disk-direct source (same-host producer's partition-
        indexed file).  Costs no memory budget and no consumer disk; the
        blocks stream from the producer's file at merge time.  Returns
        False if dropped as stale (slot reset since `generation`)."""
        with self.lock:
            if self._slot_gen.get(slot, 0) != generation:
                return False
            self._file_sources.append(
                (slot, self._seq, _FileSource(path, partition, nbytes)))
            self._seq += 1
        return True

    def on_slot_reset(self, slot: int) -> List[KVBatch]:
        """A producer is re-running.  The slot's generation bumps (so
        in-flight fetches of the old attempt drop at commit), its in-memory
        batches are discarded (and returned for accounting); if the slot's
        data already merged to disk — or is mid-merge right now — the state
        is unrecoverable in place: poison, so the consumer attempt fails
        loudly and re-runs with fresh fetches (the reference's
        too-many-failures consumer-kill escape hatch)."""
        with self.lock:
            self._slot_gen[slot] = self._slot_gen.get(slot, 0) + 1
            if slot in self._disk_slots or \
                    any(s == slot for s, _, _ in self._merging):
                self._poisoned = (
                    f"slot {slot} re-ran after its data merged to disk; "
                    f"consumer must re-fetch from scratch")
                self.lock.notify_all()
                return []
            dropped = [b for s, _, b in self._mem if s == slot]
            self._mem = [(s, q, b) for s, q, b in self._mem if s != slot]
            self._mem_bytes -= sum(b.nbytes for b in dropped)
            # disk-direct sources are never folded into shared merge files:
            # dropping the slot's entries is a complete undo
            self._file_sources = [t for t in self._file_sources
                                  if t[0] != slot]
            self.lock.notify_all()
            return dropped

    def _raise_if_broken(self) -> None:
        if self._error is not None:
            raise RuntimeError("shuffle merger failed") from self._error
        if self._poisoned is not None:
            raise RuntimeError(f"shuffle merge state lost: {self._poisoned}")

    def quiesce(self, timeout: Optional[float] = None) -> bool:
        """Block until the background merger has nothing runnable and
        nothing in flight (or the manager broke/closed).  Every state
        transition toward idle already notifies the manager Condition,
        so this is a real CV wait, not a poll — tests and drain paths
        that previously slept on private counters use this instead.
        Returns False only on timeout."""
        def _idle() -> bool:
            if self._closed or self._error is not None or \
                    self._poisoned is not None:
                return True
            if self._merging or self._disk_claim is not None:
                return False
            # sync disk cascades claim in place (the run list keeps the
            # merging prefix until the replace), so "due" covers them
            return not self._mem_merge_due() and \
                not self._disk_merge_due_locked()
        with self.lock:
            return bool(self.lock.wait_for(_idle, timeout))

    # ------------------------------------------------------- background merge
    def _wake_threshold(self) -> float:
        """Fraction of the budget at which a commit wakes the merger: the
        eager (push-overlap) threshold when enabled, else the admission-
        pressure threshold."""
        if 0.0 < self.eager_threshold < self.merge_threshold:
            return self.eager_threshold
        return self.merge_threshold

    def _mem_merge_due(self) -> bool:
        """Under lock: committed memory crossed the merge threshold (the
        eager one when push overlap is on), OR a fetcher is stalled on
        admission and there is anything at all to free (without the second
        clause a batch that doesn't fit the remaining budget while memory
        sits below the threshold would stall its fetcher forever)."""
        if not self._mem:
            return False
        return self._mem_bytes >= self.budget * self._wake_threshold() or \
            self._stalled > 0

    def _disk_merge_due_locked(self) -> bool:
        """Under lock: a disk cascade is runnable — the trigger crossed and
        no cascade is already in flight (at most one at a time keeps the
        run-age bookkeeping trivial and bounds disk-write fan-out)."""
        return self._disk_claim is None and \
            len(self._disk_runs) >= self.merge_factor

    def _merge_loop(self) -> None:
        if self._pipeline is not None:
            return self._merge_loop_async()
        while True:
            with self.lock:
                while not self._closed and self._poisoned is None and \
                        not self._mem_merge_due() and \
                        len(self._disk_runs) < self.merge_factor:
                    # every trigger crossing notifies (commit threshold,
                    # disk-run registration, stall, close): the wait is a
                    # backstop, not the wake mechanism
                    self.lock.wait(2.0)
                if self._closed or self._poisoned is not None:
                    return
                work = None
                if self._mem_merge_due():
                    # CLAIM the batches: they leave _mem (so a concurrent
                    # slot reset can't silently mutate the working set) but
                    # stay accounted in _mem_bytes until the write lands
                    work = ("mem", list(self._mem))
                    self._merging = list(self._mem)
                    self._mem = []
                elif len(self._disk_runs) >= self.merge_factor:
                    work = ("disk", self._disk_runs[:self.merge_factor])
            try:
                if work[0] == "mem":
                    self._do_mem_to_disk(work[1])
                else:
                    self._do_disk_to_disk(work[1])
            except BaseException as e:  # noqa: BLE001 — surface to callers
                with self.lock:
                    self._error = e
                    self.lock.notify_all()
                return

    def _merge_loop_async(self) -> None:
        """Async flavor: CLAIM work under the lock, hand it to the merge
        pipeline, immediately look for more.  Completion accounting happens
        in _pipe_complete (seq order), so disk-run age order is identical
        to the synchronous merger's."""
        while True:
            with self.lock:
                while not self._closed and self._poisoned is None and \
                        not self._mem_merge_due() and \
                        not self._disk_merge_due_locked():
                    self.lock.wait(2.0)
                if self._closed or self._poisoned is not None:
                    return
                if self._mem_merge_due():
                    items = list(self._mem)
                    self._merging = self._merging + items
                    self._mem = []
                    work = ("mem", items)
                elif self._disk_merge_due_locked():
                    paths = self._disk_runs[:self.merge_factor]
                    self._disk_runs = self._disk_runs[self.merge_factor:]
                    self._disk_claim = list(paths)
                    work = ("disk", paths)
                else:
                    continue        # woken with nothing runnable
                seq = self._pipe_seq
                self._pipe_seq += 1
            try:
                self._pipeline.submit(seq, work)
            except BaseException as e:  # noqa: BLE001 — surface to callers
                with self.lock:
                    self._error = e
                    self.lock.notify_all()
                return

    # -------------------------------------------------- merge pipeline lane
    def _merge_mem_items(self, items: List[Tuple[int, int, KVBatch]],
                         engine: Optional[str] = None) -> Run:
        """One mem->disk merge body (slot-major, then arrival — the order
        every path in this file merges by).  engine overrides for the
        containment plane's host failover / on-device OOM retry."""
        items = sorted(items)
        runs = [_as_run(b) for _, _, b in items if b.num_records > 0]
        return merge_sorted_runs(runs, 1, self.key_width,
                                 counters=self.counters,
                                 engine=self.engine if engine is None
                                 else engine,
                                 device_min_records=self.device_min_records,
                                 merge_factor=self.merge_factor,
                                 key_normalizer=self.key_normalizer,
                                 final=False) \
            if runs else _as_run(KVBatch.empty())

    def _pipe_dispatch(self, payload):
        """Pipeline dispatch stage (staging thread): the device/host merge
        itself.  The chaos seams (device.dispatch.{oom,hang}) and the
        dispatch watchdog wrap this call exactly as they wrap sorts."""
        kind, raw = payload
        if kind == "mem":
            return (kind, raw, self._merge_mem_items(raw))
        return (kind, raw, self._stream_merge_to_disk(raw))

    def _pipe_readback(self, inflight, ids):
        """Pipeline readback stage (worker thread): persist a mem merge as
        a chunked run.  This is the stage that overlaps the NEXT merge's
        dispatch — disk write k runs concurrently with device merge k+1."""
        kind, raw, result = inflight
        if kind == "mem":
            return (kind, raw, self._write_chunked([result]))
        return (kind, raw, result)      # disk cascades write while merging

    def _pipe_failover(self, ids, payloads):
        """Containment: re-run a claimed merge on the HOST engine from the
        raw payload (committed batches / input run paths) and persist it —
        the merge twin of DeviceSorter._async_failover."""
        kind, raw = payloads[0]
        if kind == "mem":
            merged = self._merge_mem_items(raw, engine="host")
            return (kind, raw, self._write_chunked([merged]))
        return (kind, raw, self._stream_merge_to_disk(raw, engine="host"))

    def _pipe_oom_retry(self, ids, payloads):
        """OOM ladder: halve the run set, merge each half on device, then
        merge the two results — halves are contiguous prefixes of the
        slot-major order, so the composed merge is bit-identical (run-age
        tie order preserved).  Raises to decline below 2 live runs (the
        ladder then falls through to host failover)."""
        kind, raw = payloads[0]
        if kind != "mem":
            raise MemoryError("disk cascade OOM: no device span to split")
        items = sorted(raw)
        live = [t for t in items if t[2].num_records > 0]
        if len(live) < 2:
            raise MemoryError("merge OOM split floor reached")
        mid = len(live) // 2
        halves = [self._merge_mem_items(part, engine="device")
                  for part in (live[:mid], live[mid:])]
        merged = merge_sorted_runs(halves, 1, self.key_width,
                                   counters=self.counters, engine="device",
                                   device_min_records=self.device_min_records,
                                   merge_factor=self.merge_factor,
                                   key_normalizer=self.key_normalizer,
                                   final=False)
        return (kind, raw, self._write_chunked([merged]))

    def _pipe_complete(self, ids, result) -> None:
        """Pipeline completion hook: stash by submission seq and fold every
        consecutive finished merge into the manager state (out-of-order
        readbacks never reorder the disk-run age list)."""
        with self.lock:
            for sid in ids:             # merge groups are single-span
                self._pending_out[sid] = result
            while self._next_out in self._pending_out:
                kind, raw, path = self._pending_out.pop(self._next_out)
                self._next_out += 1
                if kind == "mem":
                    self._fold_mem_locked(raw, path)
                else:
                    self._fold_disk_locked(raw, path)
            self.lock.notify_all()

    def _fold_mem_locked(self, items, path: str) -> None:
        claimed = {q for _, q, _ in items}
        self._merging = [t for t in self._merging if t[1] not in claimed]
        if self._poisoned is not None:
            # a claimed slot reset mid-merge: the written file contains
            # stale data — discard it; the consumer attempt re-runs
            try:
                os.remove(path)
            except OSError:
                pass
            return
        self._disk_slots.update(s for s, _, _ in items)
        self._mem_bytes -= sum(b.nbytes for _, _, b in items)
        self._disk_runs.append(path)
        self._mem_to_disk += 1
        self.counters.increment(TaskCounter.NUM_MEM_TO_DISK_MERGES)

    def _fold_disk_locked(self, paths: List[str], out: str) -> None:
        self._disk_claim = None
        if self._poisoned is not None:
            for p in list(paths) + [out]:
                try:
                    os.remove(p)
                except OSError:
                    pass
            return
        # the claimed paths were the OLDEST runs (list prefix): the result
        # re-enters at the front, preserving age order exactly like the
        # synchronous index-based replace
        self._disk_runs.insert(0, out)
        self._disk_to_disk += 1
        for p in paths:
            try:
                os.remove(p)
            except OSError:
                pass
        self.counters.increment(TaskCounter.NUM_DISK_TO_DISK_MERGES)

    def _do_mem_to_disk(self, items: List[Tuple[int, int, KVBatch]]) -> None:
        merged = self._merge_mem_items(items)
        path = self._write_chunked([merged])
        freed = sum(b.nbytes for _, _, b in items)
        with self.lock:
            self._merging = []
            if self._poisoned is not None:
                # a claimed slot reset mid-merge: the written file contains
                # stale data — discard it; the consumer attempt re-runs
                try:
                    os.remove(path)
                except OSError:
                    pass
                self.lock.notify_all()
                return
            self._disk_slots.update(s for s, _, _ in items)
            self._mem_bytes -= freed
            self._disk_runs.append(path)
            self._mem_to_disk += 1
            self.lock.notify_all()
        self.counters.increment(TaskCounter.NUM_MEM_TO_DISK_MERGES)

    def _do_disk_to_disk(self, paths: List[str]) -> None:
        out = self._stream_merge_to_disk(paths)
        with self.lock:
            # replace the merged inputs with the result, keeping age order
            i = self._disk_runs.index(paths[0])
            self._disk_runs = [p for p in self._disk_runs if p not in paths]
            self._disk_runs.insert(i, out)
            self._disk_to_disk += 1
            self.lock.notify_all()
        for p in paths:
            try:
                os.remove(p)
            except OSError:
                pass
        self.counters.increment(TaskCounter.NUM_DISK_TO_DISK_MERGES)

    # ------------------------------------------------------------ disk I/O
    def _write_chunked(self, runs: Sequence[Run]) -> str:
        path = os.path.join(self.spill_dir,
                            f"mmerge_{uuid.uuid4().hex}.crun")
        from tez_tpu.common import metrics
        with metrics.timer("spill.write"):
            w = ChunkedRunWriter(path, codec=self.codec,
                                 block_records=self.block_records)
            for r in runs:
                w.append(r.batch)
            w.close()
        self.counters.increment(TaskCounter.ADDITIONAL_SPILLS_BYTES_WRITTEN,
                                w.bytes_written)
        return path

    def _block_iter(self, source) -> Iterator[KVBatch]:
        """Sorted KVBatch blocks from a chunked run path, a disk-direct
        file source, or an in-RAM batch; resident memory is one block at a
        time for the disk shapes."""
        if isinstance(source, str):
            return iter_chunked_run(source)
        if isinstance(source, _FileSource):
            from tez_tpu.ops.runformat import FileRun
            return FileRun(source.path).iter_partition_blocks(
                source.partition)
        return iter([source])

    def _merged_block_iter(self, sources: Sequence,
                           engine: Optional[str] = None) -> Iterator[KVBatch]:
        """Blockwise vectorized k-way merge over paths/batches (age order =
        source order, so equal keys keep the reference MergeQueue's
        arrival-order semantics)."""
        return iter_merged_blocks(
            [self._block_iter(s) for s in sources], self.key_width,
            engine=self.engine if engine is None else engine,
            key_normalizer=self.key_normalizer,
            merge_factor=self.merge_factor,
            device_min_records=self.device_min_records,
            counters=self.counters)

    def _stream_merge_to_disk(self, paths: List[str],
                              engine: Optional[str] = None) -> str:
        out_path = os.path.join(self.spill_dir,
                                f"mmerge_{uuid.uuid4().hex}.crun")
        w = ChunkedRunWriter(out_path, codec=self.codec,
                             block_records=self.block_records)
        for block in self._merged_block_iter(paths, engine=engine):
            w.append(block)
        w.close()
        self.counters.increment(TaskCounter.ADDITIONAL_SPILLS_BYTES_WRITTEN,
                                w.bytes_written)
        return out_path

    # ------------------------------------------------------------- finish
    def finish(self) -> "MergedResult":
        """Join the merger; decide in-RAM vs streaming final merge."""
        with self.lock:
            self._closed = True
            self.lock.notify_all()
        if self._merger is not None:
            self._merger.join(timeout=300)
        if self._pipeline is not None:
            # in the async plane the background merges were mostly staged
            # (or finished) while fetches were still landing: drain is
            # usually a no-op wait on the tail merge, not a serial replay
            try:
                self._pipeline.drain()
            except BaseException as e:  # noqa: BLE001 — containment floor
                with self.lock:
                    if self._error is None:
                        self._error = e
                    self.lock.notify_all()
        with self.lock:
            self._raise_if_broken()
            # the committed batches pass to the final merge: held here any
            # longer, their views of the producers' key lanes pin HBM until
            # the collector finds this manager's cycle (its pipeline holds
            # its bound methods), DAGs later
            mem, self._mem = sorted(self._mem), []
            disk = list(self._disk_runs)
            # no byte-size filter: empty PARTITIONS never commit (gated by
            # the producer's row-count flags), and a committed source whose
            # records are all zero-length pairs still carries rows
            file_entries = sorted(self._file_sources)
        files = [fs for _, _, fs in file_entries]
        file_bytes = sum(fs.nbytes for fs in files)
        if files and self.budget > 0 and not disk and \
                file_bytes + self._mem_bytes <= \
                self.budget * self.merge_threshold:
            # small disk-direct inputs: cheaper to materialize and take the
            # in-RAM merged-batch path than to stream; slot-major order is
            # preserved by merging them into the mem list under their real
            # (slot, seq) keys
            from tez_tpu.ops.runformat import FileRun
            for s, q, fs in file_entries:
                batch = FileRun(fs.path).partition(fs.partition)
                if batch.num_records > 0:
                    mem.append((s, q, batch))
            mem.sort(key=lambda t: t[:2])
            files = []
        if not disk and not files:
            runs = [_as_run(b) for _, _, b in mem if b.num_records > 0]
            if not runs:
                return MergedResult(batch=KVBatch.empty())
            merged = runs[0] if len(runs) == 1 else merge_sorted_runs(
                runs, 1, self.key_width, counters=self.counters,
                engine=self.engine, merge_factor=self.merge_factor,
                device_min_records=self.device_min_records,
                key_normalizer=self.key_normalizer)
            return MergedResult(batch=merged.batch)
        # leftover memory becomes one more (bounded) sorted segment
        mem_runs = [_as_run(b) for _, _, b in mem if b.num_records > 0]
        mem_seg: Optional[KVBatch] = None
        if mem_runs:
            mem_seg = merge_sorted_runs(
                mem_runs, 1, self.key_width, counters=self.counters,
                engine=self.engine, merge_factor=self.merge_factor,
                device_min_records=self.device_min_records,
                key_normalizer=self.key_normalizer).batch
        return MergedResult(stream=_StreamPlan(self, disk + files, mem_seg))

    def pipeline_events(self) -> List[Tuple[Any, str, str, float]]:
        """Instrumentation events of the async merge lane (instrument=True):
        feed to ops.async_stage.overlap_pairs for the overlap witness."""
        return [] if self._pipeline is None else list(self._pipeline.events)

    def cleanup(self) -> None:
        with self.lock:
            self._closed = True
            self.lock.notify_all()
            paths = list(self._disk_runs)
            self._disk_runs = []
        for p in paths:
            try:
                os.remove(p)
            except OSError:
                pass


class _StreamPlan:
    """Re-iterable streaming merge over disk runs + the leftover mem segment
    (disk blocks re-read on every iteration; memory stays bounded)."""

    def __init__(self, mm: ShuffleMergeManager, disk: List[str],
                 mem_seg: Optional[KVBatch]):
        self.mm = mm
        self.disk = disk
        self.mem_seg = mem_seg

    def _sources(self) -> List[Any]:
        sources: List[Any] = list(self.disk)
        if self.mem_seg is not None:
            sources.append(self.mem_seg)
        return sources

    def iter_batches(self) -> Iterator[KVBatch]:
        """Globally-sorted merged blocks (the vectorized consumer path)."""
        return self.mm._merged_block_iter(self._sources())

    def iter_records(self) -> Iterator[Tuple[bytes, bytes, bytes]]:
        """Per-record view for generic consumers, built on the blockwise
        merge (one normalization pass per block, not per comparison)."""
        norm = self.mm.key_normalizer
        for batch in self.iter_batches():
            if norm is not None:
                nb, no = normalize_batch_keys(batch, norm)
                for i in range(batch.num_records):
                    yield (nb[no[i]:no[i + 1]].tobytes(), batch.key(i),
                           batch.value(i))
            else:
                for i in range(batch.num_records):
                    k = batch.key(i)
                    yield (k, k, batch.value(i))


class MergedResult:
    """Either a fully-merged in-RAM batch or a streaming merge plan."""

    def __init__(self, batch: Optional[KVBatch] = None,
                 stream: Optional[_StreamPlan] = None):
        self.batch = batch
        self.stream = stream

    @property
    def is_streaming(self) -> bool:
        return self.stream is not None
