"""Unordered data path: hash-partitioned (no sort) outputs and streaming
inputs.

Reference parity: tez-runtime-library UnorderedPartitionedKVOutput +
UnorderedPartitionedKVWriter.java:93 (per-partition chained buffers from a
shared pool, background spill, final merge or per-spill events, skip-buffer
direct-write for 1 partition), UnorderedKVOutput (broadcast writer),
ShuffleManager.java:108 + UnorderedKVReader (streaming consumption as
fetches complete, no merge).

TPU shape: records batch into spans -- pre-serialized KVBatches through
``write_batch``, single records through ``write`` -- and a span is
partitioned on the host by three native passes with the GIL released: the
FNV-1a hash of every key (byte-identical to HashPartitioner and the device
kernel), one counting pass that groups the rows by partition in arrival
order, one ragged gather that moves them.  No key ordering and no device
work: this edge's device work is its consumer's (library/join.py).  The
result is the same Run container the shuffle service serves.  The input
hands each fetched KVBatch over once, as its fetch completes
(``iter_batches``), waiting on the fetch table's condition.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from tez_tpu.api.events import (CompositeDataMovementEvent,
                                CompositeRoutedDataMovementEvent,
                                DataMovementEvent, InputFailedEvent,
                                ShufflePayload, TezAPIEvent,
                                VertexManagerEvent, pack_empty_partitions)
from tez_tpu.api.runtime import (KeyValueReader, KeyValuesWriter,
                                 LogicalInput, LogicalOutput, Reader, Writer)
from tez_tpu.common import tracing
from tez_tpu.common.counters import TaskCounter
from tez_tpu.library.inputs import ShuffleFetchTable, _conf_get
from tez_tpu.library.outputs import output_path_component
from tez_tpu.ops.native import (fnv32_partition_native,
                                group_by_partition_native)
from tez_tpu.ops.runformat import KVBatch, Run, gather_ragged
from tez_tpu.ops.serde import get_serde
from tez_tpu.ops.sorter import SpanBuffer
from tez_tpu.shuffle.service import local_shuffle_service

log = logging.getLogger(__name__)


class UnorderedPartitionedWriter:
    """Hash-partition spans on the host, natively; no key sort."""

    def __init__(self, num_partitions: int, span_budget_bytes: int,
                 counters: Any, single_partition_skip_buffer: bool = True):
        self.num_partitions = num_partitions
        self.span_budget = span_budget_bytes
        self.counters = counters
        self._span = SpanBuffer()
        self._runs: List[Run] = []
        self.num_spills = 0
        self.on_spill = None   # pipelined / no-final-merge mode
        # resolved once: find_counter locks the registry per call
        self._out_records_ctr = self.counters.find_counter(
            TaskCounter.OUTPUT_RECORDS)

    def write(self, key: bytes, value: bytes) -> None:
        self._span.add(key, value)
        self._out_records_ctr.increment()
        if self._span.nbytes >= self.span_budget:
            self._partition_span()

    def write_batch(self, batch: KVBatch) -> None:
        """A KVBatch of PRE-SERIALIZED records joins the span whole (no
        per-record Python); it may share a span with single records, whose
        batches then stand before its records."""
        self._span.add_batch(batch)
        self._out_records_ctr.increment(batch.num_records)
        if self._span.nbytes >= self.span_budget:
            self._partition_span()

    def _partition_span(self) -> None:
        if self._span.num_records == 0:
            return
        by_batch = sum(b.num_records for b in self._span.batches)
        batch = self._span.to_batch()
        self._span = SpanBuffer()
        run = self.partition_batch(batch)
        # rows that came as batches (a one-partition output places them
        # where they are): a per-record writer moves this not
        self.counters.increment(TaskCounter.UNORDERED_PARTITION_RECORDS,
                                by_batch)
        if self.on_spill is not None:
            self.on_spill(run, self.num_spills)
        else:
            self._runs.append(run)
            self.counters.increment(TaskCounter.SPILLED_RECORDS,
                                    batch.num_records)
        self.num_spills += 1

    def partition_batch(self, batch: KVBatch) -> Run:
        """The batch's rows grouped by partition, arrival order kept inside
        each: hash, one counting pass, one gather, all native."""
        n, parts = batch.num_records, self.num_partitions
        if parts == 1:
            # skip-buffer direct path (reference :direct-write mode): the
            # broadcast output moves nothing
            return Run(batch, np.array([0, n], dtype=np.int64))
        with tracing.span("unordered.partition", cat="output", stage="hash",
                          rows=n, partitions=parts):
            partitions = fnv32_partition_native(
                batch.key_bytes, batch.key_offsets, parts)
        with tracing.span("unordered.partition", cat="output", stage="group",
                          rows=n, partitions=parts):
            perm, row_index = group_by_partition_native(partitions, parts)
        with tracing.span("unordered.partition", cat="output",
                          stage="gather", rows=n, partitions=parts):
            keys = gather_ragged(batch.key_bytes, batch.key_offsets, perm)
            if batch.val_bytes.size:
                vals = gather_ragged(batch.val_bytes, batch.val_offsets,
                                     perm)
            else:                   # zero-width values: nothing to move
                vals = batch.val_bytes, batch.val_offsets
        return Run(KVBatch(*keys, *vals), row_index)

    def flush(self) -> Optional[Run]:
        if self.on_spill is not None:
            self._partition_span()
            return None
        self._partition_span()
        # the runs leave the writer with this call: the shuffle service
        # holds what it serves, and the writer, in its task's reference
        # cycles, would hold them until a full collection
        runs, self._runs = self._runs, []
        if not runs:
            return Run(KVBatch.empty(),
                       np.zeros(self.num_partitions + 1, dtype=np.int64))
        if len(runs) == 1:
            return runs[0]
        # final "merge": per-partition concatenation (no ordering contract)
        parts: List[KVBatch] = []
        counts = np.zeros(self.num_partitions, dtype=np.int64)
        for p in range(self.num_partitions):
            for r in runs:
                pb = r.partition(p)
                if pb.num_records:
                    parts.append(pb)
                    counts[p] += pb.num_records
        batch = KVBatch.concat(parts) if parts else KVBatch.empty()
        row_index = np.zeros(self.num_partitions + 1, dtype=np.int64)
        np.cumsum(counts, out=row_index[1:])
        return Run(batch, row_index)


class _UnorderedWriterFacade(KeyValuesWriter):
    def __init__(self, writer: UnorderedPartitionedWriter, key_serde, val_serde,
                 context: Any):
        self.writer = writer
        self.key_serde = key_serde
        self.val_serde = val_serde
        self.context = context
        self._n = 0
        self._out_bytes_ctr = context.counters.find_counter(
            TaskCounter.OUTPUT_BYTES)

    def write(self, key: Any, value: Any) -> None:
        k = self.key_serde.to_bytes(key)
        v = self.val_serde.to_bytes(value)
        self.writer.write(k, v)
        self._out_bytes_ctr.increment(len(k) + len(v))
        self._n += 1
        if (self._n & 0x3FFF) == 0:
            self.context.notify_progress()

    def write_batch(self, batch: KVBatch) -> None:
        """Batch-first write path: a KVBatch of PRE-SERIALIZED records goes
        to the span whole, as OrderedPartitionedKVOutput's does to its
        sorter."""
        with tracing.span("output.write", cat="task",
                          rows=batch.num_records):
            self.writer.write_batch(batch)
            self._out_bytes_ctr.increment(batch.nbytes)
            self.context.notify_progress()


class UnorderedPartitionedKVOutput(LogicalOutput):
    """Hash-partitioned, unsorted output."""

    def initialize(self) -> List[TezAPIEvent]:
        ctx = self.context
        buffer_mb = int(_conf_get(
            ctx, "tez.runtime.unordered.output.buffer.size-mb", 100))
        self.key_serde = get_serde(_conf_get(ctx, "tez.runtime.key.class",
                                             "bytes"))
        self.val_serde = get_serde(_conf_get(ctx, "tez.runtime.value.class",
                                             "bytes"))
        self._final_merge = bool(_conf_get(
            ctx, "tez.runtime.enable.final-merge.in.output", True))
        self.writer_impl = UnorderedPartitionedWriter(
            self.num_physical_outputs, buffer_mb << 20, ctx.counters)
        ctx.request_initial_memory(buffer_mb << 20, None,
                           component_type="PARTITIONED_UNSORTED_OUTPUT")
        self.service = local_shuffle_service()
        self.host = ctx.get_service_provider_metadata("shuffle") or \
            {"host": "local", "port": 0}
        self._spills_sent = 0
        if not self._final_merge:
            self.writer_impl.on_spill = self._ship_spill
        return []

    def get_writer(self) -> Writer:
        return _UnorderedWriterFacade(self.writer_impl, self.key_serde,
                                      self.val_serde, self.context)

    def handle_events(self, events: Sequence[TezAPIEvent]) -> None:
        pass

    def _payload(self, run: Run, spill_id: int, last: bool) -> ShufflePayload:
        return ShufflePayload(
            host=self.host["host"], port=self.host["port"],
            path_component=output_path_component(self.context),
            empty_partitions=pack_empty_partitions(
                run.empty_partition_flags()),
            spill_id=spill_id, last_event=last)

    def _ship_spill(self, run: Run, spill_id: int) -> None:
        self.service.register(output_path_component(self.context), spill_id,
                              run)
        self.context.send_events([
            CompositeDataMovementEvent(0, run.num_partitions,
                                       self._payload(run, spill_id, False))])
        self._spills_sent += 1

    def close(self) -> List[TezAPIEvent]:
        run = self.writer_impl.flush()
        path = output_path_component(self.context)
        if run is None:   # per-spill mode: send final marker
            empty = Run(KVBatch.empty(),
                        np.zeros(self.num_physical_outputs + 1,
                                 dtype=np.int64))
            self.service.register(path, self._spills_sent, empty)
            return [CompositeDataMovementEvent(
                0, self.num_physical_outputs,
                self._payload(empty, self._spills_sent, True))]
        self.service.register(path, -1, run)
        from tez_tpu.common import config as C
        from tez_tpu.library.util import conf_get as _conf_get
        vm_payload = {"output_size": run.nbytes}
        if _conf_get(self.context, C.REPORT_PARTITION_STATS.name,
                     C.REPORT_PARTITION_STATS.default):
            vm_payload["partition_sizes"] = [
                run.partition_nbytes(p) for p in range(run.num_partitions)]
        return [
            CompositeDataMovementEvent(
                0, run.num_partitions,
                ShufflePayload(host=self.host["host"], port=self.host["port"],
                               path_component=path,
                               empty_partitions=pack_empty_partitions(
                                   run.empty_partition_flags()),
                               spill_id=-1, last_event=True)),
            VertexManagerEvent(
                target_vertex_name=self.context.destination_vertex_name,
                user_payload=vm_payload),
        ]


class UnorderedKVOutput(UnorderedPartitionedKVOutput):
    """Single-partition / broadcast writer (reference: UnorderedKVOutput
    wrapping the partitioned writer with 1 partition)."""


class StreamingKVReader(KeyValueReader):
    """Yields records as fetches complete — no global wait (reference:
    UnorderedKVReader streaming from the completedInputs queue)."""

    def __init__(self, table: ShuffleFetchTable, key_serde, val_serde,
                 context: Any):
        self.table = table
        self.key_serde = key_serde
        self.val_serde = val_serde
        self.context = context

    def iter_batches(self) -> Iterator[KVBatch]:
        """Each fetched KVBatch once, as its fetch completes, in slot order
        among those that are there.  The wait is on the fetch table's
        condition, which every committed fetch, failed fetch and slot reset
        notifies (``shuffle.wait`` brackets it); the timeout only lets a
        killed attempt out.  A slot reset by an InputFailedEvent starts
        again empty: batches of it already handed over stay handed over,
        and as many of the re-fetched version's are skipped."""
        table = self.table
        consumed: Dict[int, int] = {}
        n = 0
        while True:
            with table.lock:
                ready = self._take_ready(consumed)
                if not ready and table.completed < table.num_slots:
                    with tracing.span("shuffle.wait", cat="shuffle"):
                        ready = self._wait_ready(consumed)
                        tracing.came_after(table.woken_by)
            if not ready:
                break
            for batch in ready:
                n += batch.num_records
                yield batch
        self.context.counters.increment(TaskCounter.INPUT_RECORDS_PROCESSED, n)

    def _take_ready(self, consumed: Dict[int, int]) -> List[KVBatch]:
        """The batches fetched since the last call (table.lock held)."""
        ready: List[KVBatch] = []
        for si, s in enumerate(self.table.slots):
            ready.extend(s.batches[consumed.get(si, 0):])
            consumed[si] = max(consumed.get(si, 0), len(s.batches))
        return ready

    def _wait_ready(self, consumed: Dict[int, int]) -> List[KVBatch]:
        """Block (table.lock held) until a batch is there or every slot is
        complete; [] means the input is at its end."""
        table = self.table
        while True:
            if table.failed:
                raise RuntimeError(f"shuffle failed: {table.diagnostics}")
            ready = self._take_ready(consumed)
            if ready or table.completed >= table.num_slots:
                return ready
            table.lock.wait(0.2)
            # raises TaskKilledError if the AM killed this attempt (or the
            # heartbeat died) — never block forever
            self.context.notify_progress()

    def __iter__(self) -> Iterator[Tuple[Any, Any]]:
        for batch in self.iter_batches():
            for k, v in batch.iter_pairs():
                yield (self.key_serde.from_bytes(k),
                       self.val_serde.from_bytes(v))


class UnorderedKVInput(LogicalInput):
    """Streaming unordered input (ShuffleManager consumer side)."""

    def initialize(self) -> List[TezAPIEvent]:
        ctx = self.context
        self.key_serde = get_serde(_conf_get(ctx, "tez.runtime.key.class",
                                             "bytes"))
        self.val_serde = get_serde(_conf_get(ctx, "tez.runtime.value.class",
                                             "bytes"))
        #: the edge's lane width and the sort plane's routing, for a
        #: processor that works on the fetched batches with the same engine
        #: (library/join.py), as OrderedGroupedKVInput exposes them
        self.key_width = int(_conf_get(ctx, "tez.runtime.tpu.key.width.bytes",
                                       16))
        self.merge_engine = _conf_get(ctx, "tez.runtime.sorter.class", "auto")
        self.merge_min_records = int(_conf_get(
            ctx, "tez.runtime.tpu.device.sort.min.records", 1 << 16))
        self.table = ShuffleFetchTable(ctx, self.num_physical_inputs,
                                       my_partition=ctx.task_index)
        ctx.request_initial_memory(0, None)
        return []

    def handle_events(self, events: Sequence[TezAPIEvent]) -> None:
        for ev in events:
            if isinstance(ev, CompositeRoutedDataMovementEvent):
                payload = ev.user_payload
                for i in range(ev.count):
                    # expansion advances BOTH indices (reference:
                    # CompositeRoutedDataMovementEvent.expand)
                    self.table.on_payload(ev.target_index_start + i,
                                          ev.source_index + i, payload,
                                          version=ev.version,
                                          after=ev.trace_after)
            elif isinstance(ev, DataMovementEvent):
                self.table.on_payload(ev.target_index, ev.source_index,
                                      ev.user_payload, version=ev.version,
                                      after=ev.trace_after)
            elif isinstance(ev, InputFailedEvent):
                self.table.on_input_failed(ev.target_index, ev.version)

    def get_reader(self) -> Reader:
        return StreamingKVReader(self.table, self.key_serde, self.val_serde,
                                 self.context)

    def close(self) -> List[TezAPIEvent]:
        self.table.shutdown()
        return []
