"""Mesh-backed ordered scatter-gather IO: the ICI data plane as a DAG edge.

Reference parity: these classes stand in the exact seam of
OrderedPartitionedKVOutput / OrderedGroupedKVInput (tez-runtime-library
library/{output,input}/), but the edge's data movement is the SPMD
all-to-all exchange (parallel/exchange.py via parallel/coordinator.py)
instead of per-task spill files + N^2 fetches: the producer-side sort, the
shuffle transport, and the consumer-side merge are ONE jitted program over
the device mesh.  Event flow is unchanged — producers still emit
DataMovementEvents through the AM (so vertex managers, recovery and
counters all see a normal SCATTER_GATHER edge); the payload's
`host="(mesh)"` marks that the bytes move through the exchange, not the
shuffle servers.

Contract: key/value slot widths auto-widen to the data, up to
tez.runtime.tpu.mesh.max.key.bytes (256) / .max.value.bytes (1024) — the
configured widths are slot-size hints (loud MeshCapacityError beyond the
caps: per-row HBM slots make one huge record tax every row — such records
belong on the host shuffle edge).  Consumer parallelism MAY exceed the
device count: the exchange routes over the largest device count dividing
the consumer count and splits each device's key-sorted output into its
consumer partitions on host.
"""
from __future__ import annotations

import logging
from typing import Any, List, Optional, Sequence

from tez_tpu.api.events import (CompositeDataMovementEvent,
                                CompositeRoutedDataMovementEvent,
                                DataMovementEvent, InputFailedEvent,
                                ShufflePayload, TezAPIEvent,
                                VertexManagerEvent)
from tez_tpu.api.runtime import (KeyValuesWriter, LogicalInput, LogicalOutput,
                                 Writer)
from tez_tpu.common import tracing
from tez_tpu.common.counters import TaskCounter
from tez_tpu.library.inputs import GroupedKVReader
from tez_tpu.library.util import conf_get as _conf_get
from tez_tpu.ops.runformat import KVBatch
from tez_tpu.ops.serde import get_serde

log = logging.getLogger(__name__)

MESH_HOST = "(mesh)"


def _edge_id(dag_id: Any, src_vertex: str, dst_vertex: str) -> str:
    return f"{dag_id}/{src_vertex}->{dst_vertex}"


class MeshOrderedPartitionedKVOutput(LogicalOutput):
    """Producer half of a mesh SCATTER_GATHER edge: collects this task's
    records and registers them with the exchange coordinator; the last
    producer to close triggers the SPMD exchange (the gang barrier)."""

    def initialize(self) -> List[TezAPIEvent]:
        ctx = self.context
        self.key_serde = get_serde(_conf_get(ctx, "tez.runtime.key.class",
                                             "bytes"))
        self.val_serde = get_serde(_conf_get(ctx, "tez.runtime.value.class",
                                             "bytes"))
        self.key_width = int(_conf_get(ctx, "tez.runtime.tpu.key.width.bytes",
                                       16))
        self.value_width = int(_conf_get(
            ctx, "tez.runtime.tpu.mesh.value.width.bytes", 16))
        self.max_key_bytes = int(_conf_get(
            ctx, "tez.runtime.tpu.mesh.max.key.bytes", 256))
        self.max_value_bytes = int(_conf_get(
            ctx, "tez.runtime.tpu.mesh.max.value.bytes", 1024))
        self.max_rows_per_round = int(_conf_get(
            ctx, "tez.runtime.tpu.mesh.max-rows-per-round", 0))
        self.exchange_engine = str(_conf_get(
            ctx, "tez.runtime.mesh.exchange.engine", "auto"))
        self.exchange_coded = str(_conf_get(
            ctx, "tez.runtime.mesh.exchange.coded", "off"))
        self.exchange_split_after = int(_conf_get(
            ctx, "tez.runtime.mesh.exchange.split.after", 2))
        if _conf_get(ctx, "tez.runtime.key.comparator.class", ""):
            raise ValueError(
                "mesh edges sort by raw key bytes on device; custom "
                "comparators need the host shuffle edge "
                "(OrderedPartitionedKVEdgeConfig)")
        self._pairs: List = []
        self._batches: List[KVBatch] = []
        ctx.request_initial_memory(0, None,
                                   component_type="PARTITIONED_SORTED_OUTPUT")
        return []

    def get_writer(self) -> Writer:
        output = self

        class _W(KeyValuesWriter):
            supports_batch = True   # no custom-partitioner mode on mesh edges

            def write(self, key, value) -> None:
                k = output.key_serde.to_bytes(key)
                v = output.val_serde.to_bytes(value)
                output._pairs.append((k, v))
                output.context.counters.increment(TaskCounter.OUTPUT_RECORDS)
                output.context.counters.increment(
                    TaskCounter.OUTPUT_BYTES, len(k) + len(v))
                if (len(output._pairs) & 0x3FFF) == 0:
                    output.context.notify_progress()

            def write_batch(self, batch: KVBatch) -> None:
                """Batch-first path: pre-serialized records."""
                with tracing.span("output.write", cat="task",
                                  rows=batch.num_records):
                    output._batches.append(batch)
                    output.context.counters.increment(
                        TaskCounter.OUTPUT_RECORDS, batch.num_records)
                    output.context.counters.increment(
                        TaskCounter.OUTPUT_BYTES, batch.nbytes)
                    output.context.notify_progress()

        return _W()

    def handle_events(self, events: Sequence[TezAPIEvent]) -> None:
        pass

    def close(self) -> List[TezAPIEvent]:
        from tez_tpu.parallel.coordinator import mesh_coordinator
        ctx = self.context
        parts = list(self._batches)
        with tracing.span("exchange.pack", cat="exchange", stage="concat",
                          batches=len(parts)):
            if self._pairs:
                parts.append(KVBatch.from_pairs(self._pairs))
            batch = KVBatch.concat(parts) if parts else KVBatch.empty()
        self._pairs = []
        self._batches = []
        edge = _edge_id(ctx.task_attempt_id.dag_id, ctx.vertex_name,
                        ctx.destination_vertex_name)
        mesh_coordinator().register_producer(
            edge, ctx.task_index,
            num_producers=ctx.vertex_parallelism,
            num_consumers=self.num_physical_outputs,
            batch=batch, key_width=self.key_width,
            value_width=self.value_width,
            max_rows_per_round=self.max_rows_per_round,
            max_key_bytes=self.max_key_bytes,
            max_value_bytes=self.max_value_bytes,
            engine=self.exchange_engine,
            coded=self.exchange_coded,
            split_after=self.exchange_split_after,
            counters=ctx.counters)
        ctx.counters.increment(TaskCounter.SHUFFLE_BYTES, batch.nbytes)
        payload = ShufflePayload(host=MESH_HOST, port=0,
                                 path_component=edge, last_event=True)
        return [
            CompositeDataMovementEvent(0, self.num_physical_outputs, payload),
            VertexManagerEvent(
                target_vertex_name=ctx.destination_vertex_name,
                user_payload={"output_size": batch.nbytes,
                              "partition_sizes": None}),
        ]


class MeshOrderedGroupedKVInput(LogicalInput):
    """Consumer half: waits for every producer's mesh DME, then reads its
    worker's sorted partition straight off the exchange (already merged —
    there is no consumer-side fetch or merge phase at all)."""

    def initialize(self) -> List[TezAPIEvent]:
        ctx = self.context
        self.key_serde = get_serde(_conf_get(ctx, "tez.runtime.key.class",
                                             "bytes"))
        self.val_serde = get_serde(_conf_get(ctx, "tez.runtime.value.class",
                                             "bytes"))
        import threading
        self._lock = threading.Condition()
        self._complete = set()
        self._failed: Optional[str] = None
        self._batch: Optional[KVBatch] = None
        self._reading = False
        self._group_starts = None
        # straggler defense on the gang barrier (0 = wait forever)
        self._deadline = float(_conf_get(
            ctx, "tez.runtime.tpu.mesh.exchange.deadline.secs", 0.0)) or None
        ctx.request_initial_memory(0, None,
                                   component_type="SORTED_MERGED_INPUT")
        return []

    def handle_events(self, events: Sequence[TezAPIEvent]) -> None:
        with self._lock:
            for ev in events:
                if isinstance(ev, (CompositeRoutedDataMovementEvent,
                                   DataMovementEvent)):
                    slot = ev.target_index_start if isinstance(
                        ev, CompositeRoutedDataMovementEvent) \
                        else ev.target_index
                    payload = ev.user_payload
                    assert isinstance(payload, ShufflePayload), payload
                    if payload.host != MESH_HOST:
                        self._failed = (
                            f"mesh input received non-mesh payload from "
                            f"slot {slot} (host {payload.host!r}): the "
                            f"edge's output class must be the mesh output")
                    self._complete.add(slot)
                elif isinstance(ev, InputFailedEvent):
                    if self._batch is not None or self._reading:
                        # this attempt already materialized the (now stale)
                        # merged result: fail loudly; the retry waits for
                        # the coordinator's re-exchange below
                        self._failed = (f"producer slot {ev.target_index} "
                                        f"re-ran after this attempt read "
                                        f"the mesh exchange")
                    else:
                        # producer re-running before we read anything: the
                        # coordinator invalidates and re-runs the exchange
                        # when the replacement span registers — just wait
                        # for the fresh DME
                        self._complete.discard(ev.target_index)
                else:
                    log.warning("MeshOrderedGroupedKVInput: unexpected "
                                "event %r", ev)
            self._lock.notify_all()

    def _wait_complete(self) -> Optional[float]:
        """Returns the REMAINING deadline budget (None = unbounded) so the
        coordinator barrier wait consumes the same window, not a fresh
        one — the configured deadline bounds the whole stall."""
        import time
        deadline = None if self._deadline is None \
            else time.monotonic() + self._deadline
        with self._lock:
            while len(self._complete) < self.num_physical_inputs:
                if self._failed:
                    raise RuntimeError(self._failed)
                if deadline is not None and time.monotonic() > deadline:
                    missing = sorted(set(range(self.num_physical_inputs)) -
                                     self._complete)
                    raise TimeoutError(
                        f"mesh edge into {self.context.vertex_name}: "
                        f"{len(self._complete)}/{self.num_physical_inputs} "
                        f"producers completed within "
                        f"{self._deadline:.0f}s; missing producer task "
                        f"indices {missing[:16]}"
                        f"{'...' if len(missing) > 16 else ''}")
                self._lock.wait(0.2)
                self.context.notify_progress()
            if self._failed:
                raise RuntimeError(self._failed)
            # atomically with the final completeness check: any producer
            # InputFailedEvent from here on marks this attempt failed —
            # no window where a failure lands between this check and the
            # batch read/assignment in get_reader
            self._reading = True
        if deadline is None:
            return None
        return max(0.5, deadline - time.monotonic())

    def get_reader(self) -> GroupedKVReader:
        with self._lock:
            if self._failed:
                raise RuntimeError(self._failed)
        if self._batch is None:
            import time
            ctx = self.context
            t0 = time.time()
            from tez_tpu.parallel.coordinator import mesh_coordinator
            edge = _edge_id(ctx.task_attempt_id.dag_id,
                            ctx.source_vertex_name, ctx.vertex_name)
            # the consumer's wait for its input, as on the host shuffle:
            # every producer's event, then the exchange itself
            with tracing.span("shuffle.wait", cat="shuffle", edge="mesh"):
                remaining = self._wait_complete()
                batch = mesh_coordinator().wait_consumer(
                    edge, ctx.task_index,
                    num_producers=self.num_physical_inputs,
                    num_consumers=ctx.vertex_parallelism,
                    timeout=remaining,
                    progress=ctx.notify_progress)
            with self._lock:
                if self._failed:
                    raise RuntimeError(self._failed)
                self._batch = batch
            ctx.counters.find_counter(TaskCounter.SHUFFLE_PHASE_TIME)\
                .increment(int((time.time() - t0) * 1000))
            ctx.counters.increment(TaskCounter.REDUCE_INPUT_RECORDS,
                                   self._batch.num_records)
            ctx.counters.increment(TaskCounter.NUM_SHUFFLED_INPUTS,
                                   self.num_physical_inputs)
        if self._group_starts is None:
            self._group_starts = GroupedKVReader._compute_groups(self._batch)
        return GroupedKVReader(self._batch, self.key_serde, self.val_serde,
                               self.context, group_starts=self._group_starts)

    def close(self) -> List[TezAPIEvent]:
        self._batch = None
        self._group_starts = None
        with self._lock:
            if self._failed:
                # the attempt consumed a generation that a producer re-ran
                # out from under it: it must not complete successfully —
                # the retry reads the re-exchanged data
                raise RuntimeError(self._failed)
        return []
