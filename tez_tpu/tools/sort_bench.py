"""External-sort scale bench: push-based vs pull-based shuffle, end to end.

Entered via ``make bench-sort`` (``TEZ_BENCH_SORT_ONLY=1 bench.py``).  The
same spill-heavy sort DAG — fixed-width random keys emitted through the
batch write path, io.sort.mb far below the per-task data size so the
producer sorter MUST spill repeatedly — runs twice through the full
framework:

1. PULL (baseline): stock config.  Producers spill to disk, merge their
   spills into one final output at close, and consumers fetch after the
   producer completes — the classic map-side external sort barrier.
2. PUSH: ``tez.runtime.shuffle.push.enabled`` routes every finished spill
   eagerly into the reducer-side buffer store mid-map-wave (pipelined
   emission, no producer final merge, no pspill file), consumers start in
   ingest mode and merge eagerly as pushes land.

Both legs must SUCCEED, both must record ``SPILLED_RECORDS > 0`` (a run
that never spilled is not an external sort — the bench refuses to report
a number for it), the push leg must record ``SHUFFLE_PUSH_BYTES > 0``
(a push bench where push never engaged is a pull bench), and the consumer
outputs — record count + key CRC per reducer, sortedness verified
block-wise — must be bit-identical.  The reported ``vs_baseline`` is
pull wall / push wall with the ``min_vs_baseline`` floor enforced by
``tools/bench_diff.py``.
"""
from __future__ import annotations

import os
import shutil
import time
import zlib
from typing import Any, Dict, Optional, Tuple

import numpy as np

from tez_tpu.library.processors import SimpleProcessor

REC_KEY_BYTES = 10
REC_VAL_BYTES = 90      # ~100 B/record: the classic sort-benchmark shape


class SortEmitProcessor(SimpleProcessor):
    """Emits ``mb_per_task`` MB of task-seeded random fixed-width records
    through the vectorized batch write path (per-record Python would be
    the bottleneck, not the shuffle plane being measured)."""

    def run(self, inputs, outputs):
        payload = self.context.user_payload.load() or {}
        per_task_mb = int(payload.get("mb_per_task", 256))
        chunk_mb = int(payload.get("chunk_mb", 32))
        from tez_tpu.ops.runformat import KVBatch
        writer = outputs["consumer"].get_writer()
        rec = REC_KEY_BYTES + REC_VAL_BYTES
        rng = np.random.default_rng(4242 + self.context.task_index)
        remaining = (per_task_mb << 20) // rec
        chunk = max(1, (chunk_mb << 20) // rec)
        while remaining > 0:
            n = min(chunk, remaining)
            kb = rng.integers(0, 256, n * REC_KEY_BYTES, dtype=np.uint8)
            ko = np.arange(n + 1, dtype=np.int64) * REC_KEY_BYTES
            vb = np.zeros(n * REC_VAL_BYTES, dtype=np.uint8)
            vo = np.arange(n + 1, dtype=np.int64) * REC_VAL_BYTES
            writer.write_batch(KVBatch(kb, ko, vb, vo))
            remaining -= n
            self.context.notify_progress()


def _check_sorted(mat: np.ndarray, prev_last: Optional[np.ndarray]) -> None:
    """Vectorized lexicographic non-decreasing check over a key block (and
    across the block seam)."""
    hi = np.ascontiguousarray(mat[:, :8]).view(">u8").ravel()
    lo = np.ascontiguousarray(mat[:, 8:REC_KEY_BYTES]).view(">u2").ravel()
    ok = (hi[:-1] < hi[1:]) | ((hi[:-1] == hi[1:]) & (lo[:-1] <= lo[1:]))
    if not bool(np.all(ok)):
        raise AssertionError("merged output not sorted within a block")
    if prev_last is not None and \
            bytes(prev_last) > bytes(mat[0]):
        raise AssertionError("merged output not sorted across blocks")


class SortCheckProcessor(SimpleProcessor):
    """Consumes the merged sorted stream block-wise and writes
    ``<records> <key-crc32>`` per reducer, so the push and pull legs can be
    compared bit-exact without materializing gigabytes twice."""

    def run(self, inputs, outputs):
        payload = self.context.user_payload.load() or {}
        reader = inputs["producer"].get_reader()
        crc, records = 0, 0
        prev_last = None
        for batch, _bounds in reader.grouped_blocks():
            kb = np.ascontiguousarray(batch.key_bytes)
            n = batch.num_records
            if n:
                mat = kb.reshape(n, REC_KEY_BYTES)
                _check_sorted(mat, prev_last)
                prev_last = mat[-1].copy()
                crc = zlib.crc32(kb.tobytes(), crc)
                records += n
            self.context.notify_progress()
        out = os.path.join(payload["result_dir"],
                           f"part-{self.context.task_index:05d}")
        with open(out, "w") as fh:
            fh.write(f"{records} {crc & 0xFFFFFFFF:08x}\n")


def _build_sort_dag(name: str, result_dir: str, producers: int,
                    consumers: int, mb_per_task: int, sort_mb: int,
                    merge_mb: int) -> Any:
    from tez_tpu.common.payload import (InputDescriptor, OutputDescriptor,
                                        ProcessorDescriptor)
    from tez_tpu.dag.dag import DAG, Edge, Vertex
    from tez_tpu.dag.edge_property import (DataMovementType, DataSourceType,
                                           EdgeProperty, SchedulingType)
    producer = Vertex.create("producer", ProcessorDescriptor.create(
        SortEmitProcessor, payload={"mb_per_task": mb_per_task}), producers)
    consumer = Vertex.create("consumer", ProcessorDescriptor.create(
        SortCheckProcessor, payload={"result_dir": result_dir}), consumers)
    # io.sort.mb rides the IO payloads, not the client conf: the PRODUCER
    # side stays far below the task's data (spill-heavy — the external
    # sort being measured) while the CONSUMER side gets a real merge
    # budget.  Both legs share the exact same split.
    out_conf = {"tez.runtime.key.class": "bytes",
                "tez.runtime.value.class": "bytes",
                "tez.runtime.io.sort.mb": sort_mb}
    in_conf = {"tez.runtime.key.class": "bytes",
               "tez.runtime.value.class": "bytes",
               "tez.runtime.io.sort.mb": merge_mb}
    prop = EdgeProperty.create(
        DataMovementType.SCATTER_GATHER, DataSourceType.PERSISTED,
        SchedulingType.SEQUENTIAL,
        OutputDescriptor.create(
            "tez_tpu.library.outputs:OrderedPartitionedKVOutput",
            payload=out_conf),
        InputDescriptor.create(
            "tez_tpu.library.inputs:OrderedGroupedKVInput",
            payload=in_conf))
    dag = DAG.create(name).add_vertex(producer).add_vertex(consumer)
    dag.add_edge(Edge.create(producer, consumer, prop))
    return dag


_COUNTER_NAMES = ("SPILLED_RECORDS", "SHUFFLE_BYTES", "SHUFFLE_PUSH_BYTES",
                  "SHUFFLE_PUSH_REJECTED")


def _run_sort(workdir: str, name: str, mb_per_task: int, producers: int,
              consumers: int, sort_mb: int, merge_mb: int,
              extra_conf: Optional[Dict] = None,
              timeout: float = 900.0) -> Tuple[str, str, Dict[str, int],
                                               float]:
    """One client + one sort DAG; returns (state, result, counters, wall).
    ``result`` concatenates every reducer's ``<records> <crc>`` line."""
    from tez_tpu.client.tez_client import TezClient
    staging = os.path.join(workdir, name, "staging")
    result_dir = os.path.join(workdir, name, "out")
    os.makedirs(result_dir, exist_ok=True)
    conf = {
        "tez.staging-dir": staging,
        "tez.am.local.num-containers": producers + consumers,
    }
    conf.update(extra_conf or {})
    t0 = time.time()
    client = TezClient.create(name, conf).start()
    try:
        dag = _build_sort_dag(name, result_dir, producers, consumers,
                              mb_per_task, sort_mb, merge_mb)
        dag_client = client.submit_dag(dag)
        status = dag_client.wait_for_completion(timeout=timeout)
        state = status.state.name
        final = dag_client.get_dag_status(with_counters=True)
    finally:
        client.stop()
    wall = time.time() - t0
    counters: Dict[str, int] = {}
    if final.counters is not None:
        for group in final.counters.to_dict().values():
            for cname in _COUNTER_NAMES:
                if cname in group:
                    counters[cname] = counters.get(cname, 0) + group[cname]
    lines = []
    for fname in sorted(os.listdir(result_dir)):
        with open(os.path.join(result_dir, fname)) as fh:
            lines.append(fh.read().strip())
    return state, "\n".join(lines), counters, wall


def _quiesce(workdir: str, name: str) -> None:
    """Drop the finished leg's files and flush dirty pages so the NEXT
    leg's wall doesn't pay this leg's background writeback (on a small
    box the kernel flushing gigabytes of dead spill pages steals the
    second leg's CPU and disk — the ratio must not depend on leg order).
    Runs outside both timed regions: neither leg is charged."""
    shutil.rmtree(os.path.join(workdir, name), ignore_errors=True)
    os.sync()


def bench_sort() -> dict:
    """The push-vs-pull external-sort record for bench.py's JSON stream."""
    import tempfile
    from tez_tpu.store import reset_store
    total_mb = int(os.environ.get("TEZ_BENCH_SORT_MB", "1024"))
    producers = int(os.environ.get("TEZ_BENCH_SORT_TASKS", "4"))
    consumers = int(os.environ.get("TEZ_BENCH_SORT_REDUCERS", "4"))
    sort_mb = int(os.environ.get("TEZ_BENCH_SORT_IOSORT_MB", "48"))
    merge_mb = int(os.environ.get("TEZ_BENCH_SORT_MERGE_MB", "512"))
    mb_per_task = max(1, total_mb // producers)
    push_conf = {
        "tez.runtime.shuffle.push.enabled": True,
        # per-source quota must clear one task's whole output, or the tail
        # spills fall back to pull and the leg measures a hybrid
        "tez.runtime.shuffle.push.source-quota-mb": mb_per_task * 2,
        "tez.runtime.store.enabled": True,
        "tez.runtime.store.device.capacity-mb": 0,
        "tez.runtime.store.host.capacity-mb": total_mb * 3,
        "tez.runtime.store.lineage.reuse": False,
    }
    workdir = tempfile.mkdtemp(prefix="tez-sortbench-")
    try:
        # warmup: tiny run loads the native sorter + merge libraries so the
        # pull leg (which runs first) doesn't eat the one-time costs
        reset_store()
        state, _, _, _ = _run_sort(workdir, "warm", 8, 2, 1,
                                   sort_mb=4, merge_mb=16)
        assert state == "SUCCEEDED", f"warmup run failed ({state})"
        _quiesce(workdir, "warm")

        state, pull_res, pull_c, pull_wall = _run_sort(
            workdir, "pull", mb_per_task, producers, consumers,
            sort_mb, merge_mb)
        assert state == "SUCCEEDED", f"pull leg failed ({state})"
        assert pull_c.get("SPILLED_RECORDS", 0) > 0, \
            "pull leg never spilled — not an external sort; shrink io.sort.mb"
        _quiesce(workdir, "pull")

        reset_store()
        try:
            state, push_res, push_c, push_wall = _run_sort(
                workdir, "push", mb_per_task, producers, consumers,
                sort_mb, merge_mb, extra_conf=push_conf)
        finally:
            reset_store()
        assert state == "SUCCEEDED", f"push leg failed ({state})"
        assert push_c.get("SPILLED_RECORDS", 0) > 0, \
            "push leg never spilled — not an external sort; shrink io.sort.mb"
        assert push_c.get("SHUFFLE_PUSH_BYTES", 0) > 0, \
            "push leg never pushed a byte — the comparison is pull vs pull"
        assert push_res == pull_res and pull_res, (
            f"push/pull outputs diverge:\npull: {pull_res!r}\n"
            f"push: {push_res!r}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    return {
        "metric": (f"external-sort push vs pull shuffle "
                   f"({total_mb / 1024:.1f} GB, {producers}x{consumers} "
                   f"tasks, io.sort.mb map={sort_mb}/reduce={merge_mb}, "
                   f"SPILLED_RECORDS "
                   f"pull={pull_c.get('SPILLED_RECORDS', 0)} "
                   f"push={push_c.get('SPILLED_RECORDS', 0)}, "
                   f"SHUFFLE_PUSH_BYTES={push_c.get('SHUFFLE_PUSH_BYTES', 0)}"
                   f", rejected={push_c.get('SHUFFLE_PUSH_REJECTED', 0)}, "
                   f"pull {pull_wall:.1f}s, outputs bit-identical)"),
        "value": round(total_mb / push_wall, 2), "unit": "MB/s",
        "vs_baseline": round(pull_wall / push_wall, 3),
        "min_vs_baseline": 1.2,
    }
