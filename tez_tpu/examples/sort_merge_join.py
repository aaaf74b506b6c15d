"""Sort-merge join: two sorted scatter-gather edges into one joiner.

Reference parity: tez-examples/.../SortMergeJoinExample.java:72 (benchmark
workload 3, BASELINE.md): both sides shuffle sorted on the join key to the
same partition space; the joiner walks the two grouped iterators in lockstep.

This example is a thin shim over the relational query layer
(tez_tpu/query/, docs/query.md): the whole workload is one logical plan —
``left SEMI-DISTINCT JOIN right`` on the tokenized word — whose
semi_distinct join REQUIRES the repartition strategy, so the planner
lowers it onto exactly the DAG shape the hand-built original used (both
scan sides terminating into key-partitioned OrderedPartitionedKVEdges
feeding a lockstep sort-merge joiner).  The output is bit-exact with the
pre-query-layer example: one ``(word, "1")`` record per distinct word
present on both sides.

``mode="vector"`` builds upstream's DAG by hand on the batch path, beside
the query-layer plan: two forwarding scanners ship KVBatches of keys with
zero-width values (upstream's NullWritable) through ``write_batch``, two
ordered edges at the DAG's key width, and a joiner that reads both inputs'
``sorted_blocks()`` into library/join.py's merge-join -- no record is
touched in Python.  It writes the lines ``mode="simple"`` writes.
"""
from __future__ import annotations

import os
import sys
from typing import Dict

from tez_tpu.api.runtime import LogicalInput, LogicalOutput
from tez_tpu.query import Table, plan_query
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common import tracing
from tez_tpu.common.payload import (InputDescriptor,
                                    InputInitializerDescriptor,
                                    OutputCommitterDescriptor,
                                    OutputDescriptor, ProcessorDescriptor)
from tez_tpu.dag.dag import (DAG, DataSinkDescriptor, DataSourceDescriptor,
                             Edge, Vertex)
from tez_tpu.library.conf import OrderedPartitionedKVEdgeConfig
from tez_tpu.library.processors import SimpleProcessor

SIDES = ("left", "right")


def build_plan(left_paths, right_paths) -> Table:
    left = Table.scan("left", list(left_paths), ["word"], mode="words")
    right = Table.scan("right", list(right_paths), ["word"], mode="words")
    # semi_distinct: one row per distinct key on both sides — the
    # lockstep emit-once-per-matching-key the original joiner performed
    return left.join(right, "word", how="semi_distinct")


class VectorForwardingProcessor(SimpleProcessor):
    """Each word of the input as a key with a zero-width value (upstream's
    ForwardingProcessor: the line as Text, NullWritable), a chunk a batch."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        import numpy as np
        from tez_tpu.ops.native import split_ws_native
        from tez_tpu.ops.runformat import KVBatch
        reader = inputs["input"].get_reader()
        writer = outputs["joiner"].get_writer()
        for chunk in reader.iter_chunks():
            with tracing.span("processor.tokenize", cat="task",
                              bytes=len(chunk)):
                key_bytes, key_offsets = split_ws_native(bytes(chunk))
                n = len(key_offsets) - 1
                if n == 0:
                    continue
                batch = KVBatch(key_bytes, key_offsets,
                                np.zeros(0, np.uint8),
                                np.zeros(n + 1, np.int64))
            writer.write_batch(batch)


def format_key_lines(keys, sep: bytes):
    """A batch of keys as ``key<sep>1`` lines (uint8 array): one ragged
    gather over [key rows, the line's tail]."""
    import numpy as np
    from tez_tpu.ops.runformat import gather_ragged
    n = keys.num_records
    tail = np.frombuffer(sep + b"1\n", np.uint8)
    pool_bytes = np.concatenate([keys.key_bytes, tail])
    pool_offsets = np.append(keys.key_offsets,
                             keys.key_offsets[-1] + len(tail))
    perm = np.full(2 * n, n, dtype=np.int64)   # key_i, tail, ...
    perm[0::2] = np.arange(n)
    return gather_ragged(pool_bytes, pool_offsets, perm)[0]


class VectorSortMergeJoinProcessor(SimpleProcessor):
    """The keys both sorted inputs hold, each once, as ``key<sep>1`` lines:
    the batch merge-join over both inputs' sorted blocks, and one ragged
    gather over [key rows, the line's tail] a block of matches."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        from tez_tpu.library.join import merge_join_blocks, open_sorted_inputs
        left, right = (inputs[side] for side in SIDES)
        writer = outputs["output"].get_writer()
        sep = getattr(writer, "sep", b"\t")
        for keys in merge_join_blocks(
                *open_sorted_inputs(left, right), key_width=left.key_width,
                engine=left.merge_engine,
                device_min_records=left.merge_min_records,
                counters=self.context.counters):
            with tracing.span("processor.format", cat="task",
                              rows=keys.num_records):
                lines = format_key_lines(keys, sep)
            writer.write_raw(memoryview(lines), keys.num_records)
            self.context.notify_progress()


def joiner_with_file_sink(processor, num_joiners: int,
                          output_path: str) -> Vertex:
    """The ``joiner`` vertex of a batch join DAG, its FileOutput sink
    committed once."""
    joiner = Vertex.create("joiner", ProcessorDescriptor.create(processor),
                           num_joiners)
    joiner.add_data_sink("output", DataSinkDescriptor.create(
        OutputDescriptor.create("tez_tpu.io.file_output:FileOutput",
                                payload={"path": output_path,
                                         "key_serde": "text",
                                         "value_serde": "text"}),
        OutputCommitterDescriptor.create(
            "tez_tpu.io.file_output:FileOutputCommitter",
            payload={"path": output_path})))
    return joiner


def forwarding_scanner(name: str, paths, parallelism: int) -> Vertex:
    """A vertex of VectorForwardingProcessor tasks over `paths` as text
    splits."""
    scanner = Vertex.create(name, ProcessorDescriptor.create(
        VectorForwardingProcessor), parallelism)
    scanner.add_data_source("input", DataSourceDescriptor.create(
        InputDescriptor.create("tez_tpu.io.text:TextInput"),
        InputInitializerDescriptor.create(
            "tez_tpu.io.text:TextSplitGenerator",
            payload={"paths": list(paths),
                     "desired_splits": parallelism})))
    return scanner


def paths_by_directory(inputs, directories) -> Dict[str, list]:
    """`inputs` grouped by the directory a path is (or lies in); a path
    under none of `directories` raises KeyError."""
    found: Dict[str, list] = {d: [] for d in directories}
    for path in inputs:
        where = path if os.path.isdir(path) else os.path.dirname(path)
        found[os.path.basename(os.path.normpath(where))].append(path)
    return found


def _build_vector_dag(left_paths, right_paths, output_path: str,
                      num_joiners: int, side_parallelism: int,
                      key_width: int) -> DAG:
    joiner = joiner_with_file_sink(VectorSortMergeJoinProcessor, num_joiners,
                                   output_path)
    dag = DAG.create("SortMergeJoin")
    dag.add_vertex(joiner)
    # both edges partition by the same hash into the same partition count,
    # or a key's two sides meet in different joiners
    edge = OrderedPartitionedKVEdgeConfig.new_builder("bytes", "bytes")\
        .set_key_width(key_width).build()
    for side, paths in zip(SIDES, (left_paths, right_paths)):
        scanner = forwarding_scanner(side, paths, side_parallelism)
        dag.add_vertex(scanner)
        dag.add_edge(Edge.create(scanner, joiner,
                                 edge.create_default_edge_property()))
    return dag


def build_dag(left_paths, right_paths, output_path: str,
              num_joiners: int = 2, side_parallelism: int = 2, conf=None,
              mode: str = "simple", key_width: int = 16):
    """mode="vector": the batch DAG, its ordered edges `key_width` bytes
    wide; "simple": the query-layer plan."""
    if mode == "vector":
        return _build_vector_dag(left_paths, right_paths, output_path,
                                 num_joiners, side_parallelism, key_width)
    merged = {"tez.query.reducers": num_joiners,
              "tez.query.scan.splits": side_parallelism, **(conf or {})}
    planned = plan_query(build_plan(left_paths, right_paths), merged,
                         output_path, dag_name="SortMergeJoin",
                         sink={"key_col": "word", "literal": "1"})
    return planned.dag


@tracing.traced("build", cat="client")
def build_bench_dag(inputs, out_dir: str, **kwargs):
    """The benchmark harness's builder: `inputs` holds both sides' paths,
    told apart by the directory a path is (or lies in): ``left``,
    ``right``."""
    by_side = paths_by_directory(inputs, SIDES)
    return build_dag(by_side["left"], by_side["right"], out_dir, **kwargs)


def run(left_paths, right_paths, output_path: str, conf=None, **kw) -> str:
    with TezClient.create("SortMergeJoin", conf or {}) as client:
        dag = build_dag(left_paths, right_paths, output_path,
                        conf=conf, **kw)
        status = client.submit_dag(dag).wait_for_completion()
        return status.state.name


if __name__ == "__main__":
    if len(sys.argv) != 4:
        print("usage: sort_merge_join <left_file> <right_file> <output_dir>")
        sys.exit(2)
    print(run([sys.argv[1]], [sys.argv[2]], sys.argv[3]))
