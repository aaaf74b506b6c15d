"""Broadcast hash join: small dim table broadcast to every fact task.

Reference parity: tez-examples/.../HashJoinExample.java:74 (benchmark
workload 5, BASELINE.md): the small side ships over a BROADCAST edge with
UnorderedKVOutput; each streaming (fact) task builds a hash set/table and
joins its split of the big side.

This example is a thin shim over the relational query layer
(tez_tpu/query/, docs/query.md): the whole workload is one logical plan —
``stream SEMI JOIN hash_side`` with the join strategy pinned to broadcast
— lowered by the planner onto exactly the DAG shape the hand-built
original used (a 1-task build vertex over a broadcast UnorderedKVEdge
into a fused scan+hash_join probe vertex with a FileOutput sink).  The
output is bit-exact with the pre-query-layer example: one
``(word, "1")`` record per stream occurrence whose word appears in the
hash side.

``mode="vector"`` builds upstream's three-vertex DAG by hand on the batch
path, beside the query-layer plan: two forwarding scanners
(examples/sort_merge_join.py's, shared) ship KVBatches of keys with
zero-width values through ``write_batch`` -- the stream side over an
unordered hash-partitioned edge, the hash side over a broadcast edge, as
HashJoinExample does with -doBroadcast -- and a joiner that holds the whole
hash side and probes the stream side past it, a block at a time
(library/join.py ``hash_join_blocks``): nothing is sorted or merged, and no
record is touched in Python.  It writes the lines ``mode="simple"`` writes.
"""
from __future__ import annotations

import sys
from typing import Dict

from tez_tpu.api.runtime import LogicalInput, LogicalOutput
from tez_tpu.query import Table, plan_query
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common import tracing
from tez_tpu.dag.dag import DAG, Edge
from tez_tpu.examples.sort_merge_join import (format_key_lines,
                                              forwarding_scanner,
                                              joiner_with_file_sink,
                                              paths_by_directory)
from tez_tpu.library.conf import (UnorderedKVEdgeConfig,
                                  UnorderedPartitionedKVEdgeConfig)
from tez_tpu.library.processors import SimpleProcessor

#: vertex name -> the directory JoinDataGen's generator writes that side to
#: (benchmarks/generators/join_keys.py: ``left`` is the larger side)
SIDES = {"stream": "left", "hashside": "right"}


def build_plan(stream_paths, hash_paths) -> Table:
    stream = Table.scan("stream", list(stream_paths), ["word"],
                        mode="lines")
    hash_side = Table.scan("hashside", list(hash_paths), ["word"],
                           mode="lines")
    # semi join: keep every stream occurrence whose word is in the hash
    # side; hash_join pins the broadcast strategy the example is about
    return stream.hash_join(hash_side, "word", how="semi")


class VectorHashJoinProcessor(SimpleProcessor):
    """Every stream key the hash side holds, as ``key<sep>1`` lines: the
    hash side read whole, the stream side probed past it a block at a
    time."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        from tez_tpu.library.join import hash_join_blocks
        stream, hashside = (inputs[name] for name in SIDES)
        writer = outputs["output"].get_writer()
        for keys in hash_join_blocks(
                hashside.get_reader().iter_batches(),
                stream.get_reader().iter_batches(),
                key_width=stream.key_width, engine=stream.merge_engine,
                device_min_records=stream.merge_min_records,
                counters=self.context.counters):
            with tracing.span("processor.format", cat="task",
                              rows=keys.num_records):
                lines = format_key_lines(keys, getattr(writer, "sep", b"\t"))
            writer.write_raw(memoryview(lines), keys.num_records)
            self.context.notify_progress()


def _build_vector_dag(stream_paths, hash_paths, output_path: str,
                      num_joiners: int, stream_parallelism: int,
                      hash_parallelism: int, key_width: int) -> DAG:
    joiner = joiner_with_file_sink(VectorHashJoinProcessor, num_joiners,
                                   output_path)
    dag = DAG.create("HashJoin")
    dag.add_vertex(joiner)
    # the stream side hash-partitioned over the joiners, unsorted; the hash
    # side whole to every joiner
    edges = {
        "stream": UnorderedPartitionedKVEdgeConfig.new_builder(
            "bytes", "bytes").set_key_width(key_width).build()
        .create_default_edge_property(),
        "hashside": UnorderedKVEdgeConfig.new_builder(
            "bytes", "bytes").set_key_width(key_width).build()
        .create_default_broadcast_edge_property()}
    for name, paths, parallelism in (
            ("stream", stream_paths, stream_parallelism),
            ("hashside", hash_paths, hash_parallelism)):
        scanner = forwarding_scanner(name, paths, parallelism)
        dag.add_vertex(scanner)
        dag.add_edge(Edge.create(scanner, joiner, edges[name]))
    return dag


def build_dag(stream_paths, hash_paths, output_path: str,
              num_joiners: int = 2, conf=None, mode: str = "simple",
              stream_parallelism: int = 2, hash_parallelism: int = 1,
              key_width: int = 16):
    """mode="vector": the batch DAG, its probe's lanes `key_width` bytes
    wide; "simple": the query-layer plan."""
    if mode == "vector":
        return _build_vector_dag(stream_paths, hash_paths, output_path,
                                 num_joiners, stream_parallelism,
                                 hash_parallelism, key_width)
    merged = {"tez.query.scan.splits": num_joiners, **(conf or {})}
    planned = plan_query(build_plan(stream_paths, hash_paths), merged,
                         output_path, dag_name="HashJoin",
                         sink={"key_col": "word", "literal": "1"})
    return planned.dag


@tracing.traced("build", cat="client")
def build_bench_dag(inputs, out_dir: str, **kwargs):
    """The benchmark harness's builder: `inputs` holds both sides' paths,
    told apart by the directory a path is (or lies in): ``left`` is the
    stream side, ``right`` the hash side, as join_keys.py names them."""
    by_dir = paths_by_directory(inputs, SIDES.values())
    return build_dag(by_dir["left"], by_dir["right"], out_dir, **kwargs)


def run(stream_paths, hash_paths, output_path: str, conf=None, **kw) -> str:
    with TezClient.create("HashJoin", conf or {}) as client:
        dag = build_dag(stream_paths, hash_paths, output_path,
                        conf=conf, **kw)
        status = client.submit_dag(dag).wait_for_completion()
        return status.state.name


if __name__ == "__main__":
    if len(sys.argv) != 4:
        print("usage: hash_join <stream_file> <hash_file> <output_dir>")
        sys.exit(2)
    print(run([sys.argv[1]], [sys.argv[2]], sys.argv[3]))
