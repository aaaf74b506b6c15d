"""MRR: 3-stage map -> reduce -> reduce chain, a chained-shuffle example
over text.

Reference parity: tez-tests mapreduce examples (TestOrderedWordCount /
MRRSleepJob): two chained sorted shuffles.  Its input is text lines of
``key<TAB>value`` through the hash partitioner, a record at a time: stage 1
splits the lines, stage 2 aggregates per key, stage 3 re-keys by aggregate
and writes globally ordered output.  It is not TeraSort: 100-byte binary
records under a sampled total-order partitioner are
``tez_tpu/examples/terasort.py``.
"""
from __future__ import annotations

import sys
from typing import Dict

from tez_tpu.api.runtime import LogicalInput, LogicalOutput
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common.payload import (InputDescriptor,
                                    InputInitializerDescriptor,
                                    OutputCommitterDescriptor,
                                    OutputDescriptor, ProcessorDescriptor)
from tez_tpu.dag.dag import (DAG, DataSinkDescriptor, DataSourceDescriptor,
                             Edge, Vertex)
from tez_tpu.library.conf import OrderedPartitionedKVEdgeConfig
from tez_tpu.library.processors import SimpleProcessor


class Stage1Map(SimpleProcessor):
    """line 'key<TAB>value' -> (key, value-length) pairs."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        writer = outputs["r1"].get_writer()
        for _off, line in inputs["input"].get_reader():
            key, _, value = line.partition(b"\t")
            writer.write(key, len(value))


class Stage2Reduce(SimpleProcessor):
    """(key, lengths) -> (total_length, key): re-key by aggregate."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        writer = outputs["r2"].get_writer()
        for key, lengths in inputs["m"].get_reader():
            writer.write(sum(lengths), key)


class Stage3Reduce(SimpleProcessor):
    """Globally ordered (total, keys) -> output lines."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        writer = outputs["output"].get_writer()
        for total, keys in inputs["r1"].get_reader():
            for key in keys:
                writer.write(key, str(total))


def build_dag(input_paths, output_path: str, map_parallelism: int = -1,
              r1_parallelism: int = 2, r2_parallelism: int = 1) -> DAG:
    m = Vertex.create("m", ProcessorDescriptor.create(Stage1Map),
                      map_parallelism)
    m.add_data_source("input", DataSourceDescriptor.create(
        InputDescriptor.create("tez_tpu.io.text:TextInput"),
        InputInitializerDescriptor.create(
            "tez_tpu.io.text:TextSplitGenerator",
            payload={"paths": list(input_paths),
                     "desired_splits": map_parallelism})))
    r1 = Vertex.create("r1", ProcessorDescriptor.create(Stage2Reduce),
                       r1_parallelism)
    r2 = Vertex.create("r2", ProcessorDescriptor.create(Stage3Reduce),
                       r2_parallelism)
    r2.add_data_sink("output", DataSinkDescriptor.create(
        OutputDescriptor.create("tez_tpu.io.file_output:FileOutput",
                                payload={"path": output_path,
                                         "key_serde": "text",
                                         "value_serde": "text"}),
        OutputCommitterDescriptor.create(
            "tez_tpu.io.file_output:FileOutputCommitter",
            payload={"path": output_path})))
    e1 = OrderedPartitionedKVEdgeConfig.new_builder("bytes", "long").build()
    e2 = OrderedPartitionedKVEdgeConfig.new_builder("long", "bytes").build()
    dag = DAG.create("MRR")
    for v in (m, r1, r2):
        dag.add_vertex(v)
    dag.add_edge(Edge.create(m, r1, e1.create_default_edge_property()))
    dag.add_edge(Edge.create(r1, r2, e2.create_default_edge_property()))
    return dag


def run(input_paths, output_path: str, conf=None, **kw) -> str:
    with TezClient.create("MRR", conf or {}) as client:
        status = client.submit_dag(
            build_dag(input_paths, output_path, **kw)).wait_for_completion()
        return status.state.name


if __name__ == "__main__":
    if len(sys.argv) < 3:
        print("usage: mrr <input...> <output_dir>")
        sys.exit(2)
    print(run(sys.argv[1:-1], sys.argv[-1]))
