"""TeraSort: sortbenchmark.org's 100-byte records into one global order.

Reference parity: Hadoop's examples/terasort (TeraGen -> TeraSort ->
TeraValidate), which on Tez runs through tez-mapreduce
(``mapreduce.framework.name=yarn-tez``) as a two-vertex DAG with one
OrderedPartitionedKVOutput -> OrderedGroupedKVInput edge: an identity map
over ``gensort`` records (10-byte binary key, 90 bytes of payload), a
TotalOrderPartitioner over split points sampled from the input's keys
(TeraInputFormat.writePartitionFile, 100,000 keys by default), an identity
reduce.  Every part file is sorted and the part files in partition order are
the global order.

The split points are sampled in ``build_dag``, client-side, from the input
files, as Hadoop's job submission does, and ride in the edge's payload.
"""
from __future__ import annotations

import sys
from typing import Dict

from tez_tpu.api.runtime import LogicalInput, LogicalOutput
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common import tracing
from tez_tpu.common.payload import (InputDescriptor,
                                    InputInitializerDescriptor,
                                    OutputCommitterDescriptor,
                                    OutputDescriptor, ProcessorDescriptor)
from tez_tpu.dag.dag import (DAG, DataSinkDescriptor, DataSourceDescriptor,
                             Edge, Vertex)
from tez_tpu.library.conf import OrderedPartitionedKVEdgeConfig
from tez_tpu.library.partitioners import SPLIT_POINTS, sample_split_points
from tez_tpu.library.processors import SimpleProcessor

KEY_BYTES = 10
VALUE_BYTES = 90
SAMPLE_KEYS = 100_000


class IdentityMap(SimpleProcessor):
    """Records as read, a batch at a time, to the ordered edge."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        reader = inputs["input"].get_reader()
        writer = outputs["reduce"].get_writer()
        for batch in reader.iter_chunks():
            writer.write_batch(batch)


class IdentityReduce(SimpleProcessor):
    """The merged records as they come, written raw: no group is formed."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        reader = inputs["map"].get_reader()
        writer = outputs["output"].get_writer()
        for block in reader.sorted_blocks():
            writer.write_batch(block)


@tracing.traced("build", cat="client")
def build_dag(input_paths, output_path: str, map_parallelism: int = -1,
              reduce_parallelism: int = 2,
              sample_keys: int = SAMPLE_KEYS) -> DAG:
    with tracing.span("partition.sample", cat="client",
                      keys=sample_keys, partitions=reduce_parallelism):
        split_points = sample_split_points(
            list(input_paths), KEY_BYTES, VALUE_BYTES, reduce_parallelism,
            sample_keys)
    mapper = Vertex.create("map", ProcessorDescriptor.create(IdentityMap),
                           map_parallelism)
    records = {"format": "fixed",
               "format_params": {"key_bytes": KEY_BYTES,
                                 "value_bytes": VALUE_BYTES}}
    mapper.add_data_source("input", DataSourceDescriptor.create(
        InputDescriptor.create("tez_tpu.io.formats:MRInput", payload=records),
        InputInitializerDescriptor.create(
            "tez_tpu.io.formats:MRSplitGenerator",
            payload={"paths": list(input_paths),
                     "desired_splits": map_parallelism, **records})))
    reducer = Vertex.create("reduce",
                            ProcessorDescriptor.create(IdentityReduce),
                            reduce_parallelism)
    reducer.add_data_sink("output", DataSinkDescriptor.create(
        OutputDescriptor.create("tez_tpu.io.file_output:FileOutput",
                                payload={"path": output_path,
                                         "key_serde": "bytes",
                                         "value_serde": "bytes"}),
        OutputCommitterDescriptor.create(
            "tez_tpu.io.file_output:FileOutputCommitter",
            payload={"path": output_path})))
    edge = OrderedPartitionedKVEdgeConfig.new_builder("bytes", "bytes")\
        .set_conf("tez.runtime.partitioner.class",
                  "tez_tpu.library.partitioners:TotalOrderPartitioner")\
        .set_conf(SPLIT_POINTS, split_points).build()
    dag = DAG.create("TeraSort")
    dag.add_vertex(mapper).add_vertex(reducer)
    dag.add_edge(Edge.create(mapper, reducer,
                             edge.create_default_edge_property()))
    return dag


def run(input_paths, output_path: str, conf=None, **kw) -> str:
    with TezClient.create("TeraSort", conf or {}) as client:
        dag = build_dag(input_paths, output_path, **kw)
        status = client.submit_dag(dag).wait_for_completion()
        return status.state.name


def main() -> int:
    if len(sys.argv) < 3:
        print("usage: terasort <input...> <output_dir>")
        return 2
    state = run(sys.argv[1:-1], sys.argv[-1])
    print(state)
    return 0 if state == "SUCCEEDED" else 1


if __name__ == "__main__":
    sys.exit(main())
