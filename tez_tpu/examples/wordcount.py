"""WordCount over the unordered (hash-partition only) path.

Reference parity: tez-examples WordCount.java -- a tokenizer writes (word,
1), a summation sums a word's counts and writes (word, count) -- with the
tokenizer-to-summation edge an UnorderedPartitionedKVOutput /
UnorderedKVInput pair, hash-partitioned and never sorted, as this repo's
BASELINE.json configuration 2 gives it; the summation aggregates with a
hash map.

``mode="simple"`` moves a Python pair a record: TokenProcessor writes each
word, SumProcessor sums them in a Counter.  ``mode="vector"`` is the batch
path: OrderedWordCount's VectorTokenProcessor ships each chunk's words as a
KVBatch through ``write_batch``, the edge's lanes are `key_width` bytes
wide, and VectorSumProcessor folds the fetched batches into a group table
on the device (library/aggregate.py ``group_sum_blocks``) and formats it a
table at a time -- no record is touched in Python.  Both write the same
``word<TAB>count`` lines.
"""
from __future__ import annotations

import sys
from collections import Counter
from typing import Dict

import numpy as np

from tez_tpu.api.runtime import LogicalInput, LogicalOutput
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common import tracing
from tez_tpu.common.payload import (InputDescriptor,
                                    InputInitializerDescriptor,
                                    OutputCommitterDescriptor,
                                    OutputDescriptor, ProcessorDescriptor)
from tez_tpu.dag.dag import (DAG, DataSinkDescriptor, DataSourceDescriptor,
                             Edge, Vertex)
from tez_tpu.examples.ordered_wordcount import VectorTokenProcessor
from tez_tpu.library.conf import UnorderedPartitionedKVEdgeConfig
from tez_tpu.library.processors import SimpleProcessor
from tez_tpu.ops.runformat import KVBatch, gather_ragged
from tez_tpu.ops.serde import decode_longs_be


class TokenProcessor(SimpleProcessor):
    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        reader = inputs["input"].get_reader()
        writer = outputs["summation"].get_writer()
        for _offset, line in reader:
            for word in line.split():
                writer.write(word, 1)


class SumProcessor(SimpleProcessor):
    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        reader = inputs["tokenizer"].get_reader()
        writer = outputs["output"].get_writer()
        counts: Counter = Counter()
        for word, one in reader:
            counts[word] += one
        for word, count in sorted(counts.items()):
            writer.write(word, str(count))


def format_count_lines(table: KVBatch, sep: bytes) -> np.ndarray:
    """A batch of (key, 8-byte long) rows as ``key<sep><value>`` lines
    (uint8 array): each value's decimal digits written a digit column at a
    time, then one ragged gather over [key rows, tail rows]."""
    n = table.num_records
    values = decode_longs_be(table.val_bytes, n)
    negative = values < 0
    magnitude = np.abs(values)
    digits = np.ones(n, dtype=np.int64)
    for power in range(1, 19):
        digits += magnitude >= 10 ** power
    first = len(sep) + negative             # a row's first digit column
    tail_lens = first + digits + 1
    tails = np.zeros((n, int(tail_lens.max(initial=0))), dtype=np.uint8)
    tails[:, :len(sep)] = np.frombuffer(sep, dtype=np.uint8)
    tails[negative, len(sep)] = ord("-")
    rows, last = np.arange(n), first + digits - 1
    for k in range(int(digits.max(initial=0))):
        live = digits > k
        tails[rows[live], last[live] - k] = magnitude[live] % 10 + ord("0")
        magnitude //= 10
    tails[rows, first + digits] = ord("\n")
    tail_bytes = tails[np.arange(tails.shape[1])[None, :] < tail_lens[:, None]]
    pool_bytes = np.concatenate([table.key_bytes, tail_bytes])
    pool_offsets = np.concatenate([table.key_offsets,
                                   table.key_offsets[-1] + np.cumsum(tail_lens)])
    perm = np.empty(2 * n, dtype=np.int64)  # key_i, tail_i, ...
    perm[0::2] = rows
    perm[1::2] = n + rows
    return gather_ragged(pool_bytes, pool_offsets, perm)[0]


class VectorSumProcessor(SimpleProcessor):
    """Every word's count as a ``word<sep><count>`` line: the unordered
    input's fetched batches folded into one group table, on the device
    where the input's routing allows (library/aggregate.py)."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        from tez_tpu.library.aggregate import group_sum_blocks
        tokens = inputs["tokenizer"]
        writer = outputs["output"].get_writer()
        sep = getattr(writer, "sep", b"\t")
        for table in group_sum_blocks(
                tokens.get_reader().iter_batches(),
                key_width=tokens.key_width, engine=tokens.merge_engine,
                device_min_records=tokens.merge_min_records,
                counters=self.context.counters):
            with tracing.span("processor.format", cat="task",
                              rows=table.num_records):
                lines = format_count_lines(table, sep)
            writer.write_raw(memoryview(lines), table.num_records)
            self.context.notify_progress()


def build_dag(input_paths, output_path: str, tokenizer_parallelism: int = -1,
              summation_parallelism: int = 2, mode: str = "simple",
              key_width: int = 16) -> DAG:
    """mode="vector": the batch DAG, the edge's lanes `key_width` bytes
    wide; "simple": a Python pair a record."""
    vector = mode == "vector"
    tokenizer = Vertex.create("tokenizer", ProcessorDescriptor.create(
        VectorTokenProcessor if vector else TokenProcessor),
        tokenizer_parallelism)
    tokenizer.add_data_source("input", DataSourceDescriptor.create(
        InputDescriptor.create("tez_tpu.io.text:TextInput"),
        InputInitializerDescriptor.create(
            "tez_tpu.io.text:TextSplitGenerator",
            payload={"paths": list(input_paths),
                     "desired_splits": tokenizer_parallelism})))
    summation = Vertex.create("summation", ProcessorDescriptor.create(
        VectorSumProcessor if vector else SumProcessor),
        summation_parallelism)
    summation.add_data_sink("output", DataSinkDescriptor.create(
        OutputDescriptor.create("tez_tpu.io.file_output:FileOutput",
                                payload={"path": output_path,
                                         "key_serde": "text",
                                         "value_serde": "text"}),
        OutputCommitterDescriptor.create(
            "tez_tpu.io.file_output:FileOutputCommitter",
            payload={"path": output_path})))
    if vector:
        # the tokenizer's values are 8-byte longs (VarLongSerde)
        edge = UnorderedPartitionedKVEdgeConfig.new_builder(
            "bytes", "long").set_key_width(key_width).build()
    else:
        edge = UnorderedPartitionedKVEdgeConfig.new_builder(
            "bytes", "pickle").build()
    dag = DAG.create("WordCount").add_vertex(tokenizer).add_vertex(summation)
    dag.add_edge(Edge.create(tokenizer, summation,
                             edge.create_default_edge_property()))
    return dag


@tracing.traced("build", cat="client")
def build_bench_dag(inputs, out_dir: str, **kwargs) -> DAG:
    """The benchmark harness's builder: `inputs` are the corpus's files or
    directories."""
    return build_dag(inputs, out_dir, **kwargs)


def run(input_paths, output_path: str, conf=None, **kw) -> str:
    with TezClient.create("WordCount", conf or {}) as client:
        status = client.submit_dag(
            build_dag(input_paths, output_path, **kw)).wait_for_completion()
        return status.state.name


if __name__ == "__main__":
    if len(sys.argv) < 3:
        print("usage: wordcount <input...> <output_dir>")
        sys.exit(2)
    print(run(sys.argv[1:-1], sys.argv[-1]))
