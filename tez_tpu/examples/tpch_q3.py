"""TPC-H Query 3, "Shipping Priority" (TPC-H specification §2.4.3), on the
batch path.

    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = :segment and c_custkey = o_custkey
      and l_orderkey = o_orderkey
      and o_orderdate < :date and l_shipdate > :date
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate
    limit 10

The plan is the one Hive gives this star join on Tez: a map join for the
small dimension, then a shuffle join.

* ``customer`` (a scan) keeps the segment's customers and broadcasts their
  keys to every ``orders`` task;
* ``orders`` (a scan) keeps the orders before the date, semi-joins them
  with the broadcast customers on ``o_custkey`` (library/join.py
  ``hash_join_blocks`` ``how="semi"``, the rows' values kept) and sends
  (``o_orderkey``; ``o_orderdate``, ``o_shippriority``) over an unordered
  edge partitioned by the order key;
* ``lineitem`` (a scan) keeps the lines shipped after the date and sends
  (``l_orderkey``; revenue) over the same kind of edge;
* ``join`` joins the two on the order key (``how="inner"``: the orders'
  keys are unique, so each line hits at most one order, whose date and
  priority are carried onto it), sums the revenue by (order key, date,
  priority) -- the date and the priority depend on the order key, so they
  ride in the group's key (library/aggregate.py ``group_sum_blocks``) --
  and keeps its own ten first by (revenue descending, date);
* ``top`` (one task) merges the joiners' tens and commits the query's.

The tables are typed column files (io/formats.py ``ColumnarFormat``):
dates are int32 days since 1970-01-01, decimals int64 in hundredths,
``c_mktsegment`` a uint8 code into ``MKTSEGMENTS``.  Revenue is
``l_extendedprice * (100 - l_discount)`` in int64 ten-thousandths, exact;
a line is ``<orderkey>\\t<revenue>\\t<yyyy-mm-dd>\\t<priority>``, the revenue
with its four decimals.  Every predicate and expression is computed over
whole columns, and no row passes through Python on its own.
"""
from __future__ import annotations

import datetime
import sys
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
from tez_tpu.examples.sort_merge_join import paths_by_directory
from tez_tpu.library.conf import (UnorderedKVEdgeConfig,
                                  UnorderedPartitionedKVEdgeConfig)
from tez_tpu.library.inputs import (long_column, pack_rows, scan_rows,
                                    unpack_columns)
from tez_tpu.library.processors import SimpleProcessor
from tez_tpu.ops import hostpool
from tez_tpu.ops.runformat import KVBatch
from tez_tpu.ops.serde import decode_longs_be

TABLES = ("customer", "orders", "lineitem")
#: the codes of ``c_mktsegment``: a segment's place here (TPC-H §4.2.2.13's
#: five segments, in name order)
MKTSEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
               "MACHINERY")
#: the columns each scan reads, and their types
COLUMNS = {
    "customer": {"c_custkey": "<i4", "c_mktsegment": "u1"},
    "orders": {"o_orderkey": "<i4", "o_custkey": "<i4",
               "o_orderdate": "<i4", "o_shippriority": "<i4"},
    "lineitem": {"l_orderkey": "<i4", "l_extendedprice": "<i8",
                 "l_discount": "<i8", "l_shipdate": "<i4"},
}
#: the order key, 4 bytes big-endian: every edge's key and the probes' lanes
KEY_WIDTH = 4
#: the group's key: order key, date and priority, 4 bytes each
GROUP_KEY = ("<i4", "<i4", "<i4")
LIMIT = 10


def epoch_day(iso: str) -> int:
    """'yyyy-mm-dd' as days since 1970-01-01."""
    return (datetime.date.fromisoformat(iso) -
            datetime.date(1970, 1, 1)).days


class _QueryProcessor(SimpleProcessor):
    """A task of the query: the payload holds its substitution
    parameters."""

    def initialize(self) -> None:
        self.query = self.context.user_payload.load() or {}


def _parts(inputs: Dict[str, LogicalInput]):
    """The task's table parts, a part's columns at a time."""
    return inputs["table"].get_reader().iter_columns()


class CustomerScan(_QueryProcessor):
    """The segment's customers: their keys, with zero-width values, to
    every orders task."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        code = MKTSEGMENTS.index(self.query["segment"])
        writer = outputs["orders"].get_writer()
        for cols in _parts(inputs):
            writer.write_batch(scan_rows(
                cols, lambda c: c["c_mktsegment"] == code,
                lambda c: ([c["c_custkey"]], []), self.context.counters))


class OrdersScan(_QueryProcessor):
    """The orders before the date whose customer is in the segment (the
    semi-join with the broadcast customers keeps the rows' values), keyed
    by the order key for the join."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        from tez_tpu.library.join import hash_join_blocks
        date = epoch_day(self.query["date"])
        counters = self.context.counters
        customers = inputs["customer"]
        writer = outputs["join"].get_writer()
        kept = (scan_rows(cols, lambda c: c["o_orderdate"] < date,
                          lambda c: ([c["o_custkey"]],
                                     [c["o_orderkey"], c["o_orderdate"],
                                      c["o_shippriority"]]), counters)
                for cols in _parts(inputs))
        for rows in hash_join_blocks(
                customers.get_reader().iter_batches(), kept, how="semi",
                values=True, key_width=KEY_WIDTH,
                engine=customers.merge_engine,
                device_min_records=customers.merge_min_records,
                counters=counters):
            with tracing.span("processor.project", cat="task",
                              rows=rows.num_records):
                key, day, priority = unpack_columns(
                    rows.val_bytes, rows.val_offsets, GROUP_KEY)
                batch = pack_rows([key], [day, priority])
            writer.write_batch(batch)


class LineitemScan(_QueryProcessor):
    """The lines shipped after the date: (order key; revenue)."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        date = epoch_day(self.query["date"])
        writer = outputs["join"].get_writer()

        def revenue(c):
            # hundredths x hundredths: ten-thousandths, exact in int64; the
            # product in pooled memory (a DAG makes the same sizes again)
            rev = np.subtract(100, c["l_discount"], out=hostpool.empty(
                len(c["l_discount"]), np.int64))
            np.multiply(rev, c["l_extendedprice"], out=rev)
            return [c["l_orderkey"]], [long_column(rev)]

        for cols in _parts(inputs):
            writer.write_batch(scan_rows(
                cols, lambda c: c["l_shipdate"] > date, revenue,
                self.context.counters))


def _top(okey, day, priority, revenue) -> np.ndarray:
    """The rows of the first LIMIT by (revenue descending, date), the
    order key breaking what ties remain."""
    return np.lexsort((okey, day, -revenue))[:LIMIT]


def _decode(batch: KVBatch):
    """(order key, date, priority, revenue) columns of group rows."""
    okey, day, priority = unpack_columns(batch.key_bytes, batch.key_offsets,
                                         GROUP_KEY)
    return okey, day, priority, decode_longs_be(
        batch.val_bytes[int(batch.val_offsets[0]):
                        int(batch.val_offsets[-1])], batch.num_records)


class JoinSumTop(_QueryProcessor):
    """Lines joined with their orders (the date and priority carried), the
    revenue summed by (order key, date, priority), the first LIMIT of this
    joiner's groups to ``top``."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        from tez_tpu.library.aggregate import group_sum_blocks
        from tez_tpu.library.join import hash_join_blocks
        orders, lines = inputs["orders"], inputs["lineitem"]
        counters = self.context.counters

        def keyed_by_group():
            for rows in hash_join_blocks(
                    orders.get_reader().iter_batches(),
                    lines.get_reader().iter_batches(), how="inner",
                    key_width=KEY_WIDTH, engine=lines.merge_engine,
                    device_min_records=lines.merge_min_records,
                    counters=counters):
                with tracing.span("processor.project", cat="task",
                                  rows=rows.num_records):
                    n = rows.num_records
                    # value: revenue (8 B), then the carried date and
                    # priority (4 B each), which join the key
                    vals = rows.val_bytes.reshape(n, 16)
                    keys = np.concatenate(
                        [rows.key_bytes.reshape(n, KEY_WIDTH), vals[:, 8:]],
                        axis=1).reshape(-1)
                    width = KEY_WIDTH + 8
                    batch = KVBatch(
                        keys, np.arange(n + 1, dtype=np.int64) * width,
                        np.ascontiguousarray(vals[:, :8]).reshape(-1),
                        np.arange(n + 1, dtype=np.int64) * 8)
                yield batch

        writer = outputs["top"].get_writer()
        for table in group_sum_blocks(
                keyed_by_group(), key_width=KEY_WIDTH + 8,
                engine=lines.merge_engine,
                device_min_records=lines.merge_min_records,
                counters=counters):
            with tracing.span("processor.topk", cat="task",
                              rows=table.num_records):
                okey, day, priority, revenue = _decode(table)
                first = _top(okey, day, priority, revenue)
                batch = pack_rows([okey[first], day[first], priority[first]],
                                  [long_column(revenue[first])])
            writer.write_batch(batch)


def format_lines(okey, day, priority, revenue) -> bytes:
    """``<orderkey>\\t<revenue>\\t<yyyy-mm-dd>\\t<priority>`` lines, the
    revenue's ten-thousandths as four decimals."""
    dates = np.datetime_as_string(day.astype("datetime64[D]"))
    whole = np.char.mod("%d", revenue // 10000)
    frac = np.char.mod("%04d", revenue % 10000)
    cells = [np.char.mod("%d", okey), np.char.add(np.char.add(whole, "."),
                                                  frac),
             dates, np.char.mod("%d", priority)]
    lines = cells[0]
    for cell in cells[1:]:
        lines = np.char.add(np.char.add(lines, "\t"), cell)
    return "".join(np.char.add(lines, "\n").tolist()).encode()


class TopMerge(_QueryProcessor):
    """The joiners' tens merged: the query's first LIMIT, one line each."""

    def run(self, inputs: Dict[str, LogicalInput],
            outputs: Dict[str, LogicalOutput]) -> None:
        parts = [b for b in inputs["join"].get_reader().iter_batches()
                 if b.num_records]
        writer = outputs["output"].get_writer()
        if not parts:
            return
        with tracing.span("processor.topk", cat="task",
                          rows=sum(b.num_records for b in parts)):
            okey, day, priority, revenue = _decode(KVBatch.concat(parts))
            first = _top(okey, day, priority, revenue)
        with tracing.span("processor.format", cat="task", rows=len(first)):
            lines = format_lines(okey[first], day[first], priority[first],
                                 revenue[first])
        writer.write_raw(memoryview(lines), len(first))


def _scan_vertex(table: str, processor, paths, parallelism: int,
                 query: dict) -> Vertex:
    vertex = Vertex.create(table, ProcessorDescriptor.create(
        processor, payload=query), parallelism)
    columns = {"format": "columnar",
               "format_params": {"table": table, "columns": COLUMNS[table]}}
    vertex.add_data_source("table", DataSourceDescriptor.create(
        InputDescriptor.create("tez_tpu.io.formats:MRInput", payload=columns),
        InputInitializerDescriptor.create(
            "tez_tpu.io.formats:MRSplitGenerator",
            payload={"paths": list(paths), "desired_splits": parallelism,
                     **columns})))
    return vertex


def build_dag(table_paths: Dict[str, list], output_path: str,
              segment: str = "BUILDING", date: str = "1995-03-15",
              customer_parallelism: int = 1, orders_parallelism: int = 2,
              lineitem_parallelism: int = 2, num_joiners: int = 2,
              mode: str = "vector") -> DAG:
    """Q3 with the substitution parameters `segment` and `date` over the
    tables' directories (`table_paths`: table -> paths).  Only
    ``mode="vector"``: the batch path."""
    if mode != "vector":
        raise ValueError(f"tpch_q3 has the batch path alone, not {mode!r}")
    if segment not in MKTSEGMENTS:
        raise ValueError(f"no market segment {segment!r}: {MKTSEGMENTS}")
    query = {"segment": segment, "date": date}
    customer = _scan_vertex("customer", CustomerScan,
                            table_paths["customer"], customer_parallelism,
                            query)
    orders = _scan_vertex("orders", OrdersScan, table_paths["orders"],
                          orders_parallelism, query)
    lineitem = _scan_vertex("lineitem", LineitemScan,
                            table_paths["lineitem"], lineitem_parallelism,
                            query)
    join = Vertex.create("join", ProcessorDescriptor.create(
        JoinSumTop, payload=query), num_joiners)
    top = Vertex.create("top", ProcessorDescriptor.create(
        TopMerge, payload=query), 1)
    top.add_data_sink("output", DataSinkDescriptor.create(
        OutputDescriptor.create("tez_tpu.io.file_output:FileOutput",
                                payload={"path": output_path,
                                         "key_serde": "text",
                                         "value_serde": "text"}),
        OutputCommitterDescriptor.create(
            "tez_tpu.io.file_output:FileOutputCommitter",
            payload={"path": output_path})))
    dag = DAG.create("TPCH-Q3")
    for v in (customer, orders, lineitem, join, top):
        dag.add_vertex(v)

    def partitioned(width: int):
        return UnorderedPartitionedKVEdgeConfig.new_builder(
            "bytes", "bytes").set_key_width(width).build()\
            .create_default_edge_property()

    dag.add_edge(Edge.create(customer, orders, UnorderedKVEdgeConfig
                             .new_builder("bytes", "bytes")
                             .set_key_width(KEY_WIDTH).build()
                             .create_default_broadcast_edge_property()))
    # both sides of the join hashed by the order key into the joiners
    dag.add_edge(Edge.create(orders, join, partitioned(KEY_WIDTH)))
    dag.add_edge(Edge.create(lineitem, join, partitioned(KEY_WIDTH)))
    dag.add_edge(Edge.create(join, top, partitioned(KEY_WIDTH + 8)))
    return dag


@tracing.traced("build", cat="client")
def build_bench_dag(inputs, out_dir: str, **kwargs) -> DAG:
    """The benchmark harness's builder: `inputs` are the tables'
    directories, told apart by name (``customer``, ``orders``,
    ``lineitem``)."""
    return build_dag(paths_by_directory(inputs, TABLES), out_dir, **kwargs)


def run(table_dir: str, output_path: str, conf=None, **kw) -> str:
    import os
    with TezClient.create("TPCH-Q3", conf or {}) as client:
        dag = build_bench_dag([os.path.join(table_dir, t) for t in TABLES],
                              output_path, **kw)
        status = client.submit_dag(dag).wait_for_completion()
        return status.state.name


if __name__ == "__main__":
    if len(sys.argv) != 3:
        print("usage: tpch_q3 <table_dir> <output_dir>")
        sys.exit(2)
    print(run(sys.argv[1], sys.argv[2]))
