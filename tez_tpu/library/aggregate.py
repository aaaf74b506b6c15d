"""Batch group-by-sum behind an unordered input.

Reference parity: tez-examples WordCount.java's SumProcessor -- every
(word, count) pair of the unordered input summed by word, one line a word,
in no particular order -- as a batch operator: no record is touched in
Python.  The fetched batches (``iter_batches()`` of an UnorderedKVInput) are
cut into blocks of a fixed row count (library/join.py ``_probe_blocks``, so
that compile keys follow the task's sizes, not fetch order); each block is
folded into a group table that stays on the device from a task's first
block to its last (ops/device.py ``group_sum``: one sort of [table, block]
by key that carries the values along, a neighbour compare, a running sum,
and a second sort that moves each group's last row to the front; no
column is gathered), and the table is read back once, at the end, as a
KVBatch of (key, sum).

The host engine (``group_sum_host``) takes a task whose first block is
under the routing floor, a key wider than the edge's lanes, or sums that
could pass the device's int32: a device table then comes back to the host
and the fold goes on there, never wrapping.
"""
from __future__ import annotations

from typing import Iterable, Iterator, Optional

import numpy as np

from tez_tpu.common import tracing
from tez_tpu.common.counters import TaskCounter, TezCounters
from tez_tpu.library.join import _probe_blocks
from tez_tpu.ops import device
from tez_tpu.ops.keycodec import encode_keys, lanes_to_matrix
from tez_tpu.ops.runformat import KVBatch
from tez_tpu.ops.serde import decode_longs_be, encode_longs_be
from tez_tpu.ops.sorter import DEVICE_SORT_MIN_RECORDS, resolve_engine

#: input rows a fold: with the table's rows it is the fold program's
#: compile key, so blocks are cut by row count, never by which fetch came
#: first
FOLD_BLOCK_ROWS = 1 << 20
#: the least rows a device table is folded at: a table that grows past a
#: power of two at one block in one run and at the next in another would
#: compile inside a window; below this floor its size does not follow the
#: order the rows arrive in
TABLE_MIN_ROWS = 1 << 18
#: bytes of one value: an 8-byte big-endian long (ops/serde.py "long")
VALUE_BYTES = 8


def _values(block: KVBatch) -> np.ndarray:
    """The block's values as int64; anything but 8-byte longs raises."""
    n = block.num_records
    if not bool(np.all(np.diff(block.val_offsets) == VALUE_BYTES)):
        raise ValueError(f"group_sum_blocks sums {VALUE_BYTES}-byte longs: "
                         f"a block of {n} rows holds values of other widths")
    return decode_longs_be(block.val_bytes[
        int(block.val_offsets[0]):int(block.val_offsets[-1])], n)


def _table_batch(lanes: np.ndarray, lens: np.ndarray, sums: np.ndarray
                 ) -> KVBatch:
    """A group table as a KVBatch: each key decoded from its lanes, its sum
    as an 8-byte long."""
    mat = lanes_to_matrix(lanes)
    offsets = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    if len(lens) and bool(np.all(lens == mat.shape[1])):
        key_bytes = mat.reshape(-1)
    else:
        key_bytes = mat[np.arange(mat.shape[1])[None, :] < lens[:, None]]
    return KVBatch(key_bytes, offsets, encode_longs_be(sums),
                   np.arange(len(sums) + 1, dtype=np.int64) * VALUE_BYTES)


def _widen(lanes: np.ndarray, num_lanes: int) -> np.ndarray:
    """Lanes padded with zero lanes: bytes beyond a key's length are zero
    in the encoding, so the order is kept."""
    return np.pad(lanes, ((0, 0), (0, num_lanes - lanes.shape[1])))


class _DeviceTable:
    """A task's group table on the device, and what the host knows of it:
    the row count of the last fold once read back, the rows a fold slices
    it to, and a bound on the sum of absolute values folded into it.

    Every block of a task is padded to the bucket of its first, and the
    empty table a task starts from has as many rows as every later table
    (the floor and one block): while the groups stay under the floor, all
    of a task's folds are one program."""

    def __init__(self, num_lanes: int, table_min_rows: int,
                 first_block_rows: int) -> None:
        self.min_rows = table_min_rows
        self.block_bucket = device._bucket(first_block_rows)
        self.rows_in = table_min_rows + self.block_bucket   # of self.table
        self.table = device.empty_group_table(self.rows_in, num_lanes)
        self.count = 0                      # live rows, as last read back
        self.pending = None                 # the last fold's count, on chip
        self.magnitude = 0.0

    def settle(self) -> int:
        """The live rows of the table: the last fold's count read back (the
        host blocks here for the device)."""
        if self.pending is not None:
            with tracing.span("agg.fold", cat="agg", stage="readback"):
                self.count = int(np.asarray(self.pending))
            self.pending = None
        return self.count

    def fold(self, lanes: np.ndarray, lens: np.ndarray, vals: np.ndarray,
             magnitude: float, counters: Optional[TezCounters]) -> None:
        """Stage the block, read the last fold's count back, launch."""
        n = len(lens)
        staged = device.stage_group_block(lanes, lens, vals,
                                          self.block_bucket)
        count = self.settle()
        rows = min(max(self.min_rows, device._bucket(count)), self.rows_in)
        self.table, self.pending = device.group_sum(self.table, rows, staged)
        self.rows_in = rows + int(staged[0].shape[0])
        self.magnitude += magnitude
        if counters is not None:
            counters.increment(TaskCounter.AGG_INPUT_ROWS, n)
            counters.increment(TaskCounter.AGG_FOLD_ROWS, n + count)
            counters.increment(TaskCounter.AGG_LAUNCHES)

    def rows(self):
        return device.group_table_rows(self.table, self.settle())


def group_sum_blocks(batches: Iterable[KVBatch], key_width: int = 16,
                     engine: str = "auto",
                     device_min_records: int = DEVICE_SORT_MIN_RECORDS,
                     counters: Optional[TezCounters] = None,
                     block_rows: int = FOLD_BLOCK_ROWS,
                     table_min_rows: int = TABLE_MIN_ROWS
                     ) -> Iterator[KVBatch]:
    """Yield one KVBatch of (key, sum of its values), one row a distinct
    key, key-sorted, values as 8-byte longs; nothing for an empty input.
    `batches` are (key, 8-byte long) rows in any order (an unordered
    input's ``iter_batches()``); a key may repeat anywhere.

    On the device engine the first block has to hold `device_min_records`
    rows, every key fit the edge's `key_width` and the absolute values
    folded stay within int32; a task that breaks one of these goes on (or
    starts) on the host engine, in lanes as wide as its longest key."""
    engine = resolve_engine(engine)
    width = ((key_width + 3) // 4) * 4      # the device's lanes: the edge's
    on_device: Optional[_DeviceTable] = None
    host: Optional[tuple] = None            # (lanes, lengths, sums)
    for block in _probe_blocks(batches, block_rows, "agg.fold", "agg",
                               stage="cut"):
        n = block.num_records
        with tracing.span("agg.fold", cat="agg", stage="encode",
                          rows=n) as span:
            vals = _values(block)
            # float64: exact far past int32, and no int64 to wrap
            magnitude = float(np.abs(vals.astype(np.float64)).sum())
            longest = int(np.diff(block.key_offsets).max())
            if on_device is None and host is None and engine == "device" \
                    and n >= device_min_records:
                on_device = _DeviceTable(width // 4, table_min_rows, n)
            if on_device is not None and (
                    longest > key_width or
                    on_device.magnitude + magnitude > device.GROUP_SUM_MAX):
                host, on_device = on_device.rows(), None
            lane_bytes = width if on_device is not None else max(
                ((max(longest, 1) + 3) // 4) * 4,
                4 * host[0].shape[1] if host is not None else 0)
            lanes, lens = encode_keys(block.key_bytes, block.key_offsets,
                                      lane_bytes)
            span.annotate(engine="device" if on_device is not None
                          else "host")
        if on_device is not None:
            on_device.fold(lanes, lens, vals, magnitude, counters)
            continue
        if host is None:
            host = (np.zeros((0, lanes.shape[1]), np.uint32),
                    np.zeros(0, np.int32), np.zeros(0, np.int64))
        with tracing.span("agg.fold", cat="agg", stage="host", rows=n):
            host = device.group_sum_host(_widen(host[0], lanes.shape[1]),
                                         host[1], host[2], lanes, lens, vals)
    if on_device is not None:
        on_device.settle()
    if on_device is None and host is None:
        return
    with tracing.span("agg.emit", cat="agg") as span:
        out = _table_batch(*(on_device.rows() if on_device is not None
                             else host))
        span.annotate(rows=out.num_records)
    if counters is not None:
        counters.increment(TaskCounter.AGG_GROUPS, out.num_records)
    if out.num_records:
        yield out
