"""Batch merge-join over two key-sorted block streams.

Reference parity: tez-examples SortMergeJoinExample.java's
SortMergeJoinProcessor -- two KeyValuesReaders walked in lockstep, a key
written once when both sides hold it -- as a batch operator: no record is
touched in Python.  The two streams are cut at a common key bound (blocks
arrive in pieces; a spilled input streams), the aligned pieces go to the
match (ops/device.py ``join_match``: one stable sort of the two sides'
lanes concatenated, then a neighbour compare), and the matching keys come
out as a KVBatch with zero-width values.

Only ``how="semi_distinct"`` -- what the source runs.  ``inner`` and
``semi`` stay with the query layer's row path (query/processors.py).
"""
from __future__ import annotations

import itertools
from typing import Any, Iterable, Iterator, Optional, Tuple

import numpy as np

from tez_tpu.common import tracing
from tez_tpu.common.counters import TaskCounter, TezCounters
from tez_tpu.ops import device
from tez_tpu.ops.keycodec import encode_keys
from tez_tpu.ops.runformat import KVBatch, gather_ragged
from tez_tpu.ops.sorter import DEVICE_SORT_MIN_RECORDS, resolve_engine


class _Side:
    """One sorted stream: the rows not yet matched, and one block of
    lookahead, so that the last block is known to be the last when it is
    matched (a one-block input then takes one match, not three)."""

    def __init__(self, blocks: Iterable[KVBatch]) -> None:
        self._blocks = iter(blocks)
        self.held = KVBatch.empty()
        self.consumed = 0
        self._next = self._pull()
        self.advance()

    def _pull(self) -> Optional[KVBatch]:
        for block in self._blocks:
            if block.num_records:
                return block
        return None

    @property
    def exhausted(self) -> bool:
        """Nothing follows the rows held."""
        return self._next is None

    def advance(self) -> None:
        """The lookahead block joins the rows held."""
        if self._next is None:
            return
        self.consumed += self._next.num_records
        self.held = self._next if not self.held.num_records \
            else KVBatch.concat([self.held, self._next])
        self._next = self._pull()

    def last_key(self) -> bytes:
        return self.held.key(self.held.num_records - 1)

    def take_below(self, bound: Optional[bytes]) -> KVBatch:
        """The held rows with keys below `bound` (all of them: None) leave;
        the rest stay."""
        n = self.held.num_records
        lo, hi = 0, n
        if bound is None:
            lo = n
        while lo < hi:                  # log2(n) byte compares, not n
            mid = (lo + hi) // 2
            if self.held.key(mid) < bound:
                lo = mid + 1
            else:
                hi = mid
        piece = self.held.slice_rows(0, lo)
        self.held = self.held.slice_rows(lo, n)
        return piece


def _match(left: KVBatch, right: KVBatch, key_width: int, engine: str,
           device_min_records: int, counters: Optional[TezCounters]
           ) -> np.ndarray:
    """Rows of `left` whose key `right` holds, one a distinct key."""
    rows = left.num_records + right.num_records
    longest = max(int(np.diff(b.key_offsets).max()) for b in (left, right))
    width = ((max(longest, 1) + 3) // 4) * 4
    # lanes hold whole keys on either engine; a key beyond the edge's lane
    # width is compared in full by the host engine
    if longest > key_width or rows < device_min_records:
        engine = "host"
    with tracing.span("join.match", cat="join", stage="encode", rows=rows,
                      engine=engine):
        sides = encode_keys(left.key_bytes, left.key_offsets, width) + \
            encode_keys(right.key_bytes, right.key_offsets, width)
    if engine == "host":
        with tracing.span("join.match", cat="join", stage="host"):
            return device.join_match_host(*sides)
    hits = device.join_match(*sides)
    if counters is not None:
        counters.increment(TaskCounter.JOIN_MATCH_ROWS, rows)
        counters.increment(TaskCounter.JOIN_MATCH_LAUNCHES)
    return hits


def merge_join_blocks(left_blocks: Iterable[KVBatch],
                      right_blocks: Iterable[KVBatch],
                      how: str = "semi_distinct", key_width: int = 16,
                      engine: str = "auto",
                      device_min_records: int = DEVICE_SORT_MIN_RECORDS,
                      counters: Optional[TezCounters] = None
                      ) -> Iterator[KVBatch]:
    """Yield the keys both streams hold, each once, in key order, as
    KVBatches with zero-width values.  Both streams are key-sorted blocks
    (``sorted_blocks()`` of an OrderedGroupedKVInput); a key may repeat
    inside a side and across its blocks.

    Each round cuts both sides below the smaller of the last keys of the
    sides that may still grow -- rows of that key itself wait: its run of
    equal keys may go on in the next block -- matches the aligned pieces,
    and reads on from the side that set the bound."""
    if how != "semi_distinct":
        raise ValueError(f"merge_join_blocks does {how!r} not: the batch "
                         f"operator is semi_distinct (query/processors.py "
                         f"has inner and semi, by row)")
    engine = resolve_engine(engine)
    left, right = _Side(left_blocks), _Side(right_blocks)
    emitted = 0
    while left.held.num_records and right.held.num_records:
        with tracing.span("join.align", cat="join"):
            growing = [s for s in (left, right) if not s.exhausted]
            bound = min(s.last_key() for s in growing) if growing else None
            limiting = [s for s in growing if s.last_key() == bound]
            pieces = left.take_below(bound), right.take_below(bound)
        if pieces[0].num_records and pieces[1].num_records:
            hits = _match(*pieces, key_width, engine, device_min_records,
                          counters)
            if len(hits):
                with tracing.span("join.emit", cat="join", rows=len(hits)):
                    keys, offsets = gather_ragged(
                        pieces[0].key_bytes, pieces[0].key_offsets, hits)
                    out = KVBatch(keys, offsets, np.zeros(0, np.uint8),
                                  np.zeros(len(hits) + 1, np.int64))
                emitted += len(hits)
                yield out
        if not growing:
            break
        for side in limiting:
            side.advance()
    if counters is not None:
        counters.increment(TaskCounter.JOIN_LEFT_RECORDS, left.consumed)
        counters.increment(TaskCounter.JOIN_RIGHT_RECORDS, right.consumed)
        counters.increment(TaskCounter.JOIN_OUTPUT_RECORDS, emitted)


def open_sorted_inputs(left_input: Any, right_input: Any
                       ) -> Tuple[Iterator[KVBatch], Iterator[KVBatch]]:
    """Both inputs' ``sorted_blocks()``, each with its first merged block
    in hand: the left input's wait and merge, then the right's, on this
    thread (upstream's joiner takes its two readers one after the other as
    well).  Both inputs' fetches are in flight from the task's start -- the
    heartbeat thread delivers them -- so the second wait is for producers
    still running.  ``join.wait_inputs`` brackets the two; the inputs' own
    ``shuffle.wait`` and ``shuffle.merge`` are its children.  The merges
    do not run side by side: a task's counters have one writer."""
    def first_block(inp: Any) -> Iterator[KVBatch]:
        blocks = iter(inp.get_reader().sorted_blocks())
        head = next(blocks, None)
        return iter(()) if head is None else itertools.chain([head], blocks)

    with tracing.span("join.wait_inputs", cat="join"):
        return first_block(left_input), first_block(right_input)
