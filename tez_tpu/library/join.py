"""Batch merge-join over two key-sorted block streams.

Reference parity: tez-examples SortMergeJoinExample.java's
SortMergeJoinProcessor -- two KeyValuesReaders walked in lockstep, a key
written once when both sides hold it -- as a batch operator: no record is
touched in Python.  The two streams are cut at a common key bound (blocks
arrive in pieces; a spilled input streams), the aligned pieces go to the
match (ops/device.py ``join_match``: one stable sort of the two sides'
lanes concatenated, then a neighbour compare), and the matching keys come
out as a KVBatch with zero-width values.

Only ``how="semi_distinct"`` -- what the source runs.  ``inner`` stays with
the query layer's row path (query/processors.py).

Beside it the batch hash join (HashJoinExample.java's HashJoinProcessor:
the hash side read whole into a set, the stream side walked past it, a key
written whenever the set holds it): ``hash_join_blocks``.  The build side
is collected whole, encoded once and kept on the device; the stream side
is cut into probe blocks by row count, each matched against the build
(ops/device.py ``join_probe``: the match's sort, then every stream row
whose run of equal keys holds a build row), the matching stream rows
emitted.  Only ``how="semi"``, for the same reason.
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
                    out = _key_batch(pieces[0], hits)
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


#: stream rows a probe block: with the build side's bucket it is the probe
#: program's compile key, so it is cut by row count, never by which fetch
#: came first
PROBE_BLOCK_ROWS = 1 << 20


def _key_batch(batch: KVBatch, rows: np.ndarray) -> KVBatch:
    """The keys of `rows` of `batch`, with zero-width values."""
    keys, offsets = gather_ragged(batch.key_bytes, batch.key_offsets, rows)
    return KVBatch(keys, offsets, np.zeros(0, np.uint8),
                   np.zeros(len(rows) + 1, np.int64))


def _probe_blocks(batches: Iterable[KVBatch], block_rows: int,
                  name: str = "join.probe", cat: str = "join", **args: Any
                  ) -> Iterator[KVBatch]:
    """The stream's rows in arrival order as blocks of exactly `block_rows`
    (the last one shorter), whatever the sizes the batches come in.  Each
    cut is a span `name` of `cat` with `args` (the hash join's
    ``join.probe``)."""
    pending: list = []
    rows = block = 0
    stream = iter(batches)
    while True:
        with tracing.span(name, cat=cat, block=block, **args) as span:
            while rows < block_rows:
                batch = next(stream, None)
                if batch is None:
                    break
                if batch.num_records:
                    pending.append(batch)
                    rows += batch.num_records
            if not rows:
                return
            # the last batch gives what the block still lacks; its other
            # rows wait for the next block
            last, over = pending[-1], max(rows - block_rows, 0)
            keep = last.num_records - over
            pieces = pending[:-1] + [last.slice_rows(0, keep)]
            piece = pieces[0] if len(pieces) == 1 else KVBatch.concat(pieces)
            pending = [last.slice_rows(keep, last.num_records)] if over \
                else []
            rows = over
            span.annotate(rows=piece.num_records)
        yield piece
        block += 1


def hash_join_blocks(build_batches: Iterable[KVBatch],
                     stream_batches: Iterable[KVBatch], how: str = "semi",
                     key_width: int = 16, engine: str = "auto",
                     device_min_records: int = DEVICE_SORT_MIN_RECORDS,
                     counters: Optional[TezCounters] = None,
                     block_rows: int = PROBE_BLOCK_ROWS
                     ) -> Iterator[KVBatch]:
    """Yield the stream rows whose key the build side holds -- every
    occurrence, in arrival order -- as KVBatches of keys with zero-width
    values, a probe block at a time.  `build_batches` is read whole first
    (an unordered input's ``iter_batches()``), `stream_batches` as it
    comes; keys may repeat on either side.

    A build side with a key beyond the edge's lane width, or with fewer
    rows than the routing floor, is probed on the host engine, in lanes as
    wide as the longest key; so is a block with a stream key beyond the
    lanes.  An empty build side yields nothing and launches nothing."""
    if how != "semi":
        raise ValueError(f"hash_join_blocks does {how!r} not: the batch "
                         f"operator is semi (query/processors.py has "
                         f"inner, by row)")
    engine = resolve_engine(engine)
    width = ((key_width + 3) // 4) * 4      # the device's lanes: the edge's
    on_device, build_longest = None, 0
    with tracing.span("join.build", cat="join") as span:
        parts = [b for b in build_batches if b.num_records]
        build = KVBatch.concat(parts) if parts else KVBatch.empty()
        n_build = build.num_records
        span.annotate(rows=n_build)
        if n_build:
            build_longest = int(np.diff(build.key_offsets).max())
            if engine == "device" and build_longest <= key_width and \
                    n_build >= device_min_records:
                lanes, lens = encode_keys(build.key_bytes, build.key_offsets,
                                          width)
                on_device = device.stage_join_build(lanes, lens)
    host_build: dict = {}       # lane width -> the build side encoded at it
    probed = emitted = 0
    for block in _probe_blocks(stream_batches, block_rows) if n_build else ():
        n = block.num_records
        probed += n
        longest = int(np.diff(block.key_offsets).max())
        if on_device is not None and longest <= key_width:
            with tracing.span("join.match", cat="join", stage="encode",
                              how="semi", rows=n, engine="device"):
                lanes, lens = encode_keys(block.key_bytes, block.key_offsets,
                                          width)
            hits = device.join_probe(lanes, lens, on_device)
            if counters is not None:
                counters.increment(TaskCounter.JOIN_MATCH_ROWS, n + n_build)
                counters.increment(TaskCounter.JOIN_MATCH_LAUNCHES)
        else:
            wide = ((max(longest, build_longest, 1) + 3) // 4) * 4
            with tracing.span("join.match", cat="join", stage="encode",
                              how="semi", rows=n, engine="host"):
                if wide not in host_build:
                    host_build[wide] = encode_keys(
                        build.key_bytes, build.key_offsets, wide)
                sides = encode_keys(block.key_bytes, block.key_offsets,
                                    wide) + host_build[wide]
            with tracing.span("join.match", cat="join", stage="host",
                              how="semi"):
                hits = device.join_probe_host(*sides)
        if len(hits):
            with tracing.span("join.emit", cat="join", rows=len(hits)):
                out = _key_batch(block, hits)
            emitted += len(hits)
            yield out
    if counters is not None:
        counters.increment(TaskCounter.JOIN_LEFT_RECORDS, probed)
        counters.increment(TaskCounter.JOIN_RIGHT_RECORDS, n_build)
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
