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

Beside it the batch hash join: ``hash_join_blocks``.  The build side is
collected whole, encoded once and kept on the device; the stream side is
cut into probe blocks by row count, each probed against the build.  Two
kinds:

* ``how="semi"`` -- HashJoinExample.java's HashJoinProcessor (the hash side
  read whole into a set, the stream side walked past it, a key written
  whenever the set holds it), and a map join's filter (TPC-H Q3's orders
  against the segment's customers, the rows' values kept): ops/device.py
  ``join_probe``, the match's sort, then every stream row whose run of
  equal keys holds a build row;
* ``how="inner"`` with the build side's keys unique -- a key-foreign-key
  join, the shuffle join of a fact table with the table its key points
  into (Q3's lines against their orders): ops/device.py ``kfk_probe``, the
  same sort and run-end carry, then the build row each stream row hit, so
  that the build row's value is carried onto it (``join.carry``).  A
  repeated build key raises: a many-to-many join, with its expansion of
  pairs, is the query layer's row path (query/processors.py).
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


def _carried(stream: KVBatch, rows: np.ndarray, build: KVBatch,
             build_rows: np.ndarray) -> KVBatch:
    """The stream's `rows` with their keys, each value followed by the
    value of the build row it hit (`build_rows`, one a row): the hits'
    values gathered from each side, then one ragged gather that puts a
    row's two pieces side by side.  Nothing the size of the block is
    copied: a block has ~100 times the rows that hit."""
    keys, key_offsets = gather_ragged(stream.key_bytes, stream.key_offsets,
                                      rows)
    s_vals, s_offsets = gather_ragged(stream.val_bytes, stream.val_offsets,
                                      rows)
    b_vals, b_offsets = gather_ragged(build.val_bytes, build.val_offsets,
                                      build_rows)
    n = len(rows)
    perm = np.empty(2 * n, dtype=np.int64)          # stream_i, build_i
    perm[0::2] = np.arange(n)
    perm[1::2] = n + np.arange(n)
    values, pair_offsets = gather_ragged(
        np.concatenate([s_vals, b_vals]),
        np.concatenate([s_offsets, b_offsets[1:] + s_offsets[-1]]), perm)
    return KVBatch(keys, key_offsets, values, pair_offsets[0::2])


def hash_join_blocks(build_batches: Iterable[KVBatch],
                     stream_batches: Iterable[KVBatch], how: str = "semi",
                     key_width: int = 16, engine: str = "auto",
                     device_min_records: int = DEVICE_SORT_MIN_RECORDS,
                     counters: Optional[TezCounters] = None,
                     block_rows: int = PROBE_BLOCK_ROWS,
                     values: bool = False) -> Iterator[KVBatch]:
    """Yield the stream rows whose key the build side holds -- every
    occurrence, in arrival order -- a probe block at a time.
    `build_batches` is read whole first (an unordered input's
    ``iter_batches()``), `stream_batches` as it comes.

    ``how="semi"``: keys may repeat on either side; the rows come as keys
    with zero-width values, or with their own values where `values`.
    ``how="inner"``: a key-foreign-key join -- the build side's keys are
    unique (a repeated one raises ValueError), so each stream row hits at
    most one build row, and its value comes out followed by that build
    row's value (``join.carry``).

    A launch whose rows, the block's and the build side's, fall under the
    routing floor runs on the host engine, in lanes as wide as the longest
    key; so does a build side with a key beyond the edge's lane width, and
    a block with a stream key beyond them.  The build side goes up once a
    joiner: at the build where it alone reaches the floor, else with the
    first block that does, and every later block is probed beside it.  An
    empty build side yields nothing and launches nothing."""
    if how not in ("semi", "inner"):
        raise ValueError(f"hash_join_blocks does {how!r} not: the batch "
                         f"operator is semi or inner (key-foreign-key); "
                         f"query/processors.py has the rest, by row")
    inner = how == "inner"
    engine = resolve_engine(engine)
    width = ((key_width + 3) // 4) * 4      # the device's lanes: the edge's
    stage = device.stage_kfk_build if inner else device.stage_join_build
    on_device, build_longest = None, 0
    with tracing.span("join.build", cat="join", how=how) as span:
        parts = [b for b in build_batches if b.num_records]
        build = KVBatch.concat(parts) if parts else KVBatch.empty()
        n_build = build.num_records
        span.annotate(rows=n_build)
        if n_build:
            build_longest = int(np.diff(build.key_offsets).max())
        device_build = engine == "device" and n_build > 0 and \
            build_longest <= key_width
        if device_build and n_build >= device_min_records:
            on_device = stage(*encode_keys(build.key_bytes, build.key_offsets,
                                           width))
    host_build: dict = {}       # lane width -> the build side encoded at it
    probed = emitted = 0
    for block in _probe_blocks(stream_batches, block_rows, how=how) \
            if n_build else ():
        n = block.num_records
        probed += n
        longest = int(np.diff(block.key_offsets).max())
        if on_device is None and device_build and \
                n + n_build >= device_min_records:
            on_device = stage(*encode_keys(build.key_bytes, build.key_offsets,
                                           width))
        if on_device is not None and longest <= key_width:
            with tracing.span("join.match", cat="join", stage="encode",
                              how=how, rows=n, engine="device"):
                lanes, lens = encode_keys(block.key_bytes, block.key_offsets,
                                          width)
            if inner:
                hit = device.kfk_probe(lanes, lens, on_device)
            else:
                hits = device.join_probe(lanes, lens, on_device)
            if counters is not None and inner:
                counters.increment(TaskCounter.KFK_PROBE_ROWS, n + n_build)
                counters.increment(TaskCounter.KFK_PROBE_STREAM_ROWS, n)
                counters.increment(TaskCounter.KFK_PROBE_LAUNCHES)
            elif counters is not None:
                counters.increment(TaskCounter.JOIN_MATCH_ROWS, n + n_build)
                counters.increment(TaskCounter.JOIN_MATCH_LAUNCHES)
        else:
            wide = ((max(longest, build_longest, 1) + 3) // 4) * 4
            with tracing.span("join.match", cat="join", stage="encode",
                              how=how, rows=n, engine="host"):
                if wide not in host_build:
                    host_build[wide] = encode_keys(
                        build.key_bytes, build.key_offsets, wide)
                sides = encode_keys(block.key_bytes, block.key_offsets,
                                    wide) + host_build[wide]
            with tracing.span("join.match", cat="join", stage="host",
                              how=how):
                if inner:
                    hit = device.kfk_probe_host(*sides)
                else:
                    hits = device.join_probe_host(*sides)
        if inner:
            hits = np.flatnonzero(hit >= 0)
        if len(hits):
            if inner:
                with tracing.span("join.carry", cat="join",
                                  rows=len(hits)) as span:
                    out = _carried(block, hits, build, hit[hits])
                    span.annotate(value_bytes=int(out.val_offsets[-1]))
            else:
                with tracing.span("join.emit", cat="join", rows=len(hits)):
                    out = block.take(hits) if values \
                        else _key_batch(block, hits)
            emitted += len(hits)
            yield out
    if counters is not None:
        counters.increment(TaskCounter.JOIN_LEFT_RECORDS, probed)
        counters.increment(TaskCounter.JOIN_RIGHT_RECORDS, n_build)
        counters.increment(TaskCounter.JOIN_OUTPUT_RECORDS, emitted)
        if inner:
            counters.increment(TaskCounter.KFK_OUTPUT_ROWS, emitted)


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
