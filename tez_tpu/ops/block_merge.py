"""Blockwise vectorized k-way merge over block-sorted KVBatch streams.

The spill-scale analog of TezMerger's record-streaming MergeQueue
(tez-runtime-library/.../common/sort/impl/TezMerger.java:76), re-thought for
this framework's batch-first data plane: instead of a per-record Python heap
(one compare + one yield per record — the round-3 45x spill cliff), sources
advance one *block prefix* at a time and every prefix set merges with the
vectorized run merge (`ops.sorter.merge_sorted_runs` — the native merge on
the host, or on the device one stable sort of the slices' concatenation,
padded once to the bucket of its rows), so Python cost is O(blocks), not
O(records).

Algorithm (classic tournament over block boundaries):
  each source = iterator of KVBatch blocks, each internally sorted and
  globally ordered across blocks within the source.  Per round:
    boundary  = min over sources of (last sort key of current block)
    cut_s     = upper_bound(boundary) within source s's current block
    emit      = vectorized merge of the `[pos, cut)` slices
  The source owning the boundary drains its whole block each round, so the
  total vectorized-merge work is one merge per record and the per-round
  Python cost is k bisects of O(log block) byte compares.

Equal keys across sources emerge in source-list order (pass sources in run
age order for the reference's MergeQueue arrival-order semantics); within a
source, producer order is preserved exactly (stable merges).
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from tez_tpu.common import tracing
from tez_tpu.ops.runformat import KVBatch, Run

__all__ = ["iter_merged_blocks"]


class _Source:
    """One block-sorted input stream with its normalized sort-key view."""

    def __init__(self, blocks: Iterator[KVBatch],
                 normalizer: Optional[Callable[[bytes], bytes]]):
        self.blocks = blocks
        self.normalizer = normalizer
        self.batch: Optional[KVBatch] = None
        self.sort_bytes: Optional[np.ndarray] = None
        self.sort_offsets: Optional[np.ndarray] = None
        self.pos = 0

    def advance(self) -> bool:
        """Load the next non-empty block; False when exhausted."""
        from tez_tpu.ops.sorter import normalize_batch_keys
        for batch in self.blocks:
            if batch.num_records == 0:
                continue
            self.batch = batch
            if self.normalizer is not None:
                self.sort_bytes, self.sort_offsets = \
                    normalize_batch_keys(batch, self.normalizer)
            else:
                self.sort_bytes = batch.key_bytes
                self.sort_offsets = batch.key_offsets
            self.pos = 0
            return True
        self.batch = None
        return False

    def sort_key(self, i: int) -> bytes:
        o = self.sort_offsets
        return self.sort_bytes[int(o[i]):int(o[i + 1])].tobytes()

    def last_key(self) -> bytes:
        return self.sort_key(self.batch.num_records - 1)

    def lower_bound(self, key: bytes) -> int:
        """First row index in [pos, n) whose sort key is >= `key`."""
        lo, hi = self.pos, self.batch.num_records
        while lo < hi:
            mid = (lo + hi) // 2
            if self.sort_key(mid) < key:
                lo = mid + 1
            else:
                hi = mid
        return lo

    def upper_bound(self, key: bytes) -> int:
        """First row index in [pos, n) whose sort key exceeds `key`."""
        lo, hi = self.pos, self.batch.num_records
        while lo < hi:
            mid = (lo + hi) // 2
            if self.sort_key(mid) <= key:
                lo = mid + 1
            else:
                hi = mid
        return lo

    def take_to(self, cut: int) -> Optional[KVBatch]:
        """Consume rows [pos, cut); None when empty."""
        if cut <= self.pos:
            return None
        piece = self.batch.slice_rows(self.pos, cut)
        self.pos = cut
        return piece

    def drain_equal(self, key: bytes) -> Iterator[KVBatch]:
        """Stream this source's entire run of rows == `key`, crossing block
        boundaries (a giant equal-key run spanning blocks must emit
        contiguously to preserve the reference MergeQueue's source-order
        semantics for ties).  Yields piece-at-a-time so a hot key never
        materializes whole — resident memory stays one block."""
        while self.batch is not None:
            if self.pos < self.batch.num_records and \
                    self.sort_key(self.pos) != key:
                return
            with tracing.span("merge.cut", cat="merge", stage="equal"):
                piece = self.take_to(self.upper_bound(key))
            if piece is not None:
                yield piece
            if self.pos < self.batch.num_records:
                return
            if not self.advance():
                return


def iter_merged_blocks(
        sources: Sequence[Iterator[KVBatch]],
        key_width: int,
        engine: str = "host",
        key_normalizer: Optional[Callable[[bytes], bytes]] = None,
        merge_factor: int = 64,
        device_min_records: Optional[int] = None,
        counters=None) -> Iterator[KVBatch]:
    """Yield globally-sorted KVBatch blocks merged from k block-sorted
    sources.  Resident memory is one block per source plus one merge round's
    output."""
    from tez_tpu.ops.sorter import DEVICE_SORT_MIN_RECORDS, merge_sorted_runs
    if device_min_records is None:
        device_min_records = DEVICE_SORT_MIN_RECORDS
    active: List[_Source] = []
    for it in sources:
        s = _Source(iter(it), key_normalizer)
        if s.advance():
            active.append(s)
    while active:
        if len(active) == 1:
            # single remaining source: its blocks are already sorted
            s = active[0]
            if s.pos == 0:
                yield s.batch
            elif s.pos < s.batch.num_records:
                yield s.batch.slice_rows(s.pos, s.batch.num_records)
            while s.advance():
                yield s.batch
            return
        # one round's cut (host work on the reducer's own thread; a span
        # a round, never across a yield)
        with tracing.span("merge.cut", cat="merge", sources=len(active)):
            boundary = min(s.last_key() for s in active)
            # phase 1: rows strictly below the boundary key — safe to merge
            # (no source can still hold an unseen row < boundary)
            slices: List[Run] = []
            for s in active:
                piece = s.take_to(s.lower_bound(boundary))
                if piece is not None:
                    slices.append(Run(piece,
                                      np.array([0, piece.num_records],
                                               dtype=np.int64)))
        if len(slices) == 1:
            yield slices[0].batch
        elif slices:
            # one round of many: the caller reports the merge as a whole
            merged = merge_sorted_runs(
                slices, 1, key_width, counters=counters, engine=engine,
                merge_factor=merge_factor, key_normalizer=key_normalizer,
                device_min_records=device_min_records, final=False)
            yield merged.batch
        # phase 2: rows == boundary, streamed per source IN SOURCE ORDER and
        # contiguously across each source's block boundaries — exactly the
        # heap-merge tie order (equal keys: all of the earlier run's rows,
        # then the next run's).  Pieces yield as they drain so a hot key
        # never materializes whole.
        for s in active:
            yield from s.drain_equal(boundary)
        active = [s for s in active if s.batch is not None]
