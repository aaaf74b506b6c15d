"""Host buffers that are found again: the blocks behind the large arrays a
DAG makes over and over.

A sort's and a merge's host work is a few array shapes made again and again:
a gather's output for every span and every merge, a concatenation before
every merge, the interleave before every part file — 21-94 MB each, about
3 GB a TeraSort DAG in under a hundred requests (PERF.md §6, PR 28).  The C
library maps each such request afresh and unmaps it on free, so every byte
is first-touched again; where a first touch is dear (1.05 ms/MB under the
chip machine's sandbox) that is a third of a DAG's CPU, and which thread's
arena happens to hold what makes one run differ from the next.

``empty(count, dtype)`` is ``np.empty`` from blocks that come back.  The
array it returns is ``np.frombuffer`` over a lease (a ctypes view of the
block); numpy keeps the lease as the ``base`` of that array and of every
view, slice and reshape made from it, so the block goes back to the pool
exactly when nothing can reach its memory any more — no call site says when it is done, and none can say so
too early.  Idle blocks are kept by size class (eighths of a power of two:
at most 12.5 % over the request) up to a share of the machine's memory; past
it the block idle longest goes, so a session's earlier DAG shapes make room
for its later ones.
"""
from __future__ import annotations

import ctypes
import os
import threading
import weakref
from typing import Dict, List, Tuple

import numpy as np

#: smaller requests stay with the C library, whose arenas reuse them
MIN_BYTES = 1 << 20


def _max_idle_bytes() -> int:
    """An eighth of physical memory, at most 8 GiB."""
    try:
        physical = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError, AttributeError):
        physical = 8 << 30
    return min(8 << 30, physical // 8)


def size_class(nbytes: int) -> int:
    """`nbytes` rounded up to an eighth of the power of two at or below."""
    step = 1 << max(nbytes.bit_length() - 4, 12)
    return -(-nbytes // step) * step


class HostPool:
    def __init__(self, max_idle_bytes: int):
        self.max_idle_bytes = max_idle_bytes
        #: size class -> [(age, block)], newest last: taken from the end
        #: (its pages are the likeliest still in cache), dropped from the
        #: front
        self._idle: Dict[int, List[Tuple[int, np.ndarray]]] = {}
        self._idle_bytes = 0
        self._age = 0
        # re-entrant: a finalizer can run wherever the collector does, also
        # in a thread that is inside the pool
        self._lock = threading.RLock()
        self.reused = 0
        self.made = 0

    def empty(self, count: int, dtype=np.uint8) -> np.ndarray:
        dtype = np.dtype(dtype)
        count = int(count)
        nbytes = count * dtype.itemsize
        if nbytes < MIN_BYTES:
            return np.empty(count, dtype)
        cap = size_class(nbytes)
        block = None
        with self._lock:
            idle = self._idle.get(cap)
            if idle:
                block = idle.pop()[1]
                self._idle_bytes -= cap
                self.reused += 1
            else:
                self.made += 1
        if block is None:
            block = np.empty(cap, np.uint8)
        # what the array's ``base`` chain ends in: a ctypes view of the
        # block, which exports its memory, owns none of it, and can carry
        # the finalizer that hands the block back
        lease = (ctypes.c_ubyte * cap).from_buffer(block)
        weakref.finalize(lease, self._give_back, block).atexit = False
        return np.frombuffer(lease, dtype=dtype, count=count)

    def _give_back(self, block: np.ndarray) -> None:
        with self._lock:
            self._age += 1
            self._idle.setdefault(block.nbytes, []).append((self._age, block))
            self._idle_bytes += block.nbytes
            while self._idle_bytes > self.max_idle_bytes:
                cap = min((c for c, idle in self._idle.items() if idle),
                          key=lambda c: self._idle[c][0][0])
                self._idle[cap].pop(0)
                self._idle_bytes -= cap

    @property
    def idle_bytes(self) -> int:
        return self._idle_bytes

    def clear(self) -> None:
        with self._lock:
            self._idle.clear()
            self._idle_bytes = 0


_POOL = HostPool(_max_idle_bytes())


def pool() -> HostPool:
    """The process's pool."""
    return _POOL


def empty(count: int, dtype=np.uint8) -> np.ndarray:
    """``np.empty(count, dtype)`` whose memory is found again once the array
    and every view of it are gone."""
    return _POOL.empty(count, dtype)


def concatenate(arrays) -> np.ndarray:
    """``np.concatenate`` of 1-D arrays of one dtype into a pooled array."""
    out = empty(sum(len(a) for a in arrays),
                np.result_type(*(a.dtype for a in arrays)))
    return np.concatenate(arrays, out=out)
