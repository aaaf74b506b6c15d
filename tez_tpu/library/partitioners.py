"""Partitioner SPI + stock partitioners.

Reference parity: tez-runtime-library/.../library/partitioner/
{HashPartitioner,RoundRobinPartitioner}.java, and Hadoop's
mapreduce/lib/partition/TotalOrderPartitioner.java with the sampler of
examples/terasort/TeraInputFormat.writePartitionFile.

A partitioner with a batch form (``batch_form()``) is run by the sorter over
whole spans of serialized keys, fused into the device span sort
(ops/device.py); one without is called a record on logical keys.
"""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence

import numpy as np


def _stable_hash(key: Any) -> int:
    """Deterministic across processes (Python's hash() is salted)."""
    if isinstance(key, (bytes, bytearray)):
        data = bytes(key)
    elif isinstance(key, str):
        data = key.encode()
    elif isinstance(key, int):
        data = key.to_bytes(8, "little", signed=True)
    else:
        data = repr(key).encode()
    # FNV-1a 32-bit — matches ops/partition.py device kernel
    h = 2166136261
    for b in data:
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


class Partitioner:
    @classmethod
    def from_conf(cls, conf: Dict[str, Any]) -> "Partitioner":
        """Built by the output from its edge payload over the task conf."""
        return cls()

    def get_partition(self, key: Any, value: Any, num_partitions: int) -> int:
        raise NotImplementedError


class HashPartitioner(Partitioner):
    def get_partition(self, key: Any, value: Any, num_partitions: int) -> int:
        return _stable_hash(key) % num_partitions


class RoundRobinPartitioner(Partitioner):
    def __init__(self) -> None:
        self._next = 0

    def get_partition(self, key: Any, value: Any, num_partitions: int) -> int:
        p = self._next % num_partitions
        self._next += 1
        return p


#: edge-payload key the split points ride in (data, not a knob: P-1 sorted
#: serialized keys, written by the client that sampled them)
SPLIT_POINTS = "partitioner.split.points"


class TotalOrderPartitioner(Partitioner):
    """Range partitioner over sorted split points, P-1 of them: partition
    = number of split points <= the key in raw-byte order, so a key equal
    to split point i goes to partition i + 1.  Partition p's keys all sort
    below partition p+1's: part files in partition order are the global
    order.  Keys are compared serialized, as the sorter compares them."""

    def __init__(self, split_points: Sequence[bytes] = ()) -> None:
        self.split_points: List[bytes] = [bytes(s) for s in split_points]
        if self.split_points != sorted(self.split_points):
            raise ValueError("split points are not sorted")

    @classmethod
    def from_conf(cls, conf: Dict[str, Any]) -> "TotalOrderPartitioner":
        if SPLIT_POINTS not in conf:
            raise ValueError(f"TotalOrderPartitioner needs {SPLIT_POINTS!r} "
                             f"in the edge payload")
        return cls(conf[SPLIT_POINTS])

    def get_partition(self, key: Any, value: Any, num_partitions: int) -> int:
        if len(self.split_points) != num_partitions - 1:
            raise ValueError(f"{len(self.split_points)} split points for "
                             f"{num_partitions} partitions")
        return bisect.bisect_right(self.split_points, bytes(key))


def batch_form(partitioner: Any) -> Optional[str]:
    """The sorter's own name for this partitioner where the sorter can
    partition a whole span of serialized keys itself ("hash", "range");
    None: called a record.  Read from the class that OWNS ``get_partition``:
    a subclass of a stock partitioner that overrides it, and any class that
    only looks like a Partitioner, is called a record, as its author
    wrote it."""
    owner = getattr(type(partitioner), "get_partition", None)
    if owner is HashPartitioner.get_partition:
        return "hash"
    if owner is TotalOrderPartitioner.get_partition:
        return "range"
    return None


def sample_split_points(paths: Sequence[str], key_bytes: int,
                        value_bytes: int, num_partitions: int,
                        sample_keys: int = 100_000) -> List[bytes]:
    """Split points from a sample of fixed-width record files, as
    TeraInputFormat.writePartitionFile takes it at job submission: the
    first ``sample_keys / samples`` keys of ``samples = min(10, files)``
    files taken at an even stride, sorted, and cut at P-1 even ranks.  No
    random draw: the same files give the same split points."""
    from tez_tpu.io.formats import compute_splits
    # the files the input itself will read: globs and directories expanded
    files = [s.path for s in compute_splits(paths, 0)]
    if num_partitions <= 1 or not files:
        return []
    rec = key_bytes + value_bytes
    samples = min(10, len(files))
    per_file = max(1, sample_keys // samples)
    stride = len(files) // samples
    keys = []
    for i in range(samples):
        raw = np.fromfile(files[i * stride], dtype=np.uint8,
                          count=per_file * rec)
        keys.append(raw[:len(raw) // rec * rec].reshape(-1, rec)[:, :key_bytes])
    mat = np.concatenate(keys)
    if not len(mat):
        return []
    # big-endian u64 columns order as the bytes do; lexsort takes its keys
    # minor to major
    cols = np.pad(mat, ((0, 0), (0, (-key_bytes) % 8))).view(">u8")
    mat = mat[np.lexsort(cols.T[::-1])]
    step = len(mat) / num_partitions
    return [mat[min(len(mat) - 1, round(step * i))].tobytes()
            for i in range(1, num_partitions)]
