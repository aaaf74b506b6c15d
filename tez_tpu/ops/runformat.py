"""Run format: the IFile analog for HBM/host-RAM resident sorted runs.

Reference parity: tez-runtime-library/.../common/sort/impl/IFile.java:67 (KV
run format with per-partition index) + TezSpillRecord.java (partition index).
Differences by design (SURVEY.md §2.5): instead of a varint byte stream, a
run is a *columnar quad* — key bytes + offsets, value bytes + offsets — plus
a partition row index.  That layout is what the device kernels consume
directly (offsets+bytes dual tensors), needs no per-record decode loop, and
serializes to disk with a checksummed header for the host-spill path
(IFileOutputStream CRC analog).
"""
from __future__ import annotations

import dataclasses
import io
import os
import struct
import zlib
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from tez_tpu.common import faults, tracing
from tez_tpu.ops import hostpool

MAGIC = b"TPRUN1"
#: MAGIC + pack("<BIQ", flag, crc32(payload), len(payload)).  The CRC covers
#: the payload only, so corrupt-injection below the header is guaranteed to
#: surface as the checksum IOError (not a codec decode error).
RUN_HEADER_NBYTES = len(MAGIC) + 13


def _zstd_codec():
    import zstandard   # baked into the image; gate loudly if ever absent
    comp = zstandard.ZstdCompressor(level=1)
    dec = zstandard.ZstdDecompressor()
    return comp.compress, dec.decompress


def _lz4_codec():
    try:
        import lz4.frame
    except ImportError:
        raise ValueError(
            "run codec 'lz4' requires the lz4 module, which is not "
            "available in this environment (supported here: zlib, zstd)"
        ) from None
    return lz4.frame.compress, lz4.frame.decompress


#: codec name -> (wire flag, lazy (compress, decompress) factory).  The flag
#: is stored in the run header, so blobs stay self-describing across codec
#: config changes (reference: per-stream codec in IFile.java:67).
_CODECS = {
    None: (0, lambda: (lambda b: b, lambda b: b)),
    "zlib": (1, lambda: (lambda b: zlib.compress(b, 1), zlib.decompress)),
    "zstd": (2, _zstd_codec),
    "lz4": (3, _lz4_codec),
}
_FLAG_TO_NAME = {flag: name for name, (flag, _) in _CODECS.items()}


def resolve_codec(codec: Optional[str]):
    """-> (wire flag, compress, decompress); loud error on unknown names —
    an unknown codec silently writing uncompressed is worse."""
    entry = _CODECS.get(codec)
    if entry is None:
        raise ValueError(f"unsupported run codec {codec!r} "
                         f"(supported: zlib, zstd, lz4)")
    flag, factory = entry
    compress, decompress = factory()
    return flag, compress, decompress


def resolve_codec_flag(flag: int):
    if flag not in _FLAG_TO_NAME:
        raise ValueError(f"unknown run codec flag {flag}")
    name = _FLAG_TO_NAME[flag]
    return (name,) + resolve_codec(name)[1:]


def _ranges(lengths: np.ndarray) -> np.ndarray:
    """[3,1,2] -> [0,1,2, 0, 0,1] (per-segment aranges)."""
    total = int(lengths.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    starts = np.zeros(len(lengths), dtype=np.int64)
    np.cumsum(lengths[:-1], out=starts[1:])
    return np.arange(total, dtype=np.int64) - np.repeat(starts, lengths)


def gather_ragged(data: np.ndarray, offsets: np.ndarray,
                  perm: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Permute a ragged array: returns (new_data, new_offsets).

    Large batches go through the native multithreaded per-row memcpy
    (native/ragged.cpp) -- rows of one width, whatever the width, as one
    strided gather with no offset lookups; numpy fancy indexing
    otherwise."""
    from tez_tpu.ops.native import MIN_NATIVE_BYTES
    if data.nbytes >= MIN_NATIVE_BYTES:
        n_src = len(offsets) - 1
        if n_src > 0:
            w = int(offsets[1]) - int(offsets[0])
            if w > 0 and int(offsets[-1]) == n_src * w and \
                    not bool((offsets[1:] != offsets[:-1] + w).any()):
                from tez_tpu.ops.native import gather_fixed_native
                new_offsets = hostpool.empty(len(perm) + 1, np.int64)
                new_offsets[0] = 0
                new_offsets[1:] = w
                np.cumsum(new_offsets, out=new_offsets)   # [0, w, 2w, ...]
                return gather_fixed_native(data, w, perm), new_offsets
        from tez_tpu.ops.native import gather_ragged_native
        return gather_ragged_native(data, offsets, perm)
    lengths = offsets[1:] - offsets[:-1]
    new_lengths = lengths[perm]
    new_offsets = np.zeros(len(perm) + 1, dtype=np.int64)
    np.cumsum(new_lengths, out=new_offsets[1:])
    idx = np.repeat(offsets[:-1][perm], new_lengths) + _ranges(new_lengths)
    return data[idx], new_offsets


def adjacent_equal_rows(data: np.ndarray, offsets: np.ndarray,
                        cand: np.ndarray) -> np.ndarray:
    """For each candidate row index i (caller guarantees rows i and i+1
    have equal byte length), return True where row i's bytes equal row
    i+1's — one flat gather per side + a per-pair reduction instead of a
    Python loop over pairs (the grouping/combine hot path: adjacent-equal
    detection over sorted runs, ValuesIterator.java:45 semantics)."""
    m = len(cand)
    if m == 0:
        return np.zeros(0, dtype=bool)
    lengths = (offsets[1:] - offsets[:-1])[cand]
    from tez_tpu.ops.native import MIN_NATIVE_BYTES
    if int(lengths.sum()) >= MIN_NATIVE_BYTES:
        # the numpy path materializes one int64 index per BYTE (8x memory
        # expansion); the native threaded memcmp avoids it on large runs
        from tez_tpu.ops.native import adjacent_equal_native
        return adjacent_equal_native(data, offsets, cand)
    out = np.ones(m, dtype=bool)          # zero-length pairs are equal
    nz = np.flatnonzero(lengths)
    if len(nz) == 0:
        return out
    nz_cand = cand[nz]
    nz_len = lengths[nz]
    within = _ranges(nz_len)
    idx_a = np.repeat(offsets[nz_cand], nz_len) + within
    idx_b = np.repeat(offsets[nz_cand + 1], nz_len) + within
    neq = data[idx_a] != data[idx_b]
    pair_starts = np.zeros(len(nz), dtype=np.int64)
    np.cumsum(nz_len[:-1], out=pair_starts[1:])
    mismatches = np.add.reduceat(neq.astype(np.int64), pair_starts)
    out[nz] = mismatches == 0
    return out


#: the widest key `group_starts` compares a word at a time: four 8-byte
#: words.  Each word is one strided pass over the block; the ragged path's
#: native memcmp is as fast at 32 B and 2^20 rows, and faster past that.
MAX_FIXED_WIDTH = 32


def fixed_key_width(offsets: np.ndarray) -> int:
    """The width `group_starts` compares keys at: the byte length every row
    shares, if at most MAX_FIXED_WIDTH (0 rows: 0); else -1, its ragged
    path."""
    n = len(offsets) - 1
    if n <= 0:
        return 0
    start, end = int(offsets[0]), int(offsets[-1])
    w = int(offsets[1]) - start
    if w > MAX_FIXED_WIDTH or end - start != n * w:
        return -1
    # compared as memoryviews, which keep the GIL: each numpy pass lets it
    # go, and among a DAG's reducer threads taking it back costs more than
    # the pass (PR 37, PERF.md §6).  Offsets never fall, so w == 0 here
    # means every row is empty
    if w and memoryview(offsets) != memoryview(np.arange(start, end + 1, w)):
        return -1
    return w


def group_starts(data: np.ndarray, offsets: np.ndarray,
                 width: Optional[int] = None) -> np.ndarray:
    """Row indices where a new key begins in a block of sorted keys: row 0
    and every row whose bytes differ from the row before
    (ValuesIterator.java:45 semantics).  `width` is `fixed_key_width`'s
    answer where the caller has it.  Keys of one width compare a word of
    the row at a time, the last word ending at the row's end (so it may
    overlap the one before: every byte is in some word); ragged keys compare
    bytes where lengths agree."""
    n = len(offsets) - 1
    if n <= 0:
        return np.zeros(0, dtype=np.int64)
    w = fixed_key_width(offsets) if width is None else width
    if w < 0:
        lengths = offsets[1:] - offsets[:-1]
        same = np.zeros(n, dtype=bool)
        cand = np.flatnonzero(lengths[1:] == lengths[:-1])
        same[cand + 1] = adjacent_equal_rows(data, offsets, cand)
        return np.flatnonzero(~same).astype(np.int64)
    if w == 0:
        return np.zeros(1, dtype=np.int64)
    rows = np.ascontiguousarray(data[offsets[0]:offsets[-1]])
    size = 8 if w >= 8 else 4 if w >= 4 else 2 if w >= 2 else 1
    new = np.empty(n, dtype=bool)
    new[0] = True
    for at in range(0, w, size):
        word = np.ndarray((n,), f"u{size}", rows, min(at, w - size), (w,))
        if at == 0:
            np.not_equal(word[1:], word[:-1], out=new[1:])
        else:
            new[1:] |= word[1:] != word[:-1]
    return np.flatnonzero(new).astype(np.int64, copy=False)


def concat_ragged(parts: Sequence[Tuple[np.ndarray, np.ndarray]]
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Concatenate (data, offsets) raggeds."""
    if not parts:
        return np.zeros(0, np.uint8), np.zeros(1, np.int64)
    datas = [p[0] for p in parts]
    data = hostpool.concatenate(datas)
    sizes = [len(p[1]) - 1 for p in parts]
    offsets = hostpool.empty(sum(sizes) + 1, np.int64)
    offsets[0] = 0
    pos, base = 1, 0
    for (d, o), sz in zip(parts, sizes):
        offsets[pos:pos + sz] = o[1:] + base
        base += len(d)
        pos += sz
    return data, offsets


@dataclasses.dataclass
class KVBatch:
    """Columnar record batch: ragged keys + ragged values.

    dev_keys optionally carries a DEVICE-resident view of the sort keys —
    (lanes u32[NB, L], lengths i32[NB], lo, hi) where rows [lo, hi) of the
    bucketed arrays align with this batch's rows and tail rows are
    sentinels.  It lets a same-process consumer merge fetched partitions
    without re-uploading key bytes (SURVEY.md §2.5 "spans = device
    buffers"); it is dropped by serialization, pickling, take() and
    concat() (order changes invalidate the row alignment)."""
    key_bytes: np.ndarray     # uint8[..]
    key_offsets: np.ndarray   # int64[N+1]
    val_bytes: np.ndarray
    val_offsets: np.ndarray
    dev_keys: Optional[tuple] = dataclasses.field(
        default=None, compare=False, repr=False)
    #: producer promise: keys in this batch are already unique (e.g. the
    #: fused tokenize+count aggregator) — the sorter skips its pre-sort
    #: hash combine for spans made only of such batches.  Dropped (False)
    #: by take()/concat()/serialization like dev_keys.
    pre_combined: bool = dataclasses.field(
        default=False, compare=False, repr=False)

    def __getstate__(self) -> dict:
        state = self.__dict__.copy()
        state["dev_keys"] = None   # device handles never cross processes
        return state

    def __setstate__(self, state: dict) -> None:
        self.__dict__.update(state)

    @property
    def num_records(self) -> int:
        return len(self.key_offsets) - 1

    @property
    def nbytes(self) -> int:
        return (self.key_bytes.nbytes + self.val_bytes.nbytes +
                self.key_offsets.nbytes + self.val_offsets.nbytes)

    def key(self, i: int) -> bytes:
        return self.key_bytes[self.key_offsets[i]:self.key_offsets[i + 1]]\
            .tobytes()

    def value(self, i: int) -> bytes:
        return self.val_bytes[self.val_offsets[i]:self.val_offsets[i + 1]]\
            .tobytes()

    def take(self, perm: np.ndarray) -> "KVBatch":
        kb, ko = gather_ragged(self.key_bytes, self.key_offsets, perm)
        vb, vo = gather_ragged(self.val_bytes, self.val_offsets, perm)
        return KVBatch(kb, ko, vb, vo)

    def slice_rows(self, start: int, stop: int) -> "KVBatch":
        ko = self.key_offsets[start:stop + 1]
        vo = self.val_offsets[start:stop + 1]
        dev = None
        if self.dev_keys is not None:
            lanes, lens, lo, _hi = self.dev_keys
            dev = (lanes, lens, lo + start, lo + stop)   # view, no copy
        # the subtraction already yields fresh int64 arrays — an astype
        # here would be a second full copy on the per-block hot path
        return KVBatch(
            self.key_bytes[ko[0]:ko[-1]], ko - ko[0],
            self.val_bytes[vo[0]:vo[-1]], vo - vo[0],
            dev_keys=dev)

    @staticmethod
    def empty() -> "KVBatch":
        z = np.zeros(0, np.uint8)
        o = np.zeros(1, np.int64)
        return KVBatch(z, o, z.copy(), o.copy())

    @staticmethod
    def concat(batches: Sequence["KVBatch"]) -> "KVBatch":
        kb, ko = concat_ragged([(b.key_bytes, b.key_offsets) for b in batches])
        vb, vo = concat_ragged([(b.val_bytes, b.val_offsets) for b in batches])
        return KVBatch(kb, ko, vb, vo)

    @staticmethod
    def from_pairs(pairs: Sequence[Tuple[bytes, bytes]]) -> "KVBatch":
        ko = np.zeros(len(pairs) + 1, dtype=np.int64)
        vo = np.zeros(len(pairs) + 1, dtype=np.int64)
        for i, (k, v) in enumerate(pairs):
            ko[i + 1] = ko[i] + len(k)
            vo[i + 1] = vo[i] + len(v)
        kb = np.frombuffer(b"".join(k for k, _ in pairs), dtype=np.uint8).copy()
        vb = np.frombuffer(b"".join(v for _, v in pairs), dtype=np.uint8).copy()
        return KVBatch(kb, ko, vb, vo)

    def iter_pairs(self) -> Iterator[Tuple[bytes, bytes]]:
        for i in range(self.num_records):
            yield self.key(i), self.value(i)


@dataclasses.dataclass
class Run:
    """A partition-sorted KV run + partition row index.

    Rows [row_index[p], row_index[p+1]) belong to partition p and are
    key-sorted within.  The TezSpillRecord analog is `row_index` (+ byte
    sizes derivable from offsets).
    """
    batch: KVBatch
    row_index: np.ndarray     # int64[P+1]

    @property
    def num_partitions(self) -> int:
        return len(self.row_index) - 1

    def partition(self, p: int) -> KVBatch:
        return self.batch.slice_rows(int(self.row_index[p]),
                                     int(self.row_index[p + 1]))

    def partition_row_count(self, p: int) -> int:
        return int(self.row_index[p + 1] - self.row_index[p])

    def partition_nbytes(self, p: int) -> int:
        s, e = int(self.row_index[p]), int(self.row_index[p + 1])
        return int((self.batch.key_offsets[e] - self.batch.key_offsets[s]) +
                   (self.batch.val_offsets[e] - self.batch.val_offsets[s]))

    def empty_partition_flags(self) -> List[bool]:
        return [self.partition_row_count(p) == 0
                for p in range(self.num_partitions)]

    @property
    def nbytes(self) -> int:
        return self.batch.nbytes

    # -- host-spill serialization (checksummed; IFileOutputStream analog) ----
    # Offset arrays (key_offsets / val_offsets) are DELTA-CODED on the
    # wire: per-record LENGTHS in the narrowest unsigned dtype that fits
    # (u8/u16/u32; i64 raw offsets beyond that).  For small-record spills
    # this is the difference between 16 B and 2 B of index per record —
    # on-disk size was otherwise ~2x the KV payload.  Wire dtype chars
    # '1'/'2'/'4' mark delta-u8/u16/u32; everything stays self-describing.
    _DELTA_CHARS = {b"1": np.uint8, b"2": np.uint16, b"4": np.uint32}

    @staticmethod
    def _encode_offsets(offsets: np.ndarray) -> Tuple[bytes, np.ndarray]:
        if len(offsets) and int(offsets[0]) != 0:
            # delta coding reconstructs from base 0: a rebased view must
            # ship raw (lossless) rather than silently rebase
            return offsets.dtype.char.encode(), offsets
        lens = np.diff(offsets)
        m = int(lens.max(initial=0))
        if m < (1 << 8):
            return b"1", lens.astype(np.uint8)
        if m < (1 << 16):
            return b"2", lens.astype(np.uint16)
        if m < (1 << 32):
            return b"4", lens.astype(np.uint32)
        return offsets.dtype.char.encode(), offsets

    @staticmethod
    def _decode_offsets(char: bytes, raw: np.ndarray) -> np.ndarray:
        offsets = np.zeros(len(raw) + 1, dtype=np.int64)
        np.cumsum(raw, out=offsets[1:])
        return offsets

    def _wire_arrays(self) -> List[Tuple[bytes, np.ndarray]]:
        kc, ko = self._encode_offsets(self.batch.key_offsets)
        vc, vo = self._encode_offsets(self.batch.val_offsets)
        return [(self.batch.key_bytes.dtype.char.encode(),
                 self.batch.key_bytes),
                (kc, ko),
                (self.batch.val_bytes.dtype.char.encode(),
                 self.batch.val_bytes),
                (vc, vo),
                (self.row_index.dtype.char.encode(), self.row_index)]

    def to_bytes(self, codec: Optional[str] = None) -> bytes:
        flag, compress, _ = resolve_codec(codec)
        buf = io.BytesIO()
        for char, a in self._wire_arrays():
            raw = compress(np.ascontiguousarray(a).tobytes())
            buf.write(struct.pack("<cQ", char, len(raw)))
            buf.write(raw)
        payload = buf.getvalue()
        header = MAGIC + struct.pack(
            "<BIQ", flag, zlib.crc32(payload), len(payload))
        return header + payload

    @staticmethod
    def from_bytes(data: bytes, where: str = "<bytes>") -> "Run":
        if data[:len(MAGIC)] != MAGIC:
            raise IOError(f"bad run magic in {where}")
        off = len(MAGIC)
        flag, crc, size = struct.unpack_from("<BIQ", data, off)
        off += 1 + 4 + 8
        payload = data[off:off + size]
        if zlib.crc32(payload) != crc:
            raise IOError(f"checksum mismatch in {where}")
        try:
            _, _, decompress = resolve_codec_flag(flag)
        except ValueError as e:
            raise IOError(f"{e} in {where}") from None
        buf = io.BytesIO(payload)
        arrays = []
        for _ in range(5):
            dtype_c, length = struct.unpack("<cQ", buf.read(9))
            raw = decompress(buf.read(length))
            dt = Run._DELTA_CHARS.get(dtype_c)
            if dt is not None:
                arrays.append(Run._decode_offsets(
                    dtype_c, np.frombuffer(raw, dtype=dt)))
            else:
                arrays.append(np.frombuffer(raw, dtype=np.dtype(
                    dtype_c.decode())).copy())
        kb, ko, vb, vo, ri = arrays
        return Run(KVBatch(kb, ko, vb, vo), ri)

    def write_to(self, fh, codec: Optional[str] = None) -> int:
        """Stream this run into an open file.  The uncompressed hot path
        writes each wire array buffer directly (one checksum pass + one
        write pass — no BytesIO assembly, no tobytes copies); codecs fall
        back to the blob builder.  Returns bytes written."""
        flag, _compress, _ = resolve_codec(codec)
        if flag != 0:
            blob = self.to_bytes(codec)
            fh.write(blob)
            return len(blob)
        pairs = [(c, np.ascontiguousarray(a)) for c, a in
                 self._wire_arrays()]
        headers = [struct.pack("<cQ", c, a.nbytes) for c, a in pairs]
        crc = 0
        for h, (_c, a) in zip(headers, pairs):
            crc = zlib.crc32(h, crc)
            crc = zlib.crc32(memoryview(a).cast("B"), crc)
        size = sum(len(h) + a.nbytes for h, (_c, a) in zip(headers, pairs))
        fh.write(MAGIC + struct.pack("<BIQ", 0, crc, size))
        for h, (_c, a) in zip(headers, pairs):
            fh.write(h)
            fh.write(memoryview(a).cast("B"))
        return len(MAGIC) + 13 + size

    def save(self, path: str, codec: Optional[str] = None) -> None:
        from tez_tpu.common import metrics
        faults.fire("spill.write", detail=path)
        with metrics.timer("spill.write"):
            tmp = path + ".tmp"
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(tmp, "wb") as fh:
                self.write_to(fh, codec)
            os.replace(tmp, path)

    @staticmethod
    def load(path: str) -> "Run":
        faults.fire("spill.read", detail=path)
        with open(path, "rb") as fh:
            data = fh.read()
        data = faults.corrupt_bytes("spill.read", path, data,
                                    lo=RUN_HEADER_NBYTES)
        return Run.from_bytes(data, where=path)

    @staticmethod
    def from_sorted_batch(batch: KVBatch, sorted_partitions: np.ndarray,
                          num_partitions: int) -> "Run":
        """Build the row index from the (sorted) per-row partition ids."""
        counts = np.bincount(sorted_partitions, minlength=num_partitions)\
            .astype(np.int64)
        row_index = np.zeros(num_partitions + 1, dtype=np.int64)
        np.cumsum(counts, out=row_index[1:])
        return Run(batch, row_index)


def _write_block(fh, piece: KVBatch, codec: Optional[str]) -> int:
    """Write one length-prefixed single-partition Run blob (the shared
    block format of ChunkedRunWriter and PartitionedRunWriter).  Returns
    the blob size (excluding the 8-byte prefix)."""
    run = Run(piece, np.array([0, piece.num_records], dtype=np.int64))
    if codec is None:
        # streamed write: length backfilled after the streaming pass (the
        # writers' targets are regular seekable files)
        at = fh.tell()
        fh.write(struct.pack("<Q", 0))
        size = run.write_to(fh)
        end = fh.tell()
        fh.seek(at)
        fh.write(struct.pack("<Q", size))
        fh.seek(end)
    else:
        blob = run.to_bytes(codec)
        size = len(blob)
        fh.write(struct.pack("<Q", size))
        fh.write(blob)
    return size


class ChunkedRunWriter:
    """Append-only on-disk run of globally-sorted record blocks.

    The consumer-side spill format (MergeManager mem->disk merge target,
    reference MergeManager.java:387 InMemoryMerger writing an IFile): a
    sequence of length-prefixed single-partition Run blobs, each internally
    sorted and globally ordered across blocks, so a reader can stream the
    run block-at-a-time with bounded memory.
    """

    def __init__(self, path: str, codec: Optional[str] = None,
                 block_records: int = 65536):
        self.path = path
        self.codec = codec
        self.block_records = block_records
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._fh = open(path + ".tmp", "wb")
        self.blocks = 0
        self.records = 0
        self.bytes_written = 0

    def append(self, batch: KVBatch) -> None:
        """Append a sorted batch, splitting into bounded blocks."""
        for s in range(0, batch.num_records, self.block_records):
            piece = batch.slice_rows(s, min(s + self.block_records,
                                            batch.num_records))
            size = _write_block(self._fh, piece, self.codec)
            self.blocks += 1
            self.records += piece.num_records
            self.bytes_written += size + 8

    def close(self) -> str:
        self._fh.close()
        os.replace(self.path + ".tmp", self.path)
        return self.path


def iter_chunked_run(path: str):
    """Stream the sorted blocks of a ChunkedRunWriter file (bounded memory:
    one block resident at a time)."""
    with open(path, "rb") as fh:
        while True:
            raw = fh.read(8)
            if len(raw) < 8:
                return
            (n,) = struct.unpack("<Q", raw)
            with tracing.span("spill.read", cat="spill", bytes=n):
                batch = Run.from_bytes(fh.read(n), where=path).batch
            yield batch


PR_MAGIC = b"TZPRUN1\n"
PR_FOOTER_MAGIC = b"TZPRIDX1"


class PartitionedRunWriter:
    """On-disk partition-indexed run: the spill-scale twin of `Run`.

    The true IFile + TezSpillRecord analog for data that must not live in
    RAM (reference: IFile.java:67 written per spill by PipelinedSorter.java:559,
    indexed by TezSpillRecord.java): a sequence of length-prefixed sorted
    single-partition Run blobs appended PARTITION-MAJOR (partition ids must
    be non-decreasing, matching a partition-sorted producer run), followed by
    a footer index of per-partition byte ranges / row counts / KV byte sizes.
    Each partition is therefore one contiguous byte range of whole blocks —
    a fetch can slice it without touching other partitions, and a merge can
    stream it block-at-a-time with bounded memory.
    """

    def __init__(self, path: str, num_partitions: int,
                 codec: Optional[str] = None, block_records: int = 65536):
        self.path = path
        self.num_partitions = num_partitions
        self.codec = codec
        self.block_records = block_records
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._fh = open(path + ".tmp", "wb")
        self._fh.write(PR_MAGIC)
        self._pos = len(PR_MAGIC)
        self._byte_off = np.full(num_partitions + 1, -1, dtype=np.int64)
        self._byte_off[0] = self._pos
        self._rows = np.zeros(num_partitions, dtype=np.int64)
        self._kv_bytes = np.zeros(num_partitions, dtype=np.int64)
        self._cur = 0
        self.bytes_written = 0

    def _advance_to(self, partition: int) -> None:
        if partition < self._cur:
            raise ValueError(
                f"partition-major order violated: {partition} after "
                f"{self._cur}")
        while self._cur < partition:
            self._cur += 1
            self._byte_off[self._cur] = self._pos

    def append(self, batch: KVBatch, partition: int) -> None:
        """Append a sorted batch belonging to `partition`, splitting into
        bounded blocks."""
        self._advance_to(partition)
        for s in range(0, batch.num_records, self.block_records):
            piece = batch.slice_rows(
                s, min(s + self.block_records, batch.num_records))
            size = _write_block(self._fh, piece, self.codec)
            self._pos += 8 + size
            self.bytes_written += 8 + size
        self._rows[partition] += batch.num_records
        self._kv_bytes[partition] += int(
            batch.key_offsets[-1] + batch.val_offsets[-1])

    def append_run(self, run: "Run") -> None:
        """Append a whole partition-sorted run (span-spill path)."""
        for p in range(run.num_partitions):
            if run.partition_row_count(p):
                self.append(run.partition(p), p)

    def abort(self) -> None:
        """Failure cleanup: close the handle and remove the temp file."""
        try:
            self._fh.close()
        except OSError:
            pass
        try:
            os.remove(self.path + ".tmp")
        except OSError:
            pass

    def close(self) -> str:
        if self.num_partitions > 0:
            self._advance_to(self.num_partitions - 1)
        self._byte_off[self.num_partitions] = self._pos
        footer = io.BytesIO()
        footer.write(struct.pack("<I", self.num_partitions))
        footer.write(self._byte_off.tobytes())
        footer.write(self._rows.tobytes())
        footer.write(self._kv_bytes.tobytes())
        payload = footer.getvalue()
        self._fh.write(payload)
        self._fh.write(struct.pack("<IQ", zlib.crc32(payload), len(payload)))
        self._fh.write(PR_FOOTER_MAGIC)
        self._fh.close()
        os.replace(self.path + ".tmp", self.path)
        return self.path


class FileRun:
    """Run-shaped view over a PartitionedRunWriter file.

    Satisfies the shuffle-service contract (`num_partitions`, `partition()`,
    `partition_nbytes()`, `partition_row_count()`, `empty_partition_flags()`,
    `nbytes`) while the record data stays on disk; `partition()` materializes
    one partition (bounded by that partition's size), and
    `iter_partition_blocks()` streams it block-at-a-time for merges."""

    def __init__(self, path: str):
        self.path = path
        with open(path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            end = fh.tell()
            fh.seek(end - len(PR_FOOTER_MAGIC) - 12)
            crc, size = struct.unpack("<IQ", fh.read(12))
            if fh.read(len(PR_FOOTER_MAGIC)) != PR_FOOTER_MAGIC:
                raise IOError(f"bad partitioned-run footer in {path}")
            fh.seek(end - len(PR_FOOTER_MAGIC) - 12 - size)
            payload = fh.read(size)
            if zlib.crc32(payload) != crc:
                raise IOError(f"partitioned-run index checksum in {path}")
            (p,) = struct.unpack_from("<I", payload)
            off = 4
            self.num_partitions = p
            self._byte_off = np.frombuffer(payload, np.int64, p + 1, off)
            off += (p + 1) * 8
            self._rows = np.frombuffer(payload, np.int64, p, off)
            off += p * 8
            self._kv_bytes = np.frombuffer(payload, np.int64, p, off)

    @property
    def nbytes(self) -> int:
        return int(self._kv_bytes.sum())

    def partition_row_count(self, p: int) -> int:
        return int(self._rows[p])

    def partition_nbytes(self, p: int) -> int:
        return int(self._kv_bytes[p])

    def empty_partition_flags(self) -> List[bool]:
        return [int(r) == 0 for r in self._rows]

    def iter_partition_blocks(self, p: int) -> Iterator[KVBatch]:
        """Stream partition p's sorted blocks (bounded memory)."""
        lo, hi = int(self._byte_off[p]), int(self._byte_off[p + 1])
        if lo >= hi:
            return
        faults.fire("spill.read", detail=self.path)
        with open(self.path, "rb") as fh:
            fh.seek(lo)
            pos = lo
            while pos < hi:
                (n,) = struct.unpack("<Q", fh.read(8))
                # one span a block read back for a merge; never across the
                # yield, where the consumer's time would be billed to it
                with tracing.span("spill.read", cat="spill", bytes=n):
                    blob = faults.corrupt_bytes(
                        "spill.read", self.path, fh.read(n),
                        lo=RUN_HEADER_NBYTES)
                    batch = Run.from_bytes(blob, where=self.path).batch
                yield batch
                pos += 8 + n

    def partition(self, p: int) -> KVBatch:
        blocks = list(self.iter_partition_blocks(p))
        if not blocks:
            return KVBatch.empty()
        return blocks[0] if len(blocks) == 1 else KVBatch.concat(blocks)

    def to_run(self) -> Run:
        """Materialize fully (compat shim for small data / legacy callers)."""
        parts = [self.partition(p) for p in range(self.num_partitions)]
        row_index = np.zeros(self.num_partitions + 1, dtype=np.int64)
        np.cumsum(self._rows, out=row_index[1:])
        return Run(KVBatch.concat(parts) if parts else KVBatch.empty(),
                   row_index)

    def delete(self) -> None:
        try:
            os.remove(self.path)
        except OSError:
            pass


def save_run_partitioned(run: Run, path: str, codec: Optional[str] = None,
                        block_records: int = 65536) -> str:
    """Write a partition-sorted in-RAM Run as a partition-indexed file."""
    from tez_tpu.common import metrics
    faults.fire("spill.write", detail=path)
    with metrics.timer("spill.write"):
        w = PartitionedRunWriter(path, run.num_partitions, codec=codec,
                                 block_records=block_records)
        w.append_run(run)
        return w.close()
