"""Normalized fixed-width key encoding for device sort.

The TPU sorter needs static shapes (SURVEY.md §7 "Variable-length KV on
TPU"): variable-length keys are carried as (bytes, offsets) pairs and, for
sorting, normalized into a fixed number of big-endian uint32 lanes so that
lane-lexicographic order == raw-byte lexicographic order (the reference's
raw-comparator semantics, ExternalSorter/IFile byte ordering).

Keys longer than the configured width sort by their prefix; equal-prefix
groups are then ordered by a host tie-break pass (sorter.py) so the final
order is exact for any key length.
"""
from __future__ import annotations

import numpy as np


def pad_to_matrix(key_bytes: np.ndarray, offsets: np.ndarray,
                  width: int) -> tuple[np.ndarray, np.ndarray]:
    """Ragged bytes -> (padded uint8[N, width], lengths int32[N]).

    Vectorized gather; pad value 0 sorts below every real byte, matching
    shorter-key-first byte order ("a" < "ab")."""
    n = len(offsets) - 1
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int64)
    mat = np.zeros((n, width), dtype=np.uint8)
    if n == 0 or key_bytes.size == 0:
        # no rows, or every key empty — nothing to gather
        return mat, lengths.astype(np.int32)
    step = int(lengths[0])
    if 0 < step <= width and \
            int(offsets[-1]) - int(offsets[0]) == step * n and \
            (lengths == step).all():
        # uniform fixed-width fast path: a reshape replaces the (n, width)
        # fancy gather — fixed-length keys are the common data-plane case
        # and the gather dominates host encode time at span scale
        fixed = key_bytes[int(offsets[0]):int(offsets[-1])].reshape(n, step)
        if step == width:
            mat = np.ascontiguousarray(fixed)
        else:
            mat[:, :step] = fixed
        return mat, lengths.astype(np.int32)
    take = np.minimum(lengths, width)
    # index matrix: offsets[i] + j  (clamped), masked by j < take[i]
    j = np.arange(width)[None, :]
    idx = offsets[:-1, None] + j
    valid = j < take[:, None]
    idx = np.where(valid, idx, 0)
    vals = key_bytes[idx]
    mat = np.where(valid, vals, 0).astype(np.uint8)
    return mat, lengths.astype(np.int32)


def matrix_to_lanes(mat: np.ndarray) -> np.ndarray:
    """uint8[N, W] -> big-endian uint32[N, W/4] lanes; W padded to mult of 4.

    Lexicographic comparison of lanes == lexicographic comparison of bytes.
    """
    n, w = mat.shape
    pad = (-w) % 4
    if pad:
        mat = np.pad(mat, ((0, 0), (0, pad)))
        w += pad
    if mat.flags.c_contiguous:
        # reinterpret rows as big-endian u32 and convert to native in one
        # pass — same packing as the shift/or chain below without the 4x
        # widening intermediate
        return mat.view(">u4").astype(np.uint32)
    lanes = mat.reshape(n, w // 4, 4).astype(np.uint32)
    return (lanes[..., 0] << 24) | (lanes[..., 1] << 16) | \
        (lanes[..., 2] << 8) | lanes[..., 3]


def encode_keys(key_bytes: np.ndarray, offsets: np.ndarray,
                width: int) -> tuple[np.ndarray, np.ndarray]:
    """Ragged keys -> (uint32 lanes [N, ceil(width/4)], lengths[N]).

    Keys of one length take pad_to_matrix's reshape; a large span of keys of
    several lengths takes the native pass, since the numpy gather builds an
    (N, width) index matrix (3 s against 0.03 s at 900,000 keys of 17-23
    bytes)."""
    from tez_tpu.ops.native import MIN_NATIVE_BYTES, encode_key_lanes_native
    n = len(offsets) - 1
    if n and key_bytes.nbytes >= MIN_NATIVE_BYTES and \
            int(offsets[-1]) - int(offsets[0]) != n * int(
                offsets[1] - offsets[0]):
        return encode_key_lanes_native(key_bytes, offsets, width)
    mat, lengths = pad_to_matrix(key_bytes, offsets, width)
    return matrix_to_lanes(mat), lengths


def range_partitions(key_bytes: np.ndarray, offsets: np.ndarray,
                     splits: "list[bytes]") -> np.ndarray:
    """Partition id of every ragged key against sorted split keys: the
    number of splits <= the key in raw-byte order (a key equal to split i
    goes to partition i + 1).  The host twin of the device range kernel
    (ops/device.py _range_partitions): the same (lanes..., length) compare,
    one vectorized pass a split, so O(rows x splits) -- the host engine's
    and the failover's path, not the span sort's."""
    n = len(offsets) - 1
    parts = np.zeros(n, dtype=np.int32)
    if n == 0 or not splits:
        return parts
    klens = offsets[1:] - offsets[:-1]
    width = max(int(klens.max(initial=1)), max(len(s) for s in splits), 1)
    lanes, lengths = encode_keys(key_bytes, offsets, width)
    split_lanes, split_lengths = encode_split_keys(splits, lanes.shape[1] * 4)
    for srow, slen in zip(split_lanes, split_lengths):
        ge = lengths >= slen
        for i in range(lanes.shape[1] - 1, -1, -1):
            col = lanes[:, i]
            ge = (col > srow[i]) | ((col == srow[i]) & ge)
        parts += ge
    return parts


def encode_split_keys(splits: "list[bytes]", width: int
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Split keys -> (uint32 lanes [S, width/4], lengths int32[S]); every
    split has to fit `width` bytes (the caller sizes it)."""
    offsets = np.zeros(len(splits) + 1, dtype=np.int64)
    np.cumsum([len(s) for s in splits], out=offsets[1:])
    data = np.frombuffer(b"".join(splits), dtype=np.uint8)
    return encode_keys(data, offsets, width)


def encode_keys_device(key_bytes: np.ndarray, offsets: np.ndarray,
                       width: int):
    """Device-resident ragged->lanes encode: upload the RAW ragged bytes +
    offsets and run the padded gather + big-endian lane packing as one XLA
    program on the chip (gather is hardware-optimized there; a hand-rolled
    per-row DMA kernel would be strictly worse).  Returns device arrays
    (lanes u32[N, ceil(width/4)], lengths i32[N]).

    This is the device twin of encode_keys — the answer to SURVEY.md §7's
    "variable-length KV on TPU" risk: ragged keys cross the PCIe/ICI
    boundary raw, and every derived fixed-width view lives in HBM.
    """
    import jax.numpy as jnp

    n = len(offsets) - 1
    if n == 0 or key_bytes.size == 0:
        return (jnp.zeros((n, max(1, (width + 3) // 4)), dtype=jnp.uint32),
                jnp.zeros((n,), dtype=jnp.int32))
    return _encode_keys_jit(jnp.asarray(key_bytes),
                            jnp.asarray(offsets.astype(np.int32)), width)


def _encode_keys_jit(key_bytes, offsets, width: int):
    import functools

    import jax

    from tez_tpu.ops import compile_cache  # noqa: F401 — places the cache

    @functools.partial(jax.jit, static_argnames=("width",))
    def go(data, offs, width: int):
        import jax.numpy as jnp
        starts = offs[:-1]
        lengths = (offs[1:] - starts).astype(jnp.int32)
        w4 = width + ((-width) % 4)
        j = jnp.arange(w4, dtype=jnp.int32)[None, :]
        idx = jnp.clip(starts[:, None] + j, 0, data.shape[0] - 1)
        # mask at WIDTH (not the lane-rounded w4): bytes past the configured
        # width must zero-pad exactly like host pad_to_matrix
        valid = j < jnp.minimum(lengths, width)[:, None]
        mat = jnp.where(valid, jnp.take(data, idx), 0).astype(jnp.uint32)
        m = mat.reshape(mat.shape[0], w4 // 4, 4)
        lanes = (m[..., 0] << 24) | (m[..., 1] << 16) | \
            (m[..., 2] << 8) | m[..., 3]
        return lanes, lengths

    return go(key_bytes, offsets, width)


def lanes_to_matrix(lanes: np.ndarray) -> np.ndarray:
    """Inverse of matrix_to_lanes: big-endian uint32[N, L] -> uint8[N, L*4]."""
    n, num_lanes = lanes.shape
    mat = np.zeros((n, num_lanes * 4), dtype=np.uint8)
    for i in range(4):
        mat[:, i::4] = ((lanes >> (24 - 8 * i)) & 0xFF).astype(np.uint8)
    return mat
