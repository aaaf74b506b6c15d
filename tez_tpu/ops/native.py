"""ctypes bindings for the native host ops (tez_tpu/native/*.cpp).

`libtezhost.so` is built from the committed sources with `make` on first
use, or loading raises: there is no prebuilt library to fall back on and no
silent switch to a numpy path, because the host half of the main path
(tokenizer, span sort, ragged gather) must not change engine unannounced.
The `.so` is git-ignored; `make` rebuilds it whenever a source is newer.

The native sources ship INSIDE the package (`tez_tpu/native/`) so pip
installs get them; when the install dir is read-only (site-packages), the
build happens in a per-user cache dir instead (`TEZ_TPU_CACHE_DIR` or
`~/.cache/tez_tpu`).
"""
from __future__ import annotations

import ctypes
import logging
import os
import shutil
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from tez_tpu.ops import hostpool

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "native")
_SOURCES = ("ragged.cpp", "spansort.cpp", "shuffle_server.cpp",
            "baseline_proxy.cpp", "Makefile")


def _build_dir() -> str:
    """Where to run make: the package dir when writable, else a user cache
    keyed by version (read-only site-packages installs)."""
    if os.access(_NATIVE_DIR, os.W_OK):
        return _NATIVE_DIR
    from tez_tpu.version import __version__
    cache_root = os.environ.get("TEZ_TPU_CACHE_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "tez_tpu")
    bdir = os.path.join(cache_root, f"native-{__version__}")
    os.makedirs(bdir, exist_ok=True)
    for fname in _SOURCES:
        src = os.path.join(_NATIVE_DIR, fname)
        dst = os.path.join(bdir, fname)
        if os.path.exists(src) and (
                not os.path.exists(dst)
                or os.path.getmtime(dst) < os.path.getmtime(src)):
            # temp + rename: a concurrent builder's `make` must never see
            # a half-copied source (the Makefile already renames the .so)
            tmp = f"{dst}.{os.getpid()}.tmp"
            shutil.copy2(src, tmp)
            os.replace(tmp, dst)
    return bdir

_lib: "ctypes.CDLL | None" = None
_lib_path = ""
_lock = threading.Lock()

#: Below this many bytes the thread spawn outweighs the copy.
MIN_NATIVE_BYTES = 1 << 20

_P, _I32, _I64 = ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64

#: symbol -> (argtypes, restype) of everything Python calls
_SIGNATURES = {
    "gather_ragged_u8": ([_P, _P, _P, _I64, _P, _P, _I32], None),
    "adjacent_equal_u8": ([_P, _P, _P, _I64, _P, _I32], None),
    "tz_wc_create": ([], _P),
    "tz_wc_feed": ([_P, _P, _I64], None),
    "tz_wc_stats": ([_P, _P, _P], None),
    "tz_wc_emit": ([_P, _P, _P, _P], None),
    "tz_wc_destroy": ([_P], None),
    "hash_sum_i64": ([_P, _P, _I64, _P, _P, _P], _I64),
    "tz_split_ws": ([_P, _I64, _P, _P], _I64),
    "tz_fnv32_partition": ([_P, _P, _I64, _I32, _P, _I32], None),
    "tz_group_by_partition": ([_P, _I64, _I32, _P, _P], None),
    "tz_sort_partition_keys": ([_P, _P, _P, _I64, _P, _I32], None),
    "tz_merge_runs": ([_P, _P, _P, _P, _I32, _P, _I32], None),
    "gather_fixed_u8": ([_P, _I64, _P, _I64, _P, _I32], None),
    "tz_span_sort_emit": ([_P, _P, _P, _P, _I64, _I32, _P, _I32,
                           _P, _P, _P, _P, _P, _P, _I32], _I32),
    "tz_merge_emit": ([_I32, _P, _P, _P, _P, _P, _P, _I32,
                       _P, _P, _P, _P, _P, _P, _I32], _I32),
    "pipelined_sorter_proxy": ([_P, _I64, _P, _I64, _I64, _I32, _I32,
                                _P, _P, _P], ctypes.c_double),
    "owc_proxy_v2": ([_P, _I64, _I32, _I32, _I32, _P, _I64, _P],
                     ctypes.c_double),
    "tz_exchange_encode": ([_P, _P, _P, _P, _I64, _I32, _I32, _P, _P, _P,
                            _I32], None),
    "tz_encode_key_lanes": ([_P, _P, _I64, _I32, _I32, _P, _P, _I32], None),
    "tz_exchange_dest_hist": ([_P, _I32, _P, _I32, _I32, _P], None),
    "tz_exchange_place": ([_I32, _P, _P, _P, _P, _P, _P, _P, _I32, _P, _P,
                           _I32, _I64, _I64, _P, _P, _I64, _I32, _I32,
                           _P, _P, _P, _P, _P], None),
    "tz_exchange_decode_sizes": ([_P, _P, _I64, _P, _I64, _I32, _I32, _I32,
                                  _P], None),
    "tz_exchange_decode_rows": ([_P, _P, _P, _I64, _P, _I64, _I32, _I32,
                                 _I32, _P, _P, _P, _P, _P], None),
}


def _load() -> "ctypes.CDLL":
    """Build (make is a no-op when the .so is newer than every source) and
    load the library; a failed build raises with the compiler's output."""
    global _lib, _lib_path
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        bdir = _build_dir()
        try:
            subprocess.run(["make", "-C", bdir, "-s"], check=True,
                           capture_output=True, timeout=300)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"building libtezhost.so in {bdir} failed:\n"
                f"{e.stderr.decode(errors='replace')[-2000:]}") from e
        so_path = os.path.join(bdir, "libtezhost.so")
        lib = ctypes.CDLL(so_path)
        for name, (argtypes, restype) in _SIGNATURES.items():
            fn = getattr(lib, name)
            fn.argtypes = argtypes
            fn.restype = restype
        _lib, _lib_path = lib, so_path
        log.info("native host ops loaded from %s", so_path)
        return lib


def loaded_path() -> str:
    """Path of the library this process loaded (builds it if need be)."""
    _load()
    return _lib_path


def native_available() -> bool:
    """True once the library is built and loaded; a failed build raises."""
    _load()
    return True


def gather_ragged_native(data: np.ndarray, offsets: np.ndarray,
                         perm: np.ndarray
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Multithreaded ragged permute."""
    lib = _load()
    n_out = len(perm)
    lengths = offsets[1:] - offsets[:-1]
    out_offsets = hostpool.empty(n_out + 1, np.int64)
    out_offsets[0] = 0
    np.cumsum(lengths[perm], out=out_offsets[1:])
    out = hostpool.empty(int(out_offsets[-1]))
    data = np.ascontiguousarray(data)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    perm64 = np.ascontiguousarray(perm, dtype=np.int64)
    threads = min(8, os.cpu_count() or 1)
    lib.gather_ragged_u8(
        data.ctypes.data_as(ctypes.c_void_p),
        offsets.ctypes.data_as(ctypes.c_void_p),
        perm64.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n_out),
        out_offsets.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(threads))
    return out, out_offsets


def gather_fixed_native(data: np.ndarray, row_len: int, perm: np.ndarray
                        ) -> np.ndarray:
    """Permute fixed-width rows: out[i] = data[perm[i]*row_len:+row_len].
    Skips the per-row offset lookups of the ragged gather (compile-time
    copy sizes for the common serde widths)."""
    lib = _load()
    n = len(perm)
    out = hostpool.empty(n * row_len)
    data = np.ascontiguousarray(data)
    perm64 = np.ascontiguousarray(perm, dtype=np.int64)
    lib.gather_fixed_u8(
        data.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(row_len),
        perm64.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(n),
        out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(min(8, os.cpu_count() or 1)))
    return out


def span_sort_emit_native(key_bytes: np.ndarray, key_offsets: np.ndarray,
                          val_bytes: np.ndarray, val_offsets: np.ndarray,
                          num_partitions: int,
                          partitions: Optional[np.ndarray],
                          compute_hash: bool
                          ) -> "Optional[tuple]":
    """Fused producer span sort: partition (optionally fnv32 in C) + stable
    (partition, key) sort + direct materialization of the sorted batch.
    Returns (out_kb, out_ko, out_vb, out_vo, row_index), or None when the
    native side rejects the input (non-zero rc)."""
    lib = _load()
    n = len(key_offsets) - 1
    key_bytes = np.ascontiguousarray(key_bytes)
    key_offsets = np.ascontiguousarray(key_offsets, dtype=np.int64)
    val_bytes = np.ascontiguousarray(val_bytes)
    val_offsets = np.ascontiguousarray(val_offsets, dtype=np.int64)
    parts_ptr = None
    if partitions is not None:
        partitions = np.ascontiguousarray(partitions, dtype=np.int32)
        parts_ptr = partitions.ctypes.data_as(ctypes.c_void_p)
    out_kb = np.empty(int(key_offsets[-1]), dtype=np.uint8)
    out_ko = np.empty(n + 1, dtype=np.int64)
    out_vb = np.empty(int(val_offsets[-1]), dtype=np.uint8)
    out_vo = np.empty(n + 1, dtype=np.int64)
    part_counts = np.empty(num_partitions, dtype=np.int64)
    rc = lib.tz_span_sort_emit(
        key_bytes.ctypes.data_as(ctypes.c_void_p),
        key_offsets.ctypes.data_as(ctypes.c_void_p),
        val_bytes.ctypes.data_as(ctypes.c_void_p),
        val_offsets.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n), ctypes.c_int32(num_partitions), parts_ptr,
        ctypes.c_int32(1 if compute_hash else 0),
        out_kb.ctypes.data_as(ctypes.c_void_p),
        out_ko.ctypes.data_as(ctypes.c_void_p),
        out_vb.ctypes.data_as(ctypes.c_void_p),
        out_vo.ctypes.data_as(ctypes.c_void_p),
        None,   # out_parts: derivable from row_index, nobody consumes it
        part_counts.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(min(8, os.cpu_count() or 1)))
    if rc != 0:
        return None
    row_index = np.zeros(num_partitions + 1, dtype=np.int64)
    np.cumsum(part_counts, out=row_index[1:])
    return out_kb, out_ko, out_vb, out_vo, row_index


def merge_emit_native(runs: "list", num_partitions: int
                      ) -> "Optional[tuple]":
    """Fused k-run merge: group-scan each (partition, key)-sorted run,
    k-way merge group heads, emit contiguous segment copies (no concat, no
    row gather).  `runs` is a list of (key_bytes, key_offsets, val_bytes,
    val_offsets, row_index) tuples.  Returns (out_kb, out_ko, out_vb,
    out_vo, row_index), or None when the native side rejects the input
    (non-zero rc)."""
    lib = _load()
    k = len(runs)
    holders = []   # keep contiguous arrays alive across the call
    kb_ptrs = (ctypes.c_void_p * k)()
    ko_ptrs = (ctypes.c_void_p * k)()
    vb_ptrs = (ctypes.c_void_p * k)()
    vo_ptrs = (ctypes.c_void_p * k)()
    ri_ptrs = (ctypes.c_void_p * k)()
    nrows = np.empty(k, dtype=np.int64)
    total_rows = total_kb = total_vb = 0
    for i, (kb, ko, vb, vo, ri) in enumerate(runs):
        kb = np.ascontiguousarray(kb)
        ko = np.ascontiguousarray(ko, dtype=np.int64)
        vb = np.ascontiguousarray(vb)
        vo = np.ascontiguousarray(vo, dtype=np.int64)
        ri = np.ascontiguousarray(ri, dtype=np.int64)
        holders.extend((kb, ko, vb, vo, ri))
        kb_ptrs[i] = kb.ctypes.data
        ko_ptrs[i] = ko.ctypes.data
        vb_ptrs[i] = vb.ctypes.data
        vo_ptrs[i] = vo.ctypes.data
        ri_ptrs[i] = ri.ctypes.data
        n = len(ko) - 1
        nrows[i] = n
        total_rows += n
        total_kb += int(ko[-1])
        total_vb += int(vo[-1])
    out_kb = np.empty(total_kb, dtype=np.uint8)
    out_ko = np.empty(total_rows + 1, dtype=np.int64)
    out_vb = np.empty(total_vb, dtype=np.uint8)
    out_vo = np.empty(total_rows + 1, dtype=np.int64)
    part_counts = np.empty(num_partitions, dtype=np.int64)
    rc = lib.tz_merge_emit(
        ctypes.c_int32(k), kb_ptrs, ko_ptrs, vb_ptrs, vo_ptrs,
        nrows.ctypes.data_as(ctypes.c_void_p), ri_ptrs,
        ctypes.c_int32(num_partitions),
        out_kb.ctypes.data_as(ctypes.c_void_p),
        out_ko.ctypes.data_as(ctypes.c_void_p),
        out_vb.ctypes.data_as(ctypes.c_void_p),
        out_vo.ctypes.data_as(ctypes.c_void_p),
        None,   # out_parts: derivable from row_index, nobody consumes it
        part_counts.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(min(8, os.cpu_count() or 1)))
    del holders
    if rc != 0:
        return None
    row_index = np.zeros(num_partitions + 1, dtype=np.int64)
    np.cumsum(part_counts, out=row_index[1:])
    return out_kb, out_ko, out_vb, out_vo, row_index


class WordCountAggregator:
    """Fused tokenize + hash-count over byte chunks (native).

    Each `feed()` must be whitespace-complete (line-aligned chunks from the
    text reader), so tokens never span feed boundaries.
    """

    def __init__(self, lib: "ctypes.CDLL"):
        self._lib = lib
        self._h = lib.tz_wc_create()

    @staticmethod
    def create() -> "WordCountAggregator":
        return WordCountAggregator(_load())

    def feed(self, chunk: bytes) -> None:
        self._lib.tz_wc_feed(self._h, chunk, len(chunk))

    def emit(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """-> (key_bytes, key_offsets, counts) in first-occurrence order."""
        n_unique = ctypes.c_int64()
        total = ctypes.c_int64()
        self._lib.tz_wc_stats(self._h, ctypes.byref(n_unique),
                              ctypes.byref(total))
        n, tot = n_unique.value, total.value
        key_bytes = np.empty(tot, dtype=np.uint8)
        key_offsets = np.empty(n + 1, dtype=np.int64)
        counts = np.empty(n, dtype=np.int64)
        if n:
            self._lib.tz_wc_emit(
                self._h, key_bytes.ctypes.data_as(ctypes.c_void_p),
                key_offsets.ctypes.data_as(ctypes.c_void_p),
                counts.ctypes.data_as(ctypes.c_void_p))
        else:
            key_offsets[0] = 0
        return key_bytes, key_offsets, counts

    def close(self) -> None:
        if self._h:
            self._lib.tz_wc_destroy(self._h)
            self._h = None

    def __del__(self):  # noqa: D105 — belt-and-braces native cleanup
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def hash_sum_native(key_bytes: np.ndarray, key_offsets: np.ndarray,
                    values: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Sum int64 `values` of equal keys (first-occurrence order): returns
    (first_idx, sums)."""
    lib = _load()
    n = len(values)
    key_bytes = np.ascontiguousarray(key_bytes)
    key_offsets = np.ascontiguousarray(key_offsets, dtype=np.int64)
    values = np.ascontiguousarray(values, dtype=np.int64)
    first_idx = np.empty(n, dtype=np.int64)
    sums = np.empty(n, dtype=np.int64)
    n_unique = lib.hash_sum_i64(
        key_bytes.ctypes.data_as(ctypes.c_void_p),
        key_offsets.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n),
        values.ctypes.data_as(ctypes.c_void_p),
        first_idx.ctypes.data_as(ctypes.c_void_p),
        sums.ctypes.data_as(ctypes.c_void_p))
    return first_idx[:n_unique].copy(), sums[:n_unique].copy()


def pipelined_sorter_proxy(keys: np.ndarray, vals: np.ndarray,
                           num_producers: int, num_partitions: int
                           ) -> "Tuple[float, np.ndarray, np.ndarray, np.ndarray]":
    """Run the PipelinedSorter/TezMerger-semantics C++ baseline proxy
    (native/baseline_proxy.cpp; see BASELINE.md) over fixed-width records.

    keys: (n, key_len) u8; vals: (n, val_len) u8.  Returns (wall_seconds,
    merged_keys, merged_vals, per_partition_counts)."""
    lib = _load()
    n, key_len = keys.shape
    val_len = vals.shape[1] if vals.size else 0
    keys = np.ascontiguousarray(keys)
    vals = np.ascontiguousarray(vals)
    out_keys = np.empty_like(keys)
    out_vals = np.empty_like(vals)
    counts = np.zeros(num_partitions, dtype=np.int64)
    secs = lib.pipelined_sorter_proxy(
        keys.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(key_len),
        vals.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(val_len),
        ctypes.c_int64(n), ctypes.c_int32(num_producers),
        ctypes.c_int32(num_partitions),
        out_keys.ctypes.data_as(ctypes.c_void_p),
        out_vals.ctypes.data_as(ctypes.c_void_p),
        counts.ctypes.data_as(ctypes.c_void_p))
    return float(secs), out_keys, out_vals, counts


def split_ws_native(chunk: bytes) -> Tuple[np.ndarray, np.ndarray]:
    """One-pass whitespace split of a text chunk into compacted ragged
    (word_bytes, word_offsets)."""
    lib = _load()
    n = len(chunk)
    out_bytes = np.empty(n, dtype=np.uint8)
    out_offsets = np.empty((n + 1) // 2 + 2, dtype=np.int64)
    words = lib.tz_split_ws(chunk, ctypes.c_int64(n),
                            out_bytes.ctypes.data_as(ctypes.c_void_p),
                            out_offsets.ctypes.data_as(ctypes.c_void_p))
    offsets = out_offsets[:words + 1].copy()
    return out_bytes[:int(offsets[-1])].copy(), offsets


def fnv32_partition_native(key_bytes: np.ndarray, key_offsets: np.ndarray,
                           num_partitions: int) -> np.ndarray:
    """Threaded 32-bit FNV-1a hash partition over full ragged keys
    (byte-identical to the device kernel and numpy host partitioner)."""
    lib = _load()
    n = len(key_offsets) - 1
    key_bytes = np.ascontiguousarray(key_bytes)
    key_offsets = np.ascontiguousarray(key_offsets, dtype=np.int64)
    parts = np.empty(n, dtype=np.int32)
    lib.tz_fnv32_partition(
        key_bytes.ctypes.data_as(ctypes.c_void_p),
        key_offsets.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n), ctypes.c_int32(num_partitions),
        parts.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(min(8, os.cpu_count() or 1)))
    return parts


def group_by_partition_native(parts: np.ndarray, num_partitions: int
                              ) -> Tuple[np.ndarray, np.ndarray]:
    """Stable grouping of rows by partition (int32 ids below
    `num_partitions`, as fnv32_partition_native gives them): one counting
    pass.  Returns (perm int64[n]: partition 0's rows in arrival order,
    then partition 1's, ...; row_index int64[P + 1])."""
    lib = _load()
    parts = np.ascontiguousarray(parts, dtype=np.int32)
    if int(parts.max(initial=0)) >= num_partitions or \
            int(parts.min(initial=0)) < 0:
        raise ValueError(f"a partition outside the {num_partitions} there "
                         f"are")
    perm = hostpool.empty(len(parts), np.int64)
    row_index = np.empty(num_partitions + 1, dtype=np.int64)
    lib.tz_group_by_partition(_ptr(parts), len(parts), int(num_partitions),
                              _ptr(perm), _ptr(row_index))
    return perm, row_index


def sort_partition_keys_native(key_bytes: np.ndarray,
                               key_offsets: np.ndarray,
                               partitions: Optional[np.ndarray]
                               ) -> np.ndarray:
    """Stable sort permutation by (partition, full key bytes) — parallel
    native merge sort over row indices, GIL released for the whole call."""
    lib = _load()
    n = len(key_offsets) - 1
    key_bytes = np.ascontiguousarray(key_bytes)
    key_offsets = np.ascontiguousarray(key_offsets, dtype=np.int64)
    parts_ptr = None
    if partitions is not None:
        partitions = np.ascontiguousarray(partitions, dtype=np.int32)
        parts_ptr = partitions.ctypes.data_as(ctypes.c_void_p)
    perm = np.empty(n, dtype=np.int64)
    lib.tz_sort_partition_keys(
        key_bytes.ctypes.data_as(ctypes.c_void_p),
        key_offsets.ctypes.data_as(ctypes.c_void_p),
        parts_ptr, ctypes.c_int64(n),
        perm.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(min(8, os.cpu_count() or 1)))
    return perm


def owc_proxy(text: bytes, num_producers: int, num_partitions: int,
              combine: bool = True) -> "Tuple[float, bytes]":
    """Run the full-OrderedWordCount reference-semantics C++ proxy
    (native/baseline_proxy.cpp) over a text corpus: tokenize -> span sort
    (+ combiner when `combine`) -> per-partition heap merge + sum ->
    count-keyed second sort -> merged output lines.  combine=False ships
    every (word, 1) record raw — the spill-bench shape.  Returns
    (wall_seconds, output_bytes)."""
    lib = _load()
    n = len(text)
    # output = unique words + "\t<count>\n" tails: usually far below the
    # input, but a mostly-distinct-short-word corpus can exceed it — grow
    # and retry on the (safe) overflow signal
    cap = max(1 << 20, n + (n >> 2))
    for _attempt in range(3):
        out = ctypes.create_string_buffer(cap)
        out_len = ctypes.c_int64()
        secs = lib.owc_proxy_v2(text, ctypes.c_int64(n),
                             ctypes.c_int32(num_producers),
                             ctypes.c_int32(num_partitions),
                             ctypes.c_int32(1 if combine else 0),
                             out, ctypes.c_int64(cap),
                             ctypes.byref(out_len))
        if secs >= 0:
            return float(secs), out.raw[:out_len.value]
        cap *= 4
    raise RuntimeError("owc_proxy output buffer overflow")


def merge_runs_native(key_bytes: np.ndarray, key_offsets: np.ndarray,
                      partitions: Optional[np.ndarray],
                      run_bounds: np.ndarray) -> np.ndarray:
    """Stable merge permutation over the concatenation of k
    (partition, key)-sorted runs — a ladder of in-place merges instead of
    a full re-sort (GIL released)."""
    lib = _load()
    key_bytes = np.ascontiguousarray(key_bytes)
    key_offsets = np.ascontiguousarray(key_offsets, dtype=np.int64)
    parts_ptr = None
    if partitions is not None:
        partitions = np.ascontiguousarray(partitions, dtype=np.int32)
        parts_ptr = partitions.ctypes.data_as(ctypes.c_void_p)
    run_bounds = np.ascontiguousarray(run_bounds, dtype=np.int64)
    n = int(run_bounds[-1])
    perm = np.empty(n, dtype=np.int64)
    lib.tz_merge_runs(
        key_bytes.ctypes.data_as(ctypes.c_void_p),
        key_offsets.ctypes.data_as(ctypes.c_void_p),
        parts_ptr,
        run_bounds.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(len(run_bounds) - 1),
        perm.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(min(8, os.cpu_count() or 1)))
    return perm


def owc_proxy_counts(corpus_path: str, num_producers: int,
                     num_partitions: int, combine: bool = True
                     ) -> "Tuple[float, dict]":
    """Baseline harness of tools/spill_bench.py: run the
    reference-semantics proxy over a corpus FILE and parse its output
    lines into {word(str): count}.  Parse errors (corrupt proxy output)
    raise."""
    with open(corpus_path, "rb") as fh:
        text = fh.read()
    secs, out_bytes = owc_proxy(text, num_producers, num_partitions,
                                combine=combine)
    counts: dict = {}
    for line in out_bytes.decode().splitlines():
        w, cnt = line.rsplit("\t", 1)
        counts[w] = counts.get(w, 0) + int(cnt)
    return secs, counts


def adjacent_equal_native(data: np.ndarray, offsets: np.ndarray,
                          cand: np.ndarray) -> np.ndarray:
    """Threaded per-pair memcmp for adjacent-row equality."""
    lib = _load()
    data = np.ascontiguousarray(data)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    cand64 = np.ascontiguousarray(cand, dtype=np.int64)
    out = np.empty(len(cand64), dtype=np.uint8)
    lib.adjacent_equal_u8(
        data.ctypes.data_as(ctypes.c_void_p),
        offsets.ctypes.data_as(ctypes.c_void_p),
        cand64.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(len(cand64)),
        out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(min(8, os.cpu_count() or 1)))
    return out.astype(bool)


# ---------------------------------------------------------------------------
# Mesh exchange row passes (parallel/coordinator.py; native/ragged.cpp says
# what each walks).  Every call releases the GIL for the whole pass.
# ---------------------------------------------------------------------------

def _threads() -> int:
    return min(8, os.cpu_count() or 1)


def _ptr(a: np.ndarray) -> ctypes.c_void_p:
    return a.ctypes.data_as(ctypes.c_void_p)


def _dests(dests: np.ndarray, num_dests: int) -> np.ndarray:
    """Destinations as the contiguous unsigned array the passes index
    their per-destination counters by."""
    if dests.dtype not in (np.uint8, np.uint16, np.uint32):
        raise TypeError(f"dests: expected uint8/16/32, got {dests.dtype}")
    if int(dests.max(initial=0)) >= num_dests:
        raise ValueError(f"a destination beyond the {num_dests} there are")
    return np.ascontiguousarray(dests)


def _rows_u32(a: np.ndarray, what: str) -> np.ndarray:
    """`a` as the C-contiguous uint32 rows the native passes index."""
    if a.dtype != np.uint32:
        raise TypeError(f"{what}: expected uint32, got {a.dtype}")
    return np.ascontiguousarray(a)


def exchange_encode_native(key_bytes: np.ndarray, key_offsets: np.ndarray,
                           val_bytes: np.ndarray, val_offsets: np.ndarray,
                           key_width: int, value_width: int
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A producer's ragged batch as exchange rows: (lanes u32[n, L], klens
    u32[n], vwords u32[n, 1 + VW]) with L / VW the widths in bytes rounded
    up to whole words — what ``keycodec.pad_to_matrix`` +
    ``matrix_to_lanes`` give for the keys, and for the values behind a
    first word that holds the value's length."""
    lib = _load()
    n = len(key_offsets) - 1
    if len(val_offsets) - 1 != n:
        raise ValueError(f"{n} keys but {len(val_offsets) - 1} values")
    num_lanes = (int(key_width) + 3) // 4
    value_words = (int(value_width) + 3) // 4
    key_bytes = np.ascontiguousarray(key_bytes, dtype=np.uint8)
    key_offsets = np.ascontiguousarray(key_offsets, dtype=np.int64)
    val_bytes = np.ascontiguousarray(val_bytes, dtype=np.uint8)
    val_offsets = np.ascontiguousarray(val_offsets, dtype=np.int64)
    if n and (int(key_offsets[-1]) > key_bytes.size or
              int(val_offsets[-1]) > val_bytes.size):
        raise ValueError("offsets run past the bytes")
    lanes = hostpool.empty(n * num_lanes, np.uint32).reshape(n, num_lanes)
    klens = hostpool.empty(n, np.uint32)
    vwords = hostpool.empty(n * (1 + value_words), np.uint32) \
        .reshape(n, 1 + value_words)
    lib.tz_exchange_encode(
        _ptr(key_bytes), _ptr(key_offsets), _ptr(val_bytes),
        _ptr(val_offsets), n, num_lanes, value_words,
        _ptr(lanes), _ptr(klens), _ptr(vwords), _threads())
    return lanes, klens, vwords


def encode_key_lanes_native(key_bytes: np.ndarray, key_offsets: np.ndarray,
                            width: int) -> Tuple[np.ndarray, np.ndarray]:
    """Ragged keys as sort lanes: (lanes u32[n, ceil(width / 4)], lengths
    i32[n]), what ``keycodec.pad_to_matrix`` + ``matrix_to_lanes`` give, in
    one threaded pass with no index matrix."""
    lib = _load()
    n = len(key_offsets) - 1
    num_lanes = (int(width) + 3) // 4
    key_bytes = np.ascontiguousarray(key_bytes, dtype=np.uint8)
    key_offsets = np.ascontiguousarray(key_offsets, dtype=np.int64)
    if n and int(key_offsets[-1]) > key_bytes.size:
        raise ValueError("offsets run past the bytes")
    lanes = hostpool.empty(n * num_lanes, np.uint32).reshape(n, num_lanes)
    lengths = hostpool.empty(n, np.int32)
    lib.tz_encode_key_lanes(_ptr(key_bytes), _ptr(key_offsets), n, int(width),
                            num_lanes, _ptr(lanes), _ptr(lengths), _threads())
    return lanes, lengths


def exchange_dest_hist_native(dests: np.ndarray, bounds: np.ndarray,
                              num_dests: int) -> np.ndarray:
    """int64[len(bounds) - 1, num_dests]: the rows of each destination in
    each chunk ``dests[bounds[t]:bounds[t + 1]]`` (unsigned, 1/2/4 bytes,
    every value below ``num_dests``)."""
    lib = _load()
    dests = _dests(dests, num_dests)
    bounds = np.ascontiguousarray(bounds, dtype=np.int64)
    if bounds[0] < 0 or bounds[-1] > dests.size or \
            (np.diff(bounds) < 0).any():
        raise ValueError("chunk bounds outside the destinations")
    hist = np.empty((len(bounds) - 1, num_dests), np.int64)
    lib.tz_exchange_dest_hist(_ptr(dests), dests.itemsize, _ptr(bounds),
                              len(bounds) - 1, num_dests, _ptr(hist))
    return hist


def exchange_place_native(chunks: "list", bounds: np.ndarray,
                          dests: np.ndarray, rank_base: np.ndarray,
                          fill_base: np.ndarray, lo: int, per_round: int,
                          chunk_d: np.ndarray, loads: np.ndarray,
                          rows_per_sender: int, num_lanes: int,
                          value_words: int) -> Tuple[np.ndarray, ...]:
    """One round's device inputs from the producers' spans where they lie
    (``tz_exchange_place``).  ``chunks[t]`` is the (lanes, klens, vwords)
    of rows ``[bounds[t], bounds[t + 1])`` of the edge: slices of one
    producer's span, possibly narrower than ``num_lanes`` /
    ``value_words`` (widened with zero words here).  ``rank_base[t, d]``
    is the rank of the chunk's first row of destination d among all rows of
    d; ``fill_base[t, s]`` the rows earlier chunks put in sender s's block
    this round; ``loads[s]`` the block's rows.  Returns (r_lanes u32[D*N,
    L], r_klens u32[D*N], r_vwords u32[D*N, VW], r_valid bool[D*N], r_dests
    u32[D*N]) in pooled memory, every element written."""
    lib = _load()
    T, D, N = len(chunks), len(loads), int(rows_per_sender)
    dests = _dests(dests, D)
    bounds = np.ascontiguousarray(bounds, dtype=np.int64)
    rank_base = np.ascontiguousarray(rank_base, dtype=np.int64)
    fill_base = np.ascontiguousarray(fill_base, dtype=np.int64)
    chunk_d = np.ascontiguousarray(chunk_d, dtype=np.int64)
    loads = np.ascontiguousarray(loads, dtype=np.int64)
    if bounds.shape != (T + 1,) or rank_base.shape != (T, D) or \
            fill_base.shape != (T, D) or chunk_d.shape != (D,) or \
            int(bounds[-1]) > dests.size or int(loads.max(initial=0)) > N or \
            int(chunk_d.min(initial=1)) < 1:
        raise ValueError("placement plan does not fit its chunks")
    held = []      # the contiguous slices the pointers point into
    ptrs = [(ctypes.c_void_p * T)() for _ in range(3)]
    widths = np.empty((2, T), np.int32)
    for t, (lanes, klens, vwords) in enumerate(chunks):
        rows = int(bounds[t + 1] - bounds[t])
        lanes, klens, vwords = (_rows_u32(lanes, "lanes"),
                                _rows_u32(klens, "klens"),
                                _rows_u32(vwords, "vwords"))
        if lanes.shape[0] != rows or klens.shape != (rows,) or \
                vwords.shape[0] != rows or lanes.shape[1] > num_lanes or \
                vwords.shape[1] > value_words:
            raise ValueError(f"chunk {t} does not match its bounds or widths")
        held.append((lanes, klens, vwords))
        for ptr, a in zip(ptrs, held[-1]):
            ptr[t] = a.ctypes.data
        widths[0, t], widths[1, t] = lanes.shape[1], vwords.shape[1]
    r_lanes = hostpool.empty(D * N * num_lanes, np.uint32) \
        .reshape(D * N, num_lanes)
    r_klens = hostpool.empty(D * N, np.uint32)
    r_vwords = hostpool.empty(D * N * value_words, np.uint32) \
        .reshape(D * N, value_words)
    r_valid = hostpool.empty(D * N, np.bool_)
    r_dests = hostpool.empty(D * N, np.uint32)
    lib.tz_exchange_place(
        T, ptrs[0], ptrs[1], ptrs[2], _ptr(widths[0]), _ptr(widths[1]),
        _ptr(bounds), _ptr(dests), dests.itemsize, _ptr(rank_base),
        _ptr(fill_base), D, int(lo), int(per_round),
        _ptr(chunk_d), _ptr(loads), N, num_lanes, value_words,
        _ptr(r_lanes), _ptr(r_klens), _ptr(r_vwords), _ptr(r_valid),
        _ptr(r_dests))
    del held
    return r_lanes, r_klens, r_vwords, r_valid, r_dests


def exchange_decode_native(lanes: np.ndarray, klens: np.ndarray,
                           vwords: np.ndarray, keep: np.ndarray,
                           value_words: Optional[int] = None
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                      np.ndarray]:
    """The rows of an exchange shard where ``keep`` is set, as ragged
    (key_bytes, key_offsets, val_bytes, val_offsets): lengths to offsets,
    then bytes, in row order.  ``value_words`` (default: all of them) is
    how many of a ``vwords`` row's words after the first are value bytes;
    further columns are skipped."""
    lib = _load()
    lanes = _rows_u32(lanes, "lanes")
    klens = _rows_u32(klens, "klens")
    vwords = _rows_u32(vwords, "vwords")
    keep = np.ascontiguousarray(keep).astype(np.bool_, copy=False)
    n, num_lanes = lanes.shape
    vstride = vwords.shape[1]
    if value_words is None:
        value_words = vstride - 1
    if klens.shape != (n,) or vwords.shape[0] != n or keep.shape != (n,) \
            or not 0 <= value_words < vstride:
        raise ValueError("shard arrays disagree on their rows or widths")
    chunks = _threads()
    sizes = np.empty((chunks, 3), np.int64)
    lib.tz_exchange_decode_sizes(_ptr(klens), _ptr(vwords), vstride,
                                 _ptr(keep), n, num_lanes, value_words,
                                 chunks, _ptr(sizes))
    starts = np.ascontiguousarray(np.cumsum(sizes, axis=0) - sizes)
    rows, key_total, val_total = (int(x) for x in sizes.sum(axis=0))
    key_bytes = hostpool.empty(key_total, np.uint8)
    val_bytes = hostpool.empty(val_total, np.uint8)
    key_offsets = hostpool.empty(rows + 1, np.int64)
    val_offsets = hostpool.empty(rows + 1, np.int64)
    key_offsets[0] = val_offsets[0] = 0
    lib.tz_exchange_decode_rows(
        _ptr(lanes), _ptr(klens), _ptr(vwords), vstride, _ptr(keep), n,
        num_lanes, value_words, chunks, _ptr(starts), _ptr(key_bytes),
        _ptr(key_offsets), _ptr(val_bytes), _ptr(val_offsets))
    return key_bytes, key_offsets, val_bytes, val_offsets
