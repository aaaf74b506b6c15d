"""jit'd device kernels for the data plane: hash-partition, segmented sort.

These are the TPU replacements for the reference's byte-crunching loops
(PipelinedSorter.collect/sort spans, HashPartitioner, TezMerger) —
SURVEY.md §2.5 "TPU-native equivalent" column.  All kernels are shape-
bucketed (power-of-two padding) so XLA compiles a bounded set of programs;
compiled functions are cached per-process (jit cache) and survive across
tasks via runner reuse.

Sorting model: keys are fixed-width uint32 lanes (ops/keycodec); the sort is
a single variadic `lax.sort` over (partition, lane_0..lane_{L-1}, length |
arrival order), every operand a key, whose outputs are the sorted columns
and the record permutation (`_lsd_passes`) — XLA lowers this to its
on-device sort; the merge of k sorted runs is the same sort of the
concatenation (sort networks beat heap-merge on TPU's vector units; the
arrival order as last key preserves within-key run order like the
reference's MergeQueue).  The group fold alone sorts by key without the
arrival order and needs no permutation: its values ride its own sort as a
payload operand (`_group_sum_impl`).
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tez_tpu.common import tracing
from tez_tpu.ops import compile_cache    # importing it places the cache
from tez_tpu.ops import hostpool

FNV_OFFSET = np.uint32(2166136261)
FNV_PRIME = np.uint32(16777619)


@functools.lru_cache(maxsize=1)
def backend_platform() -> str:
    """Platform of the INITIALISED default backend — the one backend query
    every engine decision in the process hangs off.

    Nothing here is caught: a backend that cannot initialise (the chip is
    held by another process, libtpu cannot start) raises out of the caller.
    "cpu" is an answer only when it was asked for — ``JAX_PLATFORMS=cpu``
    or the equivalent ``jax.config`` update, as the tests do.  With the
    variable unset JAX drops to the CPU silently when the accelerator
    plugin fails; that case raises too, because a process that could not
    claim its chip must not sort on the host and report success."""
    platform = jax.default_backend()
    if platform == "cpu" and not compile_cache.cpu_requested():
        raise RuntimeError(
            "JAX initialised the CPU backend without being asked to "
            f"(jax_platforms={jax.config.jax_platforms!r}): no accelerator "
            "could be claimed.  Set JAX_PLATFORMS=cpu to run on the host "
            "on purpose.")
    return platform


def accelerator_present() -> bool:
    """True when the default JAX backend is an accelerator (TPU/GPU).

    The `auto` engine routes through this: device kernels are a *loss* on
    the CPU backend (XLA CPU sort + dispatch overhead vs numpy/native), so
    auto picks the host engine there and the device engine whenever a real
    chip answers.  A backend that fails to initialise raises
    (:func:`backend_platform`) — it is never read as "no accelerator"."""
    return backend_platform() != "cpu"


# ---------------------------------------------------------------------------
# kernels: compiled ahead of launch
# ---------------------------------------------------------------------------
class KernelCompileError(RuntimeError):
    """Tracing, lowering or compiling a kernel failed.  Deterministic by
    construction — the same shapes fail the same way on a healthy chip — so
    the containment plane (ops/async_stage.py) never retries it on the host:
    the attempt fails with the kernel's name in the message."""


_compile_tls = threading.local()


@contextlib.contextmanager
def compile_listener(fn: Callable[[bool], None]) -> Iterator[None]:
    """While active on this thread, ``fn(True)`` / ``fn(False)`` bracket
    every kernel compile the thread performs.  The async pipeline uses it to
    stop its dispatch watchdog for the duration: the deadline bounds a
    LAUNCH, and a cold ladder compiles for ~25 s."""
    prev = getattr(_compile_tls, "fn", None)
    _compile_tls.fn = fn
    try:
        yield
    finally:
        _compile_tls.fn = prev


_launch_tls = threading.local()


@contextlib.contextmanager
def launch_tally() -> Iterator[Dict[str, List[int]]]:
    """While active on this thread, every kernel launch the thread makes
    lands in the yielded dict as ``{Kernel.name: [launches, rows]}``, rows
    being what the program was launched on, sentinels included.  The caller
    that owns the task's counters turns it into DEVICE_MERGE_LAUNCHES and
    DEVICE_MERGE_LAUNCH_ROWS (ops/sorter.py): launches are enqueued on the
    calling thread, so the thread is the task."""
    prev = getattr(_launch_tls, "tally", None)
    tally: Dict[str, List[int]] = {}
    _launch_tls.tally = tally
    try:
        yield tally
    finally:
        _launch_tls.tally = prev


def _first_operand_rows(*args: Any) -> int:
    shape = np.shape(jax.tree.leaves(args)[0])
    return int(shape[0]) if shape else 1


#: (kernel name, signature, compile seconds, sort operations in the lowered
#: module, wall-clock time it finished) of every compile this process did —
#: chip_smoke.py prints it as the cold set-up cost per kernel.  The time it
#: finished stays LAST: benchmarks/run.py counts the window's compiles by it.
COMPILE_LOG: List[Tuple[str, str, float, int, float]] = []


class Kernel:
    """A jitted entry point that is lowered and compiled ONCE per argument
    signature, apart from its launches.

    ``jax.jit`` compiles inside the first call, so a caller cannot tell a
    25 s compile from a hung dispatch, nor a lowering error from a device
    fault.  Here the first call with a new signature compiles under
    :func:`compile_listener` (failures raise :class:`KernelCompileError`)
    and every call then launches the compiled executable, which returns as
    soon as the work is enqueued.  Static arguments are keyword-only."""

    def __init__(self, fn: Callable, name: str,
                 static_argnames: Tuple[str, ...] = (),
                 donate_argnums: Tuple[int, ...] = (),
                 launch_rows: Callable[..., int] = _first_operand_rows
                 ) -> None:
        self.name = name
        #: the traced function's name: the profiler calls the compiled
        #: program ``jit_<program>`` (tools/trace_window_check.py puts the
        #: device time of one beside the launches of the other)
        self.program = fn.__name__
        #: rows one launch works on (padded: sentinels included), from its
        #: operands — the first operand's unless the kernel takes two runs
        self._launch_rows = launch_rows
        self._span_name = "kernel." + name
        self._jit = jax.jit(fn, static_argnames=static_argnames,
                            donate_argnums=donate_argnums)
        self._compiled: Dict[Any, Any] = {}
        self._key_locks: Dict[Any, threading.Lock] = {}
        self._lock = threading.Lock()       # guards _key_locks only

    def __call__(self, *args: Any, **static: Any) -> Any:
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple((np.shape(x), jnp.result_type(x)) for x in leaves),
               tuple(sorted(static.items())))
        exe = self._compiled.get(key)
        if exe is None:
            exe = self._compile(key, args, static)
        tally = getattr(_launch_tls, "tally", None)
        if tally is None and not tracing.armed():
            return exe(*args)
        rows = self._launch_rows(*args)
        if tally is not None:
            entry = tally.setdefault(self.name, [0, 0])
            entry[0] += 1
            entry[1] += rows
        # the launch returns once the work is enqueued: this span is the
        # enqueue, the wait shows where the host reads the result back
        with tracing.span(self._span_name, cat="kernel", rows=rows):
            return exe(*args)

    def cache_size(self) -> int:
        """Compiled signatures held (tests bound recompiles with it)."""
        return len(self._compiled)

    def _compile(self, key: Any, args: tuple, static: Dict[str, Any]) -> Any:
        listener = getattr(_compile_tls, "fn", None)
        if listener is not None:
            listener(True)
        try:
            # tasks that need the SAME signature share one compile; other
            # signatures of this kernel compile alongside it
            with self._lock:
                key_lock = self._key_locks.setdefault(key, threading.Lock())
            with key_lock:
                exe = self._compiled.get(key)
                if exe is None:
                    sig = ",".join("x".join(map(str, s)) or "()"
                                   for s, _ in key[1])
                    t0 = time.perf_counter()
                    try:
                        with tracing.span("kernel.compile", cat="kernel",
                                          kernel=self.name,
                                          signature=sig) as span:
                            lowered = self._jit.lower(*args, **static)
                            # the witness of which sort body was traced
                            sort_ops = lowered.as_text().count(
                                "stablehlo.sort")
                            span.annotate(sort_ops=sort_ops)
                            exe = lowered.compile()
                    except Exception as e:
                        raise KernelCompileError(
                            f"kernel {self.name}[{sig}] failed to compile: "
                            f"{type(e).__name__}: {e}") from e
                    COMPILE_LOG.append((self.name, sig,
                                        time.perf_counter() - t0, sort_ops,
                                        time.time()))
                    self._compiled[key] = exe
            return exe
        finally:
            if listener is not None:
                listener(False)


#: Substrings marking a device failure as an out-of-memory class.  XLA
#: surfaces HBM exhaustion as a RuntimeError/XlaRuntimeError whose message
#: carries the gRPC-style status name, not a dedicated exception type, so
#: classification is message-based; the fault plane's injected
#: `device.dispatch.oom` errors match the same way.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Resource exhausted",
                "out of memory", "OOM", "device.dispatch.oom")


def is_resource_exhausted(exc: BaseException) -> bool:
    """True when a device-attempt failure should take the OOM ladder
    (retry on-device with the span split) rather than plain host failover."""
    if isinstance(exc, MemoryError):
        return True
    msg = str(exc)
    return any(m in msg for m in _OOM_MARKERS)


#: bits of the sort's last key, which the length code and the row number
#: share, and of the group fold's compaction key, which holds a flag, the
#: place and the length code (tests narrow it to reach the branches where
#: they do not fit)
_TAIL_KEY_BITS = 32


def _bucket(n: int, floor: int = 256) -> int:
    """Round up to the shape bucket (power of two) to bound recompiles."""
    b = floor
    while b < n:
        b <<= 1
    return b


# ---------------------------------------------------------------------------
# hash partition
# ---------------------------------------------------------------------------
def _fnv_partition_impl(key_mat: jnp.ndarray, lengths: jnp.ndarray,
                        num_partitions: int) -> jnp.ndarray:
    """FNV-1a over each row's first `lengths[i]` bytes of key_mat[i, :].

    Byte-identical to library.partitioners.HashPartitioner._stable_hash for
    keys that fit the padded width.  key_mat: uint8[N, W]; returns int32[N].
    """
    h = _fnv_rows(key_mat, lengths)
    return (h % jnp.uint32(num_partitions)).astype(jnp.int32)


_fnv_partition = Kernel(_fnv_partition_impl, "fnv_partition",
                        static_argnames=("num_partitions",))


def hash_partition(key_mat: np.ndarray, lengths: np.ndarray,
                   num_partitions: int) -> np.ndarray:
    """Host wrapper with shape bucketing."""
    n = key_mat.shape[0]
    if n == 0:
        return np.zeros(0, dtype=np.int32)
    nb = _bucket(n)
    if nb != n:
        key_mat = np.pad(key_mat, ((0, nb - n), (0, 0)))
        lengths = np.pad(lengths, (0, nb - n))
    out = _fnv_partition(key_mat, lengths, num_partitions=num_partitions)
    return np.asarray(out)[:n]


# ---------------------------------------------------------------------------
# partitioned stable sort
# ---------------------------------------------------------------------------
def _fnv_rows(key_mat: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Traced FNV-1a over each row's first `lengths[i]` bytes — the ONE hash
    body shared by every kernel (host-partitioner parity)."""
    def body(j, h):
        byte = key_mat[:, j].astype(jnp.uint32)
        nh = ((h ^ byte) * FNV_PRIME).astype(jnp.uint32)
        return jnp.where(j < lengths, nh, h)

    h = jnp.full((key_mat.shape[0],), FNV_OFFSET, dtype=jnp.uint32)
    return jax.lax.fori_loop(0, key_mat.shape[1], body, h)


def _hash_to_partitions(key_mat: jnp.ndarray, hash_lengths: jnp.ndarray,
                        num_partitions: int) -> jnp.ndarray:
    """Hash + padding sentinel: rows with hash_lengths < 0 get partition MAX
    so they sort to the tail."""
    h = _fnv_rows(key_mat, hash_lengths)
    return jnp.where(
        hash_lengths < 0, jnp.int32(np.iinfo(np.int32).max),
        (h % jnp.uint32(num_partitions)).astype(jnp.int32))


def _lsd_passes(partitions, lanes: jnp.ndarray, lengths: jnp.ndarray
                ) -> Tuple[jnp.ndarray, ...]:
    """Traced body shared by every sort, merge, match and probe program:
    ONE variadic `lax.sort` by ([partition,] lane_0..lane_{L-1}, length,
    arrival order), every operand a key.  Returns (sorted partitions i32,
    perm, sorted lanes u32[n, L], sorted lengths i32[n]): the sorted
    columns are the sort's own outputs, nothing is gathered by the
    permutation (on a v5e a gather costs 5-9 ns a row, a whole sort
    operand 0.5: PERF.md PR 35).  `partitions` None: by key alone, and None
    comes back in its place.  `lengths`: i32 with -1 on a sentinel row, or
    the same bits as u32 (all ones); -1 comes back on those rows.

    The arrival order is the LAST key and unique, so the order is total: an
    unstable sort is deterministic and equal keys keep arrival order, as
    the stable LSD ladder of L+2 single-key passes this replaced did (the
    name is the ladder's).  Compile time grows with the square of the
    operands (~13 s + 5.6 s a pair of them on the chip), so the length --
    at most 4L+1, clamped at the lane cap, or all ones on a sentinel row --
    and the arrival order share one u32 key wherever the rows leave the
    bits: the length code above, the row number below."""
    n, num_lanes = lanes.shape
    order = jnp.arange(n, dtype=jnp.uint32)
    lengths = lengths.astype(jnp.uint32)
    keys = () if partitions is None else (partitions.astype(jnp.uint32),)
    first = len(keys)                       # where the lanes stand
    keys += tuple(lanes[:, i] for i in range(num_lanes))
    sentinel_code = 4 * num_lanes + 2       # over every length a row has
    shift = _TAIL_KEY_BITS - sentinel_code.bit_length()
    if n <= 1 << shift:
        tail = (jnp.minimum(lengths, sentinel_code) << shift) | order
        res = jax.lax.sort(keys + (tail,), dimension=0, is_stable=False,
                           num_keys=len(keys) + 1)
        code = (res[-1] >> shift).astype(jnp.int32)
        s_lens = jnp.where(code == sentinel_code, -1, code)
        perm = res[-1] & jnp.uint32((1 << shift) - 1)
    else:
        res = jax.lax.sort(keys + (lengths, order), dimension=0,
                           is_stable=False, num_keys=len(keys) + 2)
        s_lens, perm = res[-2].astype(jnp.int32), res[-1]
    return (None if partitions is None else res[0].astype(jnp.int32),
            perm.astype(jnp.int32),
            jnp.stack(res[first:first + num_lanes], axis=1), s_lens)


def _sort_by_key(lanes: jnp.ndarray, lens: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, ...]:
    """The sort of one partition's rows, sentinels (length < 0) included:
    by (lanes, length, arrival order).  A sentinel's lanes are all ones as
    every staging path writes them (put so again here: no real key sorts
    after it), and its length code is the largest, so the sentinels stand
    at the tail without a partition column.  Returns (perm, sorted lanes,
    sorted lengths i32)."""
    return _lsd_passes(None, jnp.where((lens < 0)[:, None],
                                       jnp.uint32(0xFFFFFFFF), lanes),
                       lens)[1:]


# ---------------------------------------------------------------------------
# device-resident span sort + merge (VERDICT r1 item 4: the framework's hot
# path keeps key material in HBM across sort -> shuffle -> merge; the host
# only sees permutations and does the leaf ragged gathers).  Only valid when
# every key fits the lane width — then lanes+lengths ARE the full key, the
# FNV hash can be derived on device (no separate hash-matrix upload) and
# prefix order IS exact byte order (no tie-break pass).
# ---------------------------------------------------------------------------
def _fnv_rows_from_lanes(lanes: jnp.ndarray,
                         lengths: jnp.ndarray) -> jnp.ndarray:
    """FNV-1a over each row's first `lengths[i]` bytes, reconstructed from
    the big-endian u32 lanes (keycodec.matrix_to_lanes packing).  Exact
    parity with _fnv_rows/HashPartitioner when true length <= lane bytes."""
    h = jnp.full((lanes.shape[0],), FNV_OFFSET, dtype=jnp.uint32)
    for j in range(lanes.shape[1] * 4):     # static unroll, W is small
        byte = (lanes[:, j // 4] >> (24 - 8 * (j % 4))) & jnp.uint32(0xFF)
        nh = ((h ^ byte) * FNV_PRIME).astype(jnp.uint32)
        h = jnp.where(j < lengths, nh, h)
    return h


def _resident_span_sort(sentinel: jnp.ndarray, partitions: jnp.ndarray,
                        lanes: jnp.ndarray, lengths: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, ...]:
    """What the fused resident span sorts share, everything but the
    partition step: sentinel rows (length < 0) take partition MAX and sort
    to the tail; the sort; its sorted key columns returned as device
    arrays so downstream merges never re-upload them.  `sentinel` is traced
    by the caller BEFORE its partition step: the hash kernel's operations
    then stand in the order they always had, and its compiled programs
    (and their compile-cache keys) are the ones from before the range
    kernel existed."""
    return _lsd_passes(
        jnp.where(sentinel, jnp.int32(np.iinfo(np.int32).max), partitions),
        lanes, lengths)


def _fused_resident_hash_sort_impl(lanes: jnp.ndarray, lengths: jnp.ndarray,
                                   num_partitions: int
                                   ) -> Tuple[jnp.ndarray, ...]:
    """hash-from-lanes + the resident span sort."""
    h = _fnv_rows_from_lanes(lanes, lengths)
    return _resident_span_sort(
        lengths < 0, (h % jnp.uint32(num_partitions)).astype(jnp.int32),
        lanes, lengths)


def _range_partitions(lanes: jnp.ndarray, lengths: jnp.ndarray,
                      split_lanes: jnp.ndarray, split_lengths: jnp.ndarray
                      ) -> jnp.ndarray:
    """Partition id = number of split rows <= the row's key, compared
    lexicographically by (lanes..., length) as the sort orders them: one
    broadcast compare of N rows against S = P-1 split rows, no gather.
    A key equal to split i goes to partition i + 1
    (library.partitioners.TotalOrderPartitioner, keycodec.range_partitions
    on the host)."""
    if split_lanes.shape[0] == 0:
        return jnp.zeros(lanes.shape[0], dtype=jnp.int32)
    ge = lengths[:, None] >= split_lengths[None, :]
    for i in range(lanes.shape[1] - 1, -1, -1):
        col, split = lanes[:, i][:, None], split_lanes[:, i][None, :]
        ge = (col > split) | ((col == split) & ge)
    return ge.sum(axis=1, dtype=jnp.int32)


def _fused_resident_range_sort_impl(lanes: jnp.ndarray, lengths: jnp.ndarray,
                                    split_lanes: jnp.ndarray,
                                    split_lengths: jnp.ndarray
                                    ) -> Tuple[jnp.ndarray, ...]:
    """range-partition-from-lanes + the resident span sort."""
    return _resident_span_sort(
        lengths < 0,
        _range_partitions(lanes, lengths, split_lanes, split_lengths),
        lanes, lengths)


def _resident_sort_kernels(fn: Callable, name: str,
                           static_argnames: Tuple[str, ...]
                           ) -> Tuple[Kernel, Kernel]:
    """(plain, donating) flavors of one fused resident span sort.  The
    donating one is the async pipeline's: the staged (bucketed) input lanes
    buffer aliases the sorted-lanes output, so the sort runs in place in
    HBM instead of holding both copies live."""
    return (Kernel(fn, name, static_argnames=static_argnames),
            Kernel(fn, name + "_donated", static_argnames=static_argnames,
                   donate_argnums=(0,)))


#: (plain, donating) by the partitioner's batch form
_HASH_SORTS = _resident_sort_kernels(
    _fused_resident_hash_sort_impl, "resident_hash_sort",
    ("num_partitions",))
_RANGE_SORTS = _resident_sort_kernels(
    _fused_resident_range_sort_impl, "fused_resident_range_sort", ())


def _resident_sort(num_partitions: int, splits, donate: bool) -> Callable:
    """The fused span sort of one partitioner, picked here and nowhere
    else: the hash kernel with its partition count, or, where `splits`
    (split_lanes u32[P-1, L], split_lengths i32[P-1], encoded at the span's
    lane width) are given, the range kernel with them.  Returns
    launch(lanes, lengths).  Donation on accelerator
    backends only -- XLA:CPU ignores it (with a warning per call)."""
    if splits is None:
        pair, rows, static = _HASH_SORTS, (), {"num_partitions":
                                               num_partitions}
    else:
        pair, rows, static = _RANGE_SORTS, tuple(
            jnp.asarray(s) for s in splits), {}
    kernel = pair[bool(donate and accelerator_present())]
    return lambda lanes, lengths: kernel(lanes, lengths, *rows, **static)


# -- decomposed resident-span stages (ops/async_stage.py pipeline) ----------
# sort_span_resident = stage + dispatch + readback run back-to-back;
# the async pipeline runs them on different threads so span k+1's staging
# overlaps span k's in-flight sort.

def _upload_rows(rows: np.ndarray, bucket: int = 0, fill: int = 0
                 ) -> jnp.ndarray:
    """A host matrix of few columns (u32[n, L] key lanes, u8[n, W] key
    bytes) as a device array of the same shape, or of `bucket` rows with
    the tail filled.  The chip keeps such an array column-major (layout
    {0,1}: the rows are the minor dimension), so handed the row-major
    matrix PJRT transposes it on its transfer threads in hundreds of
    thousands of little pieces: 740,000 `Transpose` events a hash-join DAG
    in the profiler's file, 0.7 GB of profiler memory a traced DAG (PERF.md
    PR 35).  One transposed copy here, from the pool and padded in the same
    pass, goes up as it lies; the `.T` on the device relabels its bytes."""
    n, width = rows.shape
    bucket = max(bucket, n)
    flipped = hostpool.empty(width * bucket, rows.dtype).reshape(width,
                                                                 bucket)
    np.copyto(flipped[:, :n], rows.T)
    flipped[:, n:] = fill
    return jnp.asarray(flipped).T


def _upload_padded(lanes: np.ndarray, lengths: np.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rows padded to their bucket with tail sentinels (lanes all ones,
    length -1), on the device."""
    n, nb = lanes.shape[0], _bucket(lanes.shape[0])
    return (_upload_rows(lanes, nb, 0xFFFFFFFF),
            jnp.asarray(np.pad(lengths.astype(np.int32), (0, nb - n),
                               constant_values=-1)))


def stage_resident_span(lanes: np.ndarray, lengths: np.ndarray):
    """Host bucket-pad + H2D upload.  Returns (lanes_dev, lens_dev, n)."""
    return _upload_padded(lanes, lengths) + (lanes.shape[0],)


def dispatch_resident_span(staged, num_partitions: int, splits=None):
    """Launch the fused kernel; returns in-flight device arrays immediately
    (JAX async dispatch) — block via readback_resident_span.  splits as
    _resident_sort takes them: by range instead of by hash."""
    lanes_dev, lens_dev, n = staged
    sp, perm, out_lanes, out_lens = _resident_sort(
        num_partitions, splits, donate=True)(lanes_dev, lens_dev)
    return sp, perm, out_lanes, out_lens, n


def readback_resident_span(inflight):
    """Block until host-visible; same return shape as
    sort_span_resident."""
    sp, perm, out_lanes, out_lens, n = inflight
    return (np.asarray(sp)[:n], np.asarray(perm)[:n],
            (out_lanes, out_lens, 0, n))


def sort_span_resident(lanes: np.ndarray, lengths: np.ndarray,
                       num_partitions: int, splits=None):
    """Fused span kernel, resident flavor: upload = lanes + lengths ONLY
    (~20B/row vs ~36B for the matrix path); returns host (sorted partitions,
    permutation) plus device (sorted lanes, sorted lengths, bucketed) whose
    rows >= n are tail sentinels.  Caller guarantees max true length <=
    lane bytes.  splits as dispatch_resident_span takes them."""
    n = lanes.shape[0]
    if n == 0:
        return (np.zeros(0, np.int32), np.zeros(0, np.int32), None)
    sp, perm, out_lanes, out_lens = _resident_sort(
        num_partitions, splits, donate=False)(
            *_upload_padded(lanes, lengths))
    sp = np.asarray(sp)[:n]
    perm = np.asarray(perm)[:n]
    return sp, perm, (out_lanes, out_lens, 0, n)


def _slice_to_bucket_impl(lanes: jnp.ndarray, lengths: jnp.ndarray,
                          lo, count, out_rows: int, out_lanes: int):
    """Dynamic [lo, lo+count) slice padded to a STATIC out_rows bucket with
    tail sentinels — dynamic offsets keep the compile count bounded by
    (input bucket, output bucket) pairs, not by data-dependent slice sizes.
    Narrower views widen to out_lanes with ZERO lanes: bytes beyond a key's
    length are zero in the lane encoding, so widening preserves order."""
    idx = lo + jnp.arange(out_rows)
    safe = jnp.minimum(idx, lanes.shape[0] - 1)
    sl = jnp.take(lanes, safe, axis=0)
    ln = jnp.take(lengths, safe, axis=0)
    if lanes.shape[1] < out_lanes:
        sl = jnp.pad(sl, ((0, 0), (0, out_lanes - lanes.shape[1])))
    mask = jnp.arange(out_rows) < count
    sl = jnp.where(mask[:, None], sl, jnp.uint32(0xFFFFFFFF))
    ln = jnp.where(mask, ln, -1)
    return sl, ln


_slice_to_bucket = Kernel(_slice_to_bucket_impl, "slice_to_bucket",
                          static_argnames=("out_rows", "out_lanes"))


def _fused_resident_merge_impl(lanes_list, lens_list):
    """Single-partition k-way merge of device-resident sorted key columns:
    the sort of the concatenation (TezMerger semantics — equal keys keep
    run order).  Sentinel rows (length < 0) sort to the tail."""
    return _sort_by_key(jnp.concatenate(lanes_list, axis=0),
                        jnp.concatenate(lens_list, axis=0))[0]


_fused_resident_merge = Kernel(
    _fused_resident_merge_impl, "resident_merge_sort",
    launch_rows=lambda lanes_list, lens_list: sum(
        int(l.shape[0]) for l in lanes_list))


def _map_bucketed_perm(perm: np.ndarray, counts, common: int) -> np.ndarray:
    """Map a permutation over the BUCKETED concatenation (k runs, each
    padded to `common` rows) back to host rows of the real concatenation,
    dropping sentinel positions."""
    bounds = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum([common] * len(counts), out=bounds[1:])
    host_offsets = np.zeros(len(counts), dtype=np.int64)
    np.cumsum(counts[:-1], out=host_offsets[1:])
    run_id = np.searchsorted(bounds[1:], perm, side="right")
    within = perm - bounds[run_id]
    real = within < np.asarray(counts)[run_id]
    return (host_offsets[run_id] + within)[real].astype(np.int64)


def merge_resident_slices(slices) -> np.ndarray:
    """k-way merge over device-resident key views: one sort of the slices'
    concatenation, each slice cut to a common bucket on the device.

    slices: list of (lanes_dev, lens_dev, lo, hi).  Returns the merge
    permutation into the HOST concatenation of the real rows (run order
    preserved for equal keys).  No key bytes move host->device; only the
    permutation comes back."""
    counts = [hi - lo for (_l, _n, lo, hi) in slices]
    # ONE common bucket for every slice: the merge program's compile key is
    # then (k, B, L) instead of the full ordered tuple of per-run sizes —
    # bounded compile variety at the cost of sorting k*B instead of
    # sum(bucket_i) rows (sentinels are cheap; compiles are not)
    common = _bucket(max(counts))
    width = max(l.shape[1] for (l, _n, _lo, _hi) in slices)
    lanes_list, lens_list = [], []
    with tracing.span("merge.stage", cat="merge", runs=len(slices),
                      rows=sum(counts), bucket=common):
        for (lanes, lens, lo, hi) in slices:
            sl, ln = _slice_to_bucket(lanes, lens, np.int32(lo),
                                      np.int32(hi - lo), out_rows=common,
                                      out_lanes=width)
            lanes_list.append(sl)
            lens_list.append(ln)
    with tracing.span("merge.launch", cat="merge", runs=len(slices)):
        perm_dev = _fused_resident_merge(lanes_list, lens_list)
    # the host blocks here for the device: this merge's own work and every
    # launch other threads queued ahead of it on the chip
    with tracing.span("merge.readback", cat="merge",
                      rows=common * len(counts)):
        perm = np.asarray(perm_dev)
    with tracing.span("merge.gather", cat="merge", rows=sum(counts)):
        return _map_bucketed_perm(perm, counts, common)


def _fused_hash_sort_impl(key_mat: jnp.ndarray, hash_lengths: jnp.ndarray,
                          lanes: jnp.ndarray, sort_lengths: jnp.ndarray,
                          num_partitions: int
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One dispatch: full-key FNV hash-partition + the sort.  One XLA
    program matters on TPU: per-dispatch latency (host<->device round
    trips) would otherwise dominate small spans."""
    partitions = _hash_to_partitions(key_mat, hash_lengths, num_partitions)
    return _lsd_passes(partitions, lanes, sort_lengths)[:2]


_fused_hash_sort = Kernel(_fused_hash_sort_impl, "hash_sort",
                          static_argnames=("num_partitions",))



def _sort_run_impl(partitions, lanes, lengths):
    """The sort alone: (sorted partitions, permutation)."""
    return _lsd_passes(partitions, lanes, lengths)[:2]


_fused_sort = Kernel(_sort_run_impl, "sort_run")


def hash_sort_span(key_mat: np.ndarray, hash_lengths: np.ndarray,
                   lanes: np.ndarray, lengths: np.ndarray,
                   num_partitions: int) -> tuple[np.ndarray, np.ndarray]:
    """Fused span kernel: hash-partition + stable (partition, key) sort in a
    single device dispatch.  Returns (sorted partitions, permutation)."""
    n = key_mat.shape[0]
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    width_cap = lanes.shape[1] * 4 + 1
    slen = np.minimum(lengths.astype(np.int64), width_cap)
    nb = _bucket(n)
    # the partition column (MAX on a pad row, whose hash length is -1)
    # sweeps the pads to the tail whatever their length says
    sp, perm = _fused_hash_sort(
        _upload_rows(key_mat, nb, 255),
        jnp.asarray(np.pad(hash_lengths.astype(np.int32), (0, nb - n),
                           constant_values=-1)),
        _upload_rows(lanes, nb, 0xFFFFFFFF),
        jnp.asarray(np.pad(slen.astype(np.uint32), (0, nb - n),
                           constant_values=width_cap)),
        num_partitions=num_partitions)
    sp = np.asarray(sp)
    perm = np.asarray(perm)
    if nb != n:
        keep = perm < n
        sp, perm = sp[keep], perm[keep]
    return sp, perm


def _stage_sort_columns(partitions: np.ndarray, lanes: np.ndarray,
                        lengths: np.ndarray):
    """Clamp lengths at the lane cap, pad the three sort columns to the
    bucket of their rows (pads carry partition MAX: the partition column
    alone sweeps them to the tail) and upload.  Returns the device
    operands."""
    n = partitions.shape[0]
    width_cap = lanes.shape[1] * 4 + 1
    slen = np.minimum(lengths.astype(np.int64), width_cap).astype(np.uint32)
    nb = _bucket(n)
    return (jnp.asarray(np.pad(partitions, (0, nb - n),
                               constant_values=np.iinfo(np.int32).max)),
            _upload_rows(lanes, nb),
            jnp.asarray(np.pad(slen, (0, nb - n),
                               constant_values=width_cap)))


def sort_run(partitions: np.ndarray, lanes: np.ndarray,
             lengths: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Stable sort by (partition, key lanes, clamped length): the one
    variadic `lax.sort` of `_lsd_passes` as the compiled `_fused_sort`
    program.  (Until PR 35 an LSD ladder of stable single-key passes, each
    gathering its key by the permutation, for fear of the variadic sort's
    compile time.  Measured on a v5e, PERF.md PR 35: the ladder compiles in
    20-22 s and runs 12.5 / 51 / 119 ms at 2^18 x 2 / 2^20 x 3 / 1.5 x 2^20
    x 6 lanes, nearly all of it the gathers; this sort 1.4 / 4.0 / 11 ms
    with the length as an operand of its own, compiling in 49 / 67 / 197 s
    -- compile time grows with the square of the operands.)

    The clamped length disambiguates keys whose zero padding collides (if
    padded prefixes are equal, the longer key == shorter key + trailing
    zeros, so byte order == length order); beyond-prefix lengths compare
    equal and are resolved by the host tie-break pass.

    Returns (sorted partition ids, permutation); padding rows (partition
    = MAX) sort to the tail and are stripped by the caller.
    """
    n = partitions.shape[0]
    if n == 0:
        return partitions, np.zeros(0, dtype=np.int32)
    sorted_parts, perm = _fused_sort(
        *_stage_sort_columns(partitions, lanes, lengths))
    return (np.asarray(sorted_parts)[:n], np.asarray(perm)[:n])


# ---------------------------------------------------------------------------
# merge of sorted runs = sort of concatenation (stable; run order preserved)
# ---------------------------------------------------------------------------
def _merge_sort_impl(partitions, lanes, lengths):
    """The sort under a program name of its own: the permutation."""
    return _lsd_passes(partitions, lanes, lengths)[1]


_merge_sort = Kernel(_merge_sort_impl, "merge_sort")

#: the programs that compare rows in a merge; ``slice_to_bucket`` stages
#: their operands.  DEVICE_MERGE_LAUNCH_ROWS sums the rows of these, so
#: that over DEVICE_MERGE_RECORDS it reads the padding (ops/sorter.py
#: _record_launches).
MERGE_LEVEL_KERNELS = ("merge_sort", "resident_merge_sort")


def merge_runs(partitions: np.ndarray, lanes: np.ndarray,
               lengths: np.ndarray) -> np.ndarray:
    """k-way merge of host-fed sorted runs, handed over as their
    concatenation in run-arrival order: ONE launch of the sort by
    (partition, key lanes, clamped length, arrival order) over the
    concatenation padded once to its bucket: equal keys keep run order (TezMerger
    segment-queue semantics).  Returns the permutation into the
    concatenation; pads (partition MAX) sort to the tail and are cut.  Like
    sort_run, prefix-equal beyond-cap keys compare equal here and are
    resolved by the caller's host tie-break pass."""
    n = partitions.shape[0]
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    nb = _bucket(n)
    with tracing.span("merge.stage", cat="merge", rows=n, bucket=nb):
        operands = _stage_sort_columns(partitions, lanes, lengths)
    with tracing.span("merge.launch", cat="merge"):
        perm_dev = _merge_sort(*operands)
    # the host blocks here for the device: this merge's own work and every
    # launch other threads queued ahead of it on the chip
    with tracing.span("merge.readback", cat="merge", rows=nb):
        perm = np.asarray(perm_dev)
    with tracing.span("merge.gather", cat="merge", rows=n):
        return perm[:n].astype(np.int64)


# ---------------------------------------------------------------------------
# merge-join match = sort of the two sides' concatenation + neighbour compare
# ---------------------------------------------------------------------------
def _matches_after_sort(xp, perm, s_lanes, s_lens, n_left: int):
    """`perm` orders the concatenation [left rows, right rows] by (lanes,
    length), stably: among equal keys the left rows stand first;
    `s_lanes`, `s_lens` are the key columns in that order.  A key both
    sides hold therefore shows exactly one place where a left row is
    followed by a right row of the same key: that left row's index stands
    in the result, -1 everywhere else (sentinel rows, length < 0, never
    match).  xp: numpy on the host engine, jax.numpy traced."""
    same = (s_lens[:-1] == s_lens[1:]) & (s_lens[:-1] >= 0) & \
        (s_lanes[:-1] == s_lanes[1:]).all(axis=1)
    is_left = perm < n_left
    return xp.where(is_left[:-1] & ~is_left[1:] & same, perm[:-1], -1)


def _sort_two_sides(first_lanes, first_lens, second_lanes, second_lens):
    """Traced: one sort of two sides' concatenation, each padded to its
    bucket with sentinels -- the side is the least significant column by
    the order of concatenation, which the sort keeps among equal keys.
    Returns (perm, sorted lanes, sorted lens) of the concatenation."""
    return _sort_by_key(
        jnp.concatenate([first_lanes, second_lanes], axis=0),
        jnp.concatenate([first_lens, second_lens], axis=0))


def _lexsort_two_sides(first_lanes, first_lens, second_lanes, second_lens):
    """_sort_two_sides on the host engine: numpy's stable lexsort, the
    columns gathered by its permutation."""
    lanes = np.concatenate([first_lanes, second_lanes])
    lens = np.concatenate([first_lens, second_lens]).astype(np.int32)
    perm = np.lexsort((lens,) + tuple(
        lanes[:, i] for i in range(lanes.shape[1] - 1, -1, -1)))
    return perm, lanes[perm], lens[perm]


def _join_match_impl(left_lanes, left_lens, right_lanes, right_lens):
    """Semi-join match of two key-sorted sides: the sort of their
    concatenation, then a neighbour compare.  Returns i32[B - 1]: the left
    row of every key both sides hold (once a key), -1 elsewhere, in key
    order."""
    perm, s_lanes, s_lens = _sort_two_sides(left_lanes, left_lens,
                                            right_lanes, right_lens)
    return _matches_after_sort(jnp, perm, s_lanes, s_lens,
                               left_lanes.shape[0])


_join_match = Kernel(
    _join_match_impl, "join_match",
    launch_rows=lambda ll, _ln, rl, _rn: int(ll.shape[0] + rl.shape[0]))


def join_match(left_lanes: np.ndarray, left_lens: np.ndarray,
               right_lanes: np.ndarray, right_lens: np.ndarray
               ) -> np.ndarray:
    """The left rows whose key the right side holds too, one row a distinct
    key, ascending.  Both sides are key-sorted lanes of one width holding
    whole keys (u32[n, L], lengths i32[n]); duplicates on either side are
    fine.  Each side is padded to its own bucket, so the program's compile
    key is (left bucket, right bucket, L); only the matches' row indices
    come back."""
    with tracing.span("join.match", cat="join", stage="stage",
                      rows=len(left_lens) + len(right_lens)):
        operands = _upload_padded(left_lanes, left_lens) + \
            _upload_padded(right_lanes, right_lens)
    with tracing.span("join.match", cat="join", stage="launch"):
        hits_dev = _join_match(*operands)
    # the host blocks here for the device, as in merge.readback
    with tracing.span("join.match", cat="join", stage="readback"):
        hits = np.asarray(hits_dev)
        return hits[hits >= 0].astype(np.int64)


def join_match_host(left_lanes: np.ndarray, left_lens: np.ndarray,
                    right_lanes: np.ndarray, right_lens: np.ndarray
                    ) -> np.ndarray:
    """join_match on the host engine: numpy's stable lexsort in the sort's
    place, the same neighbour compare."""
    perm, s_lanes, s_lens = _lexsort_two_sides(left_lanes, left_lens,
                                               right_lanes, right_lens)
    hits = _matches_after_sort(np, perm, s_lanes, s_lens, len(left_lens))
    return hits[hits >= 0].astype(np.int64)


# ---------------------------------------------------------------------------
# hash-join probe = sort of [stream block, build] + run-wise "holds a build
# row" -- no hash table and no binary search: the same stable passes as the
# merges and the merge-join's match
# ---------------------------------------------------------------------------
def _run_end_carry(xp, cummax_from_end, perm, s_lanes, s_lens,
                   n_stream: int):
    """`perm` orders the concatenation [stream rows, build rows] by (lanes,
    length), stably, and `s_lanes`, `s_lens` are the key columns in that
    order: inside a run of equal keys the stream rows stand first, the
    build rows last, so a run holds a build row exactly when its last row
    is one.  The run's last row carries (its distance from the end) * 2 +
    (is it a build row), and a running maximum from the end hands the
    nearest run end's value to every row before it.  Returns (is_stream,
    held): a stream row at each sorted place, its run ends in a build
    row."""
    n = perm.shape[0]
    same_as_next = (s_lens[:-1] == s_lens[1:]) & \
        (s_lanes[:-1] == s_lanes[1:]).all(axis=1)
    run_end = xp.concatenate([~same_as_next, xp.ones(1, dtype=bool)])
    is_stream = perm < n_stream
    from_end = xp.arange(n - 1, -1, -1, dtype=xp.int32)
    carried = cummax_from_end(xp.where(
        run_end, from_end * 2 + (~is_stream).astype(xp.int32), 0))
    return is_stream, (carried & 1) == 1


def _probe_hits_after_sort(xp, cummax_from_end, perm, s_lanes, s_lens,
                           n_stream: int):
    """Every stream row whose run holds a build row is a hit
    (`_run_end_carry`).  Returns i32[B]: the stream row at each sorted
    place that is a hit, -1 elsewhere (sentinel rows, length < 0, never
    match)."""
    is_stream, held = _run_end_carry(xp, cummax_from_end, perm, s_lanes,
                                     s_lens, n_stream)
    return xp.where(is_stream & held & (s_lens >= 0), perm, -1)


def _join_probe_impl(stream_lanes, stream_lens, build_lanes, build_lens):
    """Semi-join probe of one block of stream rows against the build side,
    each padded to its bucket with sentinels: join_match's sort, then every
    stream row whose key the build side holds -- each occurrence, where
    the match keeps one row a distinct key.  Returns i32[B]: hits' stream
    row indices, -1 elsewhere, in key order."""
    perm, s_lanes, s_lens = _sort_two_sides(stream_lanes, stream_lens,
                                            build_lanes, build_lens)
    return _probe_hits_after_sort(
        jnp, lambda v: jax.lax.cummax(v, axis=0, reverse=True), perm,
        s_lanes, s_lens, stream_lanes.shape[0])


_join_probe = Kernel(
    _join_probe_impl, "join_probe",
    launch_rows=lambda sl, _sn, bl, _bn: int(sl.shape[0] + bl.shape[0]))


def stage_join_build(build_lanes: np.ndarray, build_lens: np.ndarray):
    """The build side padded to its bucket and uploaded: device arrays a
    joiner keeps across its probes (``join_probe``'s `build`)."""
    with tracing.span("join.match", cat="join", stage="stage", how="semi",
                      rows=len(build_lens)):
        return _upload_padded(build_lanes, build_lens)


def join_probe(stream_lanes: np.ndarray, stream_lens: np.ndarray,
               build: tuple) -> np.ndarray:
    """The rows of one stream block whose key the build side holds, every
    occurrence, ascending by row.  `build` is ``stage_join_build``'s pair
    (lanes of the block's width holding whole keys); duplicates on either
    side are fine.  The program's compile key is (block bucket, build
    bucket, L); only the hits' row indices come back."""
    with tracing.span("join.match", cat="join", stage="stage", how="semi",
                      rows=len(stream_lens)):
        operands = _upload_padded(stream_lanes, stream_lens)
    with tracing.span("join.match", cat="join", stage="launch", how="semi"):
        hits_dev = _join_probe(*operands, *build)
    # the host blocks here for the device, as in merge.readback
    with tracing.span("join.match", cat="join", stage="readback",
                      how="semi"):
        hits = np.asarray(hits_dev)
        return np.sort(hits[hits >= 0]).astype(np.int64)


def join_probe_host(stream_lanes: np.ndarray, stream_lens: np.ndarray,
                    build_lanes: np.ndarray, build_lens: np.ndarray
                    ) -> np.ndarray:
    """join_probe on the host engine: numpy's stable lexsort in the sort's
    place, the same run-wise test."""
    perm, s_lanes, s_lens = _lexsort_two_sides(stream_lanes, stream_lens,
                                               build_lanes, build_lens)
    hits = _probe_hits_after_sort(
        np, lambda v: np.maximum.accumulate(v[::-1])[::-1], perm, s_lanes,
        s_lens, len(stream_lens))
    return np.sort(hits[hits >= 0]).astype(np.int64)


# ---------------------------------------------------------------------------
# key-foreign-key probe = the hash-join probe's sort and run-end carry, then
# the build row each stream row hit: no gather and no search, one more sort
# to put the answers back in arrival order
# ---------------------------------------------------------------------------
def _kfk_ranks_after_sort(xp, cummax_from_end, perm, s_lanes, s_lens,
                          n_stream: int):
    """With the build side's keys unique a run holds at most one build row,
    its last (`_run_end_carry`), so the build rows standing before a stream
    row are those of earlier runs: their count is the key rank of the build
    row that ends its run (its place among the build side's keys in key
    order).  Returns i32[B]: that rank at the sorted place of every stream
    row that hits, -1 elsewhere (sentinel rows, length < 0, never match)."""
    is_stream, held = _run_end_carry(xp, cummax_from_end, perm, s_lanes,
                                     s_lens, n_stream)
    live = s_lens >= 0
    is_build = (~is_stream & live).astype(xp.int32)
    before = xp.cumsum(is_build, dtype=xp.int32) - is_build
    return xp.where(is_stream & held & live, before, -1)


def _kfk_probe_impl(stream_lanes, stream_lens, build_lanes, build_lens):
    """Key-foreign-key probe of one block of stream rows against a build
    side of unique keys, each padded to its bucket with sentinels: the
    semi probe's sort, the key rank of the build row each stream row hit
    (`_kfk_ranks_after_sort`), and a sort by the permutation that carries
    the ranks back to arrival order.  Returns i32[stream bucket]: a stream
    row's build key rank, -1 where its key is not on the build side."""
    n_stream = stream_lanes.shape[0]
    perm, s_lanes, s_lens = _sort_two_sides(stream_lanes, stream_lens,
                                            build_lanes, build_lens)
    ranks = _kfk_ranks_after_sort(
        jnp, lambda v: jax.lax.cummax(v, axis=0, reverse=True), perm,
        s_lanes, s_lens, n_stream)
    _perm, by_row = jax.lax.sort((perm, ranks), dimension=0,
                                 is_stable=False, num_keys=1)
    return by_row[:n_stream]


_kfk_probe = Kernel(
    _kfk_probe_impl, "kfk_probe",
    launch_rows=lambda sl, _sn, bl, _bn: int(sl.shape[0] + bl.shape[0]))


def _build_key_order(build_lanes: np.ndarray, build_lens: np.ndarray
                     ) -> np.ndarray:
    """The build side's rows in key order (lanes, then length: the
    device sort's), or ValueError where two rows hold one key: a
    key-foreign-key join's build keys are unique."""
    order = np.lexsort((build_lens,) + tuple(
        build_lanes[:, i] for i in range(build_lanes.shape[1] - 1, -1, -1)))
    s_lanes, s_lens = build_lanes[order], build_lens[order]
    same = (s_lens[1:] == s_lens[:-1]) & \
        (s_lanes[1:] == s_lanes[:-1]).all(axis=1)
    if same.any():
        at = int(order[int(np.argmax(same))])
        raise ValueError(f"key-foreign-key join: build row {at} repeats a "
                         f"key another build row holds; the build side's "
                         f"keys have to be unique")
    return order.astype(np.int64)


def stage_kfk_build(build_lanes: np.ndarray, build_lens: np.ndarray):
    """The build side of a key-foreign-key join checked for unique keys,
    padded to its bucket and uploaded, with its rows in key order: what a
    joiner keeps across its probes (``kfk_probe``'s `build`)."""
    with tracing.span("join.match", cat="join", stage="stage", how="inner",
                      rows=len(build_lens)):
        order = _build_key_order(build_lanes, build_lens)
        return _upload_padded(build_lanes, build_lens) + (order,)


def kfk_probe(stream_lanes: np.ndarray, stream_lens: np.ndarray,
              build: tuple) -> np.ndarray:
    """The build row each row of one stream block hit, -1 where its key is
    not on the build side: i64[n], in arrival order.  `build` is
    ``stage_kfk_build``'s; lanes of the block's width hold whole keys, and
    stream keys may repeat.  The program's compile key is (block bucket,
    build bucket, L); one i32 a stream row comes back."""
    n = len(stream_lens)
    with tracing.span("join.match", cat="join", stage="stage", how="inner",
                      rows=n):
        operands = _upload_padded(stream_lanes, stream_lens)
    with tracing.span("join.match", cat="join", stage="launch", how="inner"):
        ranks_dev = _kfk_probe(*operands, *build[:2])
    # the host blocks here for the device, as in merge.readback
    with tracing.span("join.match", cat="join", stage="readback",
                      how="inner"):
        ranks = np.asarray(ranks_dev)[:n]
        return np.where(ranks >= 0, build[2][ranks], -1)


def kfk_probe_host(stream_lanes: np.ndarray, stream_lens: np.ndarray,
                   build_lanes: np.ndarray, build_lens: np.ndarray
                   ) -> np.ndarray:
    """kfk_probe on the host engine, unpadded: numpy's stable lexsort in
    the sort's place, the same ranks, put back in arrival order by
    indexing."""
    order = _build_key_order(build_lanes, build_lens)
    n = len(stream_lens)
    perm, s_lanes, s_lens = _lexsort_two_sides(stream_lanes, stream_lens,
                                               build_lanes, build_lens)
    ranks = _kfk_ranks_after_sort(
        np, lambda v: np.maximum.accumulate(v[::-1])[::-1], perm, s_lanes,
        s_lens, n)
    by_row = np.full(n, -1, dtype=np.int64)
    stream = perm < n
    by_row[perm[stream]] = ranks[stream]
    return np.where(by_row >= 0, order[by_row], -1)


# ---------------------------------------------------------------------------
# group fold = sort of [table, block] carrying the values + neighbour compare
# + segment sum: the group table of a hash aggregation kept on the device
# between launches
# ---------------------------------------------------------------------------
def _run_ends(xp, s_lanes, s_lens):
    """True on the last row of every run of equal (lanes, length) among the
    live rows (length >= 0) of a key-sorted column pair."""
    same_as_next = (s_lens[:-1] == s_lens[1:]) & \
        (s_lanes[:-1] == s_lanes[1:]).all(axis=1)
    return (s_lens >= 0) & xp.concatenate([~same_as_next,
                                           xp.ones(1, dtype=bool)])


def _group_sum_impl(t_lanes, t_lens, t_sums, b_lanes, b_lens, b_vals,
                    table_rows: int):
    """Fold one block of (key, value) rows into a group table: the first
    `table_rows` rows of the table (distinct keys, key-sorted, sentinels
    after them) and the block (sentinels: lanes all ones, length -1, value
    0, as every staging path writes them) in one sort of their own, by
    (lanes, length as u32) with the values as a payload operand, so that
    they come out in key order without a gather; a neighbour compare
    marking each run's last row, and a running sum whose differences at
    the run ends are the groups' sums.  A second sort, by (not a run end,
    place), moves the run ends to the front in key order, the lanes, the
    running sum and the length (in the key's low bits where they are
    free) riding along.

    No arrival order and no permutation: rows of one key are one group in
    whatever order they stand, and the sum's differences do not depend on
    the order it adds in.  The merges, the match and the probe need both
    and keep `_lsd_passes`.  Sums are int32: the running sum may wrap, its
    differences are exact while every group's sum fits (the caller's
    guard).  Returns the new table at table_rows + block rows (lanes,
    lengths, sums; the live groups first, sentinels after) and its row
    count, i32[]."""
    lanes = jnp.concatenate([t_lanes[:table_rows], b_lanes], axis=0)
    lens = jnp.concatenate([t_lens[:table_rows], b_lens], axis=0)
    vals = jnp.concatenate([t_sums[:table_rows], b_vals], axis=0)
    n, num_lanes = lanes.shape
    # length -1 is all ones as u32: a sentinel sorts after every real key
    # of its lanes, and its value 0 leaves the running sum as it was
    res = jax.lax.sort(
        tuple(lanes[:, i] for i in range(num_lanes))
        + (lens.astype(jnp.uint32), vals), dimension=0, is_stable=False,
        num_keys=num_lanes + 1)
    s_lanes = jnp.stack(res[:num_lanes], axis=1)
    s_lens = res[num_lanes].astype(jnp.int32)
    ends = _run_ends(jnp, s_lanes, s_lens)
    running = jnp.cumsum(res[-1], dtype=jnp.int32)
    place = jnp.arange(n, dtype=jnp.uint32)
    # a live key is at most 4L bytes (longer ones fold on the host): where
    # the bits allow, its length rides below `place` in the compaction key,
    # one operand fewer (6.03 against 6.49 ms a launch at 2^18 + 2^20 rows
    # on a TPU v5e: PERF.md §6)
    code_bits = (4 * num_lanes).bit_length()
    packed = n <= 1 << (_TAIL_KEY_BITS - 1 - code_bits)
    if packed:
        place = (place << code_bits) | \
            jnp.maximum(s_lens, 0).astype(jnp.uint32)
    res = jax.lax.sort(
        (jnp.where(ends, place, place | jnp.uint32(1 << 31)),)
        + tuple(s_lanes[:, i] for i in range(num_lanes))
        + (() if packed else (s_lens,)) + (running,), dimension=0,
        is_stable=False, num_keys=1)
    out_lens = (res[0] & jnp.uint32((1 << code_bits) - 1)).astype(
        jnp.int32) if packed else res[-2]
    count = ends.sum(dtype=jnp.int32)
    live = jnp.arange(n) < count
    at_end = res[-1]
    sums = at_end - jnp.concatenate([jnp.zeros(1, at_end.dtype),
                                     at_end[:-1]])
    return (jnp.where(live[:, None], jnp.stack(res[1:1 + num_lanes], axis=1),
                      jnp.uint32(0xFFFFFFFF)),
            jnp.where(live, out_lens, -1), jnp.where(live, sums, 0), count)


_group_sum = Kernel(
    _group_sum_impl, "group_sum", static_argnames=("table_rows",),
    launch_rows=lambda tl, _tn, _ts, bl, _bn, _bv: int(tl.shape[0] +
                                                       bl.shape[0]))

#: the largest sum a device group table holds
GROUP_SUM_MAX = int(np.iinfo(np.int32).max)


@functools.lru_cache(maxsize=8)
def empty_group_table(rows: int, num_lanes: int):
    """A group table of `rows` sentinel rows on the device: what the first
    fold of a task folds into.  Uploaded once a process: a fold reads its
    table and never writes it."""
    return (_upload_rows(np.zeros((0, num_lanes), np.uint32), rows,
                         0xFFFFFFFF),
            jnp.asarray(np.full(rows, -1, np.int32)),
            jnp.asarray(np.zeros(rows, np.int32)))


def stage_group_block(lanes: np.ndarray, lens: np.ndarray,
                      vals: np.ndarray, bucket: int = 0):
    """One block of rows padded to its bucket, or to `bucket` rows where
    that is more (sentinels: lanes all ones, length -1, value 0), and
    uploaded; the values have to fit int32."""
    n = len(lens)
    with tracing.span("agg.fold", cat="agg", stage="stage", rows=n):
        nb = max(_bucket(n), bucket)
        return (_upload_rows(lanes, nb, 0xFFFFFFFF),
                jnp.asarray(np.pad(lens.astype(np.int32), (0, nb - n),
                                   constant_values=-1)),
                jnp.asarray(np.pad(vals.astype(np.int32), (0, nb - n))))


def group_sum(table: tuple, table_rows: int, block: tuple) -> tuple:
    """Launch the fold of a staged block into the resident `table` (device
    lanes, lengths, sums of at least `table_rows` rows, every live group
    among the first `table_rows`).  Returns the new table and its row count
    as device arrays at once: the program is enqueued, nothing is waited
    for.  The compile key is (table rows in, table_rows, block bucket,
    lanes)."""
    with tracing.span("agg.fold", cat="agg", stage="launch"):
        *out, count = _group_sum(*table, *block, table_rows=table_rows)
    return tuple(out), count


def group_table_rows(table: tuple, count: int
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The first `count` rows of a device group table on the host: (lanes
    u32[n, L], lengths i32[n], sums i64[n]), key-sorted."""
    lanes, lens, sums = (np.asarray(a)[:count] for a in table)
    return lanes, lens, sums.astype(np.int64)


def group_sum_host(t_lanes: np.ndarray, t_lens: np.ndarray,
                   t_sums: np.ndarray, b_lanes: np.ndarray,
                   b_lens: np.ndarray, b_vals: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """group_sum on the host engine, unpadded: numpy's stable lexsort in
    the sort's place, the same run ends, int64 sums by np.add.reduceat.
    Returns the new table (lanes, lengths, sums), key-sorted."""
    perm, s_lanes, s_lens = _lexsort_two_sides(t_lanes, t_lens, b_lanes,
                                               b_lens)
    if not len(perm):
        return s_lanes, s_lens, np.zeros(0, np.int64)
    ends = np.flatnonzero(_run_ends(np, s_lanes, s_lens))
    vals = np.concatenate([t_sums.astype(np.int64),
                           b_vals.astype(np.int64)])[perm]
    starts = np.concatenate([[0], ends[:-1] + 1])
    return s_lanes[ends], s_lens[ends], np.add.reduceat(vals, starts)
