"""Device-resident shuffle+sort pipeline for fixed-width records.

This is the HBM-resident heart of the data plane (SURVEY.md §2.5: "spans =
device buffers", spill = device->host DMA only on overflow): records whose
keys are normalized to u32 lanes and whose values are fixed-width words flow
hash->sort->merge entirely on device — the host only sees control metadata
(partition boundaries) and whatever a leaf output finally materializes.

Two entry points:

* :func:`device_shuffle_sort` — one synchronous span (the original path).
* :class:`DeviceSpanScheduler` — the asynchronous double-buffered plane
  (ops/async_stage.py): spans submit as raw host arrays; a staging thread
  encodes/bucket-pads/uploads span k+1 while span k's `_fused_pipeline` is
  in flight and span k-1's readback drains on worker threads.  Small spans
  coalesce into one bucketed dispatch.  The variable-length KVBatch path
  (ops.sorter) builds the same AsyncSpanPipeline around its own
  Run-producing stages; this class serves raw-array producers (benchmarks,
  device-to-device edges).

The reduce side runs a third AsyncSpanPipeline instance: the merge lane in
library/merge_manager.py, whose dispatch stage is the device merge
(ops/device.py merge_runs — one stable sort of the runs' padded
concatenation) and whose readback stage is the chunked-run disk write, so
fetch/commit, device merge, and spill IO overlap.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tez_tpu.ops.device import (Kernel, _bucket, _hash_to_partitions,
                                _lsd_passes, accelerator_present,
                                uniform_clamped_lengths)


def _fused_pipeline_impl(key_mat: jnp.ndarray, hash_lengths: jnp.ndarray,
                         lanes: jnp.ndarray, sort_lengths: jnp.ndarray,
                         vals: jnp.ndarray, num_partitions: int,
                         skip_length_pass: bool = False
                         ) -> Tuple[jnp.ndarray, ...]:
    """hash-partition + LSD (partition, lanes, length) sort + payload gather,
    one dispatch, everything stays in HBM.  Hash and sort bodies are the
    shared device.py helpers — one implementation for every kernel."""
    partitions = _hash_to_partitions(key_mat, hash_lengths, num_partitions)
    sorted_parts, perm = _lsd_passes(partitions, lanes, sort_lengths,
                                     skip_length_pass)
    out_lanes = lanes[perm]
    out_vals = vals[perm]
    # per-partition row counts (for the partition index) on device:
    # sorted_parts is already sorted, so P+1 binary searches beat a
    # full bincount scan (padding sentinels carry partition INT32_MAX
    # and fall past the last boundary)
    sp32 = sorted_parts.astype(jnp.int32)
    bounds = jnp.searchsorted(
        sp32, jnp.arange(num_partitions + 1, dtype=jnp.int32))
    counts = bounds[1:] - bounds[:-1]
    return sp32, out_lanes, out_vals, perm, counts


_fused_pipeline = Kernel(
    _fused_pipeline_impl, "fused_pipeline",
    static_argnames=("num_partitions", "skip_length_pass"))

_fused_pipeline_donating = Kernel(
    _fused_pipeline_impl, "fused_pipeline_donated",
    static_argnames=("num_partitions", "skip_length_pass"),
    donate_argnums=(2, 4))


def _fused_pipeline_donated() -> Kernel:
    """Donating flavor for the async plane: the staged lane/value buffers
    alias the sorted outputs, so the sort+gather runs in-place in HBM —
    double-buffered staging slots don't triple the resident footprint.
    Accelerator backends only (XLA:CPU ignores donation, warning per call).
    """
    return _fused_pipeline_donating if accelerator_present() \
        else _fused_pipeline


def device_shuffle_sort(lanes, lengths, vals, key_mat, hash_lengths,
                        num_partitions: int, uniform_length=None):
    """Device-resident pipeline over already-device (or host) arrays.
    Returns device arrays (sorted_partitions, lanes, vals, perm, counts).

    uniform_length: pass True/False when the caller already knows (keeps the
    lengths array device-resident); None = detect from a host array."""
    n = int(lanes.shape[0])
    nb = _bucket(n)
    width_cap = lanes.shape[1] * 4 + 1
    if uniform_length is None:
        uniform = isinstance(lengths, np.ndarray) and \
            uniform_clamped_lengths(lengths, width_cap)[0]
    else:
        uniform = bool(uniform_length)
    if nb != n:
        pad = nb - n
        key_mat = jnp.pad(key_mat, ((0, pad), (0, 0)), constant_values=255)
        hash_lengths = jnp.pad(hash_lengths, (0, pad), constant_values=-1)
        lanes = jnp.pad(lanes, ((0, pad), (0, 0)),
                        constant_values=np.uint32(0xFFFFFFFF))
        lengths = jnp.pad(lengths, (0, pad), constant_values=width_cap)
        vals = jnp.pad(vals, ((0, pad),) + ((0, 0),) * (vals.ndim - 1))
    slen = jnp.minimum(lengths, width_cap).astype(jnp.uint32)
    return _fused_pipeline(jnp.asarray(key_mat),
                           jnp.asarray(hash_lengths, dtype=jnp.int32),
                           jnp.asarray(lanes), slen, jnp.asarray(vals),
                           num_partitions=num_partitions,
                           skip_length_pass=uniform)


class DeviceSpanScheduler:
    """Async double-buffered plane over fixed-width spans.

    submit() takes host arrays (lanes, lengths, vals, key_mat, hash_lengths)
    for one span; results() blocks until everything drained and returns
    {span_id: (sorted_partitions, out_lanes, out_vals, perm, counts, n)} as
    HOST arrays (n = real rows; bucketed rows beyond n are tail sentinels).
    Coalesced spans share one result tuple whose rows are the stable sort of
    the concatenated spans — identical to merging the individually sorted
    spans, since stable ties preserve arrival order.
    """

    def __init__(self, num_partitions: int, depth: int = 2,
                 coalesce_records: int = 0, readback_workers: int = 2,
                 key_width: int = 0, counters: Any = None,
                 clock: Callable[[], float] = time.perf_counter,
                 instrument: bool = False, paused: bool = False,
                 contain_failures: bool = False,
                 watchdog_dispatch_ms: float = 0.0,
                 watchdog_readback_ms: float = 0.0,
                 breaker: Any = None) -> None:
        from tez_tpu.ops.async_stage import AsyncSpanPipeline
        self.num_partitions = num_partitions
        # key_width only matters for submit_ragged(); every ragged key must
        # fit in it (the hash matrix is built at the next power-of-two width,
        # so a longer key would hash truncated and land in the wrong
        # partition)
        self.key_width = key_width
        self.pipeline = AsyncSpanPipeline(
            encode_fn=self._encode,
            stage_fn=self._h2d,
            dispatch_fn=self._dispatch,
            readback_fn=self._readback,
            coalesce_fn=self._coalesce,
            records_fn=self._records,
            depth=depth,
            coalesce_records=coalesce_records,
            readback_workers=readback_workers,
            counters=counters, clock=clock, instrument=instrument,
            paused=paused, name="device-span",
            # failure containment: a failed/hung device attempt re-sorts
            # through the numpy twin of _fused_pipeline (bit-exact)
            failover_fn=self._host_failover if contain_failures else None,
            breaker=breaker,
            watchdog_dispatch_ms=watchdog_dispatch_ms,
            watchdog_readback_ms=watchdog_readback_ms)

    def submit(self, span_id, lanes, lengths, vals, key_mat, hash_lengths,
               coalesce: bool = True) -> None:
        self.pipeline.submit(span_id, {
            "lanes": lanes, "lengths": lengths, "vals": vals,
            "key_mat": key_mat, "hash_lengths": hash_lengths,
        }, coalesce=coalesce)

    def submit_ragged(self, span_id, key_bytes, key_offsets, val_bytes,
                      val_width: int, coalesce: bool = True) -> None:
        """Submit one span of ragged key bytes + fixed-width values.  The
        lane/hash-matrix encode runs on the staging thread (this is the
        overlapped host-encode stage); requires key_width > 0 at
        construction and every key to fit in it."""
        if self.key_width <= 0:
            raise ValueError("submit_ragged requires key_width > 0")
        self.pipeline.submit(span_id, {
            "key_bytes": key_bytes, "key_offsets": key_offsets,
            "val_bytes": val_bytes, "val_width": val_width,
        }, coalesce=coalesce)

    def resume(self) -> None:
        self.pipeline.resume()

    def results(self) -> Dict[Any, Tuple]:
        return self.pipeline.drain()

    # -- stages (staging thread / readback workers) -------------------------
    @staticmethod
    def _records(p: Dict) -> int:
        if "lanes" in p:
            return int(p["lanes"].shape[0])
        return len(p["key_offsets"]) - 1

    def _encode(self, p: Dict) -> Dict:
        if "key_bytes" in p:
            return self._encode_ragged(p)
        # raw-array producers arrive lane-encoded already; the encode stage
        # normalizes dtypes so coalesce/pad are pure concatenation
        return {
            "lanes": np.ascontiguousarray(p["lanes"], dtype=np.uint32),
            "lengths": np.asarray(p["lengths"], dtype=np.int64),
            "vals": np.ascontiguousarray(p["vals"]),
            "key_mat": np.ascontiguousarray(p["key_mat"], dtype=np.uint8),
            "hash_lengths": np.asarray(p["hash_lengths"], dtype=np.int32),
        }

    def _encode_ragged(self, p: Dict) -> Dict:
        from tez_tpu.ops.keycodec import matrix_to_lanes, pad_to_matrix
        kb, ko = p["key_bytes"], p["key_offsets"]
        n = len(ko) - 1
        mat, lengths = pad_to_matrix(kb, ko, self.key_width)
        lanes = matrix_to_lanes(mat)
        hash_w = 1 << max(2, (self.key_width - 1).bit_length())
        hmat, hlens = pad_to_matrix(kb, ko, hash_w)
        vals = np.ascontiguousarray(
            p["val_bytes"].reshape(n, p["val_width"])).view(np.uint32)
        return {
            "lanes": lanes, "lengths": lengths.astype(np.int64),
            "vals": vals, "key_mat": hmat,
            "hash_lengths": hlens.astype(np.int32),
        }

    def _coalesce(self, staged: List[Dict]) -> Dict:
        # defer the merge: _h2d writes every span straight into the
        # bucketed staging buffers — one copy instead of concat-then-pad.
        # Coalesced spans must share lane/hash/value widths (the ragged
        # path guarantees it; mismatched pre-encoded spans fail loudly on
        # assignment).
        return {"_spans": staged}

    def _bucketize(self, s: Dict) -> Dict:
        """Host-side half of H2D staging: merge the (possibly coalesced)
        spans into bucket-padded numpy buffers with the device kernels' tail
        sentinels.  Shared by the device upload (_h2d) and the host failover
        twin (_host_failover) so padding semantics can never diverge."""
        spans = s["_spans"] if "_spans" in s else [s]
        first = spans[0]
        nlanes = first["lanes"].shape[1]
        width_cap = nlanes * 4 + 1
        n = sum(int(sp["lanes"].shape[0]) for sp in spans)
        nb = _bucket(n)
        # bucketed staging buffers pre-filled with the tail sentinels
        lanes = np.full((nb, nlanes), np.uint32(0xFFFFFFFF), dtype=np.uint32)
        key_mat = np.full((nb, first["key_mat"].shape[1]), 255,
                          dtype=np.uint8)
        hash_lengths = np.full(nb, -1, dtype=np.int32)
        lengths = np.full(nb, width_cap, dtype=np.int64)
        vals = np.zeros((nb,) + first["vals"].shape[1:],
                        dtype=first["vals"].dtype)
        off = 0
        for sp in spans:
            m = int(sp["lanes"].shape[0])
            lanes[off:off + m] = sp["lanes"]
            key_mat[off:off + m] = sp["key_mat"]
            hash_lengths[off:off + m] = sp["hash_lengths"]
            lengths[off:off + m] = sp["lengths"]
            vals[off:off + m] = sp["vals"]
            off += m
        uniform = n == 0 or \
            uniform_clamped_lengths(lengths[:n], width_cap)[0]
        slen = np.minimum(lengths, width_cap).astype(np.uint32)
        return {
            "key_mat": key_mat, "hash_lengths": hash_lengths,
            "lanes": lanes, "sort_lengths": slen, "vals": vals,
            "uniform": uniform, "n": n,
        }

    def _h2d(self, s: Dict) -> Dict:
        h = self._bucketize(s)
        return {
            "key_mat": jnp.asarray(h["key_mat"]),
            "hash_lengths": jnp.asarray(h["hash_lengths"], dtype=jnp.int32),
            "lanes": jnp.asarray(h["lanes"]),
            "sort_lengths": jnp.asarray(h["sort_lengths"]),
            "vals": jnp.asarray(h["vals"]),
            "uniform": h["uniform"], "n": h["n"],
        }

    def _dispatch(self, s: Dict):
        out = _fused_pipeline_donated()(
            s["key_mat"], s["hash_lengths"], s["lanes"], s["sort_lengths"],
            s["vals"], num_partitions=self.num_partitions,
            skip_length_pass=s["uniform"])
        return out + (s["n"],)

    def _readback(self, inflight, ids):
        sp, out_lanes, out_vals, perm, counts, n = inflight
        return (np.asarray(sp), np.asarray(out_lanes), np.asarray(out_vals),
                np.asarray(perm), np.asarray(counts), n)

    # -- failure containment -------------------------------------------------
    def _host_failover(self, ids, payloads) -> Tuple:
        """Numpy twin of _fused_pipeline over the RAW payloads: the same
        bucketed staging buffers, FNV hash-partition (padding rows carry
        partition INT32_MAX like _hash_to_partitions), stable
        (partition, lanes, length) sort, gather, and searchsorted counts —
        bit-exact with the device result, never touches the device."""
        from tez_tpu.ops.host_sort import host_hash_partition, host_sort_run
        staged = [self._encode(p) for p in payloads]
        one = staged[0] if len(staged) == 1 else self._coalesce(staged)
        s = self._bucketize(one)
        n = s["n"]
        parts = np.full(s["key_mat"].shape[0],
                        np.iinfo(np.int32).max, dtype=np.int32)
        if n > 0:
            parts[:n] = host_hash_partition(
                s["key_mat"][:n], s["hash_lengths"][:n], self.num_partitions)
        sp, perm = host_sort_run(parts, s["lanes"], s["sort_lengths"])
        sp32 = sp.astype(np.int32)
        bounds = np.searchsorted(
            sp32, np.arange(self.num_partitions + 1, dtype=np.int32))
        counts = (bounds[1:] - bounds[:-1]).astype(np.int32)
        return (sp32, s["lanes"][perm], s["vals"][perm],
                perm.astype(np.int32), counts, n)
