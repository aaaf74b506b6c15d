"""Distributed scatter-gather shuffle: the ICI all-to-all data plane.

This is the multi-chip replacement for the reference's fetch-based shuffle
(ShuffleHandler + Fetcher, SURVEY.md §2.10): instead of N^2 HTTP fetches, the
whole exchange is ONE jitted SPMD program over a device mesh —

    per worker:  hash-partition -> local segmented sort ->
    all-to-all over ICI          (partition p's rows land on worker p) ->
    local k-way merge (stable sort of concatenation)

Row payload is fully general KV: `lanes` carry the key bytes as big-endian
u32 words (keycodec packing, so lane order == byte order), `lengths` the
true key length (the tie-break that makes zero-padded short keys sort
exactly like raw bytes: "ab" < "ab\\x00"), and `values` V u32 words per row
(fixed-width value slots; the mesh edge layer enforces the width).

Everything is static-shape: each worker holds up to N rows (padding rows
carry partition = P_MAX so they sort to the tail and exchange as slack), and
the all-to-all moves a fixed [W, CAP] send buffer per worker — the padded
formulation of a ragged all-to-all.  Skew beyond CAP is handled above this
kernel: the mesh exchange coordinator sizes CAP from exact partition counts
and falls back to a multi-round exchange when one round would exceed the
device budget (SURVEY.md §5.7), with fair-shuffle splitting for persistent
skew.

Two first-class engines share the choreography (docs/exchange.md):

- ``padded`` — the portable default: a fixed [W, CAP] send buffer per
  worker moves over ``jax.lax.all_to_all``; padding slots cross ICI as
  slack.
- ``ragged`` — ``jax.lax.ragged_all_to_all``: only real rows cross ICI.
  TPU-only today (XLA:CPU lacks the thunk); ``probe_ragged_support``
  detects availability at runtime and ``resolve_engine`` maps the
  ``tez.runtime.mesh.exchange.engine`` knob (auto|padded|ragged) onto a
  bit-exact choice for this backend.

Routing is normally the on-device FNV-1a of each key, but callers may pass
EXPLICIT per-row destinations (``explicit_dests=True``): the coordinator
already computes the exact host-side histogram with the same hash, and
explicit routing is what lets it re-partition persistently hot keys across
sub-partitions (fair-shuffle splitter) and send coded duplicate rows to a
rotation-offset buddy device (Coded TeraSort r2) — neither destination is
derivable from the key alone.
"""
from __future__ import annotations

import functools
import logging
import threading
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tez_tpu.ops import compile_cache  # noqa: F401 — places the cache
from tez_tpu.parallel.mesh import WORKER_AXIS

log = logging.getLogger(__name__)

#: a numpy scalar: a jnp one would initialise the backend at import
INVALID = np.uint32(0xFFFFFFFF)

EXCHANGE_ENGINES = ("auto", "padded", "ragged")


def _fnv_lanes(lanes: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """FNV-1a over each row's key BYTES (big-endian expansion of the lanes,
    truncated to the true length) — the distributed kernel's partitioner,
    byte-for-byte the same hash a host HashPartitioner computes over the
    raw key, so mesh and host shuffles route identically."""
    n, num_lanes = lanes.shape
    h = jnp.full((n,), 2166136261, dtype=jnp.uint32)
    for i in range(num_lanes):
        word = lanes[:, i]
        for shift in (24, 16, 8, 0):
            byte_index = i * 4 + (3 - shift // 8)
            byte = (word >> shift) & jnp.uint32(0xFF)
            live = byte_index < lengths
            h = jnp.where(
                live, ((h ^ byte) * jnp.uint32(16777619)).astype(jnp.uint32),
                h)
    return h


def _stable_sort_rows(keys_cols, payload_cols):
    """Stable lexicographic sort by `keys_cols` (list of u32[N] arrays),
    implemented as LSD passes of single-key sorts (same trick as
    ops.device.sort_run: cheap to compile, fast on TPU)."""
    n = keys_cols[0].shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    for col in reversed(keys_cols):
        gathered = col[perm]
        _, perm = jax.lax.sort((gathered, perm), dimension=0, is_stable=True,
                               num_keys=1)
    return [c[perm] for c in keys_cols], [p[perm] for p in payload_cols], perm


def _partition_sort(lanes, lengths, values, valid, num_workers, dests=None):
    """Shared prologue: partition + stable local sort by
    (partition, key lanes, key length); invalid rows carry partition ==
    num_workers so they sort to the tail.  ``dests`` (u32[N], < num_workers)
    overrides the on-device hash routing with explicit destinations —
    the splitter/coded seam (module docstring)."""
    n, num_lanes = lanes.shape
    if dests is None:
        route = _fnv_lanes(lanes, lengths) % num_workers
    else:
        # clamp defensively: an out-of-range dest must never index past the
        # send buffer (the coordinator always passes values < num_workers)
        route = jnp.minimum(dests.astype(jnp.uint32),
                            jnp.uint32(num_workers - 1))
    part = jnp.where(valid, route, jnp.uint32(num_workers))
    key_cols = [part.astype(jnp.uint32)] + \
        [lanes[:, i] for i in range(num_lanes)] + [lengths.astype(jnp.uint32)]
    sorted_keys, sorted_payload, _ = _stable_sort_rows(
        key_cols, [values, valid.astype(jnp.uint32)])
    spart = sorted_keys[0]
    slanes = jnp.stack(sorted_keys[1:1 + num_lanes], axis=1) if num_lanes \
        else jnp.zeros((n, 0), jnp.uint32)
    slengths = sorted_keys[-1]
    svalues, svalid = sorted_payload
    return spart, slanes, slengths, svalues, svalid


def _merge_received(rlanes, rlengths, rvals, rvalid):
    """Shared epilogue: stable sort of the received concatenation by
    (key lanes, key length), validity-major (invalid rows to the tail)."""
    num_lanes = rlanes.shape[1]
    key_cols = [jnp.where(rvalid > 0, jnp.uint32(0), jnp.uint32(1))] + \
        [rlanes[:, i] for i in range(num_lanes)] + \
        [rlengths.astype(jnp.uint32)]
    sorted_keys, sorted_payload, _ = _stable_sort_rows(
        key_cols, [rvals, rvalid])
    out_lanes = jnp.stack(sorted_keys[1:1 + num_lanes], axis=1) \
        if num_lanes else rlanes
    out_lengths = sorted_keys[-1]
    out_vals, out_valid = sorted_payload
    return out_lanes, out_lengths, out_vals, out_valid


def _shuffle_step_local(lanes: jnp.ndarray, lengths: jnp.ndarray,
                        values: jnp.ndarray, valid: jnp.ndarray,
                        dests: jnp.ndarray = None,
                        *, num_workers: int,
                        cap: int) -> Tuple[jnp.ndarray, ...]:
    """Per-worker body run under shard_map.  lanes: u32[N, L]; lengths:
    u32[N]; values: u32[N, V]; valid: bool[N]; dests: optional u32[N]
    explicit routing.  Returns (lanes', lengths', values', valid', dropped)
    holding this worker's partition, key-sorted, padded to [W*cap], plus a
    per-worker count of rows lost to capacity overflow (must be zero)."""
    n, num_lanes = lanes.shape
    num_vwords = values.shape[1]
    spart, slanes, slengths, svalues, svalid = _partition_sort(
        lanes, lengths, values, valid, num_workers, dests)

    # scatter rows into the fixed [W, cap] send buffer: row i of partition p
    # goes to slot (p, rank_within_partition(i))
    ranks = jnp.arange(n, dtype=jnp.int32) - \
        jnp.searchsorted(spart, spart, side="left").astype(jnp.int32)
    in_range = (spart < num_workers) & (ranks < cap) & (svalid > 0)
    # out-of-range rows scatter to a sacrificial trailing slot (sliced off)
    # so they can never clobber slot 0
    dump = num_workers * cap
    flat_slot = jnp.where(in_range, spart.astype(jnp.int32) * cap + ranks,
                          dump)

    send_lanes = jnp.full((dump + 1, num_lanes), INVALID, dtype=jnp.uint32)
    send_lengths = jnp.zeros((dump + 1,), dtype=jnp.uint32)
    send_vals = jnp.zeros((dump + 1, num_vwords), dtype=jnp.uint32)
    send_valid = jnp.zeros((dump + 1,), dtype=jnp.uint32)
    send_lanes = send_lanes.at[flat_slot].set(slanes)
    send_lengths = send_lengths.at[flat_slot].set(slengths)
    send_vals = send_vals.at[flat_slot].set(svalues)
    send_valid = send_valid.at[flat_slot].set(jnp.uint32(1))

    # ICI all-to-all: block w of my send buffer -> worker w
    recv_lanes = jax.lax.all_to_all(
        send_lanes[:dump].reshape(num_workers, cap, num_lanes),
        WORKER_AXIS, 0, 0, tiled=False)
    recv_lengths = jax.lax.all_to_all(
        send_lengths[:dump].reshape(num_workers, cap),
        WORKER_AXIS, 0, 0, tiled=False)
    recv_vals = jax.lax.all_to_all(
        send_vals[:dump].reshape(num_workers, cap, num_vwords),
        WORKER_AXIS, 0, 0, tiled=False)
    recv_valid = jax.lax.all_to_all(
        send_valid[:dump].reshape(num_workers, cap),
        WORKER_AXIS, 0, 0, tiled=False)

    # local merge: stable sort of the received concatenation by key lanes
    # (invalid rows carry INVALID lanes -> tail)
    m = num_workers * cap
    out_lanes, out_lengths, out_vals, out_valid = _merge_received(
        recv_lanes.reshape(m, num_lanes), recv_lengths.reshape(m),
        recv_vals.reshape(m, num_vwords), recv_valid.reshape(m))
    # overflow signal: valid rows this worker could NOT send (rank >= cap).
    # Zero in correct operation; the caller MUST check it — capacity
    # overflow otherwise means silent data loss (the coordinator re-runs
    # with more rounds or splits the partition).
    dropped = jnp.sum((svalid > 0) & ~in_range).astype(jnp.int32)
    return out_lanes, out_lengths, out_vals, out_valid.astype(jnp.bool_), \
        dropped[None]


def _shuffle_step_local_ragged(lanes: jnp.ndarray, lengths: jnp.ndarray,
                               values: jnp.ndarray, valid: jnp.ndarray,
                               dests: jnp.ndarray = None,
                               *, num_workers: int,
                               out_cap: int) -> Tuple[jnp.ndarray, ...]:
    """Ragged variant: only real rows cross ICI (jax.lax.ragged_all_to_all).

    Offsets choreography: senders lay rows out destination-contiguously
    (the partition sort), sizes are exchanged with a [W]-int all_to_all,
    receivers compute exclusive output offsets and send them BACK so each
    sender knows where its block lands.  TPU-only today (XLA:CPU lacks the
    ragged-all-to-all thunk), so the padded formulation stays the portable
    default.
    """
    n, num_lanes = lanes.shape
    num_vwords = values.shape[1]
    spart, slanes, slengths, svalues, _ = _partition_sort(
        lanes, lengths, values, valid, num_workers, dests)

    raw_sizes = jnp.bincount(
        jnp.minimum(spart, num_workers).astype(jnp.int32),
        length=num_workers + 1)[:num_workers].astype(jnp.int32)
    input_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(raw_sizes)[:-1].astype(jnp.int32)])
    raw_recv = jax.lax.all_to_all(
        raw_sizes.reshape(num_workers, 1), WORKER_AXIS, 0, 0
    ).reshape(num_workers).astype(jnp.int32)
    excl = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(raw_recv)[:-1].astype(jnp.int32)])
    output_offsets = jax.lax.all_to_all(
        excl.reshape(num_workers, 1), WORKER_AXIS, 0, 0
    ).reshape(num_workers).astype(jnp.int32)
    # Sender-side overflow clamp: never write past the receiver's out_cap.
    # Each sender keeps the prefix of its block that fits below the cap
    # (offsets are the unclamped cumulative, so prefixes tile exactly);
    # clamped counts are re-exchanged so recv_sizes matches what is sent.
    send_sizes = jnp.clip(out_cap - output_offsets, 0, raw_sizes)
    recv_sizes = jax.lax.all_to_all(
        send_sizes.reshape(num_workers, 1), WORKER_AXIS, 0, 0
    ).reshape(num_workers).astype(jnp.int32)

    out_lanes = jnp.full((out_cap, num_lanes), INVALID, dtype=jnp.uint32)
    out_lengths = jnp.zeros((out_cap,), dtype=jnp.uint32)
    out_vals = jnp.zeros((out_cap, num_vwords), dtype=jnp.uint32)
    out_lanes = jax.lax.ragged_all_to_all(
        slanes, out_lanes, input_offsets, send_sizes, output_offsets,
        recv_sizes, axis_name=WORKER_AXIS)
    out_lengths = jax.lax.ragged_all_to_all(
        slengths, out_lengths, input_offsets, send_sizes, output_offsets,
        recv_sizes, axis_name=WORKER_AXIS)
    out_vals = jax.lax.ragged_all_to_all(
        svalues, out_vals, input_offsets, send_sizes, output_offsets,
        recv_sizes, axis_name=WORKER_AXIS)
    n_recv = jnp.sum(recv_sizes)
    rvalid = (jnp.arange(out_cap) < n_recv).astype(jnp.uint32)

    final_lanes, final_lengths, final_vals, final_valid = _merge_received(
        out_lanes, out_lengths, out_vals, rvalid)
    # overflow signal: rows this worker could not SEND (receiver cap hit)
    dropped = jnp.sum(raw_sizes - send_sizes).astype(jnp.int32)
    return final_lanes, final_lengths, final_vals, \
        final_valid.astype(jnp.bool_), dropped[None]


def build_distributed_shuffle(mesh, num_lanes: int, rows_per_worker: int,
                              cap_per_pair: int, value_words: int = 1,
                              ragged: bool = False,
                              explicit_dests: bool = False):
    """Compile the SPMD shuffle step for a mesh.  Returns a jitted function
    f(lanes u32[W*N, L], lengths u32[W*N], values u32[W*N, V],
      valid bool[W*N][, dests u32[W*N]]) -> per-worker sorted partitions,
    sharded over the mesh.  ``explicit_dests`` adds the dests input and
    routes by it instead of the on-device key hash (coordinator splitter /
    coded-buddy seam)."""
    num_workers = mesh.devices.size

    if ragged:
        body = functools.partial(_shuffle_step_local_ragged,
                                 num_workers=num_workers,
                                 out_cap=num_workers * cap_per_pair)
    else:
        body = functools.partial(_shuffle_step_local,
                                 num_workers=num_workers, cap=cap_per_pair)
    n_in = 5 if explicit_dests else 4
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=tuple(P(WORKER_AXIS) for _ in range(n_in)),
        out_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS),
                   P(WORKER_AXIS), P(WORKER_AXIS)),
        check_vma=False)
    return jax.jit(smapped)


# ---------------------------------------------------------------------------
# Engine selection: capability probe + knob resolution
# ---------------------------------------------------------------------------

_probe_lock = threading.Lock()
_RAGGED_PROBE: Dict[Tuple[int, str], Tuple[bool, str]] = {}


def _ragged_unsupported_reason(e: BaseException, platform: str) -> str:
    """Classify a probe failure as 'backend lacks it' vs a real bug; the
    same triage the guarded parity test used before the probe existed."""
    if "UNIMPLEMENTED" in str(e) or isinstance(e, NotImplementedError):
        return (f"{platform} backend lacks the ragged-all-to-all thunk "
                f"({type(e).__name__})")
    raise e


def probe_ragged_support(mesh) -> Tuple[bool, str]:
    """(supported, reason) for ``jax.lax.ragged_all_to_all`` on this mesh's
    backend — compiled AND executed once on a tiny shape, cached per
    (device count, platform).  A probe failure that is not the known
    missing-thunk signature re-raises: masking a real compile bug as
    'unsupported' would silently pin every exchange to the padded engine."""
    platform = mesh.devices.flat[0].platform
    key = (mesh.devices.size, platform)
    with _probe_lock:
        cached = _RAGGED_PROBE.get(key)
    if cached is not None:
        return cached
    W = mesh.devices.size
    try:
        fn = build_distributed_shuffle(mesh, 1, 1, 1, value_words=1,
                                       ragged=True)
        jax.device_get(fn(np.zeros((W, 1), np.uint32),
                          np.ones(W, np.uint32),
                          np.zeros((W, 1), np.uint32),
                          np.ones(W, bool)))
        result = (True, f"ragged_all_to_all available on {platform}")
    except Exception as e:  # noqa: BLE001 — classified, re-raised if real
        result = (False, _ragged_unsupported_reason(e, platform))
    with _probe_lock:
        _RAGGED_PROBE[key] = result
    return result


def resolve_engine(requested: str, mesh) -> Tuple[str, str]:
    """Map the ``tez.runtime.mesh.exchange.engine`` knob onto the engine
    this backend can actually run, bit-exact either way.  Returns
    (engine, reason): 'auto' takes ragged when the probe passes; an
    explicit 'ragged' on a backend without it falls back to padded with a
    loud warning (never an error — the padded formulation computes the
    identical result)."""
    if requested not in EXCHANGE_ENGINES:
        raise ValueError(
            f"tez.runtime.mesh.exchange.engine={requested!r}: expected one "
            f"of {'|'.join(EXCHANGE_ENGINES)}")
    if requested == "padded":
        return "padded", "engine=padded requested"
    ok, reason = probe_ragged_support(mesh)
    if ok:
        return "ragged", reason
    if requested == "ragged":
        log.warning("mesh exchange: engine=ragged requested but %s; "
                    "falling back to the bit-exact padded engine", reason)
    return "padded", reason


def fnv_bytes_host(key: bytes) -> int:
    """Host reference of the kernel's byte-wise FNV-1a partitioner."""
    h = 2166136261
    for b in key:
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


def distributed_shuffle_reference(lanes: np.ndarray, lengths: np.ndarray,
                                  values: np.ndarray, valid: np.ndarray,
                                  num_workers: int) -> list:
    """Host golden: what each worker should hold after the exchange."""

    def row_key_bytes(i: int) -> bytes:
        raw = b"".join(int(w).to_bytes(4, "big") for w in lanes[i])
        return raw[: int(lengths[i])]

    out = [[] for _ in range(num_workers)]
    for i in range(len(valid)):
        if not valid[i]:
            continue
        kb = row_key_bytes(i)
        w = fnv_bytes_host(kb) % num_workers
        out[w].append((tuple(lanes[i].tolist()), int(lengths[i]),
                       tuple(np.atleast_1d(values[i]).tolist())))
    for part in out:
        part.sort(key=lambda t: (t[0], t[1]))
    return out
