"""Mesh exchange coordinator: SCATTER_GATHER edges over ICI collectives.

This is the framework seam that turns a DAG edge into ONE SPMD program
(reference roles replaced: ShuffleHandler.java:159 server + Fetcher.java:79
clients + MergeManager's final merge all collapse into the jitted
all-to-all exchange of parallel/exchange.py).  Producer tasks register
their encoded spans; when the last producer lands, the coordinator sizes
the exchange from EXACT per-partition counts (so the padded kernel can
never overflow), runs it over the device mesh — multi-round when one round
would exceed the per-device row budget (SURVEY.md §5.7 multi-pass analog)
— and consumer tasks block on their sorted partition.

The exchange plane is skew- and straggler-aware:

* Round sizing comes from the per-(sender, partition) histogram, not the
  global max: each destination's round rows are balanced across senders in
  contiguous arrival-order chunks, so the per-pair CAP shrinks by up to D×
  versus the padded worst case (``plan_rounds``; ``legacy_sizing=True``
  keeps the old formulation: the tests' and chaos' golden reference).
* The fair-shuffle splitter is folded in: an edge that keeps arriving with
  one partition over ``max_rows_per_round`` (``split.after`` consecutive
  exchanges, tracked across recurring DAG runs by edge suffix) gets its hot
  partitions re-partitioned across d sub-destinations, with a merge-side
  recombine by the true consumer hash — instead of re-rounding forever.
* Coded r2 mode (Coded TeraSort-style) duplicates every row to its
  destination's rotation buddy and takes the FIRST complete copy at
  readback, masking one slow or faulted chip at 2x send flops.  The
  ``mesh.exchange.delay`` fault point fires per (round, device) on the
  readback threads so chaos can prove the masking.

The host touches every row three times, each time in one native pass
(``native/ragged.cpp``, GIL released) where the rows already are: a
producer encodes its own batch at registration, the executing thread
places a round straight from the producers' spans into pooled blocks, and
each reader thread decodes the shard it has just read.  What stays on the
executing thread is arithmetic on histograms (docs/exchange.md "Row
passes").  The coded r2 edge and legacy sizing keep the numpy placement.

Single-controller topology: every runner in this process shares one
coordinator (the analog of local_shuffle_service); a multi-host deployment
runs one coordinator per host participating in a global jax mesh, with the
same register/wait surface.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from tez_tpu.common import faults, tracing
from tez_tpu.common.counters import MESH_EXCHANGE_GROUP
from tez_tpu.obs import flight as _flight
from tez_tpu.ops import hostpool
from tez_tpu.ops.native import (exchange_decode_native,
                                exchange_dest_hist_native,
                                exchange_encode_native,
                                exchange_place_native,
                                fnv32_partition_native)
from tez_tpu.ops.runformat import KVBatch

log = logging.getLogger(__name__)


class MeshCapacityError(RuntimeError):
    """A single partition exceeds what the mesh exchange can carry even
    multi-round; callers fall back to the fair-shuffle split path."""


def _decode_shard(lanes: np.ndarray, lengths: np.ndarray, values: np.ndarray,
                  keep: np.ndarray,
                  value_words: Optional[int] = None) -> KVBatch:
    """The kept rows of an exchange output shard -> KVBatch, in one native
    pass (lengths to offsets, then bytes); ``value_words`` as
    ``exchange_decode_native`` takes it."""
    return KVBatch(*exchange_decode_native(lanes, lengths, values, keep,
                                           value_words))


class _EdgeState:
    def __init__(self, num_producers: int, num_consumers: int,
                 edge_id: str = ""):
        self.edge_id = edge_id
        self.num_producers = num_producers
        self.num_consumers = num_consumers
        self.max_rows_per_round: Optional[int] = None   # per-edge conf
        self.engine: Optional[str] = None     # auto|padded|ragged (per-edge)
        self.coded: Optional[str] = None      # off|r2 (per-edge)
        self.split_after: Optional[int] = None
        self.counters = None                  # triggering producer's sink
        #: producer -> (key lanes, key lengths, value words, consumer
        #: partition of each row: hash % num_consumers, routed by the producer)
        self.spans: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray,
                                    np.ndarray]] = {}
        #: producer -> (time.time() its rows were in, its trace context),
        #: kept while the span plane is armed: what the
        #: exchange.wait_peers spans are made from
        self.arrived: Dict[int, Tuple[float, object, str]] = {}
        #: tracing.here() of the thread that ran the exchange, as it made
        #: the results: what the consumers' ``shuffle.wait`` is ``after``
        self.done_by = ""
        self.results: Optional[List[KVBatch]] = None
        self.error: Optional[BaseException] = None
        self.executing = False     # an _execute is in flight on some thread
        self.dirty = False         # spans changed while executing: re-run


def plan_rounds(counts: np.ndarray, per_round: int, num_devices: int,
                legacy: bool = False) -> List[Tuple[np.ndarray, int]]:
    """Round plan for an exchange with per-destination row ``counts``:
    a list of (quota, cap) where quota[d] is destination d's rows in that
    round and cap the per-(sender, dest) slot count the kernel compiles
    with.  Round r carries each destination's arrival ranks
    [r*per_round, (r+1)*per_round), so quota = clip(counts - r*per_round,
    0, per_round) and no quota ever exceeds the device budget.

    Legacy sizing pads every pair to the round's largest partition — any
    one sender COULD hold a whole destination's rows.  Histogram sizing
    instead balances each destination's quota across all D senders in
    contiguous chunks (the coordinator owns placement, so it can promise
    this), shrinking cap to ceil(quota.max()/D): up to D× less padded ICI
    traffic under skew.  Power-of-two bucketing keeps compile keys stable.
    """
    from tez_tpu.ops.device import _bucket
    counts = np.asarray(counts, dtype=np.int64)
    max_part = int(counts.max()) if counts.size else 0
    if max_part == 0:
        return []          # nothing to send: no rounds at all
    rounds = -(-max_part // per_round)
    plan: List[Tuple[np.ndarray, int]] = []
    for r in range(rounds):
        quota = np.clip(counts - r * per_round, 0, per_round)
        if legacy:
            cap = min(_bucket(min(max_part, per_round)), per_round)
        else:
            chunk = max(1, -(-int(quota.max()) // num_devices))
            cap = min(_bucket(chunk), per_round)
        plan.append((quota, cap))
    return plan


def arrival_ranks(group: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Each row's rank among the rows of its group, in arrival order;
    ``counts`` is the groups' histogram.  Callers pass ``group`` in the
    narrowest unsigned dtype that holds it: numpy's stable argsort is a
    radix sort up to 16 bits and a merge sort of whole keys beyond."""
    within = np.arange(group.size, dtype=np.int64)
    within -= np.repeat(np.cumsum(counts) - counts, counts)
    ranks = np.empty(group.size, dtype=np.int64)
    ranks[np.argsort(group, kind="stable")] = within
    return ranks


#: chunks the native row passes cut an edge's rows into: a thread each
PLACE_CHUNKS = 8


def _row_chunks(spans) -> Tuple[List[Tuple[np.ndarray, ...]], np.ndarray]:
    """The producers' rows where they lie, cut into about ``PLACE_CHUNKS``
    runs of consecutive rows, none across two producers: (chunks, bounds)
    with chunks[t] the (lanes, klens, vwords) views of rows [bounds[t],
    bounds[t + 1]) of the edge in arrival order (producer by producer)."""
    total = sum(s[0].shape[0] for s in spans)
    chunks: List[Tuple[np.ndarray, ...]] = []
    bounds = [0]
    for lanes, klens, vwords, _ in spans:
        n = lanes.shape[0]
        if n == 0:
            continue
        step = -(-n // max(1, round(PLACE_CHUNKS * n / total)))
        for a in range(0, n, step):
            b = min(n, a + step)
            chunks.append((lanes[a:b], klens[a:b], vwords[a:b]))
            bounds.append(bounds[-1] + b - a)
    return chunks, np.asarray(bounds, dtype=np.int64)


def _place_round_native(chunks, bounds: np.ndarray, rdest: np.ndarray,
                        hist: np.ndarray, lo: int, per_round: int,
                        quota: np.ndarray, num_lanes: int, value_words: int):
    """One round's device inputs by the native row pass: balanced blocked
    placement (destination d's round rows in <= D contiguous arrival-order
    runs, run j -> sender j), as ``_ReferencePlacement`` does it with
    index arrays.  Here the arithmetic is on ``hist`` (rows of each
    destination in each chunk) alone: it says where each chunk's rows of a
    destination begin in that destination's ranks, so how many of them each
    sender takes this round and where in a sender's block each chunk starts
    to fill; the pass then walks every chunk once with running counters.
    Returns ((r_lanes, r_klens, r_vwords, r_valid, r_dests), N)."""
    from tez_tpu.ops.device import _bucket
    D = hist.shape[1]
    rank_base = np.cumsum(hist, axis=0) - hist
    # a chunk's rows of destination d hold the round ranks [first, end)
    first = np.clip(rank_base - lo, 0, quota)
    end = np.clip(rank_base + hist - lo, 0, quota)
    chunk_d = np.maximum(1, -(-quota // D))
    # sender j takes d's round ranks [j * chunk_d[d], (j + 1) * chunk_d[d])
    edges = np.arange(D + 1) * chunk_d[:, None]
    per_sender = np.clip(
        np.minimum(end[:, :, None], edges[:, 1:]) -
        np.maximum(first[:, :, None], edges[:, :-1]), 0, None).sum(axis=1)
    loads = per_sender.sum(axis=0)
    N = _bucket(int(loads.max()))
    fill_base = np.cumsum(per_sender, axis=0) - per_sender
    return exchange_place_native(
        chunks, bounds, rdest, rank_base, fill_base, lo, per_round, chunk_d,
        loads, N, num_lanes, value_words), N


class _ReferencePlacement:
    """A round placed with numpy index arrays over one concatenation of
    the edge's rows: what the coded r2 edge (every row twice, a routing tag
    word) and legacy sizing (the tests' and chaos' golden reference) use."""

    def __init__(self, spans, rdest: np.ndarray, counts: np.ndarray,
                 num_lanes: int, value_words: int):
        def _rows(arrays: List[np.ndarray], width: int) -> np.ndarray:
            # narrow spans zero-padded to the edge's width; pooled memory
            return hostpool.concatenate(
                [(a if a.shape[1] == width else
                  np.pad(a, ((0, 0), (0, width - a.shape[1])))).reshape(-1)
                 for a in arrays]).reshape(-1, width)

        self.lanes = _rows([s[0] for s in spans], num_lanes)
        self.klens = hostpool.concatenate([s[1] for s in spans])
        self.vwords = _rows([s[2] for s in spans], value_words)
        self.rdest = rdest
        # rank of each row within its routing partition (arrival order)
        self.ranks = arrival_ranks(rdest, counts)

    def place_round(self, lo: int, per_round: int, quota: np.ndarray,
                    cap: int, coded: bool, legacy: bool):
        """-> ((r_lanes, r_klens, r_vwords, r_valid, r_dests), N, cap, the
        round's rows a destination)."""
        from tez_tpu.ops.device import _bucket
        D = quota.size
        rdest, ranks = self.rdest, self.ranks
        value_words = self.vwords.shape[1]
        sel = np.flatnonzero((ranks >= lo) & (ranks < lo + per_round))
        rows_idx = sel
        dests_all = rdest[sel]
        rtag = None
        if coded:
            # r2: every row ALSO goes to its destination's rotation
            # buddy.  An extra value word carries the routing partition
            # (same on both copies) so each shard can tell its primary
            # rows from buddy copies — not derivable from the key once
            # the splitter has re-routed rows.
            rows_idx = np.concatenate([sel, sel])
            rtag = np.concatenate([dests_all, dests_all]).astype(np.uint32)
            dests_all = np.concatenate([dests_all, (dests_all + 1) % D])
            # duplication doubled the quotas; re-derive the balanced
            # cap from the combined histogram (coded always uses
            # balanced placement — legacy tail-packing could put a
            # whole destination's copies on one sender)
            qc = np.bincount(dests_all, minlength=D)
            cap = min(_bucket(max(1, -(-int(qc.max()) // D))), per_round)
        else:
            qc = quota
        if coded or not legacy:
            # balanced blocked placement: destination d's rows split
            # into <= D contiguous arrival-order chunks, chunk j ->
            # sender j, so no (sender, dest) pair exceeds
            # ceil(quota_d / D) <= cap.  Contiguous chunks + the
            # receiver's stable sender-major merge preserve global
            # arrival order for equal keys.
            if coded:
                # both copies of a row, ranked within their destinations
                lrank = arrival_ranks(dests_all, qc)
            else:
                # a row's rank within the round is its rank within
                # its destination less the round's first
                lrank = ranks[sel] - lo
            chunk_d = np.maximum(1, -(-qc // D))
            senders = (lrank // chunk_d[dests_all]).astype(rdest.dtype)
            loads = np.bincount(senders, minlength=D)
            N = _bucket(int(loads.max()))
            pos = senders.astype(np.int64) * N + \
                arrival_ranks(senders, loads)
        else:
            # legacy layout: rows in arrival order, zero tail pad
            N = _bucket(-(-dests_all.size // D))
            pos = np.arange(dests_all.size, dtype=np.int64)
        vw = value_words + (1 if coded else 0)
        r_lanes = np.zeros((D * N, self.lanes.shape[1]), np.uint32)
        r_klens = np.zeros(D * N, np.uint32)
        r_vwords = np.zeros((D * N, vw), np.uint32)
        r_valid = np.zeros(D * N, bool)
        r_dests = np.zeros(D * N, np.uint32)
        r_lanes[pos] = self.lanes[rows_idx]
        r_klens[pos] = self.klens[rows_idx]
        r_vwords[pos, :value_words] = self.vwords[rows_idx]
        if coded:
            r_vwords[pos, value_words] = rtag
        r_valid[pos] = True
        r_dests[pos] = dests_all.astype(np.uint32)
        return (r_lanes, r_klens, r_vwords, r_valid, r_dests), N, cap, qc


class MeshExchangeCoordinator:
    """Per-process exchange coordinator (one per runner host)."""

    def __init__(self, mesh=None, max_rows_per_round: int = 1 << 20,
                 engine: str = "auto", legacy_sizing: bool = False,
                 split_after: int = 2):
        self._mesh = mesh
        self.max_rows_per_round = max_rows_per_round
        self.engine = engine            # default; per-edge conf overrides
        self.legacy_sizing = legacy_sizing   # golden reference: max-part CAP
        self.split_after = split_after  # 0 = splitter disabled
        self.lock = threading.Condition()
        self.edges: Dict[str, _EdgeState] = {}
        # compiled exchange programs keyed by (devices, shape...) — meshes
        # are cached per size below so these keys are stable across edges
        self._compiled: Dict[Tuple[int, int, int, int, int, bool], object] \
            = {}
        self._meshes: Dict[int, object] = {}
        self.exchanges_run = 0
        self.rows_exchanged = 0
        self.multi_round_exchanges = 0
        self.partition_splits = 0
        self.coded_buddy_wins = 0
        self.last_engine: Optional[str] = None
        #: lane -> jax device.id that held the newest exchange's output
        #: shard (chip_smoke.py prints it: proof of which chips took part)
        self.last_shard_devices: Dict[int, int] = {}
        #: cumulative rows landed per device lane (coded duplicates
        #: included — they occupy the lane), feeding the
        #: ``mesh.lane.<i>.*`` occupancy gauges via telemetry_collector
        self.lane_rows: Dict[int, int] = {}
        # consecutive over-budget streak per recurring edge (keyed by the
        # edge id MINUS the per-run dag prefix, so history survives re-runs)
        self._skew_history: Dict[str, int] = {}

    # ------------------------------------------------------------------ mesh
    def devices_for(self, num_consumers: int) -> int:
        """How many devices carry a W-consumer exchange: the largest device
        count d <= |devices| with W % d == 0.  d < W means each device
        carries W/d consumer partitions (routing hash%d is consistent with
        consumer partition hash%W exactly when d divides W), split apart on
        host after the exchange."""
        import jax
        avail = len(jax.devices())
        d = min(avail, num_consumers)
        while num_consumers % d != 0:
            d -= 1
        if d * 2 <= min(avail, num_consumers):
            # e.g. W=7 consumers on 4 devices -> d=1: the whole exchange
            # funnels through one device and splits on host.  Legal but
            # quietly wasteful — surface it so the operator can pick a
            # consumer count that divides (or is a multiple of) the mesh.
            log.warning(
                "mesh exchange: %d consumers on %d devices routes through "
                "only %d device(s) (largest divisor); consider a consumer "
                "parallelism divisible by the device count", num_consumers,
                avail, d)
        return d

    def mesh_for(self, num_devices: int):
        from tez_tpu.parallel.mesh import make_mesh
        if self._mesh is not None and \
                self._mesh.devices.size == num_devices:
            return self._mesh
        cached = self._meshes.get(num_devices)
        if cached is not None:
            return cached
        mesh = make_mesh(n_devices=num_devices)
        self._meshes[num_devices] = mesh
        return mesh

    # ------------------------------------------------------------- producers
    def register_producer(self, edge_id: str, task_index: int,
                          num_producers: int, num_consumers: int,
                          batch: KVBatch, key_width: int,
                          value_width: int,
                          max_rows_per_round: Optional[int] = None,
                          max_key_bytes: int = 256,
                          max_value_bytes: int = 1024,
                          engine: Optional[str] = None,
                          coded: Optional[str] = None,
                          split_after: Optional[int] = None,
                          counters=None) -> None:
        """Record one producer span (encoded).  The LAST registration runs
        the exchange inline on that producer's thread — the gang barrier:
        by then every producer's data is resident, which is exactly the
        gang-scheduling condition CONCURRENT edges declare.

        Widths AUTO-WIDEN to the span's actual max key/value (rounded to
        whole u32 lanes) up to the hard caps — the configured widths are
        slot-size hints, not limits (VERDICT r2 item 5; reference carries
        arbitrary KV, IFile.java:67).  Spans with different widths zero-pad
        to the edge max at exchange time (zero lanes == absent bytes, so
        ordering is unaffected).  Beyond the caps the record belongs on the
        host shuffle edge — HBM slots are per-row, so a single huge record
        would tax every row."""
        if len(batch.key_offsets) > 1:
            max_key = int(np.max(np.diff(batch.key_offsets)))
            if max_key > max_key_bytes:
                raise MeshCapacityError(
                    f"mesh edge carries keys up to "
                    f"tez.runtime.tpu.mesh.max.key.bytes={max_key_bytes}B, "
                    f"found {max_key}B; use the host shuffle edge for "
                    f"records this large")
            key_width = max(key_width, ((max_key + 3) // 4) * 4)
            max_val = int(np.max(np.diff(batch.val_offsets)))
            if max_val > max_value_bytes:
                raise MeshCapacityError(
                    f"mesh edge carries values up to "
                    f"tez.runtime.tpu.mesh.max.value.bytes="
                    f"{max_value_bytes}B, found {max_val}B; use the host "
                    f"shuffle edge for records this large")
            value_width = max(value_width, ((max_val + 3) // 4) * 4)
        with tracing.span("exchange.pack", cat="exchange", stage="producer",
                          rows=batch.num_records) as pack:
            # both native, GIL released, in this producer's thread while
            # slower producers still produce.  The rows: key lanes (the
            # zero-padded key as big-endian words), key lengths, value
            # words behind a first word that holds the value's length
            lanes, klens, vwords = exchange_encode_native(
                batch.key_bytes, batch.key_offsets, batch.val_bytes,
                batch.val_offsets, key_width, value_width)
            # the routing, once a row, where the raw key bytes are: the
            # consumer partition hash % W
            part = fnv32_partition_native(
                batch.key_bytes, batch.key_offsets, num_consumers).astype(
                    np.min_scalar_type(num_consumers))
        pack_id = pack.span_id
        with self.lock:
            st = self.edges.setdefault(
                edge_id, _EdgeState(num_producers, num_consumers, edge_id))
            if num_consumers != st.num_consumers:
                raise ValueError(
                    f"mesh edge {edge_id}: producer {task_index} routed its "
                    f"rows over {num_consumers} consumers, the edge has "
                    f"{st.num_consumers}")
            if max_rows_per_round:
                st.max_rows_per_round = int(max_rows_per_round)
            if engine:
                st.engine = engine
            if coded:
                st.coded = coded
            if split_after is not None:
                st.split_after = int(split_after)
            if counters is not None:
                st.counters = counters
            st.spans[task_index] = (lanes, klens, vwords, part)
            if tracing.armed():
                # here(): the encode of this producer's rows just above
                st.arrived[task_index] = (time.time(),
                                          tracing.current_context(),
                                          pack_id)
            if isinstance(st.error, TimeoutError):
                # a straggler poisoned the edge, and here it is: the edge
                # is viable again — consumer RETRIES must see a fresh
                # barrier, not the stale poison
                st.error = None
            if st.results is not None:
                # a producer RE-RAN after the exchange: invalidate and
                # re-exchange with the replacement span (consumers that
                # already read the old result fail on their
                # InputFailedEvent and re-run against the fresh one)
                log.warning("mesh edge %s: producer %d re-registered after "
                            "the exchange; re-running it", edge_id,
                            task_index)
                st.results = None
            if st.executing:
                st.dirty = True    # the in-flight run is stale; rerun after
                return
            ready = len(st.spans) >= st.num_producers
            if ready:
                st.executing = True
        if not ready:
            return
        while True:
            try:
                results = self._execute(st)
            except BaseException as e:  # noqa: BLE001 — consumers must wake
                with self.lock:
                    st.error = e
                    st.executing = False
                    self.lock.notify_all()
                raise
            with self.lock:
                if st.dirty:
                    st.dirty = False
                    continue           # spans changed mid-run: go again
                st.results = results
                st.done_by = tracing.here()
                st.error = None
                st.executing = False
                self.lock.notify_all()
                return

    # ------------------------------------------------------------- consumers
    def wait_consumer(self, edge_id: str, consumer_index: int,
                      num_producers: int, num_consumers: int,
                      timeout: Optional[float] = None,
                      progress=None) -> KVBatch:
        """Block until the edge's exchange lands.  `timeout` is the
        straggler defense for the gang barrier (VERDICT r3 item 7): a
        producer that never registers would otherwise stall every consumer
        forever.  On expiry the whole edge is POISONED (st.error) naming
        the missing producers, so sibling consumers fail fast instead of
        each burning its own full deadline — the actionable failure path;
        the AM's task retry / failure blame takes it from there (reference
        analog: the fetch penalty box + ShuffleScheduler.java:179
        too-long-stalled escape)."""
        import time
        deadline = None if timeout is None else time.monotonic() + timeout
        with self.lock:
            st = self.edges.setdefault(
                edge_id, _EdgeState(num_producers, num_consumers, edge_id))
            while st.results is None and st.error is None:
                # the deadline guards the PRODUCER barrier only: once every
                # span is in (or an exchange is in flight), a slow exchange
                # is compute, not a straggler — AM task-level failure
                # detection owns hung exchanges
                barrier_open = len(st.spans) < st.num_producers and \
                    not st.executing
                if deadline is not None and barrier_open and \
                        time.monotonic() > deadline:
                    missing = sorted(set(range(st.num_producers)) -
                                     set(st.spans))
                    err = TimeoutError(
                        f"mesh exchange {edge_id}: "
                        f"{len(st.spans)}/{st.num_producers} producers "
                        f"after {timeout:.0f}s; missing producer task "
                        f"indices {missing[:16]}"
                        f"{'...' if len(missing) > 16 else ''}")
                    # the edge cannot complete without the missing spans —
                    # poison it so sibling consumers fail fast
                    st.error = err
                    self.lock.notify_all()
                    raise err
                self.lock.wait(0.2)
                if progress is not None:
                    progress()
            if st.error is not None:
                raise RuntimeError(
                    f"mesh exchange {edge_id} failed") from st.error
            tracing.came_after(st.done_by)     # the caller's shuffle.wait
            return st.results[consumer_index]

    def cleanup_edge(self, edge_id: str) -> None:
        with self.lock:
            self.edges.pop(edge_id, None)

    def cleanup_dag(self, dag_id_prefix: str) -> int:
        """Deletion tracking (reference: DeletionTracker/DagDeleteRunnable):
        drop every edge of a finished DAG — spans and materialized results."""
        with self.lock:
            doomed = [e for e in self.edges if e.startswith(dag_id_prefix)]
            for e in doomed:
                del self.edges[e]
            return len(doomed)

    # -------------------------------------------------------------- exchange
    def _compiled_fn(self, mesh, num_lanes: int, rows_per_worker: int,
                     cap: int, value_words: int, ragged: bool = False):
        from tez_tpu.parallel.exchange import build_distributed_shuffle
        key = (mesh.devices.size, num_lanes, rows_per_worker, cap,
               value_words, ragged)
        fn = self._compiled.get(key)
        if fn is None:
            # always explicit_dests: the coordinator owns routing (splitter
            # re-targets, coded duplicates) — the kernel must not re-derive
            # destinations from the key hash
            fn = build_distributed_shuffle(mesh, num_lanes, rows_per_worker,
                                           cap, value_words=value_words,
                                           ragged=ragged,
                                           explicit_dests=True)
            self._compiled[key] = fn
        return fn

    def _read_shards(self, arrs, mesh, edge_id: str, round_idx: int,
                     decode=None, after: str = ""):
        """Materialize the exchange outputs one device at a time, each on
        its own daemon reader thread.  Every reader fires the
        ``mesh.exchange.delay`` fault point (detail
        ``<edge>:round=<r>:device=<d>``) before touching its shard — the
        chaos lever that turns one chip into a readback straggler, since
        the jitted SPMD body itself is not instrumentable.  With ``decode``
        the reader that has just materialized a shard also decodes it,
        under its own ``exchange.decode`` span, so the shards decode side
        by side and the executing thread only collects them.  Returns
        (events, results, any_done); results[d] becomes
        ``decode(lanes, klens, vwords, valid)`` of the device's shard (the
        tuple itself without ``decode``), or the exception its reader hit
        (a faulted chip), once events[d] is set.  ``after``: where the
        executing thread had got to as it started the readers (its wait for
        the round's dropped flag, after the launch), which their spans
        come after; ``done_ids[d]`` (a fourth result) is where reader d had
        got to as it set its event."""
        D = mesh.devices.size
        pos = {dev: i for i, dev in enumerate(mesh.devices.flat)}
        shard_maps = []
        for a in arrs:
            shard_maps.append(
                {pos[s.device]: s.data for s in a.addressable_shards})
        self.last_shard_devices = {
            pos[s.device]: s.device.id for s in arrs[0].addressable_shards}
        events = [threading.Event() for _ in range(D)]
        results: List[object] = [None] * D
        done_ids = [""] * D
        any_done = threading.Event()
        ctx = tracing.current_context()    # the executing producer's

        def _read(d: int) -> None:
            try:
                with tracing.span("exchange.readback", cat="exchange",
                                  parent=ctx, device=d, round=round_idx,
                                  after=after):
                    faults.fire(
                        "mesh.exchange.delay",
                        detail=f"{edge_id}:round={round_idx}:device={d}")
                    shard = tuple(np.asarray(m[d]) for m in shard_maps)
                if decode is not None:
                    with tracing.span("exchange.decode", cat="exchange",
                                      parent=ctx, device=d, round=round_idx):
                        shard = decode(*shard)
                results[d] = shard
            except BaseException as e:  # noqa: BLE001 — surfaced by reader
                results[d] = e
            finally:
                done_ids[d] = tracing.here()
                events[d].set()
                any_done.set()

        for d in range(D):
            # daemon: a delayed/hung reader is ABANDONED once its buddy's
            # copy wins — it must never pin process exit
            threading.Thread(target=_read, args=(d,), daemon=True,
                             name=f"mesh-exchange-read-{d}").start()
        return events, results, any_done, done_ids

    def _select_coded(self, events, results, any_done,
                      num_devices: int) -> Tuple[Dict[int, int], int]:
        """First-complete-copy selection for coded r2: partition p is
        served by whichever of (primary p, buddy (p+1)%D) materializes
        first; ties prefer the primary so buddy wins are a true straggler
        signal.  A reader that FAILED (fault, not delay) is skipped — the
        surviving copy masks faulted chips too; only both copies failing
        surfaces an error."""
        from tez_tpu.parallel.mesh import coded_buddy
        remaining = set(range(num_devices))
        chosen: Dict[int, int] = {}
        wins = 0
        while remaining:
            progressed = False
            for p in sorted(remaining):
                cands = (p, coded_buddy(p, num_devices))
                done = [d for d in cands if events[d].is_set() and
                        not isinstance(results[d], BaseException)]
                if done:
                    chosen[p] = done[0]
                    wins += int(done[0] != p)
                    remaining.discard(p)
                    progressed = True
                elif all(events[d].is_set() for d in cands):
                    err = next(results[d] for d in cands
                               if isinstance(results[d], BaseException))
                    raise RuntimeError(
                        f"mesh exchange: both copies of partition {p} "
                        f"failed under coded r2") from err
            if remaining and not progressed:
                any_done.wait(0.05)
                any_done.clear()
        return chosen, wins

    def _execute(self, st: _EdgeState) -> List[KVBatch]:
        """Run the SPMD exchange for a complete edge.  CAP comes from exact
        host-side partition counts (each producer routed its own rows with
        the native FNV == the kernel's partitioner; here they are only
        added up), so the padded all-to-all cannot overflow; when the
        biggest partition exceeds max_rows_per_round the exchange runs in
        rank-sliced rounds and each consumer's rounds merge at the end.
        See the module docstring for the skew levers layered on top
        (histogram round sizing, the splitter, coded r2)."""
        from tez_tpu.common import metrics
        from tez_tpu.ops.sorter import merge_sorted_runs
        from tez_tpu.ops.runformat import Run
        from tez_tpu.parallel.exchange import resolve_engine

        # host-level seam: the jitted SPMD body is not instrumentable, so
        # chaos hits the exchange at entry (the caller's error path turns
        # this into the edge-wide failure consumers see)
        faults.fire("mesh.exchange", detail=st.edge_id)
        if tracing.armed():
            # how long each producer's rows lay waiting for the slowest
            # peer: one span a producer, made now that the wait is over, on
            # a lane of its own (no thread waited: the rows did)
            with self.lock:
                arrived = dict(st.arrived)
            slowest = max(arrived.values(), key=lambda a: a[0],
                          default=(0.0, None, ""))[2]
            for producer, (t_in, ctx, _pack) in arrived.items():
                tracing.start_span(
                    "exchange.wait_peers", cat="exchange", parent=ctx,
                    lane=f"exchange.wait_peers#{st.edge_id}/{producer}",
                    start=t_in, producer=producer, after=slowest).finish()
        with tracing.span("exchange.plan", cat="exchange"):
            W = st.num_consumers
            D = self.devices_for(W)     # devices carrying the exchange; each
            mesh = self.mesh_for(D)     # holds W/D consumer partitions
            with self.lock:
                spans = [st.spans[i] for i in sorted(st.spans)]
            # widths: spans auto-widened independently — the narrow ones
            # are zero-padded to the edge's (zero lanes/words == absent
            # bytes; order unaffected) where a round is placed
            num_lanes = max((s[0].shape[1] for s in spans), default=1)
            value_words = max((s[2].shape[1] for s in spans), default=1)
            total = sum(s[0].shape[0] for s in spans)
            if total == 0:
                return [KVBatch.empty() for _ in range(W)]

            # exact routing: every producer hashed its own rows' raw key
            # bytes to the consumer partition hash % W at registration; the
            # plan only adds up.  Routing is hash % D; with D | W that equals
            # (hash % W) % D, so device d receives exactly the rows of
            # consumer partitions {c : c % D == d} (split apart after the
            # exchange).  Destinations stay in the narrowest dtype holding D
            # (a byte a row to concatenate, count and re-home).  The rows
            # themselves stay where the producers left them, cut into
            # chunks the native passes take a thread each; the plan works
            # on the chunks' histogram of destinations.
            rdest = np.concatenate([s[3] for s in spans])
            if W != D:
                rdest = (rdest % D).astype(np.min_scalar_type(D), copy=False)
            chunks, bounds = _row_chunks(spans)
            hist = exchange_dest_hist_native(rdest, bounds, D)
            counts = hist.sum(axis=0)
            per_round = st.max_rows_per_round or self.max_rows_per_round

            # ---- fair-shuffle splitter: an edge whose largest partition has
            # exceeded the round budget split_after times IN A ROW (recurring
            # runs share the id suffix; the dag prefix changes per run) gets
            # each hot destination re-partitioned across d_sub sub-destinations
            # in contiguous arrival blocks.  Routing stops being key-derivable
            # for those rows, but the CONSUMER identity (hash % W) still is —
            # the merge-side recombine below reassembles split partitions.
            skew_key = st.edge_id.split("/", 1)[-1] or st.edge_id
            over_budget = int(counts.max()) > per_round
            with self.lock:
                if over_budget:
                    streak = self._skew_history.get(skew_key, 0) + 1
                    self._skew_history[skew_key] = streak
                else:
                    self._skew_history.pop(skew_key, None)
                    streak = 0
            split_after = st.split_after if st.split_after is not None \
                else self.split_after
            splits = 0
            if over_budget and D > 1 and split_after > 0 and \
                    streak >= split_after:
                hot = np.flatnonzero(counts > per_round)
                load = counts.astype(np.int64).copy()
                load[hot] = per_round      # each hot dest keeps a full round
                # split every hot dest against the ORIGINAL routing snapshot:
                # rows an earlier split re-homed INTO d are not d's to re-split
                orig_rdest = rdest.copy()
                # biggest partition gets first pick of the headroom
                for d in hot[np.argsort(-counts[hot], kind="stable")]:
                    n_d = int(counts[d])
                    amounts = np.zeros(D, dtype=np.int64)
                    amounts[d] = per_round
                    remaining = n_d - per_round
                    # fill other destinations' headroom, least-loaded first:
                    # whenever the total fits in D*per_round at all, the
                    # exchange comes out single-round
                    for t in np.argsort(load, kind="stable"):
                        if remaining == 0:
                            break
                        if t == d or load[t] >= per_round:
                            continue
                        take = min(int(per_round - load[t]), remaining)
                        amounts[t] += take
                        load[t] += take
                        remaining -= take
                    if remaining:
                        # no headroom left: multi-round is inevitable; spread
                        # the rest evenly so no destination re-rounds alone
                        base, extra = divmod(remaining, D)
                        add = np.full(D, base, dtype=np.int64)
                        add[:extra] += 1
                        amounts += add
                        load += add
                    # carve d's arrival-ordered rows into contiguous blocks
                    # handed to destinations in ASCENDING device index: the
                    # consumer-side recombine merges runs in device order, so
                    # ascending blocks reconstruct arrival order exactly
                    # (deterministic equal-key ties, same as the unsplit path)
                    rows = np.flatnonzero(orig_rdest == d)  # ascending==arrival
                    rdest[rows] = np.repeat(np.arange(D), amounts)
                    splits += 1
                hist = exchange_dest_hist_native(rdest, bounds, D)
                counts = hist.sum(axis=0)
                with self.lock:
                    self.partition_splits += splits
                log.info("mesh exchange %s: splitter engaged after %d "
                         "over-budget exchange(s); %d hot partition(s) "
                         "re-partitioned", st.edge_id, streak, splits)

            engine, engine_reason = resolve_engine(st.engine or self.engine,
                                                   mesh)
            self.last_engine = engine
            log.debug("mesh exchange %s: engine=%s (%s)", st.edge_id, engine,
                      engine_reason)
            coded = (st.coded or "off") == "r2" and D > 1
            plan = plan_rounds(counts, per_round, D, legacy=self.legacy_sizing)
            _flight.record(_flight.EXCHANGE, "plan", st.edge_id,
                           a=len(plan), b=total)
            # who places a round: the native row pass, unless the edge is
            # coded (rows duplicated, a routing tag word) or sized the
            # legacy way (the golden reference) — those keep the numpy
            # placement, over one concatenation and one ranking of the rows
            native = not coded and not self.legacy_sizing
            if not native:
                reference = _ReferencePlacement(spans, rdest, counts,
                                                num_lanes, value_words)

        row_words = num_lanes + 1 + value_words   # lanes + klen + vwords
        sent_rows = native_rows = dup_rows = buddy_wins = rounds_run = 0
        lane_counts = np.zeros(D, dtype=np.int64)
        per_round_results: List[List[KVBatch]] = []
        for r, (quota, cap) in enumerate(plan):
            lo = r * per_round
            # the round carries each destination's arrival ranks
            # [lo, lo + per_round): its histogram is the plan's quota
            n_round = int(quota.sum())
            if n_round == 0:
                continue
            t_round = time.perf_counter()
            with tracing.span("exchange.pack", cat="exchange", round=r,
                              rows=n_round):
                if native:
                    device_inputs, N = _place_round_native(
                        chunks, bounds, rdest, hist, lo, per_round, quota,
                        num_lanes, value_words)
                    native_rows += n_round
                    lane_counts += quota
                else:
                    device_inputs, N, cap, qc = reference.place_round(
                        lo, per_round, quota, cap, coded,
                        self.legacy_sizing)
                    lane_counts += qc
                    if coded:
                        dup_rows += n_round
                vw = device_inputs[2].shape[1]
            with tracing.span("exchange.launch", cat="exchange", round=r,
                              rows=D * N):
                fn = self._compiled_fn(mesh, num_lanes, N, cap, vw,
                                       ragged=(engine == "ragged"))
                out_lanes, out_klens, out_vwords, out_valid, dropped = \
                    fn(*device_inputs)
            # the dropped flag is a tiny replicated array: reading it does
            # not serialize the per-device readback below (the delay fault
            # stalls our reader threads, not device compute) — but it does
            # wait for the program, so it is the first of the readback
            with tracing.span("exchange.readback", cat="exchange", round=r,
                              what="dropped"):
                dropped_total = int(np.asarray(dropped).sum())
            if dropped_total:
                raise MeshCapacityError(
                    f"mesh exchange overflow: {dropped_total} rows dropped "
                    f"(cap {cap}, round {r}) — capacity accounting bug")
            events, results, any_done, done_ids = self._read_shards(
                (out_lanes, out_klens, out_vwords, out_valid), mesh,
                st.edge_id, r, decode=None if coded else _decode_shard,
                after=tracing.here())
            round_parts: List[KVBatch] = []
            if coded:
                with tracing.span("exchange.readback", cat="exchange",
                                  round=r, what="first_copies"):
                    chosen, wins = self._select_coded(events, results,
                                                      any_done, D)
                buddy_wins += wins
                with tracing.span("exchange.decode", cat="exchange",
                                  round=r):
                    for p in range(D):
                        dl, dk, dv, dval = results[chosen[p]]
                        keep = dval.astype(bool) & \
                            (dv[:, value_words] == p)
                        round_parts.append(
                            _decode_shard(dl, dk, dv, keep, value_words))
            else:
                # every reader decodes the shard it read: collect them
                for d in range(D):
                    with tracing.span("exchange.readback", cat="exchange",
                                      round=r, what="shard", device=d):
                        events[d].wait()
                        tracing.came_after(done_ids[d])
                    if isinstance(results[d], BaseException):
                        raise results[d]
                    round_parts.append(results[d])
            # the round's inputs go back to the host pool only now that its
            # outputs are read: XLA:CPU may alias a numpy buffer it was
            # handed instead of copying it
            del device_inputs
            per_round_results.append(round_parts)
            metrics.observe("mesh.exchange.round",
                            (time.perf_counter() - t_round) * 1000.0,
                            st.counters)
            _flight.record(_flight.EXCHANGE, "round", st.edge_id,
                           a=r, b=n_round)
            sent_rows += n_round
            rounds_run += 1
            with self.lock:
                self.rows_exchanged += n_round
        with self.lock:
            self.exchanges_run += 1
            self.coded_buddy_wins += buddy_wins
            if rounds_run > 1:
                self.multi_round_exchanges += 1
            for d in range(D):
                self.lane_rows[d] = \
                    self.lane_rows.get(d, 0) + int(lane_counts[d])
        if st.counters is not None:
            g = st.counters.group(MESH_EXCHANGE_GROUP)
            g.find_counter("exchange.rows.sent").increment(sent_rows)
            g.find_counter("exchange.rows.placed.native").increment(
                native_rows)
            g.find_counter("exchange.bytes.sent").increment(
                sent_rows * row_words * 4)
            g.find_counter("exchange.rounds").increment(rounds_run)
            g.find_counter("exchange.splits").increment(splits)
            g.find_counter("exchange.coded.duplicate.bytes").increment(
                dup_rows * row_words * 4)
            g.find_counter("exchange.coded.buddy.wins").increment(
                buddy_wins)

        with tracing.span("exchange.decode", cat="exchange", stage="assemble"):
            if len(per_round_results) == 1:
                per_device = per_round_results[0]
            else:
                per_device = []
                for w in range(D):
                    runs = [Run(res[w],
                                np.array([0, res[w].num_records],
                                         dtype=np.int64))
                            for res in per_round_results
                            if res[w].num_records > 0]
                    if not runs:
                        per_device.append(KVBatch.empty())
                    elif len(runs) == 1:
                        per_device.append(runs[0].batch)
                    else:
                        per_device.append(merge_sorted_runs(
                            runs, 1, num_lanes * 4, engine="host").batch)
            if W == D and splits == 0:
                return per_device
            # general consumer assembly, covering both W > D (device d holds
            # consumer partitions {c : c % D == d} key-sorted) and the
            # splitter's merge-side recombine (a split consumer's rows landed
            # on several devices; each device's slice is key-sorted, so a
            # stable host merge in device order reassembles the partition with
            # arrival-order ties).  The TRUE consumer hash (fnv % W) is always
            # key-derivable, even for re-routed rows.
            runs_per_consumer: List[List[KVBatch]] = [[] for _ in range(W)]
            for d in range(D):
                batch = per_device[d]
                if batch.num_records == 0:
                    continue
                c_part = fnv32_partition_native(batch.key_bytes,
                                                batch.key_offsets, W)
                for c in np.unique(c_part):
                    csel = np.flatnonzero(c_part == c)
                    runs_per_consumer[int(c)].append(batch.take(csel))
            results_out: List[KVBatch] = []
            for c in range(W):
                runs = runs_per_consumer[c]
                if not runs:
                    results_out.append(KVBatch.empty())
                elif len(runs) == 1:
                    results_out.append(runs[0])
                else:
                    results_out.append(merge_sorted_runs(
                        [Run(b, np.array([0, b.num_records], dtype=np.int64))
                         for b in runs], 1, num_lanes * 4,
                        engine="host").batch)
            return results_out


_coordinator: Optional[MeshExchangeCoordinator] = None
_coordinator_lock = threading.Lock()


def mesh_coordinator() -> MeshExchangeCoordinator:
    global _coordinator
    with _coordinator_lock:
        if _coordinator is None:
            import os
            _coordinator = MeshExchangeCoordinator(
                max_rows_per_round=int(os.environ.get(
                    "TEZ_TPU_MESH_MAX_ROWS_PER_ROUND", 1 << 20)))
        return _coordinator


def reset_coordinator() -> None:
    """Test hook: drop all edge state (fresh process semantics)."""
    global _coordinator
    with _coordinator_lock:
        _coordinator = None


def telemetry_collector() -> Dict[str, float]:
    """Live-telemetry hook (obs/timeseries registry): per-device-lane
    exchange occupancy gauges — each lane's cumulative landed rows and
    its share of all landed rows, so ``graft top`` shows a skewed mesh as
    one hot lane instead of an averaged-away total.  Never *creates* the
    coordinator: an AM that ran no exchange reports nothing."""
    with _coordinator_lock:
        coord = _coordinator
    if coord is None:
        return {}
    with coord.lock:
        lane_rows = dict(coord.lane_rows)
    total = float(sum(lane_rows.values()))
    out: Dict[str, float] = {}
    for d, rows in lane_rows.items():
        out[f"mesh.lane.{d}.rows"] = float(rows)
        out[f"mesh.lane.{d}.occupancy"] = \
            round(rows / total, 6) if total else 0.0
    return out
