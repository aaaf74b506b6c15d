"""Benchmark legs: OrderedWordCount shuffle+sort on one TPU chip.

Runs in ONE process, on the device JAX reports, and fails if there is none:
without a chip the only way to run it is an explicit ``JAX_PLATFORMS=cpu``,
and every line it prints carries ``platform`` / ``device_kind`` /
``device_count`` so a CPU line can never be read as a chip number.  A leg
that raises fails the run — nothing is re-run elsewhere and no failure is
printed as a metric.

Default run, one JSON line per leg (the LAST line is the headline):

1. info: async device pipeline vs the numpy-lexsort host engine, with the
   per-stage breakdown.
2. FRAMEWORK: OrderedWordCount end-to-end through the full stack — DAG
   submission, vectorized tokenizer, device sorter, shuffle service,
   consumer merge, committed file output — following BASELINE.md's protocol
   (input MB/s, SHUFFLE_BYTES / SPILLED_RECORDS counters, output verified
   against a host golden).  vs_baseline = proxy_wall / framework_wall
   against the C++ reference-semantics OrderedWordCount proxy
   (native/baseline_proxy.cpp owc_proxy) on the identical corpus.  Runs in
   this same process: the parent holds the chip, so a child could not.
3. KERNEL (headline): the partitioned sort + k-way merge core
   (PipelinedSorter/TezMerger semantics, SURVEY.md §2.5) on synthetic
   records, device-resident, keys+values byte-verified; vs_baseline is the
   C++ PipelinedSorter/TezMerger proxy (no JVM in this image; BASELINE.md).

``TEZ_BENCH_{STORE,SORT,EXCHANGE,QUERY}_ONLY=1`` run one leg each
(the Makefile's bench-* targets).  These legs are what earlier rounds left;
ROADMAP S1 replaces them with the cell table.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def make_records(num_records: int, key_len: int = 12, seed: int = 0):
    """Synthetic word-count-ish records: zipfian keys, 8-byte long values."""
    rng = np.random.default_rng(seed)
    vocab = 50_000
    word_ids = rng.zipf(1.3, num_records).astype(np.int64) % vocab
    digits = np.zeros((num_records, key_len), dtype=np.uint8)
    digits[:, 0] = ord("w")
    ids = word_ids.copy()
    for i in range(key_len - 1, 0, -1):
        digits[:, i] = ord("0") + (ids % 10)
        ids //= 10
    key_bytes = digits.reshape(-1)
    key_offsets = np.arange(num_records + 1, dtype=np.int64) * key_len
    val_bytes = rng.integers(0, 256, num_records * 8, dtype=np.int64)\
        .astype(np.uint8)
    val_offsets = np.arange(num_records + 1, dtype=np.int64) * 8
    return key_bytes, key_offsets, val_bytes, val_offsets


def host_baseline(key_bytes, key_offsets, val_bytes, val_offsets,
                  num_producers: int, num_partitions: int, key_len: int):
    """Vectorized host implementation of the same partition+sort+merge."""
    n = len(key_offsets) - 1
    keys = key_bytes.reshape(n, key_len)
    h = np.full(n, 2166136261, dtype=np.uint64)
    for j in range(key_len):
        h = ((h ^ keys[:, j].astype(np.uint64)) * np.uint64(16777619)) \
            & np.uint64(0xFFFFFFFF)
    part = (h % np.uint64(num_partitions)).astype(np.int64)
    per = n // num_producers
    producer_runs = []
    for p in range(num_producers):
        sl = slice(p * per, (p + 1) * per if p < num_producers - 1 else n)
        cols = [keys[sl, j] for j in range(key_len - 1, -1, -1)]
        order = np.lexsort(cols + [part[sl]])
        producer_runs.append((part[sl][order], keys[sl][order]))
    out = []
    for c in range(num_partitions):
        segs = []
        for parts, ks in producer_runs:
            lo = np.searchsorted(parts, c, "left")
            hi = np.searchsorted(parts, c, "right")
            segs.append(ks[lo:hi])
        allk = np.concatenate(segs) if segs else np.zeros((0, key_len),
                                                          np.uint8)
        cols = [allk[:, j] for j in range(key_len - 1, -1, -1)]
        out.append(allk[np.lexsort(cols)])
    return out


def prepare_device_inputs(key_bytes, key_offsets, val_bytes, val_offsets,
                          key_len: int):
    """Normalize + upload ONCE (the data plane is HBM-resident: records are
    produced on device and stay there; host<->device DMA is not part of the
    shuffle+sort path being measured)."""
    import jax
    import jax.numpy as jnp
    from tez_tpu.ops.keycodec import matrix_to_lanes, pad_to_matrix
    n = len(key_offsets) - 1
    mat, lengths = pad_to_matrix(key_bytes, key_offsets, key_len)
    lanes = matrix_to_lanes(mat)
    hash_w = 1 << max(2, (key_len - 1).bit_length())
    hmat, hlens = pad_to_matrix(key_bytes, key_offsets, hash_w)
    vals = np.ascontiguousarray(val_bytes.reshape(n, 8)).view(np.uint32)
    from tez_tpu.ops.device import uniform_clamped_lengths
    uniform, _ = uniform_clamped_lengths(lengths, lanes.shape[1] * 4 + 1)
    dev = [jnp.asarray(x) for x in (lanes, lengths.astype(np.int64), vals,
                                    hmat, hlens.astype(np.int32))]
    jax.block_until_ready(dev)
    return dev + [uniform]


def tpu_path(dev_inputs, num_partitions: int):
    """The measured region: hash-partition + global (partition, key) sort +
    payload gather + partition index, all device-resident — the single-chip
    equivalent of producer sort + exchange + consumer merge (on one chip the
    exchange is an HBM-resident buffer handoff).

    Completion is forced by fetching an output that depends on the whole
    pipeline (the tiny counts vector): dispatch is asynchronous."""
    from tez_tpu.ops.device_pipeline import device_shuffle_sort
    lanes, lengths, vals, hmat, hlens, uniform = dev_inputs
    out = device_shuffle_sort(lanes, lengths, vals, hmat, hlens,
                              num_partitions, uniform_length=uniform)
    _ = np.asarray(out[4])   # counts: forces full execution, ~P ints D2H
    return out


def make_spans(key_bytes, val_bytes, key_len: int, num_records: int,
               num_spans: int):
    """Slice the record stream into producer spans of RAW host bytes —
    encode/H2D happen inside the pipeline's staging thread, where the async
    plane overlaps them with in-flight dispatches."""
    spans = []
    per = num_records // num_spans
    for p in range(num_spans):
        lo = p * per
        hi = (p + 1) * per if p < num_spans - 1 else num_records
        m = hi - lo
        spans.append((key_bytes[lo * key_len:hi * key_len],
                      np.arange(m + 1, dtype=np.int64) * key_len,
                      val_bytes[lo * 8:hi * 8]))
    return spans


def pipeline_path(spans, num_partitions: int, key_len: int):
    """The measured region for the async device plane (ops/async_stage.py):
    submit every span's raw bytes, drain.  Spans below the coalesce budget
    merge into ONE bucketed dispatch — a stable sort of the concatenation is
    bit-identical to merging the individually-sorted spans — so the result
    is the same global partition-major order the sync path produces.
    paused=True defers the staging thread until all spans are queued,
    making the coalesce grouping deterministic."""
    from tez_tpu.ops.device_pipeline import DeviceSpanScheduler
    total = sum(len(ko) - 1 for _, ko, _ in spans)
    sched = DeviceSpanScheduler(num_partitions, depth=2,
                                coalesce_records=total, key_width=key_len,
                                paused=True)
    for sid, (kb, ko, vb) in enumerate(spans):
        sched.submit_ragged(sid, kb, ko, vb, 8)
    sched.resume()
    return sched.results()


def bench_store(num_records: int, key_len: int) -> dict:
    """Tiered buffer-store short-circuit vs loopback TCP fetch (info line).

    The same registered spills are fetched two ways: (A) over the
    keep-alive DCN shuffle socket on loopback — connect + HMAC handshake
    paid once, then per-partition request/serialize/copy per fetch — and
    (B) through ShuffleBufferStore.fetch_partition, the leased zero-copy
    view the fetch scheduler's local_probe takes for same-host producers.
    vs_baseline = TCP wall / store wall; min_vs_baseline is the ratio
    floor bench_diff enforces (the short-circuit losing its edge over the
    wire means the lease path grew a copy).  The metric text also reports
    the session-mode leg: spills sealed under lineage keys, republished
    to a second DAG's path, and re-fetched bit-exact as cache hits."""
    from tez_tpu.common.security import JobTokenSecretManager
    from tez_tpu.ops.runformat import KVBatch, Run
    from tez_tpu.shuffle.server import FetchSession, ShuffleServer
    from tez_tpu.shuffle.service import ShuffleService
    from tez_tpu.store.buffer_store import ShuffleBufferStore

    n = min(num_records, 400_000)
    num_spills, num_partitions = 4, 4
    per = n // num_spills
    service = ShuffleService()
    store = ShuffleBufferStore(device_capacity=0, host_capacity=1 << 30)
    service.attach_buffer_store(store)
    paths = []
    for s in range(num_spills):
        kb, ko, vb, vo = make_records(per, key_len, seed=100 + s)
        bounds = np.linspace(0, per, num_partitions + 1).astype(np.int64)
        path = f"bench_dag/attempt_{s}/cons"
        service.register(path, -1, Run(KVBatch(kb, ko, vb, vo), bounds),
                         lineage=f"benchlin{s}/0/cons")
        paths.append(path)

    reps = 3
    secrets = JobTokenSecretManager()
    server = ShuffleServer(secrets, service).start()
    try:
        sess = FetchSession(secrets, "127.0.0.1", server.port)
        try:
            tcp_probe = sess.fetch(paths[0], -1, 1)        # warm + verify
            for path in paths:
                sess.fetch_range(path, -1, 0, num_partitions)
            t0 = time.time()
            for _ in range(reps):
                for path in paths:
                    sess.fetch_range(path, -1, 0, num_partitions)
            tcp_s = (time.time() - t0) / reps
        finally:
            sess.close()
    finally:
        server.stop()

    bytes_per_pass = 0
    for path in paths:                                      # warm
        for p in range(num_partitions):
            bytes_per_pass += store.fetch_partition(path, -1, p).nbytes
    store_probe = store.fetch_partition(paths[0], -1, 1)
    assert np.array_equal(tcp_probe.key_bytes, store_probe.key_bytes) and \
        np.array_equal(tcp_probe.val_bytes, store_probe.val_bytes), \
        "TCP and store short-circuit served different partition bytes"
    t0 = time.time()
    for _ in range(reps):
        for path in paths:
            for p in range(num_partitions):
                store.fetch_partition(path, -1, p)
    store_s = (time.time() - t0) / reps

    # session-mode leg: DAG commits -> seal, DAG aliases drop, a recurring
    # DAG republishes the sealed entries under its own path and re-fetches
    sealed = store.seal_lineage("bench_dag")
    service.unregister_prefix("bench_dag")
    hits = 0
    for s in range(num_spills):
        new_path = f"bench_dag2/attempt_{s}/cons"
        hits += len(store.republish_lineage(f"benchlin{s}/0/cons", new_path))
        reused = store.fetch_partition(new_path, -1, 1)
        if s == 0:
            assert np.array_equal(reused.key_bytes, store_probe.key_bytes), \
                "lineage-republished partition diverges from the original"
    store.close()

    return {
        "metric": (f"store short-circuit vs loopback TCP fetch (info line; "
                   f"{num_spills} spills x {num_partitions} partitions, "
                   f"{bytes_per_pass / 1e6:.1f} MB/pass, keep-alive TCP "
                   f"session {bytes_per_pass / 1e6 / tcp_s:.0f} MB/s; "
                   f"session leg: {sealed} sealed, {hits} lineage hits "
                   f"republished + re-fetched bit-exact)"),
        "value": round(bytes_per_pass / 1e6 / store_s, 2), "unit": "MB/s",
        "vs_baseline": round(tcp_s / store_s, 3),
        "min_vs_baseline": 1.5,
    }


_DEVICE_STAGES = (("encode", "device.encode"), ("h2d", "device.h2d"),
                  ("dispatch_wait", "device.dispatch_wait"),
                  ("d2h", "device.d2h"))


def device_stage_ms():
    """Cumulative wall ms per async-plane stage, from the in-process
    metrics histograms the pipeline feeds (docs/device_pipeline.md)."""
    from tez_tpu.common import metrics
    hs = metrics.registry().histograms()
    return {short: round(float(hs[name].sum_ms), 1) if name in hs else 0.0
            for short, name in _DEVICE_STAGES}


# ---------------------------------------------------------------------------
# framework E2E (BASELINE.md protocol: full stack, counters, verified output)
# ---------------------------------------------------------------------------
def _make_corpus(path: str, target_mb: int, seed: int = 0):
    """Zipfian word corpus; returns (bytes_written, golden Counter-dict)."""
    rng = np.random.default_rng(seed)
    vocab = 20_000
    words = np.array([f"w{i:06d}" for i in range(vocab)])
    total = 0
    counts = np.zeros(vocab, dtype=np.int64)
    chunk_words = 1 << 20
    words_per_line = 8192   # ~64 KB lines: text splits stay balanced
    # (multi-MB lines would skew line-aligned splits across tokenizers)
    with open(path, "w") as fh:
        while total < target_mb << 20:
            ids = rng.zipf(1.3, chunk_words).astype(np.int64) % vocab
            counts += np.bincount(ids, minlength=vocab)
            chunk = words[ids]
            for s in range(0, len(chunk), words_per_line):
                text = " ".join(chunk[s:s + words_per_line])
                fh.write(text)
                fh.write("\n")
                total += len(text) + 1
    golden = {words[i]: int(counts[i]) for i in np.flatnonzero(counts)}
    return total, golden


def _run_wordcount(corpus: str, out_dir: str, staging: str,
                   engine: str) -> dict:
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.examples import ordered_wordcount
    conf = {"tez.staging-dir": staging,
            "tez.runtime.sorter.class": engine,
            "tez.runtime.io.sort.mb": 512}
    with TezClient.create("bench-owc", conf) as client:
        dag = ordered_wordcount.build_dag(
            [corpus], out_dir, tokenizer_parallelism=4,
            summation_parallelism=4, sorter_parallelism=1,
            combine=True, tokenizer_mode="vector")
        dag_client = client.submit_dag(dag)
        status = dag_client.wait_for_completion()
        final = dag_client.get_dag_status(with_counters=True)
    counters = {}
    if final.counters is not None:
        d = final.counters.to_dict()
        for group in d.values():
            for name in ("SHUFFLE_BYTES", "SPILLED_RECORDS",
                         "OUTPUT_RECORDS", "REDUCE_INPUT_RECORDS"):
                if name in group:
                    counters[name] = counters.get(name, 0) + group[name]
    return {"state": status.state.name, "counters": counters}


def _verify_output(out_dir: str, golden: dict) -> None:
    got = {}
    for name in sorted(os.listdir(out_dir)):
        with open(os.path.join(out_dir, name)) as fh:
            for line in fh.read().splitlines():
                if line.strip():
                    w, c = line.rsplit(None, 1)
                    got[w] = int(c)
    assert got == golden, (
        f"framework output mismatch: {len(got)} words vs {len(golden)}")


def bench_framework(platform: str) -> dict:
    """OrderedWordCount through the full stack; returns the JSON record."""
    import shutil
    import tempfile
    from tez_tpu.ops.native import owc_proxy_counts
    default_mb = 32 if platform == "cpu" else 96
    target_mb = int(os.environ.get("TEZ_BENCH_E2E_MB", str(default_mb)))
    td = tempfile.mkdtemp(prefix="tez_bench_")
    try:
        corpus = os.path.join(td, "corpus.txt")
        nbytes, golden = _make_corpus(corpus, target_mb)

        # BASELINE.md protocol: 3 runs per engine, median wall-clock (the
        # first device run additionally pays trace/compile warmup; the
        # median reports steady state for BOTH engines identically)
        reps = max(1, int(os.environ.get("TEZ_BENCH_E2E_REPS", "3")))
        runs = {}
        for engine in ("device", "host"):
            walls = []
            counters = {}
            for rep in range(reps):
                out_dir = os.path.join(td, f"out_{engine}_{rep}")
                t0 = time.time()
                r = _run_wordcount(corpus, out_dir, os.path.join(td, "stg"),
                                   engine)
                walls.append(time.time() - t0)
                assert r["state"] == "SUCCEEDED", r
                _verify_output(out_dir, golden)
                counters = r["counters"]
                shutil.rmtree(out_dir, ignore_errors=True)
            walls.sort()
            runs[engine] = (walls[len(walls) // 2], counters)

        dev_wall, counters = runs["device"]
        host_wall, _ = runs["host"]

        # EXTERNAL baseline (BASELINE.md protocol): the reference-semantics
        # C++ OrderedWordCount proxy over the IDENTICAL corpus — tokenize,
        # span sort + combine, per-partition heap merge + sum, count-keyed
        # second sort, merged output — output verified against the same
        # golden.  vs_baseline = proxy wall / framework wall ( >1 means the
        # framework beats reference semantics at equal work on this host).
        pw = []
        for _ in range(reps):
            secs, got = owc_proxy_counts(corpus, 4, 4)
            pw.append(secs)
        if got != golden:
            raise RuntimeError(
                f"owc_proxy output mismatch: {len(got)} words vs "
                f"golden {len(golden)}")
        pw.sort()
        proxy_wall = pw[len(pw) // 2]
        return {
            "metric": (f"OrderedWordCount E2E through full framework "
                       f"({target_mb} MB input, 4x4x1 tasks, device sorter, "
                       f"median of {reps}, verified vs host golden; "
                       f"SHUFFLE_BYTES={counters.get('SHUFFLE_BYTES', 0)}, "
                       f"SPILLED_RECORDS="
                       f"{counters.get('SPILLED_RECORDS', 0)}; "
                       f"baseline=C++ OrderedWordCount reference-semantics "
                       f"proxy {proxy_wall:.2f}s on the same corpus)"),
            "value": round(nbytes / 1e6 / dev_wall, 2),
            "unit": "MB/s",
            "vs_baseline": round(proxy_wall / dev_wall, 3),
            "host_engine_wall_ratio": round(host_wall / dev_wall, 3),
        }
    finally:
        shutil.rmtree(td, ignore_errors=True)


def main() -> int:
    # the one backend query: raises when the chip cannot be claimed and when
    # JAX lands on the CPU without having been asked to (no fallback)
    import jax
    from tez_tpu.ops.device import backend_platform
    platform = backend_platform()
    dev = jax.devices()[0]
    tag = {"platform": platform, "device_kind": dev.device_kind,
           "device_count": len(jax.devices())}

    def emit(rec: dict) -> None:
        print(json.dumps({**rec, **tag}), flush=True)

    if os.environ.get("TEZ_BENCH_STORE_ONLY") == "1":
        # make bench-store: the buffer-store short-circuit info line
        num_records = int(sys.argv[1]) if len(sys.argv) > 1 else 400_000
        emit(bench_store(num_records, 12))
        return 0
    if os.environ.get("TEZ_BENCH_SORT_ONLY") == "1":
        # make bench-sort: the external-sort push-vs-pull scale leg through
        # the full framework
        from tez_tpu.tools.sort_bench import bench_sort
        emit(bench_sort())
        return 0
    if os.environ.get("TEZ_BENCH_EXCHANGE_ONLY") == "1":
        # make bench-exchange: the MULTICHIP skewed-key corpus through the
        # mesh exchange plane — padded baseline vs ragged/skew-aware/coded
        # legs, one metric line each (the skew-aware line carries the
        # bench_diff min_vs_baseline floor)
        from tez_tpu.tools.exchange_bench import bench_exchange
        for rec in bench_exchange():
            emit(rec)
        return 0
    if os.environ.get("TEZ_BENCH_QUERY_ONLY") == "1":
        # make bench-query: broadcast-vs-repartition info lines on the
        # uniform and zipf corpora + the adaptive-replan headline whose
        # min_vs_baseline floor bench-diff enforces (run 2, replanned
        # from observed stats, must beat the naive run 1)
        from tez_tpu.tools.query_bench import bench_query
        for rec in bench_query():
            emit(rec)
        return 0
    num_records = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
    key_len = 12
    num_producers, num_partitions = 4, 4

    # -- stage 1: kernel bench at full size; the first call compiles
    kb, ko, vb, vo = make_records(num_records, key_len)
    total_mb = (kb.nbytes + vb.nbytes) / 1e6
    dev_in = prepare_device_inputs(kb, ko, vb, vo, key_len)
    tpu_path(dev_in, num_partitions)      # warm the full-size program
    del dev_in

    # -- the measured region is the ASYNC device plane: raw producer spans
    # submitted to DeviceSpanScheduler (staging-thread encode + H2D +
    # coalesced dispatch + worker readback), drained to host arrays.  The
    # warm above compiled the same _fused_pipeline program/shape.
    spans = make_spans(kb, vb, key_len, num_records, num_producers)
    res = pipeline_path(spans, num_partitions, key_len)
    assert all(res[i] is res[0] for i in range(num_producers)), \
        "spans did not coalesce into one dispatch"

    stage_before = device_stage_ms()
    t0 = time.time()
    reps = 3
    for _ in range(reps):
        res = pipeline_path(spans, num_partitions, key_len)
    tpu_s = (time.time() - t0) / reps
    stage_after = device_stage_ms()
    stage_ms = {k: round((stage_after[k] - stage_before[k]) / reps, 1)
                for k in stage_after}
    # the satellite breakdown wants sort wall: in-flight time minus D2H
    stage_ms["sort"] = round(
        max(0.0, stage_ms.pop("dispatch_wait") - stage_ms["d2h"]), 1)
    tpu_out = res[0]

    t0 = time.time()
    host_out = host_baseline(kb, ko, vb, vo, num_producers, num_partitions,
                             key_len)
    host_s = time.time() - t0

    # reference baseline: PipelinedSorter/TezMerger semantics in C++
    # (BASELINE.md — no JVM in this image, proxy clearly labeled)
    from tez_tpu.ops.native import pipelined_sorter_proxy
    n = num_records
    proxy_s, proxy_keys, proxy_vals, proxy_counts = pipelined_sorter_proxy(
        kb.reshape(n, key_len), vb.reshape(n, 8), num_producers,
        num_partitions)

    # byte-identity: device keys AND values vs the host golden.  The spans
    # are adjacent slices submitted in order, so the coalesced concat
    # preserves global record order and perm indexes kb directly.
    sorted_parts, out_lanes, out_vals, perm, counts, _nreal = \
        [np.asarray(x) for x in tpu_out]
    sorted_keys = kb.reshape(n, key_len)[perm[:n]]
    bounds = np.zeros(num_partitions + 1, dtype=np.int64)
    np.cumsum(counts, out=bounds[1:])
    for c in range(num_partitions):
        got = sorted_keys[bounds[c]:bounds[c + 1]]
        assert got.shape == host_out[c].shape, \
            f"partition {c}: {got.shape} vs {host_out[c].shape}"
        assert np.array_equal(got, host_out[c]), f"partition {c} mismatch"
    assert np.array_equal(proxy_counts, counts[:num_partitions]), \
        "proxy/device partition counts diverge"
    assert np.array_equal(sorted_keys, proxy_keys), \
        "proxy/device key order diverges"
    # values from the DEVICE output (not reconstructed via perm):
    # byte-identical payloads are the reducer-output contract
    dev_vals = out_vals[:n].copy().view(np.uint8).reshape(n, 8)
    assert np.array_equal(dev_vals, proxy_vals), \
        "device values diverge from baseline"

    mbps = total_mb / tpu_s
    emit({
        "metric": (f"ordered-shuffle-sort vs numpy-lexsort host engine "
                   f"(info line; async device pipeline, {num_producers} "
                   f"spans coalesced; same {num_records} recs)"),
        "value": round(mbps, 2), "unit": "MB/s",
        "vs_baseline": round(host_s / tpu_s, 3),
        "stage_ms": stage_ms})

    # -- stage 2: framework E2E, in THIS process — it holds the chip
    if os.environ.get("TEZ_BENCH_SKIP_E2E") != "1":
        emit(bench_framework(platform))

    emit({
        "metric": (f"ordered-shuffle-sort throughput ({num_records} "
                   f"recs, {num_partitions} partitions, HBM-resident, "
                   f"keys+values byte-verified; baseline=PipelinedSorter-"
                   f"semantics C++ proxy {proxy_s:.2f}s (no JVM in image; "
                   f"BASELINE.md))"),
        "value": round(mbps, 2),
        "unit": "MB/s",
        "vs_baseline": round(proxy_s / tpu_s, 3),
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
