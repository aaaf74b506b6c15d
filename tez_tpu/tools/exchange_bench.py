"""MULTICHIP mesh-exchange bench on a skewed-key corpus.

Zipf-drawn keys with one hot partition (~30% of all rows hash to one of
the W consumer partitions) — the exact pathology ISSUE/ROADMAP item 3
names: under the legacy padded formulation CAP is set by that one hot
partition, so every (sender, dest) pair's buffer inflates to it and the
padding crosses ICI as slack.  The bench times four legs over the same
corpus and asserts they are bit-identical:

- ``padded-maxcap`` — the legacy baseline (``legacy_sizing=True``).
- ``skew-aware`` — histogram-sized rounds + balanced placement
  (engine=auto); the HEADLINE metric, floored at 1.3x the baseline via
  ``min_vs_baseline`` (tools/bench_diff.py enforces it).
- ``ragged`` — only real rows cross ICI; emitted with the 0.0
  "unavailable" sentinel where the backend lacks the thunk (XLA:CPU).
- ``coded-r2`` — the redundant exchange; informational (it SPENDS send
  flops to buy straggler masking, so no floor).

Run via ``make bench-exchange`` (TEZ_BENCH_EXCHANGE_ONLY=1 bench.py);
each leg prints one JSON metric line in the bench_diff schema.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from tez_tpu.ops.runformat import KVBatch

ROWS = 120_000
KEY_BYTES = 8
VAL_BYTES = 12
CONSUMERS = 8
PRODUCERS = 4
HOT_FRAC = 0.30          # fraction of rows landing in the hot partition
HOT_PART = 0
REPS = 3
MIN_VS_BASELINE = 1.3    # bench_diff floor for the skew-aware leg


def _skewed_corpus(seed: int = 11) -> List[KVBatch]:
    """PRODUCERS spans of Zipf-drawn keys with HOT_FRAC of all rows
    hashing to consumer partition HOT_PART of CONSUMERS."""
    from tez_tpu.ops.host_sort import fnv_rows_host
    rng = np.random.default_rng(seed)
    # classify a candidate key pool by the REAL partitioner so the hot
    # fraction is exact by construction, not a hash accident
    pool = rng.integers(0, 256, size=(40_000, KEY_BYTES), dtype=np.uint8)
    part = fnv_rows_host(pool, np.full(pool.shape[0], KEY_BYTES,
                                       dtype=np.int64)) % CONSUMERS
    hot_pool = pool[part == HOT_PART]
    cold_pool = pool[part != HOT_PART]
    # Zipf-ish popularity inside each pool: low ranks dominate, so the
    # corpus has genuinely repeated hot keys (grouped-reader reality),
    # not 120k distinct ones
    def _draw(p: np.ndarray, n: int) -> np.ndarray:
        ranks = np.minimum(rng.zipf(1.3, size=n) - 1, p.shape[0] - 1)
        return p[ranks]

    n_hot = int(ROWS * HOT_FRAC)
    keys = np.concatenate([_draw(hot_pool, n_hot),
                           _draw(cold_pool, ROWS - n_hot)])
    keys = keys[rng.permutation(ROWS)]
    vals = rng.integers(0, 256, size=(ROWS, VAL_BYTES), dtype=np.uint8)
    spans = []
    for i in range(PRODUCERS):
        k, v = keys[i::PRODUCERS], vals[i::PRODUCERS]
        n = k.shape[0]
        spans.append(KVBatch(
            k.reshape(-1), np.arange(n + 1, dtype=np.int64) * KEY_BYTES,
            v.reshape(-1), np.arange(n + 1, dtype=np.int64) * VAL_BYTES))
    return spans


def _run_leg(coord, spans: List[KVBatch], edge: str,
             **kw) -> List[KVBatch]:
    for i, b in enumerate(spans):
        coord.register_producer(edge, i, PRODUCERS, CONSUMERS, b,
                                KEY_BYTES, VAL_BYTES, **kw)
    return [coord.wait_consumer(edge, c, PRODUCERS, CONSUMERS, timeout=300)
            for c in range(CONSUMERS)]


def _time_leg(coord, spans: List[KVBatch], tag: str,
              **kw) -> Tuple[float, List[KVBatch]]:
    """(best wall secs, outputs): one warmup exchange (compile), then the
    best of REPS timed runs — each on a fresh edge id so the coordinator
    actually re-runs the exchange (results are cached per edge)."""
    out = _run_leg(coord, spans, f"warm-{tag}/a->b", **kw)
    best = float("inf")
    for rep in range(REPS):
        t0 = time.perf_counter()
        out = _run_leg(coord, spans, f"rep{rep}-{tag}/a->b", **kw)
        best = min(best, time.perf_counter() - t0)
    return best, out


def _sig(res: List[KVBatch]) -> List[Tuple[bytes, bytes]]:
    return [(np.asarray(b.key_bytes).tobytes(),
             np.asarray(b.val_bytes).tobytes()) for b in res]


def _mbs(wall: float) -> float:
    return ROWS * (KEY_BYTES + VAL_BYTES) / wall / 1e6


def bench_exchange() -> List[Dict]:
    """Metric records for the four exchange legs (bench_diff schema)."""
    import jax
    from tez_tpu.parallel.coordinator import MeshExchangeCoordinator
    from tez_tpu.parallel.exchange import probe_ragged_support

    if len(jax.devices()) < 2:
        return [{"metric": "exchange skewed shuffle (needs >= 2 devices)",
                 "value": 0.0, "unit": "MB/s", "vs_baseline": 0.0}]
    spans = _skewed_corpus()

    base_wall, base_out = _time_leg(
        MeshExchangeCoordinator(legacy_sizing=True), spans,
        "padded", engine="padded")
    skew_wall, skew_out = _time_leg(
        MeshExchangeCoordinator(), spans, "skew", engine="auto")
    assert _sig(skew_out) == _sig(base_out), \
        "skew-aware exchange output diverged from the padded baseline"
    coded_wall, coded_out = _time_leg(
        MeshExchangeCoordinator(), spans, "coded", engine="auto",
        coded="r2")
    assert _sig(coded_out) == _sig(base_out), \
        "coded r2 exchange output diverged from the padded baseline"

    mesh = MeshExchangeCoordinator().mesh_for(
        MeshExchangeCoordinator().devices_for(CONSUMERS))
    ragged_ok, ragged_reason = probe_ragged_support(mesh)
    if ragged_ok:
        ragged_wall, ragged_out = _time_leg(
            MeshExchangeCoordinator(), spans, "ragged", engine="ragged")
        assert _sig(ragged_out) == _sig(base_out), \
            "ragged exchange output diverged from the padded baseline"
        ragged_rec = {
            "metric": f"exchange skewed shuffle ragged ({ROWS} rows)",
            "value": round(_mbs(ragged_wall), 3), "unit": "MB/s",
            "vs_baseline": round(base_wall / ragged_wall, 3)}
    else:
        ragged_rec = {
            "metric": f"exchange skewed shuffle ragged ({ragged_reason})",
            "value": 0.0, "unit": "MB/s", "vs_baseline": 0.0}

    hot_pct = int(HOT_FRAC * 100)
    return [
        {"metric": f"exchange skewed shuffle padded-maxcap ({ROWS} rows, "
                   f"{hot_pct}% hot)",
         "value": round(_mbs(base_wall), 3), "unit": "MB/s",
         "vs_baseline": 1.0},
        ragged_rec,
        {"metric": f"exchange skewed shuffle coded-r2 ({ROWS} rows, "
                   f"{hot_pct}% hot)",
         "value": round(_mbs(coded_wall), 3), "unit": "MB/s",
         "vs_baseline": round(base_wall / coded_wall, 3)},
        # headline LAST: bench_diff keeps the last record per normalized
        # name, and the skew-aware leg is the one carrying the floor
        {"metric": f"exchange skewed shuffle skew-aware ({ROWS} rows, "
                   f"{hot_pct}% hot)",
         "value": round(_mbs(skew_wall), 3), "unit": "MB/s",
         "vs_baseline": round(base_wall / skew_wall, 3),
         "min_vs_baseline": MIN_VS_BASELINE},
    ]
