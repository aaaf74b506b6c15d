"""Byte-exactness of the reduce-side merge-path kernel (ops/device.py
merge_path_runs / merge_resident_slices kernel="merge_path") against the
host merge engine and the concatenate+re-sort device kernel.

The contract under test is the TezMerger MergeQueue one: merged output is
(partition, key)-sorted with equal (partition, key) groups emitting in run
arrival order — keys AND values byte-identical across engines, across the
property matrix (random widths past the lane cap, duplicate-heavy keys,
empty runs, single runs, > merge_factor cascades).
"""
import random

import jax.numpy as jnp
import numpy as np
import pytest

from tez_tpu.ops import device
from tez_tpu.ops.keycodec import matrix_to_lanes, pad_to_matrix
from tez_tpu.ops.runformat import KVBatch, Run
from tez_tpu.ops.sorter import merge_sorted_runs

from test_ops import golden_sorted, random_pairs


def _partition_sorted_run(pairs, num_partitions):
    golden = golden_sorted(pairs, num_partitions)
    batch = KVBatch.from_pairs([(k, v) for _, k, _, v in golden])
    counts = np.bincount([p for p, *_ in golden], minlength=num_partitions)
    row_index = np.zeros(num_partitions + 1, dtype=np.int64)
    np.cumsum(counts, out=row_index[1:])
    return Run(batch, row_index)


def _merge_both_engines(chunks, num_partitions, key_width, merge_factor=0):
    """Merge the same pre-sorted runs through the device merge-path tail
    and the host engine; return both pair lists."""
    runs_d = [_partition_sorted_run(c, num_partitions) for c in chunks]
    runs_h = [_partition_sorted_run(c, num_partitions) for c in chunks]
    dev = merge_sorted_runs(runs_d, num_partitions, key_width,
                            engine="device", merge_factor=merge_factor,
                            device_min_records=0)
    host = merge_sorted_runs(runs_h, num_partitions, key_width,
                             engine="host", merge_factor=merge_factor)
    return dev, host


@pytest.mark.parametrize("seed", range(6))
def test_merge_path_matches_host_engine_property_matrix(seed):
    rng = random.Random(seed)
    num_partitions = rng.choice([1, 4, 7])
    key_width = rng.choice([4, 12, 16])
    # max_key beyond key_width exercises the beyond-cap host tie-break;
    # small alphabets force duplicate keys across and within runs
    max_key = rng.choice([3, key_width, key_width + 9])
    k = rng.randrange(2, 7)
    chunks = []
    for i in range(k):
        n = rng.choice([0, 1, rng.randrange(2, 400)])
        chunks.append([(bytes(rng.randrange(4) for _ in
                        range(rng.randrange(1, max_key + 1))),
                        bytes([i, j % 256])) for j in range(n)])
    dev, host = _merge_both_engines(chunks, num_partitions, key_width)
    assert list(dev.batch.iter_pairs()) == list(host.batch.iter_pairs())
    np.testing.assert_array_equal(dev.row_index, host.row_index)


def test_merge_path_equal_keys_keep_run_arrival_order():
    # every run holds the SAME keys; values carry (run, row) so any tie
    # mis-order is visible in the value column
    keys = [b"a", b"a", b"b", b"zz"]
    chunks = [[(k, bytes([r, j])) for j, k in enumerate(keys)]
              for r in range(5)]
    dev, host = _merge_both_engines(chunks, 2, 8)
    got = list(dev.batch.iter_pairs())
    assert got == list(host.batch.iter_pairs())
    for key in set(keys):
        runs_seen = [v[0] for kk, v in got if kk == key]
        assert runs_seen == sorted(runs_seen)


def test_merge_path_single_run_and_all_empty():
    pairs = random_pairs(200, seed=9)
    dev, host = _merge_both_engines([pairs], 3, 16)
    assert list(dev.batch.iter_pairs()) == list(host.batch.iter_pairs())
    dev, host = _merge_both_engines([[], [], []], 3, 16)
    assert dev.batch.num_records == 0
    assert list(dev.batch.iter_pairs()) == list(host.batch.iter_pairs())


def test_merge_path_cascade_beyond_merge_factor():
    pairs = random_pairs(700, seed=10, max_key=6)   # duplicate-heavy
    chunks = [pairs[i::7] for i in range(7)]
    dev, host = _merge_both_engines(chunks, 4, 16, merge_factor=3)
    one_pass, _ = _merge_both_engines(chunks, 4, 16)
    assert list(dev.batch.iter_pairs()) == list(host.batch.iter_pairs())
    assert list(dev.batch.iter_pairs()) == list(one_pass.batch.iter_pairs())


def _resident_view(keys, key_width):
    """Device-resident (lanes, lengths, lo, hi) view of an already-sorted
    key list — the dev_keys shape producers hand to the resident merge."""
    b = KVBatch.from_pairs([(k, b"") for k in keys])
    mat, lengths = pad_to_matrix(b.key_bytes, b.key_offsets, key_width)
    lanes = matrix_to_lanes(mat)
    return (jnp.asarray(lanes), jnp.asarray(lengths.astype(np.int32)),
            0, len(keys))


@pytest.mark.parametrize("seed", range(4))
def test_merge_resident_kernels_agree(seed):
    rng = random.Random(100 + seed)
    key_width = rng.choice([4, 8])
    views, all_keys = [], []
    for _ in range(rng.randrange(2, 6)):
        n = rng.choice([1, rng.randrange(1, 300)])
        keys = sorted(bytes(rng.randrange(5) for _ in
                            range(rng.randrange(1, key_width + 1)))
                      for _ in range(n))
        views.append(_resident_view(keys, key_width))
        all_keys.extend(keys)
    perm_mp = device.merge_resident_slices(views, kernel="merge_path")
    perm_sort = device.merge_resident_slices(views, kernel="sort")
    np.testing.assert_array_equal(perm_mp, perm_sort)
    merged = [all_keys[i] for i in perm_mp]
    assert merged == sorted(all_keys)   # ties resolved by run order = concat
    np.testing.assert_array_equal(np.sort(perm_mp), np.arange(len(all_keys)))


def test_rank_search_matches_bisect_reference():
    """_rank_search — the one rank body since the Pallas twin was removed
    (Mosaic refuses its gather, CHANGES.md PR 21) — against Python bisect
    over the same composite (lanes..., length) keys, both flavors."""
    import bisect
    rng = np.random.default_rng(7)
    n, m, w = 173, 512, 3
    run_lanes = rng.integers(0, 4, (n, w)).astype(np.uint32)
    run_lens = rng.integers(1, 9, n).astype(np.uint32)
    q_lanes = rng.integers(0, 4, (m, w)).astype(np.uint32)
    q_lens = rng.integers(1, 9, m).astype(np.uint32)
    # the run must be sorted under the composite comparator (lanes
    # most-significant-first, then length): np.lexsort keys go least
    # significant first
    order = np.lexsort((run_lens,) + tuple(
        run_lanes[:, i] for i in range(w - 1, -1, -1)))
    run_lanes, run_lens = run_lanes[order], run_lens[order]
    run_keys = [tuple(run_lanes[i]) + (run_lens[i],) for i in range(n)]
    q_keys = [tuple(q_lanes[j]) + (q_lens[j],) for j in range(m)]
    for count_equal, side in ((False, bisect.bisect_left),
                              (True, bisect.bisect_right)):
        got = device._rank_search(
            jnp.asarray(run_lanes), jnp.asarray(run_lens),
            jnp.asarray(q_lanes), jnp.asarray(q_lens), count_equal)
        want = np.array([side(run_keys, q) for q in q_keys], np.int32)
        np.testing.assert_array_equal(np.asarray(got), want)
