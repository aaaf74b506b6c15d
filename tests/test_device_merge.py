"""Byte-exactness and launch shape of the device merge (ops/device.py
merge_runs / merge_resident_slices): ONE stable sort of ONE padded
concatenation of the runs, against the host merge engine and Python's
stable ``sorted``.

The contract under test is the TezMerger MergeQueue one: merged output is
(partition, key)-sorted with equal (partition, key) groups emitting in run
arrival order — keys AND values byte-identical across engines, across the
property matrix (random widths past the lane cap, duplicate-heavy keys,
empty runs, single runs, > merge_factor cascades).
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tez_tpu.common.counters import TaskCounter, TezCounters
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
    """Merge the same pre-sorted runs through the device merge and the host
    engine; return both pair lists."""
    runs_d = [_partition_sorted_run(c, num_partitions) for c in chunks]
    runs_h = [_partition_sorted_run(c, num_partitions) for c in chunks]
    dev = merge_sorted_runs(runs_d, num_partitions, key_width,
                            engine="device", merge_factor=merge_factor,
                            device_min_records=0)
    host = merge_sorted_runs(runs_h, num_partitions, key_width,
                             engine="host", merge_factor=merge_factor)
    return dev, host


@pytest.mark.parametrize("seed", range(6))
def test_device_merge_matches_host_engine_property_matrix(seed):
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


def test_device_merge_equal_keys_keep_run_arrival_order():
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


def test_device_merge_single_run_and_all_empty():
    pairs = random_pairs(200, seed=9)
    dev, host = _merge_both_engines([pairs], 3, 16)
    assert list(dev.batch.iter_pairs()) == list(host.batch.iter_pairs())
    dev, host = _merge_both_engines([[], [], []], 3, 16)
    assert dev.batch.num_records == 0
    assert list(dev.batch.iter_pairs()) == list(host.batch.iter_pairs())


def test_device_merge_cascade_beyond_merge_factor():
    pairs = random_pairs(700, seed=10, max_key=6)   # duplicate-heavy
    chunks = [pairs[i::7] for i in range(7)]
    dev, host = _merge_both_engines(chunks, 4, 16, merge_factor=3)
    one_pass, _ = _merge_both_engines(chunks, 4, 16)
    assert list(dev.batch.iter_pairs()) == list(host.batch.iter_pairs())
    assert list(dev.batch.iter_pairs()) == list(one_pass.batch.iter_pairs())


def _key_columns(keys, key_width):
    b = KVBatch.from_pairs([(k, b"") for k in keys])
    mat, lengths = pad_to_matrix(b.key_bytes, b.key_offsets, key_width)
    return matrix_to_lanes(mat), lengths


def _resident_view(keys, key_width):
    """Device-resident (lanes, lengths, lo, hi) view of an already-sorted
    key list — the dev_keys shape producers hand to the resident merge."""
    lanes, lengths = _key_columns(keys, key_width)
    return (jnp.asarray(lanes), jnp.asarray(lengths.astype(np.int32)),
            0, len(keys))


@pytest.mark.parametrize("seed", range(4))
def test_resident_merge_is_the_stable_sort_of_the_concatenation(seed):
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
    perm = device.merge_resident_slices(views)
    # Python's sorted is stable: ties resolve by position in the concat
    want = sorted(range(len(all_keys)), key=all_keys.__getitem__)
    np.testing.assert_array_equal(perm, want)


# ---------------------------------------------------- the mechanism itself

def _host_fed_runs(sizes, seed=0):
    rng = random.Random(seed)
    runs = []
    for r, n in enumerate(sizes):
        keys = sorted(f"w{rng.randrange(10 ** 7):07d}".encode()
                      for _ in range(n))
        batch = KVBatch.from_pairs([(k, bytes([r])) for k in keys])
        runs.append(Run(batch, np.array([0, n], dtype=np.int64)))
    return runs


def _merge_counted(runs):
    counters = TezCounters()
    merged = merge_sorted_runs(runs, 1, 8, counters=counters,
                               engine="device", device_min_records=0)
    return merged, {t: counters.find_counter(t).value for t in (
        TaskCounter.DEVICE_MERGE_RECORDS, TaskCounter.DEVICE_MERGE_LAUNCHES,
        TaskCounter.DEVICE_MERGE_LAUNCH_ROWS)}


@pytest.mark.parametrize("k", range(2, 6))
def test_host_fed_merge_is_one_launch_on_one_bucket(k):
    """Whatever k: ONE program, and it is a comparing one (only
    MERGE_LEVEL_KERNELS count rows), launched on the bucket of the sum —
    not k runs each padded to the largest run's bucket."""
    sizes = [6000 + 700 * i for i in range(k)]
    merged, c = _merge_counted(_host_fed_runs(sizes, seed=k))
    assert c[TaskCounter.DEVICE_MERGE_RECORDS] == sum(sizes)
    assert c[TaskCounter.DEVICE_MERGE_LAUNCHES] == 1
    assert c[TaskCounter.DEVICE_MERGE_LAUNCH_ROWS] == device._bucket(
        sum(sizes))
    keys = [merged.batch.key(i) for i in range(merged.batch.num_records)]
    assert keys == sorted(keys)


def test_run_sizes_with_one_sum_bucket_share_one_compiled_program():
    """The compile key is (bucket of the sum, lanes, the length-pass flag):
    how the rows are split into runs never reaches it."""
    device._merge_sort._compiled.clear()
    for sizes in ([9000, 9000, 9000], [20000, 300, 40], [5000] * 5,
                  [17000, 1]):
        assert device._bucket(sum(sizes)) == 1 << 15
        merge_sorted_runs(_host_fed_runs(sizes), 1, 8, engine="device",
                          device_min_records=0)
    assert device._merge_sort.cache_size() == 1


ALL_FF = b"\xff" * 8


def test_host_fed_all_ff_keys_at_the_lane_cap_sort_before_the_pads():
    """Real rows of 0xFF bytes filling every lane look like nothing else a
    pad could be told from by its lanes: the partition pass alone places
    the pads, so no pad index reaches the permutation."""
    runs = [[b"a", ALL_FF, ALL_FF], [ALL_FF], [b"b", b"c", ALL_FF]]
    keys = [k for run in runs for k in run]
    lanes, lengths = _key_columns(keys, 8)
    partitions = np.zeros(len(keys), dtype=np.int32)
    perm = device.merge_runs(partitions, lanes, lengths)
    np.testing.assert_array_equal(
        perm, sorted(range(len(keys)), key=keys.__getitem__))
    # a partition id as large as they come is still under the pads'
    perm = device.merge_runs(partitions + (np.iinfo(np.int32).max - 1),
                             lanes, lengths)
    np.testing.assert_array_equal(np.sort(perm), np.arange(len(keys)))


def test_resident_all_ff_keys_at_the_lane_cap_sort_before_the_pads():
    runs = [[b"a", ALL_FF, ALL_FF], [ALL_FF], [b"b", b"c", ALL_FF]]
    keys = [k for run in runs for k in run]
    perm = device.merge_resident_slices([_resident_view(r, 8) for r in runs])
    np.testing.assert_array_equal(
        perm, sorted(range(len(keys)), key=keys.__getitem__))
    # one length everywhere: the pads' length code still puts them last
    same_length = [[ALL_FF, ALL_FF], [b"aaaaaaaa", ALL_FF]]
    perm = device.merge_resident_slices(
        [_resident_view(r, 8) for r in same_length])
    np.testing.assert_array_equal(perm, [2, 0, 1, 3])


def test_one_program_whatever_the_lengths_say():
    """The length rides in the sort's last key beside the arrival order, so
    rows of one length and rows of several run the SAME compiled program
    (the ladder compiled one with the length pass and one without) and
    both come out in key order."""
    rng = random.Random(5)
    keys = sorted(f"w{rng.randrange(10 ** 4):07d}".encode()
                  for _ in range(500)) * 2          # two equal runs
    partitions = np.zeros(len(keys), dtype=np.int32)
    mixed = [b"w"] + keys[:499] + keys[500:]        # still two sorted runs

    device._merge_sort._compiled.clear()
    for rows in (keys, mixed):
        lanes, lengths = _key_columns(rows, 8)
        np.testing.assert_array_equal(
            device.merge_runs(partitions, lanes, lengths),
            sorted(range(len(rows)), key=rows.__getitem__))
    assert device._merge_sort.cache_size() == 1


def test_resident_merge_is_one_program_for_uniform_and_mixed_runs():
    """merge_sorted_runs hands the resident views over as they are: no
    host pass over the runs' key lengths decides which program runs."""
    def resident_run(keys):
        batch = KVBatch.from_pairs([(k, b"v") for k in keys])
        batch.dev_keys = _resident_view(keys, 8)
        return Run(batch, np.array([0, len(keys)], dtype=np.int64))

    device._fused_resident_merge._compiled.clear()
    for keys_a in ([b"aaaa", b"cccc"], [b"a", b"cccc"]):
        merged = merge_sorted_runs(
            [resident_run(keys_a), resident_run([b"bbbb", b"cccc"])], 1, 8,
            engine="device", device_min_records=0)
        assert [merged.batch.key(i) for i in range(4)] == \
            sorted(keys_a + [b"bbbb", b"cccc"])
    assert device._fused_resident_merge.cache_size() == 1


def _sort_operands(lowered_text):
    """Operands of every sort operation in a lowered module."""
    import re
    return [len(args.split(",")) for args in re.findall(
        r'"stablehlo\.sort"\(([^)]*)\)', lowered_text)]


def _sort_body_rows(num_lanes, one_length, seed):
    """512 rows for the sort body: duplicates at every lane, zero-length
    keys beside "\\0", and both kinds of padding rows."""
    rng = np.random.default_rng(seed)
    n, real = 512, 401
    cap = num_lanes * 4 + 1
    # a three-value alphabet and all-0xFF lanes: duplicates at every lane
    lanes = rng.choice(np.array([0, 1, 0xFFFFFFFF], dtype=np.uint32),
                       size=(n, num_lanes), p=[0.45, 0.45, 0.1])
    partitions = rng.integers(0, 3, n).astype(np.int32)
    if one_length:
        lengths = np.full(n, num_lanes * 4, dtype=np.uint32)
    else:
        lengths = rng.integers(0, cap + 1, n).astype(np.uint32)
        # zero-length keys beside "\0" and "\0\0": equal (all-zero) lanes,
        # only the length tells them apart
        lanes[:60] = 0
        lengths[:60] = rng.integers(0, 3, 60)
    # padding rows as the two staging paths write them: partition MAX, and
    # lanes/lengths either all-ones (resident) or zero/at the cap (host-fed)
    partitions[real:] = np.iinfo(np.int32).max
    lanes[real:450] = 0xFFFFFFFF
    lengths[real:450] = 0xFFFFFFFF
    lanes[450:] = 0
    return partitions, lanes, lengths, real


def _assert_sort_body_is_lexsort(partitions, lanes, lengths, real):
    """`_lsd_passes` is jitted afresh: a cached Kernel, or jit's own cache
    of the function, would hand back what it traced first.  Returns the
    lowered text."""
    fn = jax.jit(lambda p, l, n_: device._lsd_passes(p, l, n_))
    sp, perm, s_lanes, s_lens = (np.asarray(x) for x in fn(
        partitions, lanes, lengths))
    want = np.lexsort(                    # stable; the last key is primary
        [lengths] + [lanes[:, i] for i in range(lanes.shape[1] - 1, -1, -1)]
        + [partitions.astype(np.uint32)])
    np.testing.assert_array_equal(perm, want)
    np.testing.assert_array_equal(sp, partitions[want])
    np.testing.assert_array_equal(s_lanes, lanes[want])
    np.testing.assert_array_equal(s_lens, lengths[want].astype(np.int32))
    assert (sp[real:] == np.iinfo(np.int32).max).all()
    return fn.lower(partitions, lanes, lengths).as_text()


@pytest.mark.parametrize("one_length", [False, True])
@pytest.mark.parametrize("num_lanes", [1, 2, 3, 4, 6])
def test_sort_body_is_lexsort_and_returns_the_sorted_columns(
        num_lanes, one_length):
    """The one sort body every sort, merge, match and probe program traces
    gives numpy's stable lexsort by (partition, lanes..., length) -- the
    same partitions, the same permutation -- and the key columns it returns
    are the columns gathered by that permutation: rows of one length and
    of several, duplicates, zero-length keys, both kinds of padding."""
    text = _assert_sort_body_is_lexsort(*_sort_body_rows(
        num_lanes, one_length, 100 * num_lanes + one_length))
    # length and arrival order share the last key: partition + lanes + 1
    assert _sort_operands(text) == [num_lanes + 2]


def test_sort_body_where_length_and_row_number_do_not_share_a_key(
        monkeypatch):
    """More rows than the last key has bits for beside the length code: the
    length and the arrival order are an operand each, same answer."""
    monkeypatch.setattr(device, "_TAIL_KEY_BITS", 12)   # 512 rows need 9
    text = _assert_sort_body_is_lexsort(*_sort_body_rows(2, False, 9))
    assert _sort_operands(text) == [2 + 3]


def _six_programs():
    """name -> (traced function, arguments at two lanes, sort operands):
    the six programs the cells launch.  A span sort keeps its partition
    column; a merge, the match and the probe sort by key alone."""
    lanes = np.zeros((256, 2), np.uint32)
    lens = np.zeros(256, np.int32)
    splits = (np.zeros((3, 2), np.uint32), np.zeros(3, np.int32))
    two_sides = (lanes, lens, lanes, lens)
    return {
        "resident_hash_sort": (
            lambda a, b: device._fused_resident_hash_sort_impl(a, b, 4),
            (lanes, lens), 4),
        "fused_resident_range_sort": (
            device._fused_resident_range_sort_impl, (lanes, lens) + splits, 4),
        "resident_merge_sort": (
            device._fused_resident_merge_impl, ([lanes] * 4, [lens] * 4), 3),
        "merge_sort": (
            device._merge_sort_impl, (lens, lanes, lens.astype(np.uint32)), 4),
        "join_match": (device._join_match_impl, two_sides, 3),
        "join_probe": (device._join_probe_impl, two_sides, 3),
    }


@pytest.mark.parametrize("name", sorted(_six_programs()))
def test_each_program_is_one_sort_and_gathers_nothing(name):
    """The lowered text of each of the six programs holds ONE sort
    operation, of as many operands as its key columns and the shared last
    key, and no gather: the sorted columns are the sort's outputs.  (The
    ladder lowered to L+2 sorts with a gather before each and two after.)"""
    fn, args, operands = _six_programs()[name]
    text = jax.jit(fn).lower(*args).as_text()
    assert _sort_operands(text) == [operands]
    assert "stablehlo.gather" not in text
    assert "dynamic_slice" not in text


def test_compile_span_and_log_carry_the_sort_ops_of_the_lowered_module():
    """The witness of which sort body a program traced: `sort_ops` on the
    `kernel.compile` span and in COMPILE_LOG, documented with the span."""
    import os
    from tez_tpu.common import tracing
    from tests.trace_schema import undocumented_span_args
    rng = np.random.default_rng(7)
    lanes = rng.integers(0, 9, (300, 2)).astype(np.uint32)
    device._merge_sort._compiled.clear()
    logged = len(device.COMPILE_LOG)
    tracing.clear_all()
    tracing.arm("sort-ops-test")
    try:
        device.merge_runs(np.zeros(300, np.int32), lanes,
                          np.full(300, 8, np.int32))
        spans = tracing.snapshot()
    finally:
        tracing.clear_all()
    (compiled,) = [s for s in spans if s.name == "kernel.compile"]
    assert compiled.args["kernel"] == "merge_sort"
    assert compiled.args["sort_ops"] == 1
    (entry,) = device.COMPILE_LOG[logged:]
    name, _sig, secs, sort_ops, t_done = entry
    assert (name, sort_ops) == ("merge_sort", 1)
    # benchmarks/run.py counts the window's compiles by the LAST field
    assert secs < 1e6 < t_done
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "observability.md")).read()
    assert undocumented_span_args("kernel.compile", compiled.args,
                                  doc) == set()
    assert undocumented_span_args("kernel.compile", {"made_up": 1},
                                  doc) == {"made_up"}
