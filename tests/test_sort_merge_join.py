"""The sort-merge join on the batch path, at CPU sizes: the vector DAG
through TezClient against the benchmark generator's plain reference and
against the query-layer plan, and each piece the deployment forced -- the
match kernel, the block alignment, six-lane keys on the resident span path,
zero-width values through sort, spill and merge -- against numpy and plain
Python."""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

from tez_tpu.client.dag_client import DAGStatusState
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common.counters import TaskCounter, TezCounters
from tez_tpu.examples import sort_merge_join
from tez_tpu.library.join import merge_join_blocks
from tez_tpu.ops import device
from tez_tpu.ops.keycodec import (encode_keys, matrix_to_lanes,
                                  pad_to_matrix)
from tez_tpu.ops.runformat import FileRun, KVBatch, Run
from tez_tpu.ops.sorter import DeviceSorter, merge_sorted_runs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the device engine forced, as the configuration's rehearse_conf does: on a
#: CPU backend `auto` means the host engine
DEVICE = {"tez.runtime.sorter.class": "device",
          "tez.runtime.tpu.device.sort.min.records": 0}
DATA = {"key_letters": 13, "parts": 4, "overlap_every": 2}
KWARGS = {"side_parallelism": 4, "num_joiners": 4, "key_width": 24}


@pytest.fixture(scope="module")
def join_keys():
    spec = importlib.util.spec_from_file_location(
        "join_keys", os.path.join(ROOT, "benchmarks", "generators",
                                  "join_keys.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _batch(keys):
    """Keys (bytes, in the order given) as a KVBatch with zero-width
    values."""
    offsets = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    return KVBatch(np.frombuffer(b"".join(keys), dtype=np.uint8).copy(),
                   offsets, np.zeros(0, np.uint8),
                   np.zeros(len(keys) + 1, np.int64))


def _keys_of(batch):
    return [batch.key(i) for i in range(batch.num_records)]


def _run_dag(tmp_path, dag, conf):
    conf = {"tez.staging-dir": str(tmp_path / "staging"),
            "tez.runner.mode": "threads", **conf}
    with TezClient.create("smj", conf) as client:
        status = client.submit_dag(dag).wait_for_completion(timeout=240)
    assert status.state is DAGStatusState.SUCCEEDED, status.diagnostics
    return status.counters.to_dict()["TaskCounter"]


# ---------------------------------------------------------------------------
# the DAG
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("left,right,sort_mb", [(16_000, 8_000, 64),
                                                (240_000, 120_000, 1)])
def test_vector_dag_equals_the_reference(tmp_path, join_keys, left, right,
                                         sort_mb):
    """4 + 4 x 4 through TezClient local mode, device engine: one span a
    scanner and both inputs merged in memory; then io.sort.mb 1, where the
    scanners sort several spans and both inputs of every joiner spill and
    stream, so that the join aligns blocks."""
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": left,
                               "right_keys": right}, seed=3000000019)
    out = str(tmp_path / "out")
    counters = _run_dag(
        tmp_path, sort_merge_join.build_bench_dag(
            made["inputs"], out, mode="vector", **KWARGS),
        {**DEVICE, "tez.runtime.io.sort.mb": sort_mb})
    assert join_keys.compare(out, made["reference"]) == \
        {k: 0 for k in join_keys.LIMITS}
    assert counters["DEVICE_SORT_RECORDS"] == made["records"] == left + right
    assert counters.get("HOST_SORT_RECORDS", 0) == 0
    assert counters["DEVICE_MERGE_RECORDS"] > 0
    assert counters["JOIN_LEFT_RECORDS"] == left
    assert counters["JOIN_RIGHT_RECORDS"] == right
    assert counters["JOIN_OUTPUT_RECORDS"] == right // 2
    # every row of both sides went through the device match, a joiner's in
    # one launch where its inputs are one block each
    assert counters["JOIN_MATCH_ROWS"] <= left + right
    assert counters["JOIN_MATCH_ROWS"] > 0.99 * (left + right)
    if sort_mb == 64:
        assert counters["JOIN_MATCH_LAUNCHES"] == 4
        assert counters.get("NUM_MEM_TO_DISK_MERGES", 0) == 0
    else:
        # every fetched run of either input passes a quarter of the
        # input's budget and goes to disk: both inputs stream in blocks
        assert counters["SHUFFLE_BYTES_TO_DISK"] > 0
        assert counters.get("SHUFFLE_BYTES_TO_MEM", 0) == 0
        assert counters["JOIN_MATCH_LAUNCHES"] > 8


def test_vector_mode_writes_what_simple_mode_writes(tmp_path, join_keys):
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": 2_000,
                               "right_keys": 1_000}, seed=11)
    lines = {}
    for mode in ("simple", "vector"):
        out = str(tmp_path / mode)
        kwargs = dict(KWARGS) if mode == "vector" else \
            {"side_parallelism": 4, "num_joiners": 4}
        _run_dag(tmp_path / mode, sort_merge_join.build_bench_dag(
            made["inputs"], out, mode=mode, **kwargs), DEVICE)
        lines[mode] = sorted(
            line for name in os.listdir(out) if name.startswith("part-")
            for line in open(os.path.join(out, name), "rb"))
    assert len(lines["vector"]) == 500
    assert lines["vector"] == lines["simple"]


def test_bench_builder_tells_the_sides_by_directory(tmp_path):
    for side in ("left", "right"):
        os.makedirs(tmp_path / side)
        (tmp_path / side / "part-00000").write_text("k\n")
    by_dir = sort_merge_join.build_bench_dag(
        [str(tmp_path / "right"), str(tmp_path / "left")], "out",
        mode="vector")
    by_file = sort_merge_join.build_bench_dag(
        [str(tmp_path / "right" / "part-00000"),
         str(tmp_path / "left" / "part-00000")], "out", mode="vector")
    for dag, leaf in ((by_dir, ""), (by_file, "part-00000")):
        for side in ("left", "right"):
            source = dag.vertices[side].data_sources["input"]
            paths = source.initializer.payload.load()["paths"]
            assert paths == [os.path.join(str(tmp_path / side), leaf)
                             .rstrip("/")]
    with pytest.raises(KeyError):
        sort_merge_join.build_bench_dag([str(tmp_path)], "out")


# ---------------------------------------------------------------------------
# the match
# ---------------------------------------------------------------------------

def _random_keys(rng, n, lo=1, hi=24, alphabet=4):
    return sorted(bytes(rng.integers(97, 97 + alphabet, int(w),
                                     dtype=np.uint8))
                  for w in rng.integers(lo, hi + 1, n))


def _cases():
    rng = np.random.default_rng(7)
    few = _random_keys(rng, 300, hi=3)          # duplicates on both sides
    more = _random_keys(rng, 500, hi=3)
    prefixes = sorted(b"ab" * k + b"a" * j for k in range(6)
                      for j in range(3))        # unequal lengths, one prefix
    wide = _random_keys(rng, 400, lo=17, hi=24, alphabet=26)
    return {
        "duplicates_on_either_side": (few, more),
        "unequal_lengths_sharing_a_prefix": (prefixes, prefixes[::2] +
                                             [b"abab" + b"\x00"]),
        "empty_left": ([], wide),
        "empty_right": (wide, []),
        "all_match": (wide, sorted(wide + wide[:50])),
        "no_match": (wide, [k + b"z" for k in wide if len(k) < 24]),
        "one_key_length": ([k for k in wide if len(k) == 20],
                           [k for k in wide if len(k) == 20][::3]),
    }


@pytest.mark.parametrize("match", [device.join_match,
                                   device.join_match_host],
                         ids=["device", "host"])
@pytest.mark.parametrize("case", sorted(_cases()))
def test_join_match_equals_intersect1d(case, match):
    left, right = (sorted(side) for side in _cases()[case])
    sides = ()
    for keys in (left, right):
        b = _batch(keys)
        sides += encode_keys(b.key_bytes, b.key_offsets, 24)
    hits = match(*sides)
    want = np.intersect1d(np.array(left, dtype=object),
                          np.array(right, dtype=object))
    assert [left[i] for i in hits] == list(want)       # once a key, in order
    assert hits.dtype == np.int64


@pytest.mark.parametrize("left_block,right_block", [(1, 1), (3, 7), (7, 3),
                                                    (64, 5), (10_000, 10_000)])
def test_merge_join_blocks_with_cuts_inside_runs_of_equal_keys(left_block,
                                                                right_block):
    """Streams whose blocks end inside a run of equal keys, a run that
    covers whole blocks among them: every key both sides hold comes out
    once, in order."""
    rng = np.random.default_rng(left_block * 100 + right_block)
    left = _random_keys(rng, 400, hi=2, alphabet=3) + [b"zz"] * 40
    right = [b"a"] * 25 + _random_keys(rng, 300, hi=2, alphabet=3) + \
        [b"zz"] * 9
    left.sort()
    right.sort()

    def blocks(keys, size):
        return (_batch(keys[i:i + size]) for i in range(0, len(keys), size))

    counters = TezCounters()
    out = [k for batch in merge_join_blocks(
        blocks(left, left_block), blocks(right, right_block), key_width=8,
        engine="device", device_min_records=0, counters=counters)
        for k in _keys_of(batch)]
    assert out == sorted(set(left) & set(right))
    got = counters.to_dict()["TaskCounter"]
    assert got["JOIN_OUTPUT_RECORDS"] == len(out)
    assert got["JOIN_LEFT_RECORDS"] <= len(left)
    assert got["JOIN_MATCH_ROWS"] > 0


@pytest.mark.parametrize("engine,min_records,width,on_device", [
    ("device", 0, 24, True),
    ("device", 1 << 16, 24, False),     # under the routing floor
    ("device", 0, 4, False),            # keys beyond the lanes: full compare
    ("host", 0, 24, False)])
def test_merge_join_routes_the_match(engine, min_records, width, on_device):
    rng = np.random.default_rng(3)
    left = _random_keys(rng, 200, lo=5, hi=9)
    right = left[::4] + _random_keys(rng, 50, lo=10, hi=12)
    right.sort()
    counters = TezCounters()
    out = [k for batch in merge_join_blocks(
        [_batch(left)], [_batch(right)], key_width=width, engine=engine,
        device_min_records=min_records, counters=counters)
        for k in _keys_of(batch)]
    assert out == sorted(set(left) & set(right))
    got = counters.to_dict()["TaskCounter"]
    assert (got.get("JOIN_MATCH_ROWS", 0) > 0) is on_device
    assert got.get("JOIN_MATCH_LAUNCHES", 0) == (1 if on_device else 0)


def test_merge_join_blocks_is_semi_distinct_only():
    with pytest.raises(ValueError, match="semi_distinct"):
        list(merge_join_blocks([], [], how="inner"))


# ---------------------------------------------------------------------------
# six lanes, zero-width values
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [10, 22, 24])
def test_ragged_keys_encode_natively_as_numpy_does(width):
    """A large span of keys of several lengths takes the native pass: the
    same lanes and lengths as pad_to_matrix + matrix_to_lanes."""
    rng = np.random.default_rng(width)
    lengths = rng.integers(1, 30, 80_000)
    offsets = np.zeros(len(lengths) + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    data = rng.integers(0, 256, int(offsets[-1]), dtype=np.uint8)
    assert data.nbytes >= 1 << 20
    mat, want_lengths = pad_to_matrix(data, offsets, width)
    lanes, got_lengths = encode_keys(data, offsets, width)
    assert np.array_equal(lanes, matrix_to_lanes(mat))
    assert np.array_equal(got_lengths, want_lengths)
    assert lanes.dtype == np.uint32 and got_lengths.dtype == np.int32


@pytest.mark.parametrize("longest,resident", [(23, True), (24, True),
                                              (25, False)])
def test_resident_span_path_holds_24_byte_keys_and_not_25(longest, resident):
    """At the edge's key width of 24 a span of keys up to 24 bytes sorts on
    the device-resident path (six lanes, sorted lanes kept in HBM); one
    25-byte key sends the span to the generic path."""
    rng = np.random.default_rng(longest)
    keys = [bytes(rng.integers(97, 123, int(w), dtype=np.uint8))
            for w in rng.integers(17, 24, 500)] + [b"k" * longest]
    batch = _batch(keys)
    sorter = DeviceSorter(num_partitions=4, key_width=24, engine="device",
                          device_min_records=0)
    encoded = sorter._resident_encode(batch, "device")
    assert (encoded is not None) is resident
    run = sorter.sort_batch(batch)
    if resident:
        assert encoded[0].shape == (501, 6)
        assert run.batch.dev_keys[0].shape[1] == 6
    else:
        assert run.batch.dev_keys is None
    for p in range(4):
        part = _keys_of(run.partition(p))
        assert part == sorted(part)
    assert sorted(_keys_of(run.batch)) == sorted(keys)
    assert int(run.batch.val_offsets[-1]) == 0


@pytest.mark.parametrize("engine", ["device", "host"])
def test_zero_width_values_through_sort_spill_from_bytes_and_merge(
        tmp_path, engine):
    rng = np.random.default_rng(5)
    keys = [bytes(rng.integers(97, 123, int(w), dtype=np.uint8))
            for w in rng.integers(17, 24, 6_000)]
    counters = TezCounters()
    sorter = DeviceSorter(num_partitions=3, key_width=24, engine=engine,
                          device_min_records=0, span_budget_bytes=64 << 10,
                          mem_budget_bytes=96 << 10,
                          spill_dir=str(tmp_path), counters=counters)
    for i in range(0, len(keys), 500):
        sorter.write_batch(_batch(keys[i:i + 500]))
    result = sorter.flush_run()
    assert isinstance(result, FileRun)                  # spans spilled
    assert counters.find_counter(
        TaskCounter.ADDITIONAL_SPILL_COUNT).value > 0
    run = result.to_run()
    assert run.batch.val_bytes.size == 0
    assert not run.batch.val_offsets.any()
    assert sorted(_keys_of(run.batch)) == sorted(keys)
    for p in range(3):
        part = _keys_of(run.partition(p))
        assert part == sorted(part)
    # the wire form and back, value column of width 0
    back = Run.from_bytes(run.to_bytes())
    assert _keys_of(back.batch) == _keys_of(run.batch)
    assert back.batch.val_bytes.size == 0
    assert np.array_equal(back.batch.val_offsets, run.batch.val_offsets)
    assert np.array_equal(back.row_index, run.row_index)
    # a merge of two such runs
    halves = [Run(_batch(sorted(keys[i::2])), np.array([0, 3_000]))
              for i in range(2)]
    merged = merge_sorted_runs(halves, 1, 24, engine=engine,
                               device_min_records=0)
    assert _keys_of(merged.batch) == sorted(keys)
    assert merged.batch.val_bytes.size == 0
    assert len(merged.batch.val_offsets) == len(keys) + 1


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def test_generator_writes_the_published_keys(tmp_path, join_keys):
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": 4_000,
                               "right_keys": 2_000}, seed=2147483659)
    sides = {}
    for side in ("left", "right"):
        sides[side] = [
            line for k in range(4) for line in open(os.path.join(
                str(tmp_path / "in"), side, f"part-{k:05d}"),
                "rb").read().split()]
    assert len(sides["left"]) == 4_000 and len(sides["right"]) == 2_000
    everything = sides["left"] + sides["right"]
    both = set(sides["left"]) & set(sides["right"])
    assert len(set(everything)) == len(everything) - len(both)   # unique
    assert both == made["reference"]["expected"] and len(both) == 1_000
    assert set(sides["right"][0::2]) == both        # every second right key
    for key in everything:
        letters, task, count = key.split(b"_")
        assert len(letters) == 13 and letters.isalpha()
        assert letters.islower() is (key in both)
        assert letters.islower() or letters.isupper()
        assert 0 <= int(task) < 4 and str(int(count)).encode() == count
    assert made["records"] == 6_000
    assert made["input_bytes"] == sum(len(k) + 1 for k in everything)
    again = join_keys.generate(
        str(tmp_path / "again"), {**DATA, "left_keys": 4_000,
                                  "right_keys": 2_000}, seed=2147483659)
    assert again["reference"]["expected"] == both


@pytest.mark.parametrize("broken,number", [
    (None, None), ("match_dropped", "keys_missing"),
    ("left_side_only", "keys_invented"),
    ("committed_twice", "keys_repeated")])
def test_reference_controls_each_read_not_correct(tmp_path, join_keys,
                                                  broken, number):
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": 4_000,
                               "right_keys": 2_000}, seed=7)
    assert set(join_keys.CONTROLS) == {"match_dropped", "left_side_only",
                                       "committed_twice"}
    out = str(tmp_path / "out")
    join_keys.reference_output(out, made["reference"], broken)
    numbers = join_keys.compare(out, made["reference"])
    others = {k: v for k, v in numbers.items() if k != number}
    assert not any(others.values()), others     # each through its own number
    if broken is not None:
        assert numbers[number] > 0
        assert numbers["keys_missing"] == (1 if broken == "match_dropped"
                                           else 0)


def test_comparison_counts_malformed_lines_and_missing_commits(tmp_path,
                                                               join_keys):
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": 400,
                               "right_keys": 200}, seed=1)
    out = str(tmp_path / "out")
    join_keys.reference_output(out, made["reference"])
    with open(os.path.join(out, "part-00000"), "ab") as fh:
        fh.write(b"no_tail\nhalf a line")
    os.remove(os.path.join(out, "_SUCCESS"))
    numbers = join_keys.compare(out, made["reference"])
    assert numbers["lines_malformed"] == 2 and numbers["commits_missing"] == 1
    assert numbers["keys_missing"] == numbers["keys_invented"] == 0


# ---------------------------------------------------------------------------
# the join's spans
# ---------------------------------------------------------------------------

def test_traced_join_spans_hang_under_the_dag_root_and_are_documented(
        tmp_path, join_keys):
    from tez_tpu.common import tracing
    from tests.test_tracing import _chains_end_in
    from tests.trace_schema import undocumented_spans
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": 8_000,
                               "right_keys": 4_000}, seed=5)
    tracing.clear_all()
    try:
        _run_dag(tmp_path, sort_merge_join.build_bench_dag(
            made["inputs"], str(tmp_path / "out"), mode="vector", **KWARGS),
            {**DEVICE, "tez.trace.enabled": True,
             "tez.trace.buffer.spans": 65536})
        spans, dropped = tracing.snapshot(), tracing.dropped()
    finally:
        tracing.clear_all()
    assert dropped == 0
    (root,) = [s for s in spans if s.cat == "dag"]
    assert root.name == "dag:SortMergeJoin"
    assert _chains_end_in(spans, root) == []
    names = {s.name for s in spans}
    assert {"join.wait_inputs", "join.align", "join.match", "join.emit",
            "kernel.join_match", "kernel.resident_merge_sort",
            "processor.tokenize", "processor.format"} <= names
    # the scanners' spans sort on the device-resident path
    assert any(n.startswith("kernel.resident_hash_sort") for n in names)
    assert "kernel.hash_sort" not in names
    assert {s.args.get("stage") for s in spans if s.name == "join.match"} \
        == {"encode", "stage", "launch", "readback"}
    # a joiner's span brackets both inputs' waits and merges
    waits = {s.span_id for s in spans if s.name == "join.wait_inputs"}
    assert len(waits) == 4
    for name in ("shuffle.wait", "shuffle.merge"):
        parents = [s.parent_id for s in spans if s.name == name]
        assert len(parents) == 8 and set(parents) == waits
    doc = open(os.path.join(ROOT, "docs", "observability.md")).read()
    assert undocumented_spans(names, doc) == set()


def test_a_finished_dag_leaves_no_key_lanes_on_the_device(tmp_path,
                                                          join_keys):
    """Both inputs' fetched runs are views of the producers' sorted key
    lanes in device memory; once a DAG is done nothing may hold them (a
    merge manager that kept its committed batches pinned 176 MB a DAG of
    the benchmark cell until the collector found its cycle)."""
    import gc
    import jax
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": 8_000,
                               "right_keys": 4_000}, seed=9)
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}
    conf = {"tez.staging-dir": str(tmp_path / "staging"),
            "tez.runner.mode": "threads", **DEVICE}
    with TezClient.create("smj", conf, session=True) as client:
        for n in range(2):
            status = client.submit_dag(sort_merge_join.build_bench_dag(
                made["inputs"], str(tmp_path / f"out{n}"), mode="vector",
                **KWARGS)).wait_for_completion(timeout=240)
            assert status.state is DAGStatusState.SUCCEEDED
            gc.collect()
            left = [a.shape for a in jax.live_arrays()
                    if id(a) not in before]
            assert left == [], (n, left)
