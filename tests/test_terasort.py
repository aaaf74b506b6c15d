"""TeraSort on the normal path, at CPU sizes: the DAG through TezClient
against the benchmark generator's plain reference, and each piece the
deployment forced -- the range-partition kernel, the sampler, the batch
forms of writer, reader and gather -- against numpy."""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

from tez_tpu.client.dag_client import DAGStatusState
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common.counters import TaskCounter, TezCounters
from tez_tpu.examples import terasort
from tez_tpu.library.partitioners import (SPLIT_POINTS, HashPartitioner,
                                          RoundRobinPartitioner,
                                          TotalOrderPartitioner,
                                          sample_split_points)
from tez_tpu.ops import device
from tez_tpu.ops.keycodec import (encode_keys, encode_split_keys,
                                  range_partitions)
from tez_tpu.ops.runformat import KVBatch, gather_ragged
from tez_tpu.ops.sorter import DeviceSorter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the device engine forced, as the configuration's rehearse_conf does: on a
#: CPU backend `auto` means the host engine
DEVICE = {"tez.runtime.sorter.class": "device",
          "tez.runtime.tpu.device.sort.min.records": 0}
DATA = {"record_bytes": 100, "key_bytes": 10, "parts": 16, "partitions": 4}


@pytest.fixture(scope="module")
def gensort():
    spec = importlib.util.spec_from_file_location(
        "gensort_records", os.path.join(ROOT, "benchmarks", "generators",
                                        "gensort_records.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _keys(rng, n, width=10):
    """Seeded keys with the corners in: all-zero, all-0xFF, duplicates."""
    mat = rng.integers(0, 256, (n, width), dtype=np.uint8)
    mat[0] = 0
    mat[1] = 255
    mat[2:6] = mat[6:10]                     # duplicate keys
    return mat


def _batch(mat, vals=None):
    n, w = mat.shape
    vals = np.zeros((n, 0), np.uint8) if vals is None else vals
    return KVBatch(np.ascontiguousarray(mat).reshape(-1),
                   np.arange(n + 1, dtype=np.int64) * w,
                   np.ascontiguousarray(vals).reshape(-1),
                   np.arange(n + 1, dtype=np.int64) * vals.shape[1])


def _searchsorted_partitions(mat, splits):
    """The oracle: numpy.searchsorted over the keys as Python bytes (object
    arrays compare as bytes do: no NUL is stripped)."""
    keys = np.array([row.tobytes() for row in mat], dtype=object)
    return np.searchsorted(np.array(splits, dtype=object), keys,
                           side="right").astype(np.int32)


def _splits(mat, num_partitions):
    """P-1 sorted split keys, some of them keys of `mat` themselves."""
    if num_partitions == 1:
        return []
    rows = sorted(row.tobytes() for row in mat)
    step = len(rows) / num_partitions
    return [rows[round(step * i)] for i in range(1, num_partitions)]


# ---------------------------------------------------------------------------
# the DAG
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("records,sort_mb", [(1 << 14, 64), (1 << 16, 1)])
def test_terasort_dag_equals_the_reference(tmp_path, gensort, records,
                                           sort_mb):
    """4 x 4 through TezClient local mode, device engine: one span a mapper,
    and two to three with a final merge; every guarantee of the generator's
    comparison holds and the device did the sorting."""
    made = gensort.generate(str(tmp_path / "in"), {**DATA, "records": records},
                            seed=3000000019)
    out = str(tmp_path / "out")
    conf = {**DEVICE, "tez.staging-dir": str(tmp_path / "staging"),
            "tez.runner.mode": "threads", "tez.runtime.io.sort.mb": sort_mb}
    with TezClient.create("tera", conf) as client:
        status = client.submit_dag(terasort.build_dag(
            made["inputs"], out, map_parallelism=4, reduce_parallelism=4,
            sample_keys=10_000)).wait_for_completion(timeout=120)
    assert status.state is DAGStatusState.SUCCEEDED, status.diagnostics
    assert gensort.compare(out, made["reference"]) == \
        {k: 0 for k in gensort.LIMITS}
    parts = sorted(f for f in os.listdir(out) if f.startswith("part-"))
    assert len(parts) == 4
    # the sampled split points balance the reducers (uniform keys)
    sizes = [os.path.getsize(os.path.join(out, f)) // 100 for f in parts]
    assert min(sizes) > records // 4 * 0.8
    counters = status.counters.to_dict()["TaskCounter"]
    assert counters["DEVICE_SORT_RECORDS"] == records
    assert counters.get("HOST_SORT_RECORDS", 0) == 0
    assert counters["DEVICE_MERGE_RECORDS"] > 0
    assert counters["PAYLOAD_GATHER_BYTES"] >= 100 * records


def test_reference_controls_each_read_not_correct(tmp_path, gensort):
    made = gensort.generate(str(tmp_path / "in"), {**DATA, "records": 1 << 12},
                            seed=7)
    sound = str(tmp_path / "sound")
    gensort.reference_output(sound, made["reference"])
    assert gensort.compare(sound, made["reference"]) == \
        {k: 0 for k in gensort.LIMITS}
    caught_by = {"record_dropped": "records_lost_or_invented",
                 "payload_swapped": "records_lost_or_invented",
                 "unordered": "records_out_of_order",
                 "hash_partitioned": "partitions_out_of_order",
                 "committed_twice": "records_lost_or_invented"}
    assert set(caught_by) == set(gensort.CONTROLS)
    for broken, number in caught_by.items():
        out = str(tmp_path / broken)
        gensort.reference_output(out, made["reference"], broken)
        assert gensort.compare(out, made["reference"])[number] > 0, broken


# ---------------------------------------------------------------------------
# the range partition: kernel, host twin, per-record form
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_partitions", [1, 4])
def test_range_partitions_equal_searchsorted(num_partitions):
    """Device kernel, host twin and get_partition against numpy.searchsorted
    on seeded 10-byte keys: keys equal to a split point (they go to the
    partition above it), duplicates, all-zero and all-0xFF keys."""
    mat = _keys(np.random.default_rng(11), 4096)
    splits = _splits(mat, num_partitions)
    want = _searchsorted_partitions(mat, splits)
    if splits:
        equal = [i for i, row in enumerate(mat) if row.tobytes() in splits]
        assert equal and all(
            want[i] == splits.index(mat[i].tobytes()) + 1 for i in equal)
    batch = _batch(mat)
    assert np.array_equal(
        range_partitions(batch.key_bytes, batch.key_offsets, splits), want)
    lanes, lengths = encode_keys(batch.key_bytes, batch.key_offsets, 10)
    split_lanes, split_lengths = encode_split_keys(splits, 12)
    got = device._range_partitions(lanes, lengths, split_lanes,
                                   split_lengths)
    assert np.array_equal(np.asarray(got), want)
    partitioner = TotalOrderPartitioner(splits)
    assert [partitioner.get_partition(row.tobytes(), None, num_partitions)
            for row in mat[:64]] == list(want[:64])


def test_range_partitions_order_keys_of_unequal_length():
    """(lanes, length) order is raw-byte order: a key sorts above its own
    prefix, and a zero byte counts."""
    keys = [b"", b"a", b"a\x00", b"a\x00\x00b", b"ab", b"b"]
    splits = [b"a", b"a\x00", b"ab"]
    data = np.frombuffer(b"".join(keys), dtype=np.uint8)
    offsets = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    want = [0, 1, 2, 2, 3, 3]
    assert list(range_partitions(data, offsets, splits)) == want
    lanes, lengths = encode_keys(data, offsets, 4)
    got = device._range_partitions(lanes, lengths,
                                   *encode_split_keys(splits, 4))
    assert list(np.asarray(got)) == want


@pytest.mark.parametrize("num_partitions", [1, 4])
def test_fused_range_sort_equals_host_lexsort(num_partitions):
    """The fused kernel's permutation is the stable host lexsort of
    (partition, key), and its partitions come back sorted."""
    mat = _keys(np.random.default_rng(13), 3000)        # 3000 -> bucket 4096
    splits = _splits(mat, num_partitions)
    batch = _batch(mat)
    lanes, lengths = encode_keys(batch.key_bytes, batch.key_offsets, 10)
    sp, perm, dev = device.sort_span_resident(
        lanes, lengths, num_partitions, encode_split_keys(splits, 12))
    parts = _searchsorted_partitions(mat, splits)
    columns = [lanes[:, i] for i in range(lanes.shape[1] - 1, -1, -1)]
    want = np.lexsort(columns + [parts])                # stable, major last
    assert np.array_equal(perm, want)
    assert np.array_equal(sp, parts[want])
    out_lanes, _out_lengths, lo, hi = dev
    assert (lo, hi) == (0, len(mat))
    assert np.array_equal(np.asarray(out_lanes)[:hi], lanes[want])


def test_sorter_range_path_is_the_device_resident_one():
    """DeviceSorter(partitioner="range") takes the fused kernel (no host
    partition pass), and the host engine gives the same run."""
    rng = np.random.default_rng(17)
    mat = _keys(rng, 5000)
    vals = rng.integers(0, 256, (5000, 90), dtype=np.uint8)
    splits = _splits(mat, 4)
    runs = {}
    for engine in ("device", "host"):
        counters = TezCounters()
        sorter = DeviceSorter(num_partitions=4, partitioner="range",
                              split_points=splits, engine=engine,
                              device_min_records=0, counters=counters)
        sorter.write_batch(_batch(mat, vals))
        run = sorter.flush()
        runs[engine] = run
        sorted_counter = TaskCounter.DEVICE_SORT_RECORDS \
            if engine == "device" else TaskCounter.HOST_SORT_RECORDS
        assert counters.find_counter(sorted_counter).value == 5000
    assert runs["device"].batch.dev_keys is not None
    for field in ("key_bytes", "val_bytes"):
        assert np.array_equal(getattr(runs["device"].batch, field),
                              getattr(runs["host"].batch, field))
    assert np.array_equal(runs["device"].row_index, runs["host"].row_index)
    parts = _searchsorted_partitions(mat, splits)
    assert list(np.diff(runs["device"].row_index)) == \
        list(np.bincount(parts, minlength=4))
    with pytest.raises(ValueError, match="split points"):
        DeviceSorter(num_partitions=4, partitioner="range",
                     split_points=splits[:1])


# ---------------------------------------------------------------------------
# the sampler
# ---------------------------------------------------------------------------

def test_sampler_is_deterministic_and_its_split_points_sorted(tmp_path,
                                                             gensort):
    made = gensort.generate(str(tmp_path / "in"), {**DATA, "records": 1 << 14},
                            seed=23)
    splits = sample_split_points(made["inputs"], 10, 90, 4, sample_keys=4000)
    assert splits == sample_split_points(made["inputs"], 10, 90, 4,
                                         sample_keys=4000)
    assert len(splits) == 3 and all(len(s) == 10 for s in splits)
    assert splits == sorted(splits)
    # uniform keys: the quartiles of the sample are near those of the bytes
    assert [s[0] for s in splits] == pytest.approx([64, 128, 192], abs=12)
    again = gensort.generate(str(tmp_path / "again"),
                             {**DATA, "records": 1 << 14}, seed=23)
    assert sample_split_points(again["inputs"], 10, 90, 4, 4000) == splits
    assert sample_split_points(made["inputs"], 10, 90, 1, 4000) == []
    with pytest.raises(ValueError, match="not sorted"):
        TotalOrderPartitioner([b"b", b"a"])


# ---------------------------------------------------------------------------
# batch forms: writer, reader, gather, raw output
# ---------------------------------------------------------------------------

class _Payload:
    def __init__(self, payload):
        self._payload = payload

    def load(self):
        return self._payload


class _Context:
    """The least of an output's context that initialize() and a writer
    touch."""

    def __init__(self, tmp_path, payload):
        from tez_tpu.common.ids import DAGId, TaskAttemptId, TaskId, VertexId
        self.conf = {**DEVICE, "tez.runtime.tpu.host.spill.dir":
                     str(tmp_path / "spill")}
        self.user_payload = _Payload(payload)
        self.counters = TezCounters()
        self.work_dirs = [str(tmp_path)]
        self.destination_vertex_name = "reduce"
        self.task_index = 0
        self.task_attempt_id = TaskAttemptId(
            TaskId(VertexId(DAGId("app_1_1", 1), 0), 0), 0)

    def request_initial_memory(self, *args, **kwargs):
        pass

    def get_service_provider_metadata(self, name):
        return None

    def notify_progress(self):
        pass


class SecondByte(RoundRobinPartitioner):
    """An arbitrary custom partitioner: no batch form."""

    def get_partition(self, key, value, num_partitions):
        return key[1] % num_partitions


class HashOfSecondByte(HashPartitioner):
    """A stock partitioner's subclass with a get_partition of its own: the
    batch form belongs to the class that owns get_partition, not to its
    heirs."""

    def get_partition(self, key, value, num_partitions):
        return key[1] % num_partitions


class LooksLikeOne:
    """No Partitioner base, no from_conf: called a record all the same."""

    def get_partition(self, key, value, num_partitions):
        return key[1] % num_partitions


@pytest.mark.parametrize("partitioner,accepted", [
    ("tez_tpu.library.partitioners:HashPartitioner", True),
    ("tez_tpu.library.partitioners:TotalOrderPartitioner", True),
    ("tests.test_terasort:SecondByte", False),
    ("tests.test_terasort:HashOfSecondByte", False),
    ("tests.test_terasort:LooksLikeOne", False)])
def test_write_batch_goes_by_the_partitioners_batch_form(tmp_path,
                                                         partitioner,
                                                         accepted):
    from tez_tpu.library.outputs import OrderedPartitionedKVOutput
    mat = _keys(np.random.default_rng(29), 512)
    splits = _splits(mat, 4)
    out = OrderedPartitionedKVOutput(
        _Context(tmp_path, {"tez.runtime.partitioner.class": partitioner,
                            SPLIT_POINTS: splits}), 4)
    out.initialize()
    writer = out.get_writer()
    assert writer.supports_batch is accepted
    if not accepted:
        with pytest.raises(ValueError, match="batch form"):
            writer.write_batch(_batch(mat))
        # the per-record path holds, and it is the class's own partition
        for row in mat:
            writer.write(row.tobytes(), b"v")
        run = out.sorter.flush()
        assert list(np.diff(run.row_index)) == list(
            np.bincount(mat[:, 1] % 4, minlength=4))
        return
    writer.write_batch(_batch(mat))
    run = out.sorter.flush()
    want = _searchsorted_partitions(mat, splits) if "Total" in partitioner \
        else np.array([HashPartitioner().get_partition(r.tobytes(), None, 4)
                       for r in mat])
    assert list(np.diff(run.row_index)) == list(np.bincount(want,
                                                            minlength=4))


def test_fixed_width_batch_reader_equals_the_per_record_reader(tmp_path):
    """A file whose size is no multiple of the granule (nor of the record):
    the batches' rows are the per-record reader's, in order, and the
    counters agree."""
    from tez_tpu.io.formats import FixedWidthKVFormat
    rec, n = 100, 1037
    data = np.random.default_rng(31).integers(0, 256, n * rec + 7,
                                              dtype=np.uint8)
    path = tmp_path / "records.bin"
    data.tofile(path)
    fmt = FixedWidthKVFormat({"key_bytes": 10, "value_bytes": 90})
    splits = fmt.compute_splits([str(path)], 3, min_split_bytes=rec)
    assert sum(s.length for s in splits) == n * rec
    ctx_a, ctx_b = _Context(tmp_path, None), _Context(tmp_path, None)
    pairs = list(fmt.open(splits, ctx_a))
    batches = list(fmt.open(splits, ctx_b).iter_chunks(chunk_bytes=4096))
    assert len(batches) > len(splits)              # several granules a split
    assert all(b.num_records * rec <= 4096 for b in batches)
    assert [p for b in batches for p in b.iter_pairs()] == pairs
    assert len(pairs) == n
    rows = data[:n * rec].reshape(n, rec)
    assert pairs[5] == (rows[5, :10].tobytes(), rows[5, 10:].tobytes())
    for ctx in (ctx_a, ctx_b):
        assert ctx.counters.find_counter(
            TaskCounter.INPUT_RECORDS_PROCESSED).value == n


@pytest.mark.parametrize("width", [90, 64, 65, 200])
def test_gather_of_wide_fixed_rows_equals_fancy_indexing(width):
    """Rows past the old 64-byte cap take the same strided native gather
    (2^14 rows x 90 B is over the native floor)."""
    rng = np.random.default_rng(37)
    n = 1 << 14
    rows = rng.integers(0, 256, (n, width), dtype=np.uint8)
    perm = rng.permutation(n)[:n - 5]
    data, offsets = gather_ragged(rows.reshape(-1),
                                  np.arange(n + 1, dtype=np.int64) * width,
                                  perm)
    assert np.array_equal(data.reshape(-1, width), rows[perm])
    assert np.array_equal(offsets,
                          np.arange(len(perm) + 1, dtype=np.int64) * width)


def test_part_writer_writes_raw_records_from_a_batch(tmp_path):
    from tez_tpu.io.file_output import _PartWriter
    rng = np.random.default_rng(41)
    mat = _keys(rng, 300)
    vals = rng.integers(0, 256, (300, 90), dtype=np.uint8)
    path = str(tmp_path / "out" / "part-00000")
    writer = _PartWriter(path, None, None, _Context(tmp_path, None))
    writer.write_batch(_batch(mat, vals))
    writer.write_batch(_batch(mat[:0], vals[:0]))
    writer.close()
    assert np.array_equal(np.fromfile(path, np.uint8).reshape(-1, 100),
                          np.hstack([mat, vals]))


def test_sorted_blocks_computes_no_group_boundaries(tmp_path, monkeypatch):
    """The identity reducer's reader path never runs group detection; a
    grouped consumer of the same reader still gets its groups, once."""
    from tez_tpu.library import inputs
    from tez_tpu.ops.serde import BytesSerde
    mat = np.sort(_keys(np.random.default_rng(43), 64), axis=0)
    calls = []
    compute = inputs.GroupedKVReader._compute_groups
    monkeypatch.setattr(
        inputs.GroupedKVReader, "_compute_groups",
        staticmethod(lambda *a, **k: calls.append(1) or compute(*a, **k)))
    reader = inputs.GroupedKVReader(_batch(mat), BytesSerde(), BytesSerde(),
                                    _Context(tmp_path, None))
    blocks = list(reader.sorted_blocks())
    assert len(blocks) == 1 and blocks[0].num_records == 64 and not calls
    _batch_, starts = reader.grouped_batch()
    assert len(starts) <= 64 and len(calls) == 1
    list(reader.grouped_blocks())
    assert len(calls) == 1
