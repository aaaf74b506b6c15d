"""The broadcast hash join on the batch path, at CPU sizes: the vector DAG
through TezClient against the benchmark generator's plain reference and
against the query-layer plan, and each piece the deployment forced -- the
probe kernel, the probe blocks, the unordered writer's batch path, the
unordered input's batch reader -- against plain Python."""
from __future__ import annotations

import importlib.util
import os
import threading
import time

import numpy as np
import pytest

from tez_tpu.api.events import ShufflePayload
from tez_tpu.client.dag_client import DAGStatusState
from tez_tpu.client.tez_client import TezClient
from tez_tpu.common.counters import TezCounters
from tez_tpu.examples import hash_join
from tez_tpu.library.inputs import ShuffleFetchTable
from tez_tpu.library.join import hash_join_blocks
from tez_tpu.library.partitioners import HashPartitioner
from tez_tpu.library.unordered import (StreamingKVReader,
                                       UnorderedPartitionedWriter)
from tez_tpu.ops import device
from tez_tpu.ops.keycodec import encode_keys
from tez_tpu.ops.runformat import KVBatch, Run
from tez_tpu.shuffle.service import local_shuffle_service

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the device engine forced, as the configuration's rehearse_conf does: on a
#: CPU backend `auto` means the host engine
DEVICE = {"tez.runtime.sorter.class": "device",
          "tez.runtime.tpu.device.sort.min.records": 0}
DATA = {"key_letters": 13, "parts": 4, "overlap_every": 2}
KWARGS = {"stream_parallelism": 4, "hash_parallelism": 1, "num_joiners": 4,
          "key_width": 24}


@pytest.fixture(scope="module")
def join_keys():
    spec = importlib.util.spec_from_file_location(
        "join_keys", os.path.join(ROOT, "benchmarks", "generators",
                                  "join_keys.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _batch(keys, values=None):
    """Keys (bytes, in the order given) as a KVBatch; zero-width values
    unless given."""
    values = [b""] * len(keys) if values is None else values
    cols = []
    for col in (keys, values):
        offsets = np.zeros(len(col) + 1, dtype=np.int64)
        np.cumsum([len(x) for x in col], out=offsets[1:])
        cols += [np.frombuffer(b"".join(col), dtype=np.uint8).copy(), offsets]
    return KVBatch(*cols)


def _keys_of(batch):
    return [batch.key(i) for i in range(batch.num_records)]


def _random_keys(rng, n, lo=1, hi=23, alphabet=4):
    return [bytes(rng.integers(97, 97 + alphabet, int(w), dtype=np.uint8))
            for w in rng.integers(lo, hi + 1, n)]


def reference(hash_keys, stream_keys):
    """The plain reference: HashJoinProcessor's set and walk."""
    held = set(hash_keys)
    return [k for k in stream_keys if k in held]


def _blocks(keys, size):
    return [_batch(keys[i:i + size]) for i in range(0, len(keys), size)]


def _joined(build, stream, **kw):
    counters = TezCounters()
    kw = {"key_width": 24, "engine": "device", "device_min_records": 0,
          "counters": counters, **kw}
    out = [k for batch in hash_join_blocks(build, stream, **kw)
           for k in _keys_of(batch)]
    return out, counters.to_dict().get("TaskCounter", {})


# ---------------------------------------------------------------------------
# the operator
# ---------------------------------------------------------------------------

def _cases():
    rng = np.random.default_rng(34)
    short = _random_keys(rng, 300, hi=3)        # duplicates on both sides
    wide = _random_keys(rng, 400, lo=17, hi=23, alphabet=26)
    prefixes = [b"ab" * k + b"a" * j for k in range(6) for j in range(3)]
    return {
        "duplicates_on_both_sides": (short[:120], short + short[:50]),
        "lengths_1_to_23": (_random_keys(rng, 300),
                            _random_keys(rng, 900)),
        "unequal_lengths_sharing_a_prefix": (prefixes[::2] +
                                             [b"abab" + b"\x00"],
                                             prefixes + prefixes),
        "empty_build": ([], wide),
        "empty_stream": (wide, []),
        "build_of_one_row": (wide[7:8], wide + wide[:20]),
        "all_match": (wide, wide[::-1] + wide[:50]),
        "no_match": (wide, [k + b"z" for k in wide if len(k) < 23]),
        "one_key_length": ([k for k in wide if len(k) == 20][::3],
                           [k for k in wide if len(k) == 20]),
    }


@pytest.mark.parametrize("engine", ["device", "host"])
@pytest.mark.parametrize("block_rows", [1 << 20, 64, 7])
@pytest.mark.parametrize("case", sorted(_cases()))
def test_hash_join_blocks_equals_the_reference(case, block_rows, engine):
    """Every stream occurrence the build side holds, in arrival order,
    whatever sizes the batches come in and wherever a block is cut (inside
    a run of equal keys among them)."""
    build, stream = _cases()[case]
    out, got = _joined(_blocks(build, 50), _blocks(stream, 130),
                       engine=engine, block_rows=block_rows)
    assert out == reference(build, stream)
    on_device = engine == "device" and bool(build) and bool(stream)
    launches = -(-len(stream) // block_rows) if on_device else 0
    assert got.get("JOIN_MATCH_LAUNCHES", 0) == launches
    assert got.get("JOIN_MATCH_ROWS", 0) == \
        (len(stream) + launches * len(build) if on_device else 0)
    assert got["JOIN_LEFT_RECORDS"] == (len(stream) if build else 0)
    assert got["JOIN_RIGHT_RECORDS"] == len(build)
    assert got["JOIN_OUTPUT_RECORDS"] == len(out)


@pytest.mark.parametrize("long_on", ["build", "stream"])
def test_a_key_past_the_lane_width_takes_the_host_twin(long_on):
    """Lanes hold whole keys on either engine: a 30-byte key on an edge of
    24 is compared in full on the host engine -- the whole join where the
    build side holds it, the block where a stream block does."""
    rng = np.random.default_rng(5)
    build = _random_keys(rng, 200, lo=17, hi=23)
    stream = build[::3] + _random_keys(rng, 400, lo=17, hi=23)
    long_key = b"k" * 30
    (build if long_on == "build" else stream).insert(40, long_key)
    stream.append(long_key[:24])          # shares the lanes' prefix
    out, got = _joined(_blocks(build, 80), _blocks(stream, 100),
                       block_rows=256)
    assert out == reference(build, stream)
    device_blocks = got.get("JOIN_MATCH_LAUNCHES", 0)
    assert device_blocks == (0 if long_on == "build" else 1)   # of two


def test_a_build_side_under_the_routing_floor_is_probed_on_the_host():
    build, stream = [b"a", b"b"], [b"b", b"c", b"b"]
    out, got = _joined([_batch(build)], [_batch(stream)],
                       device_min_records=1 << 16)
    assert out == [b"b", b"b"] and "JOIN_MATCH_LAUNCHES" not in got


def test_hash_join_blocks_is_semi_only():
    """Semi, and inner where the build side's keys are unique (a
    key-foreign-key join, tests/test_tpch_q3.py): every other kind of join
    raises."""
    for how in ("left_outer", "semi_distinct", "anti"):
        with pytest.raises(ValueError, match="semi or inner"):
            list(hash_join_blocks([], [], how=how))


# ---------------------------------------------------------------------------
# the probe
# ---------------------------------------------------------------------------

def _encoded(keys):
    b = _batch(keys)
    return encode_keys(b.key_bytes, b.key_offsets, 24)


def _device_probe(stream_lanes, stream_lens, build_lanes, build_lens):
    return device.join_probe(stream_lanes, stream_lens,
                             device.stage_join_build(build_lanes,
                                                     build_lens))


@pytest.mark.parametrize("probe", [_device_probe, device.join_probe_host],
                         ids=["device", "host"])
@pytest.mark.parametrize("case", sorted(c for c, (b, s) in _cases().items()
                                        if b and s))
def test_join_probe_gives_every_stream_row_the_build_holds(case, probe):
    build, stream = _cases()[case]
    hits = probe(*_encoded(stream), *_encoded(build))
    assert hits.dtype == np.int64
    held = set(build)
    assert hits.tolist() == [i for i, k in enumerate(stream) if k in held]


def test_join_probe_agrees_with_join_match_where_keys_are_unique():
    rng = np.random.default_rng(11)
    keys = sorted(set(_random_keys(rng, 600, lo=17, hi=23, alphabet=26)))
    stream, build = keys[::2] + keys[1::4], keys[1::4] + keys[3::4]
    stream.sort(), build.sort()
    sides = _encoded(stream) + _encoded(build)
    assert np.array_equal(_device_probe(*sides), device.join_match(*sides))
    # one key length over both sides: the same program, the host twin's hits
    same = [k for k in keys if len(k) == 20]
    sides = _encoded(same) + _encoded(same[::3])
    assert np.array_equal(_device_probe(*sides),
                          device.join_probe_host(*sides))


# ---------------------------------------------------------------------------
# the unordered output's batch path
# ---------------------------------------------------------------------------

def _records(rng, n):
    keys = _random_keys(rng, n, lo=1, hi=23, alphabet=26)
    return keys, [bytes(rng.integers(0, 256, int(w), dtype=np.uint8))
                  for w in rng.integers(0, 6, n)]


@pytest.mark.parametrize("with_values", [True, False])
@pytest.mark.parametrize("partitions", [4, 7, 1])
def test_write_batch_equals_the_per_record_writer(partitions, with_values):
    """A row's partition is HashPartitioner's, arrival order is kept inside
    a partition, and the batch writer's run is the per-record writer's."""
    rng = np.random.default_rng(partitions)
    keys, values = _records(rng, 3_000)
    if not with_values:
        values = [b""] * len(keys)
    by_batch = UnorderedPartitionedWriter(partitions, 1 << 30, TezCounters())
    for i in range(0, len(keys), 700):
        by_batch.write_batch(_batch(keys[i:i + 700], values[i:i + 700]))
    by_record = UnorderedPartitionedWriter(partitions, 1 << 30, TezCounters())
    for k, v in zip(keys, values):
        by_record.write(k, v)
    got, want = by_batch.flush(), by_record.flush()
    assert np.array_equal(got.row_index, want.row_index)
    assert list(got.batch.iter_pairs()) == list(want.batch.iter_pairs())
    part = HashPartitioner()
    for p in range(partitions):
        assert list(got.partition(p).iter_pairs()) == [
            (k, v) for k, v in zip(keys, values)
            if part.get_partition(k, v, partitions) == p]
    counted = by_batch.counters.to_dict()["TaskCounter"]
    assert counted["OUTPUT_RECORDS"] == len(keys)
    assert counted["UNORDERED_PARTITION_RECORDS"] == len(keys)
    assert by_record.counters.to_dict()["TaskCounter"][
        "UNORDERED_PARTITION_RECORDS"] == 0


def test_one_partition_moves_nothing():
    """The broadcast output's one batch is handed on as it came."""
    rng = np.random.default_rng(1)
    batch = _batch(*_records(rng, 500))
    writer = UnorderedPartitionedWriter(1, 1 << 30, TezCounters())
    writer.write_batch(batch)
    run = writer.flush()
    assert run.batch is batch and run.row_index.tolist() == [0, 500]


def test_batches_and_records_share_a_span_and_spans_concatenate():
    rng = np.random.default_rng(2)
    keys, values = _records(rng, 900)
    writer = UnorderedPartitionedWriter(3, 4_000, TezCounters())
    for i in range(0, 900, 100):            # a span every few writes
        if i % 200:
            writer.write_batch(_batch(keys[i:i + 100], values[i:i + 100]))
        else:
            for k, v in zip(keys[i:i + 100], values[i:i + 100]):
                writer.write(k, v)
    run = writer.flush()
    assert writer.num_spills > 1
    part = HashPartitioner()
    for p in range(3):
        # inside a span its batches stand before its single records
        assert sorted(run.partition(p).iter_pairs()) == sorted(
            (k, v) for k, v in zip(keys, values)
            if part.get_partition(k, v, 3) == p)
    counted = writer.counters.to_dict()["TaskCounter"]
    assert counted["UNORDERED_PARTITION_RECORDS"] == 400
    assert counted["OUTPUT_RECORDS"] == 900


# ---------------------------------------------------------------------------
# the unordered input's batch reader
# ---------------------------------------------------------------------------

class _Ctx:
    def __init__(self):
        self.counters = TezCounters()
        self.conf = {}
        self.events = []

    def get_service_provider_metadata(self, name):
        return {"host": "local", "port": 0}

    def send_events(self, events):
        self.events.extend(events)

    def notify_progress(self):
        pass


@pytest.fixture
def fetch_table():
    service, made = local_shuffle_service(), []

    def produce(name, keys, spill=-1):
        made.append(name)
        service.register(name, spill, Run(
            _batch(keys), np.array([0, len(keys)], dtype=np.int64)))
        return ShufflePayload(host="local", port=0, path_component=name,
                              spill_id=spill, last_event=True)

    table = ShuffleFetchTable(_Ctx(), num_slots=3, my_partition=0)
    yield table, produce
    table.shutdown()
    for name in made:
        service.unregister_prefix(name)


class _Consumer(threading.Thread):
    """Reads iter_batches() on a thread of its own, stamping each batch."""

    def __init__(self, table):
        super().__init__(daemon=True)
        self.reader = StreamingKVReader(table, None, None, table.context)
        self.got, self.error = [], None

    def run(self):
        try:
            for batch in self.reader.iter_batches():
                self.got.append((time.perf_counter(), _keys_of(batch)))
        except Exception as e:              # read by the test
            self.error = e

    def wait_for(self, n):
        deadline = time.time() + 10
        while len(self.got) < n and time.time() < deadline:
            time.sleep(0.001)
        assert len(self.got) >= n, (len(self.got), self.error)


def test_iter_batches_yields_each_fetch_once_as_it_completes(
        fetch_table, monkeypatch):
    """Woken by the fetch table's condition: no sleep on the reader's
    thread, and a batch is in hand well inside the wait's own 0.2 s."""
    table, produce = fetch_table
    consumer = _Consumer(table)
    real_sleep, slept = time.sleep, []

    def sleep(seconds):
        if threading.current_thread() is consumer:
            slept.append(seconds)
        real_sleep(seconds)

    monkeypatch.setattr(time, "sleep", sleep)
    consumer.start()
    lags = []
    for slot in (2, 0, 1):                  # whichever fetch ends first
        real_sleep(0.25)                    # the reader is waiting by now
        t0 = time.perf_counter()
        table.on_payload(slot, 0, produce(f"hj-once-{slot}",
                                          [b"k%d" % slot] * (slot + 1)))
        consumer.wait_for(len(lags) + 1)
        lags.append(consumer.got[-1][0] - t0)
    consumer.join(timeout=10)
    assert not consumer.is_alive() and consumer.error is None
    assert [keys for _t, keys in consumer.got] == \
        [[b"k2"] * 3, [b"k0"], [b"k1"] * 2]
    assert slept == []
    assert max(lags) < 0.1, lags
    assert table.context.counters.to_dict()["TaskCounter"][
        "INPUT_RECORDS_PROCESSED"] == 6


def test_iter_batches_across_an_input_failed_event_and_a_refetch(
        fetch_table):
    """A slot reset before its batch was read yields the re-fetched version
    alone; one reset after it was read does not yield it twice; the reader
    ends only when every slot is complete again."""
    table, produce = fetch_table
    consumer = _Consumer(table)
    table.on_payload(0, 0, produce("hj-v0-slot0", [b"stale"]), version=0)
    table.on_input_failed(0, 0)             # before the reader saw it
    consumer.start()
    table.on_payload(1, 0, produce("hj-v0-slot1", [b"b"]), version=0)
    consumer.wait_for(1)
    table.on_input_failed(1, 0)             # after the reader saw it
    table.on_payload(2, 0, produce("hj-v0-slot2", [b"c"]), version=0)
    consumer.wait_for(2)
    time.sleep(0.05)
    assert consumer.is_alive()              # two slots still owed
    table.on_payload(0, 0, produce("hj-v1-slot0", [b"fresh"]), version=1)
    table.on_payload(1, 0, produce("hj-v1-slot1", [b"b"]), version=1)
    consumer.join(timeout=10)
    assert not consumer.is_alive() and consumer.error is None
    assert [keys for _t, keys in consumer.got] == [[b"b"], [b"c"],
                                                   [b"fresh"]]


def test_row_reader_is_built_on_the_batch_reader(fetch_table):
    from tez_tpu.ops.serde import get_serde
    table, produce = fetch_table
    for slot in range(3):
        table.on_payload(slot, 0, produce(f"hj-rows-{slot}",
                                          [b"r%d" % slot, b"s%d" % slot]))
    serde = get_serde("bytes")
    reader = StreamingKVReader(table, serde, serde, table.context)
    assert list(reader) == [(b"r%d" % s if i == 0 else b"s%d" % s, b"")
                            for s in range(3) for i in range(2)]


def test_a_failed_shuffle_ends_the_wait(fetch_table):
    table, _produce = fetch_table
    consumer = _Consumer(table)
    consumer.start()
    time.sleep(0.05)
    with table.lock:
        table.failed, table.diagnostics = True, "no such output"
        table.lock.notify_all()
    consumer.join(timeout=10)
    assert isinstance(consumer.error, RuntimeError)
    assert "no such output" in str(consumer.error)


# ---------------------------------------------------------------------------
# the DAG
# ---------------------------------------------------------------------------

def _run_dag(tmp_path, dag, conf):
    conf = {"tez.staging-dir": str(tmp_path / "staging"),
            "tez.runner.mode": "threads", **conf}
    with TezClient.create("hj", conf) as client:
        status = client.submit_dag(dag).wait_for_completion(timeout=240)
    assert status.state is DAGStatusState.SUCCEEDED, status.diagnostics
    return status.counters.to_dict()["TaskCounter"]


def _lines(out):
    return sorted(line for name in os.listdir(out)
                  if name.startswith("part-")
                  for line in open(os.path.join(out, name), "rb"))


def test_vector_dag_equals_the_reference_and_simple_mode(tmp_path, join_keys):
    """4 + 1 scanners, 4 joiners through TezClient local mode, device
    engine: JoinDataGen's keys against the generator's expected set, and
    the lines the query-layer plan writes."""
    left, right = 16_000, 2_000
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": left,
                               "right_keys": right}, seed=3000000034)
    out = str(tmp_path / "vector")
    counters = _run_dag(tmp_path / "v", hash_join.build_bench_dag(
        made["inputs"], out, mode="vector", **KWARGS), DEVICE)
    assert join_keys.compare(out, made["reference"]) == \
        {k: 0 for k in join_keys.LIMITS}
    # nothing sorted, nothing merged
    for name in ("DEVICE_SORT_RECORDS", "HOST_SORT_RECORDS",
                 "DEVICE_MERGE_RECORDS", "HOST_MERGE_RECORDS"):
        assert counters.get(name, 0) == 0, name
    # every key of both sides took the batch writer; each of the four
    # joiners held the whole hash side and probed its share of the stream
    assert counters["UNORDERED_PARTITION_RECORDS"] == made["records"] \
        == left + right
    assert counters["JOIN_LEFT_RECORDS"] == left
    assert counters["JOIN_RIGHT_RECORDS"] == 4 * right
    assert counters["JOIN_OUTPUT_RECORDS"] == right // 2
    assert counters["JOIN_MATCH_LAUNCHES"] == 4
    assert counters["JOIN_MATCH_ROWS"] == left + 4 * right
    simple = str(tmp_path / "simple")
    _run_dag(tmp_path / "s", hash_join.build_bench_dag(
        made["inputs"], simple, mode="simple", num_joiners=4), DEVICE)
    assert len(_lines(out)) == right // 2
    assert _lines(out) == _lines(simple)


def test_vector_dag_writes_every_stream_occurrence(tmp_path):
    """Data on which "every occurrence" and "once a key" differ: keys
    repeated on both sides, the hash side in two files read by one
    scanner."""
    rng = np.random.default_rng(8)
    pool = _random_keys(rng, 300, lo=1, hi=23, alphabet=26)
    stream = [pool[i] for i in rng.integers(0, 300, 5_000)]
    hashed = [pool[i] for i in rng.integers(0, 150, 400)] + [b"only_here"]
    for side, keys in (("left", stream), ("right", hashed)):
        os.makedirs(tmp_path / side)
        half = len(keys) // 2
        for n, part in enumerate((keys[:half], keys[half:])):
            (tmp_path / side / f"part-{n:05d}").write_bytes(
                b"".join(k + b"\n" for k in part))
    out = str(tmp_path / "out")
    counters = _run_dag(tmp_path, hash_join.build_bench_dag(
        [str(tmp_path / "left"), str(tmp_path / "right")], out,
        mode="vector", **{**KWARGS, "stream_parallelism": 2}), DEVICE)
    want = sorted(k + b"\t1\n" for k in reference(hashed, stream))
    assert _lines(out) == want and len(set(want)) < len(want)
    assert counters["JOIN_OUTPUT_RECORDS"] == len(want)


def test_bench_builder_tells_the_sides_by_directory(tmp_path):
    for side in ("left", "right"):
        os.makedirs(tmp_path / side)
        (tmp_path / side / "part-00000").write_text("k\n")
    by_dir = hash_join.build_bench_dag(
        [str(tmp_path / "right"), str(tmp_path / "left")], "out",
        mode="vector")
    by_file = hash_join.build_bench_dag(
        [str(tmp_path / "right" / "part-00000"),
         str(tmp_path / "left" / "part-00000")], "out", mode="vector")
    for dag, leaf in ((by_dir, ""), (by_file, "part-00000")):
        for vertex, side in hash_join.SIDES.items():
            source = dag.vertices[vertex].data_sources["input"]
            paths = source.initializer.payload.load()["paths"]
            assert paths == [os.path.join(str(tmp_path / side), leaf)
                             .rstrip("/")]
    with pytest.raises(KeyError):
        hash_join.build_bench_dag([str(tmp_path)], "out", mode="vector")


def test_the_two_edges_are_upstreams_kinds_at_the_dags_key_width(tmp_path):
    from tez_tpu.dag.edge_property import DataMovementType
    dag = hash_join.build_bench_dag([], "out", mode="vector", key_width=24)
    edges = {e.input_vertex.name: e.edge_property for e in dag.edges}
    assert edges["stream"].data_movement_type is \
        DataMovementType.SCATTER_GATHER
    assert edges["hashside"].data_movement_type is DataMovementType.BROADCAST
    for name, output in (("stream", "UnorderedPartitionedKVOutput"),
                         ("hashside", "UnorderedKVOutput")):
        prop = edges[name]
        assert prop.edge_source.class_name.endswith(":" + output)
        assert prop.edge_destination.class_name.endswith(":UnorderedKVInput")
        assert prop.edge_source.payload.load()[
            "tez.runtime.tpu.key.width.bytes"] == 24


def test_traced_spans_hang_under_the_dag_root_and_are_documented(
        tmp_path, join_keys):
    from tez_tpu.common import tracing
    from tests.test_tracing import _chains_end_in
    from tests.trace_schema import undocumented_spans
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": 8_000,
                               "right_keys": 1_000}, seed=5)
    tracing.clear_all()
    try:
        _run_dag(tmp_path, hash_join.build_bench_dag(
            made["inputs"], str(tmp_path / "out"), mode="vector", **KWARGS),
            {**DEVICE, "tez.trace.enabled": True,
             "tez.trace.buffer.spans": 65536})
        spans, dropped = tracing.snapshot(), tracing.dropped()
    finally:
        tracing.clear_all()
    assert dropped == 0
    (root,) = [s for s in spans if s.cat == "dag"]
    assert root.name == "dag:HashJoin"
    assert _chains_end_in(spans, root) == []
    names = {s.name for s in spans}
    assert {"unordered.partition", "join.build", "join.probe", "join.match",
            "join.emit", "kernel.join_probe", "processor.tokenize",
            "processor.format", "output.write", "shuffle.fetch"} <= names
    # nothing sorts and nothing merges
    assert not {n for n in names if n.startswith(
        ("sort.", "merge.", "device.", "kernel.resident", "kernel.merge"))}
    assert "shuffle.merge" not in names
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # four stream scanners partition; the broadcast output moves nothing
    assert {s.args["stage"] for s in by_name["unordered.partition"]} == \
        {"hash", "group", "gather"}
    assert len(by_name["unordered.partition"]) == 4 * 3
    assert {s.args["partitions"] for s in by_name["unordered.partition"]} \
        == {4}
    assert {s.args.get("stage") for s in by_name["join.match"]} == \
        {"encode", "stage", "launch", "readback"}
    assert {s.args["how"] for s in by_name["join.match"]} == {"semi"}
    assert len(by_name["join.build"]) == 4
    assert sum(s.args["rows"] for s in by_name["join.build"]) == 4 * 1_000
    assert sum(s.args.get("rows", 0) for s in by_name["join.probe"]) == 8_000
    # a joiner's waits for its two inputs stand under its build and probe
    holders = {s.span_id: s.name
               for s in by_name["join.build"] + by_name["join.probe"]}
    waits = [holders.get(s.parent_id) for s in by_name.get("shuffle.wait",
                                                           [])]
    assert set(waits) <= {"join.build", "join.probe"}   # where it waited
    doc = open(os.path.join(ROOT, "docs", "observability.md")).read()
    assert undocumented_spans(names, doc) == set()


def test_a_finished_dag_leaves_no_build_side_on_the_device(tmp_path,
                                                           join_keys):
    """Every joiner keeps the hash side's lanes on the device for its
    probes; once a DAG is done nothing may hold them."""
    import gc
    import jax
    made = join_keys.generate(
        str(tmp_path / "in"), {**DATA, "left_keys": 8_000,
                               "right_keys": 1_000}, seed=9)
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}
    conf = {"tez.staging-dir": str(tmp_path / "staging"),
            "tez.runner.mode": "threads", **DEVICE}
    with TezClient.create("hj", conf, session=True) as client:
        for n in range(2):
            status = client.submit_dag(hash_join.build_bench_dag(
                made["inputs"], str(tmp_path / f"out{n}"), mode="vector",
                **KWARGS)).wait_for_completion(timeout=240)
            assert status.state is DAGStatusState.SUCCEEDED
            gc.collect()
            left = [a.shape for a in jax.live_arrays()
                    if id(a) not in before]
            assert left == [], (n, left)
