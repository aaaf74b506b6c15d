"""TPC-H Q3 on the batch path, at CPU sizes: the DAG through TezClient
against the benchmark generator's plain reference on both engines, and each
piece the query forced -- the columnar scan, the semi-join that keeps its
values, the key-foreign-key probe and its host twin, the carried columns,
the int64 group sum -- against numpy or plain Python; the traced DAG's
spans and counters against docs/observability.md."""
from __future__ import annotations

import importlib.util
import os
import time

import numpy as np
import pytest

from tez_tpu.client.tez_client import TezClient
from tez_tpu.common import tracing
from tez_tpu.common.counters import TezCounters
from tez_tpu.examples import tpch_q3
from tez_tpu.library.aggregate import group_sum_blocks
from tez_tpu.library.inputs import (long_column, pack_rows, scan_rows,
                                    unpack_columns)
from tez_tpu.library.join import hash_join_blocks
from tez_tpu.ops import device
from tez_tpu.ops.keycodec import encode_keys
from tez_tpu.ops.runformat import KVBatch
from tez_tpu.ops.serde import decode_longs_be

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the device engine forced, as the configuration's rehearse_conf does: on a
#: CPU backend `auto` means the host engine
ENGINES = {"device": {"tez.runtime.sorter.class": "device",
                      "tez.runtime.tpu.device.sort.min.records": 0},
           "host": {"tez.runtime.sorter.class": "host"}}
KWARGS = {"customer_parallelism": 1, "orders_parallelism": 4,
          "lineitem_parallelism": 4, "num_joiners": 4}
QUERY = {"parts": 4, "segment": "BUILDING", "date": "1995-03-15"}


@pytest.fixture(scope="module")
def tpch():
    spec = importlib.util.spec_from_file_location(
        "tpch_q3_generator", os.path.join(ROOT, "benchmarks", "generators",
                                          "tpch_q3.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _session(tmp_path, engine, **conf):
    return TezClient.create("tpch-q3", {
        "tez.staging-dir": str(tmp_path / "staging"),
        "tez.runner.mode": "threads",
        "tez.runtime.tpu.host.spill.dir": str(tmp_path / "spill"),
        **ENGINES[engine], **conf}, session=True).start()


# ---------------------------------------------------------------------------
# the query, end to end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("data_seed", [3, 19, 2147483659])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_q3_through_tezclient_equals_the_reference(tmp_path, tpch, engine,
                                                   data_seed):
    """SF 0.01: every rank of the committed answer is the reference's, and
    on the device engine every probe ran on the device."""
    made = tpch.generate(str(tmp_path / "tables"),
                         {**QUERY, "scale_factor": 0.01,
                          "data_seed": data_seed}, data_seed + 1)
    client = _session(tmp_path, engine)
    try:
        out = str(tmp_path / "out")
        status = client.submit_dag(tpch_q3.build_bench_dag(
            made["inputs"], out, **KWARGS)).wait_for_completion(timeout=300)
    finally:
        client.stop()
    assert status.state.name == "SUCCEEDED", status.diagnostics
    assert tpch.compare(out, made["reference"]) == {
        "rows_wrong": 0, "rows_missing_or_extra": 0, "lines_malformed": 0,
        "commits_missing": 0}
    got = status.counters.to_dict()["TaskCounter"]
    assert got["SCAN_BYTES_READ"] == made["input_bytes"]
    assert got["AGG_GROUPS"] == len(made["reference"]["groups"])
    if engine == "device":
        # one launch a joiner, every row the reference sends it
        assert got["KFK_PROBE_LAUNCHES"] == 4
        assert got["KFK_PROBE_ROWS"] == made["records"]
        assert got["JOIN_MATCH_LAUNCHES"] == 4
    else:
        assert not {"KFK_PROBE_ROWS", "JOIN_MATCH_ROWS"} & set(got)


def test_the_reference_sums_past_int32(tpch, tmp_path):
    """The answer's revenues pass int32 ten-thousandths at SF 0.01 already:
    an int32 path cannot give them."""
    made = tpch.generate(str(tmp_path / "t"), {**QUERY, "scale_factor": 0.01,
                                                "data_seed": 3}, 1)
    top = made["reference"]["top"]
    assert len(top) == 10 and top[0][1] > np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------

class _Context:
    def __init__(self):
        self.counters = TezCounters()

    def notify_progress(self):
        pass


def test_columnar_scan_reads_the_named_columns_and_filters_like_numpy(
        tmp_path, tpch):
    from tez_tpu.io.formats import resolve_format
    made = tpch.generate(str(tmp_path / "t"), {**QUERY, "scale_factor": 0.002,
                                                "data_seed": 4}, 9)
    table = os.path.join(made["inputs"][2])            # lineitem
    fmt = resolve_format("columnar", {
        "table": "lineitem", "columns": {"l_orderkey": "<i4",
                                         "l_shipdate": "<i4"}})
    splits = fmt.compute_splits([table], 4)
    assert len(splits) == 4 and all(s.start == 0 for s in splits)
    ctx = _Context()
    parts = list(fmt.open(splits, ctx).iter_columns())
    assert all(set(p) == {"l_orderkey", "l_shipdate"} for p in parts)
    got = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
    want = {k: np.concatenate([np.fromfile(os.path.join(s.path, k), "<i4")
                               for s in splits]) for k in got}
    for k in got:
        np.testing.assert_array_equal(got[k], want[k])
    counters = ctx.counters.to_dict()["TaskCounter"]
    assert counters["SCAN_ROWS_READ"] == len(got["l_orderkey"])
    assert counters["SCAN_BYTES_READ"] == 8 * len(got["l_orderkey"])
    assert counters["SCAN_BYTES_READ"] == sum(s.length for s in splits)
    # a predicate and an expression over whole columns, packed as the edge
    date = tpch_q3.epoch_day("1995-03-15")
    batch = scan_rows(got, lambda c: c["l_shipdate"] > date,
                      lambda c: ([c["l_orderkey"]],
                                 [c["l_shipdate"] - date,
                                  long_column(c["l_orderkey"] * 1000)]),
                      ctx.counters)
    keep = got["l_shipdate"] > date
    key, = unpack_columns(batch.key_bytes, batch.key_offsets, ["<i4"])
    later, _long = unpack_columns(batch.val_bytes, batch.val_offsets,
                                  ["<i4", "<u8"])
    np.testing.assert_array_equal(key, got["l_orderkey"][keep])
    np.testing.assert_array_equal(later, got["l_shipdate"][keep] - date)
    vals = np.ascontiguousarray(batch.val_bytes.reshape(-1, 12)[:, 4:])
    np.testing.assert_array_equal(
        decode_longs_be(vals.reshape(-1), len(key)),
        got["l_orderkey"][keep].astype(np.int64) * 1000)
    # the key's bytes are big-endian: byte order is numeric order
    assert bytes(batch.key_bytes[:4]) == int(key[0]).to_bytes(4, "big")
    assert ctx.counters.to_dict()["TaskCounter"]["SCAN_ROWS_KEPT"] == \
        int(keep.sum())


def test_a_part_whose_columns_disagree_raises(tmp_path):
    from tez_tpu.io.formats import resolve_format
    part = tmp_path / "t" / "part-00000"
    part.mkdir(parents=True)
    np.arange(5, dtype="<i4").tofile(part / "a")
    np.arange(4, dtype="<i4").tofile(part / "b")
    fmt = resolve_format("columnar", {"columns": {"a": "<i4", "b": "<i4"}})
    with pytest.raises(ValueError, match="not one count"):
        list(fmt.open(fmt.compute_splits([str(tmp_path / "t")], 1),
                      _Context()).iter_columns())


# ---------------------------------------------------------------------------
# the joins
# ---------------------------------------------------------------------------

def _batch(keys, values=None):
    values = [b""] * len(keys) if values is None else values
    cols = []
    for col in (keys, values):
        offsets = np.zeros(len(col) + 1, dtype=np.int64)
        np.cumsum([len(x) for x in col], out=offsets[1:])
        cols += [np.frombuffer(b"".join(col), dtype=np.uint8).copy(),
                 offsets]
    return KVBatch(*cols)


def _rows(batches):
    return [(b.key(i), b.value(i)) for b in batches
            for i in range(b.num_records)]


def _blocks(keys, values, size):
    return [_batch(keys[i:i + size], values[i:i + size])
            for i in range(0, len(keys), size)]


def _keys(rng, n, lo=1, hi=9, alphabet=5):
    return [bytes(rng.integers(97, 97 + alphabet, int(w), dtype=np.uint8))
            for w in rng.integers(lo, hi + 1, n)]


def _join_case(name):
    rng = np.random.default_rng(42)
    build = list(dict.fromkeys(_keys(rng, 400)))
    stream = _keys(rng, 1500)                      # repeats, misses
    return {
        "repeats_and_misses": (build, stream),
        "every_stream_key_held": (build, build[::-1] + build[:40]),
        "no_stream_key_held": (build[:100], [k + b"!" for k in build]),
        "build_of_one_row": (build[3:4], stream + build[3:4] * 3),
    }[name]


JOIN_CASES = ("repeats_and_misses", "every_stream_key_held",
              "no_stream_key_held", "build_of_one_row")


@pytest.mark.parametrize("block_rows", [1 << 20, 100, 7])
@pytest.mark.parametrize("engine", ["device", "host"])
@pytest.mark.parametrize("case", JOIN_CASES)
def test_key_foreign_key_join_carries_the_build_rows_value(case, engine,
                                                           block_rows):
    """Every stream row the build side holds, in arrival order, its value
    followed by the value of the one build row of its key."""
    build, stream = _join_case(case)
    build_vals = [b"B%04d" % i for i in range(len(build))]
    stream_vals = [b"s%d" % i for i in range(len(stream))]
    counters = TezCounters()
    out = _rows(hash_join_blocks(
        _blocks(build, build_vals, 64), _blocks(stream, stream_vals, 130),
        how="inner", key_width=12, engine=engine, device_min_records=0,
        counters=counters, block_rows=block_rows))
    held = dict(zip(build, build_vals))
    assert out == [(k, v + held[k]) for k, v in zip(stream, stream_vals)
                   if k in held]
    got = counters.to_dict()["TaskCounter"]
    launches = -(-len(stream) // block_rows) if engine == "device" else 0
    assert got.get("KFK_PROBE_LAUNCHES", 0) == launches
    assert got.get("KFK_PROBE_ROWS", 0) == \
        (len(stream) + launches * len(build) if launches else 0)
    assert got.get("KFK_PROBE_STREAM_ROWS", 0) == (len(stream) if launches
                                                   else 0)
    assert got["KFK_OUTPUT_ROWS"] == got["JOIN_OUTPUT_RECORDS"] == len(out)
    assert "JOIN_MATCH_ROWS" not in got


@pytest.mark.parametrize("engine", ["device", "host"])
def test_key_foreign_key_join_refuses_a_repeated_build_key(engine):
    build = [b"a", b"bb", b"c", b"bb"]
    with pytest.raises(ValueError, match="unique"):
        list(hash_join_blocks([_batch(build)], [_batch([b"c", b"bb"])],
                              how="inner", key_width=4, engine=engine,
                              device_min_records=0))


@pytest.mark.parametrize("engine", ["device", "host"])
def test_semi_join_with_values_keeps_every_stream_rows_value(engine):
    """The semi-join's stream rows as they came, each with its own value;
    without `values` the same keys with zero-width values."""
    build, stream = _join_case("repeats_and_misses")
    stream_vals = [b"v%05d" % i for i in range(len(stream))]
    held = set(build)
    kw = {"how": "semi", "key_width": 12, "engine": engine,
          "device_min_records": 0, "block_rows": 256}
    out = _rows(hash_join_blocks([_batch(build)],
                                 _blocks(stream, stream_vals, 100),
                                 values=True, **kw))
    assert out == [(k, v) for k, v in zip(stream, stream_vals) if k in held]
    bare = _rows(hash_join_blocks([_batch(build)],
                                  _blocks(stream, stream_vals, 100), **kw))
    assert bare == [(k, b"") for k, _v in out]


@pytest.mark.parametrize("block_rows,launches", [(100, 0), (200, 2)])
def test_the_routing_floor_counts_the_launchs_rows_with_the_build(
        block_rows, launches):
    """A build side under the floor goes up with the first block whose rows
    and the build's reach it, and every later block is probed beside it;
    where none does, the host twin takes the join."""
    build = [b"k%03d" % i for i in range(50)]
    stream = [b"k%03d" % (i % 80) for i in range(300)]
    counters = TezCounters()
    out = _rows(hash_join_blocks(
        [_batch(build, [b"x"] * 50)], _blocks(stream, [b""] * 300, 64),
        how="inner", key_width=4, engine="device", device_min_records=200,
        counters=counters, block_rows=block_rows))
    assert out == [(k, b"x") for k in stream if k in set(build)]
    got = counters.to_dict()["TaskCounter"]
    # 100 + 50 rows stay under 200; 200 + 50 reach it, and 100 + 50 follow
    assert got.get("KFK_PROBE_LAUNCHES", 0) == launches
    assert got.get("KFK_PROBE_STREAM_ROWS", 0) == (300 if launches else 0)


# ---------------------------------------------------------------------------
# the probe and its twin
# ---------------------------------------------------------------------------

def _encoded(keys, width=12):
    b = _batch(keys)
    return encode_keys(b.key_bytes, b.key_offsets, width)


def _device_kfk(stream_lanes, stream_lens, build_lanes, build_lens):
    return device.kfk_probe(stream_lanes, stream_lens,
                            device.stage_kfk_build(build_lanes, build_lens))


@pytest.mark.parametrize("probe", [_device_kfk, device.kfk_probe_host],
                         ids=["device", "host"])
@pytest.mark.parametrize("case", JOIN_CASES)
def test_kfk_probe_gives_each_stream_row_its_build_row(case, probe):
    """The build row each stream row hit, -1 where none: repeated stream
    keys, misses, keys of every length up to the lanes', and the sentinel
    rows of the padding (the device's buckets) matching nothing."""
    build, stream = _join_case(case)
    hit = probe(*_encoded(stream), *_encoded(build))
    assert hit.dtype == np.int64 and hit.shape == (len(stream),)
    at = {k: i for i, k in enumerate(build)}
    assert hit.tolist() == [at.get(k, -1) for k in stream]


def test_kfk_probe_finds_no_sentinel_among_all_ones_keys():
    """A key whose lanes are all ones is the sentinel's lanes: its length
    still tells them apart."""
    ones = [b"\xff" * 4, b"\xff" * 8, b"\xff"]
    stream = ones + [b"\xff" * 2]
    for probe in (_device_kfk, device.kfk_probe_host):
        assert probe(*_encoded(stream, 8), *_encoded(ones, 8)).tolist() == \
            [0, 1, 2, -1]


@pytest.mark.parametrize("probe", [_device_kfk, device.kfk_probe_host],
                         ids=["device", "host"])
def test_kfk_probe_refuses_a_repeated_build_key(probe):
    with pytest.raises(ValueError, match="unique"):
        probe(*_encoded([b"a", b"b"]), *_encoded([b"b", b"a", b"b"]))


# ---------------------------------------------------------------------------
# the grouped sum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["device", "host"])
def test_group_sum_of_revenue_is_exact_past_int32(engine):
    """Sums past 2^31 (and past 2^32) come out exact whichever engine the
    fold starts on: a device table goes to the host before it could
    wrap."""
    rng = np.random.default_rng(8)
    keys = rng.integers(0, 40, 5000).astype(np.int32)
    vals = rng.integers(0, 1_050_000_000, 5000)
    batches = [pack_rows([keys[i:i + 700]], [long_column(vals[i:i + 700])])
               for i in range(0, 5000, 700)]
    (table,) = group_sum_blocks(batches, key_width=4, engine=engine,
                                device_min_records=0, block_rows=1024,
                                table_min_rows=256)
    got_keys, = unpack_columns(table.key_bytes, table.key_offsets, ["<i4"])
    sums = decode_longs_be(table.val_bytes, table.num_records)
    want = np.zeros(40, np.int64)
    np.add.at(want, keys, vals)
    np.testing.assert_array_equal(got_keys, np.flatnonzero(want))
    np.testing.assert_array_equal(sums, want[want > 0])
    assert sums.max() > 1 << 32


# ---------------------------------------------------------------------------
# the traced DAG
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_q3(tmp_path_factory, tpch):
    """Three Q3 DAGs back to back in one traced session, device engine
    forced, as the benchmark's loop submits them (the first warms up)."""
    tmp_path = tmp_path_factory.mktemp("q3trace")
    made = tpch.generate(str(tmp_path / "tables"),
                         {**QUERY, "scale_factor": 0.01, "data_seed": 7}, 8)
    tracing.clear_all()
    client = _session(tmp_path, "device", **{
        "tez.trace.enabled": True, "tez.trace.buffer.spans": 262144})
    dags = []
    try:
        for n in range(3):
            t_submit = time.time()
            handle = client.submit_dag(tpch_q3.build_bench_dag(
                made["inputs"], str(tmp_path / f"out{n}"), **KWARGS))
            status = handle.wait_for_completion(timeout=300)
            dags.append({"t_submit": t_submit, "t_done": time.time(),
                         "status": status})
    finally:
        client.stop()
    spans = [s for s in tracing.snapshot() if s.end is not None]
    dropped = tracing.dropped()
    tracing.clear_all()
    return dags, spans, dropped


def _doc():
    return open(os.path.join(ROOT, "docs", "observability.md")).read()


def test_traced_q3_spans_and_counters_are_documented(traced_q3):
    from tests.trace_schema import (undocumented_counters,
                                    undocumented_span_args,
                                    undocumented_spans)
    dags, spans, dropped = traced_q3
    assert dropped == 0
    assert all(d["status"].state.name == "SUCCEEDED" for d in dags)
    doc = _doc()
    names = {s.name for s in spans}
    assert undocumented_spans(names, doc) == set()
    assert {"scan.read", "scan.filter", "join.carry", "join.build",
            "join.probe", "join.match", "processor.project",
            "processor.topk", "kernel.kfk_probe",
            "kernel.join_probe"} <= names
    for name in ("scan.read", "scan.filter", "join.carry"):
        one = next(s for s in spans if s.name == name)
        assert undocumented_span_args(name, one.args, doc) == set(), name
    inner = {s.name for s in spans if s.args.get("how") == "inner"}
    assert {"join.build", "join.probe", "join.match"} <= inner
    counters = dags[-1]["status"].counters.to_dict()["TaskCounter"]
    scan_kfk = {k for k in counters if k.startswith(("SCAN_", "KFK_"))}
    assert len(scan_kfk) == 7
    assert undocumented_counters(scan_kfk, doc) == set()


def test_traced_q3_hangs_under_its_roots_and_its_path_guesses_nothing(
        traced_q3):
    from tez_tpu.tools.trace_export import critical_path
    dags, spans, _dropped = traced_q3
    by_id = {s.span_id: s for s in spans}
    roots = {s.trace_id for s in spans if s.cat == "dag"}
    assert len(roots) == 3
    for s in spans:
        if s.parent_id is None:
            assert s.cat in ("dag", "client", "host"), s
        else:
            assert s.parent_id in by_id and s.trace_id in roots, s
    window = dags[1:]
    periods = [(window[0]["t_submit"], window[1]["t_submit"]),
               (window[1]["t_submit"], window[1]["t_done"])]
    thread = next(s.thread for s in spans if s.name == "submit_dag")
    path = critical_path(spans, periods, thread=thread)
    assert path["miss"] == 0
    assert path["steps"]["guess"] == 0, path["steps"]
    assert {"scan.read", "scan.filter"} & set(path["by_name"])


def test_the_trace_tool_reads_the_scans_by_table_and_the_joins_counters(
        traced_q3):
    import sys
    spec = importlib.util.spec_from_file_location(
        "trace_window_check", os.path.join(ROOT, "tools",
                                           "trace_window_check.py"))
    saved = list(sys.path)
    try:
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
    finally:
        sys.path[:] = saved
    assert tool.kernel_programs()["kfk_probe"] == "_kfk_probe_impl"
    dags, spans, _dropped = traced_q3
    counters = dags[-1]["status"].counters.to_dict()["TaskCounter"]
    scans = tool.scan_tables(spans, 3)
    assert set(scans) == {"customer", "orders", "lineitem"}
    assert sum(t["rows_read"] for t in scans.values()) == \
        counters["SCAN_ROWS_READ"]
    assert sum(t["rows_kept"] for t in scans.values()) == \
        counters["SCAN_ROWS_KEPT"]
    assert sum(t["bytes_read"] for t in scans.values()) == \
        counters["SCAN_BYTES_READ"]
    assert tool.kfk_counters([{"counters": {"TaskCounter": counters}}]) == {
        k: float(counters[k]) for k in tool.KFK_COUNTERS}
