"""WordCount over the unordered edge on the batch path, at CPU sizes: the
group fold (ops/device.py ``group_sum`` and its host twin), the block loop
(library/aggregate.py ``group_sum_blocks``) and the whole ``mode="vector"``
DAG through TezClient, each against a plain reference -- ``np.unique`` on
the key bytes and ``np.add.at`` of the values in float64 -- exactly."""
from __future__ import annotations

import importlib.util
import os
import time

import numpy as np
import pytest

from tez_tpu.client.tez_client import TezClient
from tez_tpu.common import tracing
from tez_tpu.common.counters import TaskCounter, TezCounters
from tez_tpu.examples import wordcount
from tez_tpu.library import aggregate
from tez_tpu.library.aggregate import group_sum_blocks
from tez_tpu.ops import device
from tez_tpu.ops.keycodec import encode_keys
from tez_tpu.ops.runformat import KVBatch
from tez_tpu.ops.serde import decode_longs_be, encode_longs_be

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the device engine forced: on a CPU backend `auto` means the host engine
DEVICE = {"tez.runtime.sorter.class": "device",
          "tez.runtime.tpu.device.sort.min.records": 0}
HOST = {"tez.runtime.sorter.class": "host"}
#: a small table floor keeps the CPU's compiles small
SMALL = {"table_min_rows": 256, "device_min_records": 0}


def reference(keys, values):
    """The plain reference: one sum a distinct key, key-sorted."""
    uniq, inverse = np.unique(np.array(keys, dtype=object),
                              return_inverse=True)
    sums = np.zeros(len(uniq), dtype=np.float64)
    np.add.at(sums, inverse, np.asarray(values, dtype=np.float64))
    return list(uniq), [int(s) for s in sums]


def _batch(keys, values):
    """(key, 8-byte long) rows as a KVBatch, in the order given."""
    offsets = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    return KVBatch(np.frombuffer(b"".join(keys), dtype=np.uint8).copy(),
                   offsets, encode_longs_be(np.asarray(values, np.int64)),
                   np.arange(len(keys) + 1, dtype=np.int64) * 8)


def _pairs(batch):
    keys = [batch.key(i) for i in range(batch.num_records)]
    return keys, [int(v) for v in decode_longs_be(batch.val_bytes,
                                                  batch.num_records)]


def _rows(rng, case, n=3000):
    """Keys and values of one test case: ragged keys of 1-8 bytes; one key
    holding over half the rows; values other than 1."""
    keys = [bytes(rng.integers(97, 101, int(w), dtype=np.uint8))
            for w in rng.integers(1, 9, n)]
    values = np.ones(n, dtype=np.int64)
    if case == "hot_key":
        for i in np.flatnonzero(rng.random(n) < 0.6):
            keys[i] = b"hot"
    if case == "values":
        values = rng.integers(-1000, 100_000, n)
    return keys, values


#: (table keys, table values, block keys, block values) of the cases that
#: fold into a table already holding groups; the table comes in as the
#: reference's answer for its rows (distinct keys, key-sorted)
def _table_case(rng, case):
    if case == "repeats":
        # keys repeating inside the block and between table and block
        vocabulary = [b"k%d" % i for i in range(40)]
        table = [vocabulary[i] for i in rng.integers(0, 30, 200)]
        block = [vocabulary[i] for i in rng.integers(10, 40, 2500)]
        return (table, rng.integers(-50, 50, 200), block,
                rng.integers(-50, 50, 2500))
    if case == "ff_keys":
        # keys of 0xFF bytes, the 8-byte one with lanes all ones like a
        # sentinel's: never one group with the padding rows after them
        ff = [b"\xff" * w for w in (4, 5, 8)]
        block = [ff[i] for i in rng.integers(0, 3, 900)] + [b"\xfe"] * 100
        return ff[2:] + [b"\xff" * 7], [5, 6], block, np.arange(1000)
    if case == "lengths":
        # one set of lanes, told apart by the length alone
        shapes = [b"", b"\0", b"ab", b"ab\0", b"ab\0\0", b"ab\0\0\0\0\0\0"]
        block = [shapes[i] for i in rng.integers(0, 6, 1500)]
        return shapes[::2], [1, 2, 3], block, rng.integers(1, 9, 1500)
    if case == "all_sentinels":
        return [b"a", b"bb", b"\xff" * 8], [7, -3, 11], [], []
    assert case == "wraps"
    # 30 groups of +-2e9 each: the running sum wraps int32 many times
    # over, every group's own sum fits
    block = [b"g%02d" % (i % 30) for i in range(600)]
    values = [(1 if i % 30 < 20 else -1) * 100_000_000 for i in range(600)]
    return [b"g00", b"g29"], [47_483_647, -47_483_647], block, values


TABLE_CASES = ["repeats", "ff_keys", "lengths", "all_sentinels", "wraps"]


def _device_table(keys, sums, rows):
    """A device group table of `rows` rows holding the given distinct,
    key-sorted groups, sentinels after them."""
    lanes, lens = _encoded(keys) if keys else (np.zeros((0, 2), np.uint32),
                                              np.zeros(0, np.int32))
    return device.stage_group_block(lanes, lens, np.asarray(sums, np.int64),
                                    rows)


def _table_pairs(lanes, lens, sums):
    return _pairs(aggregate._table_batch(lanes, lens,
                                         np.asarray(sums, np.int64)))


def _encoded(keys):
    batch = _batch(keys, np.zeros(len(keys), np.int64))
    return encode_keys(batch.key_bytes, batch.key_offsets, 8)


@pytest.mark.parametrize("case", ["ragged", "hot_key", "values"]
                         + TABLE_CASES)
@pytest.mark.parametrize("engine", ["device", "host"])
def test_the_fold_of_one_block_is_the_reference(engine, case):
    rng = np.random.default_rng([7, len(case)])
    if case in TABLE_CASES:
        t_keys, t_values, keys, values = _table_case(rng, case)
        t_keys, t_sums = reference(t_keys, t_values)
    else:
        (keys, values), t_keys, t_sums = _rows(rng, case), [], []
    lanes, lens = _encoded(keys) if keys else (np.zeros((0, 2), np.uint32),
                                               np.zeros(0, np.int32))
    values = np.asarray(values, np.int64)
    if engine == "device":
        table = _device_table(t_keys, t_sums, 256 + 4096)
        out, count = device.group_sum(
            table, 256, device.stage_group_block(lanes, lens, values, 4096))
        got = device.group_table_rows(out, int(np.asarray(count)))
    else:
        t_lanes, t_lens = _encoded(t_keys) if t_keys else (
            np.zeros((0, 2), np.uint32), np.zeros(0, np.int32))
        got = device.group_sum_host(t_lanes, t_lens,
                                    np.asarray(t_sums, np.int64), lanes,
                                    lens, values)
    assert _table_pairs(*got) == reference(t_keys + list(keys),
                                           list(t_sums) + list(values))


def _fold_lowered(table_rows=256, block_rows=1024):
    """The fold's lowered text at a small shape, traced afresh (a Kernel's
    compiled signatures, or jit's cache of the function, would hand back
    what was traced first)."""
    import jax
    import jax.numpy as jnp
    s = jax.ShapeDtypeStruct
    rows = table_rows + block_rows
    return jax.jit(lambda *a: device._group_sum_impl(
        *a, table_rows=table_rows)).lower(
        s((rows, 2), jnp.uint32), s((rows,), jnp.int32), s((rows,), jnp.int32),
        s((block_rows, 2), jnp.uint32), s((block_rows,), jnp.int32),
        s((block_rows,), jnp.int32)).as_text()


def _sort_operands(text):
    import re
    return [len(args.split(",")) for args in re.findall(
        r'"stablehlo\.sort"\(([^)]*)\)', text)]


def test_the_fold_moves_no_column_by_a_gather():
    """Every column the fold needs in key order rides a sort as an operand:
    the first sort by (2 lanes, length) carries the values, the compaction
    sort by (not a run end, place, length code) the 2 lanes and the running
    sum.  Two sorts, no gather."""
    text = _fold_lowered()
    assert "stablehlo.gather" not in text
    assert "dynamic_gather" not in text
    assert text.count("stablehlo.sort") == 2
    assert _sort_operands(text) == [4, 4]


def test_a_fold_too_large_to_pack_the_length_carries_it(monkeypatch):
    """Where place and the length code do not fit the compaction key
    together, the length is an operand of its own: the same answer."""
    import jax
    monkeypatch.setattr(device, "_TAIL_KEY_BITS", 12)  # 1,280 rows need 11
    assert _sort_operands(_fold_lowered()) == [4, 5]
    rng = np.random.default_rng(5)
    t_keys, t_values, keys, values = _table_case(rng, "lengths")
    t_keys, t_sums = reference(t_keys, t_values)
    lanes, lens = _encoded(keys)
    fold = jax.jit(lambda *a: device._group_sum_impl(*a, table_rows=256))
    *out, count = fold(*_device_table(t_keys, t_sums, 256 + 1024),
                       *device.stage_group_block(lanes, lens, values, 1024))
    got = device.group_table_rows(out, int(np.asarray(count)))
    assert _table_pairs(*got) == reference(t_keys + keys,
                                           list(t_sums) + list(values))


def test_an_empty_block_leaves_the_table_as_it_was():
    rng = np.random.default_rng(3)
    keys, values = _rows(rng, "values", n=500)
    lanes, lens = _encoded(keys)
    table, count = device.group_sum(
        device.empty_group_table(256 + 512, 2), 256,
        device.stage_group_block(lanes, lens, values, 512))
    rows = int(np.asarray(count))
    none = np.zeros((0, 2), np.uint32), np.zeros(0, np.int32)
    again, count2 = device.group_sum(
        table, 512, device.stage_group_block(*none, np.zeros(0), 512))
    assert int(np.asarray(count2)) == rows
    assert _table_pairs(*device.group_table_rows(again, rows)) == \
        reference(keys, values)
    host = device.group_sum_host(*none, np.zeros(0, np.int64), *none,
                                 np.zeros(0, np.int64))
    assert [len(a) for a in host] == [0, 0, 0]


@pytest.mark.parametrize("blocks", [1, 2, 5])
@pytest.mark.parametrize("engine", ["device", "host"])
def test_a_table_carried_over_blocks_is_the_reference(engine, blocks):
    """Rows sorted by key, so that every block edge splits a key's run."""
    rng = np.random.default_rng([11, blocks])
    vocabulary, values = _rows(rng, "values", n=2000)
    keys = sorted(vocabulary[i] for i in rng.integers(0, 60, 2000))
    size = -(-len(keys) // blocks)
    edges = range(size, len(keys), size)
    assert all(keys[e - 1] == keys[e] for e in edges)
    batches = [_batch(keys[i:i + 700], values[i:i + 700])
               for i in range(0, len(keys), 700)]
    counters = TezCounters()
    out = list(group_sum_blocks(batches, key_width=8, engine=engine,
                                counters=counters, block_rows=size, **SMALL))
    assert len(out) == 1
    assert _pairs(out[0]) == reference(keys, values)
    launches = counters.find_counter(TaskCounter.AGG_LAUNCHES).value
    assert launches == (blocks if engine == "device" else 0)
    groups = counters.find_counter(TaskCounter.AGG_GROUPS).value
    assert groups == len(set(keys))
    if engine == "device":
        assert counters.find_counter(
            TaskCounter.AGG_INPUT_ROWS).value == len(keys)
        # every fold after the first counts the table it was handed again
        assert counters.find_counter(
            TaskCounter.AGG_FOLD_ROWS).value > len(keys) or blocks == 1


def test_an_empty_input_yields_nothing_and_launches_nothing():
    counters = TezCounters()
    assert list(group_sum_blocks([KVBatch.empty()], key_width=8,
                                 engine="device", counters=counters,
                                 **SMALL)) == []
    assert counters.find_counter(TaskCounter.AGG_LAUNCHES).value == 0


def test_sums_past_int32_go_on_on_the_host_and_stay_exact():
    """The device's sums are int32: a task whose values could pass it
    brings its table back and folds on in int64, never wrapping."""
    keys = [b"a", b"b", b"a", b"c"] * 300
    # a block of 300 rows sums to 1.26e9, two to more than 2^31 - 1
    values = np.full(len(keys), 2 ** 22, dtype=np.int64)
    counters = TezCounters()
    out = list(group_sum_blocks([_batch(keys, values)], key_width=8,
                                engine="device", counters=counters,
                                block_rows=300, **SMALL))
    expected = reference(keys, values)
    assert max(expected[1]) > device.GROUP_SUM_MAX
    assert _pairs(out[0]) == expected
    launched = counters.find_counter(TaskCounter.AGG_LAUNCHES).value
    assert launched == 1            # the first block on the device, then not


def test_a_key_wider_than_the_lanes_goes_on_on_the_host():
    keys = [b"ab", b"q", b"ab", b"xyz"] * 100 + \
        [b"ab", b"abcdefghijk", b"ab", b"xyz"] * 100
    values = np.arange(len(keys))
    counters = TezCounters()
    out = list(group_sum_blocks([_batch(keys[:400], values[:400]),
                                 _batch(keys[400:], values[400:])],
                                key_width=8, engine="device",
                                counters=counters, block_rows=300, **SMALL))
    assert _pairs(out[0]) == reference(keys, values)
    assert counters.find_counter(TaskCounter.AGG_LAUNCHES).value == 1


def test_values_of_another_width_raise_rather_than_count_rows():
    batch = _batch([b"a", b"b"], [1, 2])
    bad = KVBatch(batch.key_bytes, batch.key_offsets,
                  np.zeros(10, np.uint8), np.array([0, 4, 10], np.int64))
    with pytest.raises(ValueError, match="8-byte longs"):
        list(group_sum_blocks([bad], key_width=8, engine="host"))


def test_count_lines_are_what_python_writes():
    keys = [b"w0000001", b"x", b"longer-key"]
    values = [0, -12345, 10 ** 18 + 7]
    lines = wordcount.format_count_lines(_batch(keys, values), b"\t")
    assert lines.tobytes() == b"".join(b"%s\t%d\n" % (k, v)
                                       for k, v in zip(keys, values))


# ------------------------------------------------- the whole vector DAG

@pytest.fixture(scope="module")
def zipf_words():
    spec = importlib.util.spec_from_file_location(
        "zipf_words", os.path.join(ROOT, "benchmarks", "generators",
                                   "zipf_words.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def corpus(tmp_path_factory, zipf_words):
    """1 MiB of owc-1chip's words (zipf 1.2 over 2,000,000 w%07d)."""
    where = tmp_path_factory.mktemp("wc")
    made = zipf_words.generate(
        str(where / "corpus"), {"vocab": 2000000, "distribution": "zipf",
                                "zipf_a": 1.2, "parts": 4, "corpus_mib": 1,
                                "data_seed": 25}, 3000000001)
    return where, made


def _run(where, made, name, conf, **kw):
    out = str(where / name)
    with TezClient.create("wc-" + name, {
            "tez.staging-dir": str(where / ("staging-" + name)),
            "tez.runner.mode": "threads", **conf}) as client:
        status = client.submit_dag(wordcount.build_dag(
            made["inputs"], out, tokenizer_parallelism=2,
            summation_parallelism=3, **kw)).wait_for_completion(timeout=300)
    assert status.state.name == "SUCCEEDED", status.diagnostics
    counters = {k: v for group in status.counters.to_dict().values()
                for k, v in group.items()}
    return out, counters


def _part_lines(out):
    return sorted(line for name in os.listdir(out) if name.startswith("part")
                  for line in open(os.path.join(out, name), "rb"))


@pytest.fixture(scope="module")
def simple_lines(corpus):
    where, made = corpus
    out, _ = _run(where, made, "simple", HOST)
    return _part_lines(out)


@pytest.mark.parametrize("engine", ["device", "host"])
def test_the_vector_dag_counts_every_word_as_the_reference(
        corpus, zipf_words, simple_lines, engine):
    where, made = corpus
    out, counters = _run(where, made, "vector-" + engine,
                         DEVICE if engine == "device" else HOST,
                         mode="vector", key_width=8)
    numbers = zipf_words.compare(out, made["reference"])
    # no order is promised over the unordered edge
    numbers.pop("lines_out_of_order")
    assert not any(numbers.values()), numbers
    assert _part_lines(out) == simple_lines
    words = made["records"]
    assert counters["UNORDERED_PARTITION_RECORDS"] == words
    assert counters["AGG_GROUPS"] == int(
        np.count_nonzero(made["reference"]["counts"]))
    if engine == "device":
        assert counters["AGG_INPUT_ROWS"] == words
        assert counters["AGG_LAUNCHES"] >= 3
    else:
        assert "AGG_INPUT_ROWS" not in counters or \
            counters["AGG_INPUT_ROWS"] == 0
    assert not counters.get("DEVICE_SORT_RECORDS") and \
        not counters.get("DEVICE_MERGE_RECORDS")


@pytest.fixture(scope="module")
def traced_vector(corpus):
    """Two vector DAGs in one traced session, device engine forced."""
    where, made = corpus
    tracing.clear_all()
    conf = {"tez.staging-dir": str(where / "staging-traced"),
            "tez.runner.mode": "threads", **DEVICE,
            "tez.trace.enabled": True, "tez.trace.buffer.spans": 262144}
    client = TezClient.create("traced-wc", conf, session=True).start()
    dags = []
    try:
        for n in range(2):
            t_submit = time.time()
            handle = client.submit_dag(wordcount.build_bench_dag(
                made["inputs"], str(where / f"traced{n}"),
                tokenizer_parallelism=2, summation_parallelism=3,
                mode="vector", key_width=8))
            status = handle.wait_for_completion(timeout=300)
            dags.append({"t_submit": t_submit, "t_done": time.time(),
                         "status": status})
    finally:
        client.stop()
    spans = [s for s in tracing.snapshot() if s.end is not None]
    dropped = tracing.dropped()
    tracing.clear_all()
    return dags, spans, dropped


def test_the_traced_dag_names_its_spans_and_counters(traced_vector):
    from tests.trace_schema import (undocumented_counters,
                                    undocumented_span_args,
                                    undocumented_spans)
    dags, spans, dropped = traced_vector
    assert dropped == 0
    assert all(d["status"].state.name == "SUCCEEDED" for d in dags)
    doc = open(os.path.join(ROOT, "docs", "observability.md")).read()
    names = {s.name for s in spans}
    assert {"agg.fold", "agg.emit", "kernel.group_sum", "build"} <= names
    assert undocumented_spans(names, doc) == set()
    stages = {s.args.get("stage") for s in spans if s.name == "agg.fold"}
    assert stages == {"cut", "encode", "stage", "readback", "launch"}
    fold_args = set().union(*(s.args for s in spans if s.name == "agg.fold"))
    assert undocumented_span_args("agg.fold", fold_args - {"after"},
                                  doc) == set()
    assert undocumented_counters(
        [c.name for c in TaskCounter if c.name.startswith("AGG_")],
        doc) == set()
    assert undocumented_counters(["AGG_MADE_UP"], doc) == {"AGG_MADE_UP"}


def test_the_path_crosses_into_the_folds_without_a_guess(traced_vector):
    from tez_tpu.tools.trace_export import critical_path, path_class
    dags, spans, _dropped = traced_vector
    thread = next(s.thread for s in spans if s.name == "submit_dag")
    path = critical_path(spans, [(dags[1]["t_submit"], dags[1]["t_done"])],
                         thread=thread)
    assert path["miss"] == 0
    assert path["steps"]["guess"] == 0, path["steps"]
    assert "agg.fold" in path["by_name"] and "agg.emit" in path["by_name"]
    readback = next(s for s in spans if s.name == "agg.fold"
                    and s.args.get("stage") == "readback")
    assert path_class(readback) == "device wait"
    encode = next(s for s in spans if s.name == "agg.fold"
                  and s.args.get("stage") == "encode")
    assert path_class(encode) == "host work"


def test_the_trace_tool_counts_the_folds_beside_the_programs():
    spec = importlib.util.spec_from_file_location(
        "trace_window_check", os.path.join(ROOT, "tools",
                                           "trace_window_check.py"))
    import sys
    saved = list(sys.path)
    try:
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
    finally:
        sys.path[:] = saved
    assert tool.kernel_programs()["group_sum"] == "_group_sum_impl"
    dags = [{"counters": {"TaskCounter": {"AGG_LAUNCHES": 11,
                                          "AGG_FOLD_ROWS": 100,
                                          "AGG_GROUPS": 4}}},
            {"counters": {"TaskCounter": {"AGG_LAUNCHES": 9,
                                          "AGG_FOLD_ROWS": 60,
                                          "AGG_GROUPS": 4}}}]
    assert tool.agg_counters(dags) == {"AGG_LAUNCHES": 10.0,
                                       "AGG_FOLD_ROWS": 80.0,
                                       "AGG_INPUT_ROWS": 0.0,
                                       "AGG_GROUPS": 4.0}
