"""Byte-exactness tests for the device data plane (sort/partition/merge/run
format) against numpy/pure-Python goldens — the TestIFile/TestPipelinedSorter
analog (SURVEY.md §4 tier 1 'real byte paths')."""
import os
import random

import numpy as np
import pytest

from tez_tpu.library.partitioners import HashPartitioner
from tez_tpu.ops import device
from tez_tpu.ops.keycodec import encode_keys, matrix_to_lanes, pad_to_matrix
from tez_tpu.ops.runformat import KVBatch, Run
from tez_tpu.ops.serde import VarLongSerde, get_serde
from tez_tpu.ops.sorter import (DeviceSorter, merge_sorted_runs,
                                sum_long_combiner)


def random_pairs(n, seed=0, max_key=12, max_val=8):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        k = bytes(rng.randrange(256) for _ in range(rng.randrange(1, max_key)))
        v = bytes(rng.randrange(256) for _ in range(rng.randrange(0, max_val)))
        out.append((k, v))
    return out


def golden_sorted(pairs, num_partitions):
    hp = HashPartitioner()
    decorated = [(hp.get_partition(k, v, num_partitions), k, i, v)
                 for i, (k, v) in enumerate(pairs)]
    decorated.sort(key=lambda t: (t[0], t[1], t[2]))  # stable by arrival
    return decorated


def test_kvbatch_roundtrip():
    pairs = random_pairs(100)
    b = KVBatch.from_pairs(pairs)
    assert list(b.iter_pairs()) == pairs
    assert b.num_records == 100
    perm = np.arange(99, -1, -1)
    rev = b.take(perm)
    assert list(rev.iter_pairs()) == pairs[::-1]


def test_pad_and_lanes_order_preserving():
    keys = [b"a", b"ab", b"b", b"", b"a\x00", b"\xff" * 20]
    b = KVBatch.from_pairs([(k, b"") for k in keys])
    mat, lengths = pad_to_matrix(b.key_bytes, b.key_offsets, 16)
    lanes = matrix_to_lanes(mat)
    order = sorted(range(len(keys)),
                   key=lambda i: tuple(lanes[i].tolist()) + (i,))
    golden = sorted(range(len(keys)), key=lambda i: (keys[i][:16], i))
    assert order == golden


def test_device_hash_matches_host_partitioner():
    pairs = random_pairs(500, seed=1, max_key=40)
    b = KVBatch.from_pairs(pairs)
    hp = HashPartitioner()
    golden = np.array([hp.get_partition(k, None, 7) for k, _ in pairs])
    klens = b.key_offsets[1:] - b.key_offsets[:-1]
    w = 1 << max(2, (int(klens.max()) - 1).bit_length())
    mat, lengths = pad_to_matrix(b.key_bytes, b.key_offsets, w)
    got = device.hash_partition(mat, lengths, 7)
    np.testing.assert_array_equal(got, golden)


@pytest.mark.parametrize("n,width", [(1000, 16), (1000, 4), (0, 16), (1, 16)])
def test_device_sorter_byte_exact(n, width):
    pairs = random_pairs(n, seed=2, max_key=24)  # keys can exceed width=4/16
    sorter = DeviceSorter(num_partitions=5, key_width=width)
    for k, v in pairs:
        sorter.write(k, v)
    run = sorter.flush()
    golden = golden_sorted(pairs, 5)
    got = list(run.batch.iter_pairs())
    assert got == [(k, v) for _, k, _, v in golden]
    # partition index correct
    for p in range(5):
        part = run.partition(p)
        expected = [(k, v) for pp, k, _, v in golden if pp == p]
        assert list(part.iter_pairs()) == expected


def test_sorter_multi_span_merge():
    pairs = random_pairs(3000, seed=3)
    sorter = DeviceSorter(num_partitions=3, key_width=16,
                          span_budget_bytes=4096)  # force many spans
    for k, v in pairs:
        sorter.write(k, v)
    run = sorter.flush()
    assert sorter.num_spills > 1
    golden = golden_sorted(pairs, 3)
    assert list(run.batch.iter_pairs()) == [(k, v) for _, k, _, v in golden]


def test_sorter_host_spill(tmp_path):
    pairs = random_pairs(2000, seed=4)
    sorter = DeviceSorter(num_partitions=2, span_budget_bytes=2048,
                          spill_dir=str(tmp_path), mem_budget_bytes=4096)
    for k, v in pairs:
        sorter.write(k, v)
    # spans over the mem budget spill as partition-indexed files
    assert any(f.endswith(".prun") for f in os.listdir(tmp_path))
    run = sorter.flush()
    golden = golden_sorted(pairs, 2)
    assert list(run.batch.iter_pairs()) == [(k, v) for _, k, _, v in golden]
    # flush consumed and removed the span spills (the final FileRun was
    # materialized and deleted by the flush() compat shim)
    assert not any(f.endswith(".prun") for f in os.listdir(tmp_path))


def test_run_save_load_checksum(tmp_path):
    pairs = random_pairs(50, seed=5)
    sorter = DeviceSorter(num_partitions=4)
    for k, v in pairs:
        sorter.write(k, v)
    run = sorter.flush()
    p = str(tmp_path / "x.run")
    run.save(p)
    run2 = Run.load(p)
    assert list(run2.batch.iter_pairs()) == list(run.batch.iter_pairs())
    np.testing.assert_array_equal(run2.row_index, run.row_index)
    # corrupt -> checksum failure
    data = bytearray(open(p, "rb").read())
    data[-1] ^= 0xFF
    open(p, "wb").write(bytes(data))
    with pytest.raises(IOError, match="checksum"):
        Run.load(p)


def test_merge_sorted_runs_equals_single_sort():
    pairs = random_pairs(900, seed=6)
    chunks = [pairs[:300], pairs[300:600], pairs[600:]]
    runs = []
    for c in chunks:
        s = DeviceSorter(num_partitions=4)
        for k, v in c:
            s.write(k, v)
        runs.append(s.flush())
    merged = merge_sorted_runs(runs, 4, 16)
    # golden: all pairs, arrival order = chunk order (stability contract)
    golden = golden_sorted(pairs, 4)
    assert list(merged.batch.iter_pairs()) == \
        [(k, v) for _, k, _, v in golden]


def test_pipelined_spills_emitted():
    pairs = random_pairs(1000, seed=7)
    sorter = DeviceSorter(num_partitions=2, span_budget_bytes=4096)
    spills = []
    sorter.on_spill = lambda run, sid: spills.append((sid, run))
    for k, v in pairs:
        sorter.write(k, v)
    assert sorter.flush() is None
    assert len(spills) >= 2
    total = sum(r.batch.num_records for _, r in spills)
    assert total == 1000


def test_sum_long_combiner():
    serde = VarLongSerde()
    words = [b"a", b"b", b"a", b"c", b"a", b"b"]
    sorter = DeviceSorter(num_partitions=2, combiner=sum_long_combiner)
    for w in words:
        sorter.write(w, serde.to_bytes(1))
    run = sorter.flush()
    got = {k: serde.from_bytes(v) for k, v in run.batch.iter_pairs()}
    assert got == {b"a": 3, b"b": 2, b"c": 1}


def test_varlong_serde_order_and_values():
    s = VarLongSerde()
    vals = [-(2**62), -5, -1, 0, 1, 7, 2**62]
    encs = [s.to_bytes(v) for v in vals]
    assert encs == sorted(encs)
    assert [s.from_bytes(e) for e in encs] == vals


def test_empty_partition_flags():
    sorter = DeviceSorter(num_partitions=8)
    sorter.write(b"onlykey", b"v")
    run = sorter.flush()
    flags = run.empty_partition_flags()
    assert flags.count(False) == 1 and flags.count(True) == 7


def test_split_boundary_no_lost_or_duplicated_lines(tmp_path):
    """Every line is read by exactly one split, including lines starting
    exactly at a split boundary (LineRecordReader semantics)."""
    from tez_tpu.io.text import FileSplit, _LineReader, compute_splits

    from tez_tpu.common.counters import TezCounters

    class _Ctx:
        counters = TezCounters()

        def notify_progress(self):
            pass

    p = tmp_path / "t.txt"
    lines = [f"line{i:04d}" for i in range(1000)]
    p.write_text("\n".join(lines) + "\n")
    size = p.stat().st_size
    # brute-force every 2-way split point, including line boundaries
    for cut in list(range(1, size, 97)) + [9, 10, 11, 18, 19, 20, 21]:
        splits = [FileSplit(str(p), 0, cut), FileSplit(str(p), cut, size - cut)]
        got = []
        for s in splits:
            got.extend(l.decode() for _, l in _LineReader([s], _Ctx()))
        assert got == lines, f"cut={cut}"


def test_custom_partitioner_spi():
    """Explicit per-record partitions (a custom Partitioner's output over
    logical keys) route records instead of the device hash."""
    from tez_tpu.ops.sorter import DeviceSorter

    sorter = DeviceSorter(num_partitions=3)
    pairs = [(bytes([i % 7]) + b"key", b"v") for i in range(60)]
    for k, v in pairs:
        sorter.write(k, v, partition=k[0] % 3)
    run = sorter.flush()
    total = 0
    for p in range(3):
        for k, _ in run.partition(p).iter_pairs():
            assert k[0] % 3 == p
            total += 1
    assert total == 60


def test_multi_pass_merge_factor():
    """More runs than io.sort.factor merge hierarchically with identical
    output (TezMerger computeBytesInMerges semantics)."""
    from tez_tpu.ops.sorter import DeviceSorter, merge_sorted_runs
    pairs = random_pairs(600, seed=21)
    runs = []
    for i in range(0, 600, 60):   # 10 runs
        s = DeviceSorter(num_partitions=2)
        for k, v in pairs[i:i + 60]:
            s.write(k, v)
        runs.append(s.flush())
    one_pass = merge_sorted_runs(runs, 2, 16)
    multi = merge_sorted_runs(runs, 2, 16, merge_factor=3)
    assert list(one_pass.batch.iter_pairs()) == \
        list(multi.batch.iter_pairs())


def test_async_sortmaster_matches_sync():
    """Background span sorting produces the same result as inline."""
    from tez_tpu.ops.sorter import DeviceSorter
    pairs = random_pairs(2500, seed=22)
    outs = []
    for threads in (0, 2):
        s = DeviceSorter(num_partitions=3, span_budget_bytes=4096,
                         sort_threads=threads)
        for k, v in pairs:
            s.write(k, v)
        outs.append(s.flush())
    assert list(outs[0].batch.iter_pairs()) == \
        list(outs[1].batch.iter_pairs())


def test_fnv_partition_kernel_matches_bytewise_reference():
    """The XLA FNV kernel (the one hash path since the Pallas twin was
    removed — Mosaic refuses it, CHANGES.md PR 21) == a per-key Python
    FNV-1a, on a shape that is neither bucket- nor block-aligned."""
    from tez_tpu.parallel.exchange import fnv_bytes_host
    pairs = random_pairs(700, seed=31, max_key=24)
    b = KVBatch.from_pairs(pairs)
    klens = b.key_offsets[1:] - b.key_offsets[:-1]
    w = 1 << max(2, (int(klens.max()) - 1).bit_length())
    mat, lengths = pad_to_matrix(b.key_bytes, b.key_offsets, w)
    got = device.hash_partition(mat, lengths, 5)
    want = np.array([fnv_bytes_host(k) % 5 for k, _ in pairs], np.int32)
    np.testing.assert_array_equal(got, want)


def test_custom_comparator_sorter_and_merge():
    """Comparator-as-normalizer: ReverseByteKeyComparator sorts descending;
    merge honors the same order (reference: tez.runtime.key.comparator.class
    raw comparators, expressed as key normalization)."""
    from tez_tpu.library.comparators import ReverseByteKeyComparator
    from tez_tpu.ops.sorter import DeviceSorter, merge_sorted_runs
    norm = ReverseByteKeyComparator().normalize
    keys = [b"aaaa", b"zzzz", b"mmmm", b"bbbb", b"yyyy"]
    s = DeviceSorter(num_partitions=1, key_normalizer=norm)
    for k in keys:
        s.write(k, b"v")
    run = s.flush()
    got = [k for k, _v in run.batch.iter_pairs()]
    assert got == sorted(keys, reverse=True)       # descending
    # merge two descending runs stays descending
    s2 = DeviceSorter(num_partitions=1, key_normalizer=norm)
    for k in (b"cccc", b"xxxx"):
        s2.write(k, b"v")
    merged = merge_sorted_runs([run, s2.flush()], 1, 16, key_normalizer=norm)
    got = [k for k, _v in merged.batch.iter_pairs()]
    assert got == sorted(keys + [b"cccc", b"xxxx"], reverse=True)


def test_custom_comparator_long_keys_tiebreak():
    """Keys longer than the device prefix width still order exactly under a
    normalizer (the host tie-break pass compares NORMALIZED keys)."""
    from tez_tpu.library.comparators import ReverseByteKeyComparator
    from tez_tpu.ops.sorter import DeviceSorter
    norm = ReverseByteKeyComparator().normalize
    base = b"p" * 20     # beyond the 16-byte prefix
    keys = [base + suf for suf in (b"a", b"c", b"b", b"e", b"d")]
    s = DeviceSorter(num_partitions=1, key_width=16, key_normalizer=norm)
    for k in keys:
        s.write(k, b"v")
    got = [k for k, _v in s.flush().batch.iter_pairs()]
    assert got == sorted(keys, reverse=True)


def test_custom_comparator_multi_span_flush():
    """Comparator order survives the span-spill + final-merge path (a tiny
    span budget forces multiple spans; regression: flush() once merged by
    raw bytes, undoing the comparator)."""
    from tez_tpu.library.comparators import ReverseByteKeyComparator
    from tez_tpu.ops.sorter import DeviceSorter
    norm = ReverseByteKeyComparator().normalize
    keys = [f"k{i:03d}".encode() for i in range(16)]
    s = DeviceSorter(num_partitions=1, key_normalizer=norm,
                     span_budget_bytes=64)   # ~3 records per span
    for k in keys:
        s.write(k, b"v")
    assert s.num_spills > 1, "test must exercise the multi-span merge"
    got = [k for k, _v in s.flush().batch.iter_pairs()]
    assert got == sorted(keys, reverse=True)


def _first_prun_blob(path):
    """First length-prefixed Run blob inside a partition-indexed spill file
    (container header, then [u64 len][blob]...)."""
    import struct
    from tez_tpu.ops.runformat import PR_MAGIC
    data = open(path, "rb").read()
    assert data.startswith(PR_MAGIC)
    off = len(PR_MAGIC)
    (blob_len,) = struct.unpack_from("<Q", data, off)
    return data[off + 8:off + 8 + blob_len]


def test_spill_compression_conf(tmp_path):
    """Compressed spills: Run blobs carry the codec flag; reads are
    transparent (self-describing header, reference: IFile codec)."""
    import os
    import struct
    from tez_tpu.ops.runformat import MAGIC, PR_MAGIC
    from tez_tpu.ops.sorter import DeviceSorter
    spill = str(tmp_path)
    s = DeviceSorter(num_partitions=2, span_budget_bytes=4096,
                     mem_budget_bytes=1, spill_dir=spill, spill_codec="zlib")
    for i in range(2000):
        s.write(f"key{i % 20:03d}".encode(), b"v" * 16)
    files = [f for f in os.listdir(spill) if f.endswith(".prun")]
    assert files, "nothing spilled"
    blob = _first_prun_blob(os.path.join(spill, files[0]))
    assert blob.startswith(MAGIC)
    assert blob[len(MAGIC)] == 1      # codec flag = compressed
    total = sum(os.path.getsize(os.path.join(spill, f)) for f in files)
    run = s.flush()
    assert run.batch.num_records == 2000
    # compressed spill should beat the raw size for this repetitive data
    raw = 2000 * (6 + 16)
    assert total < raw


def test_compress_conf_wired_end_to_end(tmp_path):
    """tez.runtime.compress travels through the edge payload into the sorter
    spill path (and an unsupported codec errors loudly)."""
    import collections
    from tez_tpu.examples import ordered_wordcount
    from tez_tpu.ops.runformat import MAGIC
    corpus = tmp_path / "in.txt"
    # unique words -> ~1.5MB of sorter payload, over the 1MiB span budget
    with open(corpus, "w") as fh:
        for i in range(60000):
            fh.write(f"uniqueword{i:06d} ")
    spill_dir = str(tmp_path / "spill")
    out = str(tmp_path / "out")
    from tez_tpu.client.tez_client import TezClient
    conf = {"tez.staging-dir": str(tmp_path / "s"),
            "tez.runtime.io.sort.mb": 1,
            "tez.runtime.compress": True,
            "tez.runtime.tpu.host.spill.dir": spill_dir}
    with TezClient.create("compress-e2e", conf) as client:
        dag = ordered_wordcount.build_dag([str(corpus)], out,
                                          tokenizer_parallelism=1)
        dag_client = client.submit_dag(dag)
        state = dag_client.wait_for_completion().state.name
        final = dag_client.get_dag_status(with_counters=True)
    assert state == "SUCCEEDED"
    # spill files are consumed (and removed) by the streaming final merge,
    # so compression is proven by the byte counters: actual disk writes
    # (compressed) must undercut the logical spilled KV payload
    tc = final.counters.to_dict().get("TaskCounter", {})
    spilled_records = tc.get("SPILLED_RECORDS", 0)
    host_spill = tc.get("HOST_SPILL_BYTES", 0)
    logical = tc.get("OUTPUT_BYTES", 0)
    assert spilled_records > 0, "span spill never engaged"
    assert host_spill > 0
    assert host_spill < logical, (host_spill, logical)


def test_codec_registry_zstd_roundtrip(tmp_path):
    """zstd codec: self-describing flag 2; roundtrip byte-identical;
    lz4 (absent in this image) errors loudly instead of silently
    uncompressing (reference: pluggable Hadoop codecs behind
    tez.runtime.compress.codec)."""
    import numpy as np
    import pytest
    pytest.importorskip("zstandard", reason="zstd wheel absent")
    from tez_tpu.ops.runformat import (KVBatch, MAGIC, Run, resolve_codec)
    batch = KVBatch.from_pairs(
        [(f"k{i % 7}".encode(), b"payload" * 8) for i in range(500)])
    run = Run(batch, np.array([0, 250, 500], dtype=np.int64))
    for codec, flag in ((None, 0), ("zlib", 1), ("zstd", 2)):
        blob = run.to_bytes(codec)
        assert blob[len(MAGIC)] == flag
        back = Run.from_bytes(blob)
        assert list(back.batch.iter_pairs()) == list(batch.iter_pairs())
        assert np.array_equal(back.row_index, run.row_index)
    assert len(run.to_bytes("zstd")) < len(run.to_bytes(None))
    with pytest.raises(ValueError, match="lz4"):
        run.to_bytes("lz4")
    with pytest.raises(ValueError, match="unsupported"):
        resolve_codec("snappy")


def test_zstd_conf_through_sorter(tmp_path):
    import os
    import pytest
    pytest.importorskip("zstandard", reason="zstd wheel absent")
    from tez_tpu.ops.runformat import MAGIC
    from tez_tpu.ops.sorter import DeviceSorter
    spill = str(tmp_path)
    s = DeviceSorter(num_partitions=2, span_budget_bytes=512,
                     mem_budget_bytes=1, spill_dir=spill, spill_codec="zstd")
    for i in range(200):
        s.write(f"key{i % 20:03d}".encode(), b"v" * 16)
    blob = _first_prun_blob(os.path.join(spill, os.listdir(spill)[0]))
    assert blob[len(MAGIC)] == 2      # zstd flag
    run = s.flush()
    assert run.batch.num_records == 200


def test_device_resident_span_and_merge():
    """Resident path (VERDICT r1 item 4): span sort keeps sorted key lanes
    on device, partition slicing preserves the view, and the consumer merge
    runs off those views without re-uploading — byte-identical to the host
    merge."""
    import numpy as np
    from tez_tpu.ops import device
    from tez_tpu.ops.runformat import KVBatch
    from tez_tpu.ops.sorter import DeviceSorter, merge_sorted_runs

    rng = np.random.default_rng(42)
    num_partitions = 3
    producer_runs = []
    golden_rows = {p: [] for p in range(num_partitions)}
    for prod in range(3):
        s = DeviceSorter(num_partitions=num_partitions, key_width=16,
                         device_min_records=0)   # force the resident path
        pairs = []
        for i in range(400):
            k = f"k{rng.integers(0, 120):04d}".encode()   # <= 16B: resident
            v = f"v{prod}_{i}".encode()
            pairs.append((k, v))
            s.write(k, v)
        run = s.flush()
        assert run.batch.dev_keys is not None, "span sort not resident"
        producer_runs.append((run, pairs))
    # golden: per partition, concat producer-partition slices then stable
    # sort by key (equal keys keep producer order)
    from tez_tpu.library.partitioners import _stable_hash
    for run, pairs in producer_runs:
        per_part = {p: [] for p in range(num_partitions)}
        for k, v in pairs:
            per_part[_stable_hash(k) % num_partitions].append((k, v))
        for p in range(num_partitions):
            golden_rows[p].append(sorted(per_part[p], key=lambda kv: kv[0]))
    for p in range(num_partitions):
        slices = [run.partition(p) for run, _ in producer_runs]
        for sl in slices:
            assert sl.dev_keys is not None, "partition slice lost the view"
        from tez_tpu.ops.runformat import Run
        runs = [Run(sl, np.array([0, sl.num_records], np.int64))
                for sl in slices]
        merged = merge_sorted_runs(runs, 1, 16, engine="device")
        got = list(merged.batch.iter_pairs())
        expect = []
        rows = [list(r) for r in golden_rows[p]]
        import heapq
        expect = [kv for kv, _, _ in heapq.merge(
            *[[(kv, i, j) for j, kv in enumerate(r)]
              for i, r in enumerate(rows)],
            key=lambda t: (t[0][0], t[1], t[2]))]
        assert got == expect, f"partition {p} merge mismatch"


def test_resident_view_dropped_on_serialization():
    import numpy as np
    import pickle
    from tez_tpu.ops.runformat import KVBatch, Run
    from tez_tpu.ops.sorter import DeviceSorter
    s = DeviceSorter(num_partitions=2, device_min_records=0)
    for i in range(50):
        s.write(f"k{i:02d}".encode(), b"v")
    run = s.flush()
    assert run.batch.dev_keys is not None
    back = Run.from_bytes(run.to_bytes())
    assert back.batch.dev_keys is None
    assert pickle.loads(pickle.dumps(run.batch)).dev_keys is None
    assert list(back.batch.iter_pairs()) == list(run.batch.iter_pairs())


def test_long_keys_fall_back_to_exact_path():
    """Keys beyond the configured width take the matrix path with host
    tie-break — still byte-exact."""
    import numpy as np
    from tez_tpu.ops.sorter import DeviceSorter
    s = DeviceSorter(num_partitions=1, key_width=8)
    keys = [b"prefix__" + bytes([c]) * 4 for c in (3, 1, 2)] + [b"prefix__"]
    for k in keys:
        s.write(k, b"v")
    run = s.flush()
    assert run.batch.dev_keys is None   # not resident-eligible
    got = [k for k, _ in run.batch.iter_pairs()]
    assert got == sorted(keys)


def test_resident_merge_mixed_lane_widths():
    """Producers whose spans saw different max key lengths produce device
    views with different lane counts; the merge widens narrow views with
    zero lanes on device and stays byte-exact."""
    import numpy as np
    from tez_tpu.ops.runformat import Run
    from tez_tpu.ops.sorter import DeviceSorter, merge_sorted_runs
    runs = []
    all_keys = []
    for prod, klen in enumerate((4, 12)):      # 1 lane vs 3 lanes
        s = DeviceSorter(num_partitions=1, key_width=16,
                         device_min_records=0)
        for i in range(120):
            k = f"{i % 37:0{klen}d}".encode()
            all_keys.append((k, prod, i))
            s.write(k, f"v{prod}".encode())
        run = s.flush()
        assert run.batch.dev_keys is not None
        runs.append(run)
    assert runs[0].batch.dev_keys[0].shape[1] != \
        runs[1].batch.dev_keys[0].shape[1]
    merged = merge_sorted_runs(runs, 1, 16, engine="device")
    got = [k for k, _ in merged.batch.iter_pairs()]
    assert got == sorted(got) and len(got) == 240
    assert sorted(got) == sorted(k for k, _, _ in all_keys)


def test_encode_keys_device_parity():
    """Device ragged->lanes encode == host encode (keycodec twins)."""
    import numpy as np
    from tez_tpu.ops.keycodec import encode_keys, encode_keys_device
    rng = np.random.default_rng(3)
    # lengths up to 40 so every width below has over-width keys (the
    # mask-at-width-vs-rounded-lanes distinction only shows then)
    rows = [rng.integers(97, 123, rng.integers(0, 41), dtype=np.int64)
            .astype(np.uint8) for _ in range(500)]
    kb = np.concatenate([r for r in rows if len(r)] or
                        [np.zeros(0, np.uint8)])
    ko = np.cumsum([0] + [len(r) for r in rows]).astype(np.int64)
    for width in (4, 16, 31):
        lanes_h, lens_h = encode_keys(kb, ko, width)
        lanes_d, lens_d = encode_keys_device(kb, ko, width)
        assert np.array_equal(lanes_h, np.asarray(lanes_d)), width
        assert np.array_equal(lens_h.astype(np.int64),
                              np.asarray(lens_d).astype(np.int64)), width


def test_native_wordcount_aggregator_matches_counter():
    """Fused native tokenize+count == collections.Counter over bytes.split()
    (the WordCount map task's whole data plane in one C pass)."""
    from collections import Counter
    from tez_tpu.ops.native import WordCountAggregator
    agg = WordCountAggregator.create()
    if agg is None:
        import pytest
        pytest.skip("native lib unavailable")
    chunks = [b"the cat\tsat  on\nthe mat\n", b"", b"mat cat mat\r\nthe\x0bend\n"]
    for c in chunks:
        agg.feed(c)
    kb, ko, counts = agg.emit()
    agg.close()
    got = {bytes(kb[ko[i]:ko[i + 1]]): int(counts[i])
           for i in range(len(counts))}
    assert got == dict(Counter(b"".join(chunks).split()))


def test_native_hash_sum_matches_python():
    import numpy as np
    from tez_tpu.ops.native import hash_sum_native
    rng = np.random.default_rng(5)
    keys = [f"k{rng.integers(0, 50)}".encode() for _ in range(3000)]
    vals = rng.integers(-100, 100, 3000).astype(np.int64)
    offsets = np.zeros(3001, np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    kb = np.frombuffer(b"".join(keys), dtype=np.uint8)
    res = hash_sum_native(kb, offsets, vals)
    if res is None:
        import pytest
        pytest.skip("native lib unavailable")
    first_idx, sums = res
    golden: dict = {}
    order = []
    for k, v in zip(keys, vals.tolist()):
        if k not in golden:
            golden[k] = 0
            order.append(k)
        golden[k] += v
    assert [keys[i] for i in first_idx.tolist()] == order
    assert {keys[i]: int(s) for i, s in zip(first_idx, sums)} == golden


def test_presort_hash_combine_shrinks_sort_and_keeps_result():
    """With a sum combiner and long values, duplicate keys collapse BEFORE
    the device sort (COMBINE_* counters record it) and the run equals the
    post-sort-combine result."""
    from tez_tpu.common.counters import TaskCounter, TezCounters
    from tez_tpu.ops.serde import VarLongSerde
    serde = VarLongSerde()
    words = [f"w{i % 7}".encode() for i in range(5000)]
    counters = TezCounters()
    sorter = DeviceSorter(num_partitions=2, combiner=sum_long_combiner,
                          counters=counters)
    for w in words:
        sorter.write(w, serde.to_bytes(1))
    run = sorter.flush()
    got = {k: serde.from_bytes(v) for k, v in run.batch.iter_pairs()}
    from collections import Counter
    assert got == {k: c for k, c in Counter(words).items()}
    snap = counters.to_dict()
    combine_in = sum(g.get("COMBINE_INPUT_RECORDS", 0)
                     for g in snap.values())
    combine_out = sum(g.get("COMBINE_OUTPUT_RECORDS", 0)
                      for g in snap.values())
    assert combine_in == 5000 and combine_out == 7


def test_pre_combined_span_skips_hash_combine():
    """A span made of ONE pre_combined batch (the fused tokenize+count
    aggregator's promise: keys already unique) must skip the pre-sort hash
    pass entirely — COMBINE_INPUT_RECORDS stays 0 (ADVICE r3: the skip
    logic was dead because no emitter set the flag)."""
    import numpy as np

    from tez_tpu.common.counters import TezCounters
    from tez_tpu.ops.runformat import KVBatch
    from tez_tpu.ops.serde import VarLongSerde
    serde = VarLongSerde()
    keys = [f"w{i:04d}".encode() for i in range(512)]
    ko = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=ko[1:])
    kb = np.frombuffer(b"".join(keys), dtype=np.uint8).copy()
    vb = np.frombuffer(b"".join(serde.to_bytes(i + 1) for i in
                                range(len(keys))), dtype=np.uint8).copy()
    vo = np.arange(len(keys) + 1, dtype=np.int64) * 8
    counters = TezCounters()
    sorter = DeviceSorter(num_partitions=2, combiner=sum_long_combiner,
                          counters=counters)
    sorter.write_batch(KVBatch(kb, ko, vb, vo, pre_combined=True))
    run = sorter.flush()
    got = {k: serde.from_bytes(v) for k, v in run.batch.iter_pairs()}
    assert got == {k: i + 1 for i, k in enumerate(keys)}
    snap = counters.to_dict()
    assert sum(g.get("COMBINE_INPUT_RECORDS", 0)
               for g in snap.values()) == 0


def test_owc_reference_proxy_matches_golden():
    """The C++ OrderedWordCount reference-semantics proxy (the external
    E2E baseline, BASELINE.md protocol) produces the exact word->count
    map, count-sorted output."""
    import collections
    from tez_tpu.ops.native import owc_proxy
    text = (b"tick tock tick boom tick tock\n" * 3000 +
            b"quux tock\n" * 1500)
    res = owc_proxy(text, 4, 4)
    if res is None:
        import pytest as _pytest
        _pytest.skip("native lib unavailable")
    secs, out = res
    golden = collections.Counter(text.split())
    got = {}
    prev = -1
    for line in out.decode().splitlines():
        w, c = line.rsplit("\t", 1)
        got[w.encode()] = int(c)
        assert int(c) >= prev
        prev = int(c)
    assert got == dict(golden)
    assert secs > 0
