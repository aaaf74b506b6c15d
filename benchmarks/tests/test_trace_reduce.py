"""benchmarks/trace_reduce.py against values worked out by hand: a synthetic
trace small enough to do in the head, and v5e_trace_cut.json, the first
0.22 s of a recorded window of owc_session_small on a TPU v5 lite."""
from __future__ import annotations

import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TESTS))

import trace_reduce as tr  # noqa: E402


def test_union_and_gaps():
    busy = tr.union([(5.0, 6.0), (1.0, 2.0), (1.5, 3.0), (2.5, 2.75)])
    assert busy == [(1.0, 3.0), (5.0, 6.0)]
    assert tr.total(busy) == 3.0
    assert tr.gaps(busy, 0.0, 10.0) == [(0.0, 1.0), (3.0, 5.0), (6.0, 10.0)]
    assert tr.clip([(0.0, 2.0), (3.0, 4.0)], 1.0, 3.5) == [(1.0, 2.0),
                                                           (3.0, 3.5)]


def test_program_names():
    assert tr.program_name("jit__merge_path_pair_impl(5022875811147184778)") \
        == "_merge_path_pair_impl"
    assert tr.program_name("jit_step") == "step"


def synthetic():
    """Window 100.0 .. 110.0 epoch seconds; the mark sits at trace second
    1.0, so trace seconds = epoch - 99.  Chip 0 is busy 2..3, 2.5..4 (nested
    op) and 8..9 trace seconds = 3 s; chip 1 is busy 5..6 = 1 s."""
    s = 1_000_000_000

    def plane(dev, ops, modules):
        return {"name": f"/device:TPU:{dev}", "lines": [
            {"name": "XLA Ops",
             "events": [[n, a * s, (b - a) * s] for n, a, b in ops]},
            {"name": "XLA Modules",
             "events": [[n, a * s, (b - a) * s] for n, a, b in modules]}]}

    planes = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            [tr.MARK, 1 * s, 1000]]}]},
        plane(1, [("%a", 5, 6)], [("jit_sort(1)", 5, 6)]),
        plane(0, [("%while", 2, 4), ("%body", 2.5, 3), ("%b", 8, 9)],
              [("jit_merge(7)", 2, 4), ("jit_sort(1)", 8, 9)])]}
    marks = {"start": 100.0, "mark": 100.0, "stop": 110.0}
    # thread t1: task.run 100..108 with a child device.d2h 104..107;
    # thread t2: task.run 103..106
    spans = [("task.run", 100.0, 108.0, "t1"),
             ("device.d2h", 104.0, 107.0, "t1"),
             ("task.run", 103.0, 106.0, "t2")]
    return planes, marks, spans


def test_synthetic_window_by_hand():
    planes, marks, spans = synthetic()
    r = tr.reduce_planes(planes, 2, spans, marks)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s_by_device"] == {0: pytest.approx(3.0),
                                     1: pytest.approx(1.0)}
    assert r["busy_s_fullest"] == pytest.approx(3.0)
    assert r["busy_s_mean"] == pytest.approx(2.0)
    assert r["breakdown"]["device_ops"] == [["merge", pytest.approx(2.0)],
                                            ["sort", pytest.approx(2.0)]]
    # chip 0 idle, trace seconds: 1..2, 4..8, 9..11 -> epoch 100..101,
    # 103..107, 108..110.  103..107: t1 in task.run for 1 s and in d2h for
    # 3 s, t2 in task.run for 3 s -> task.run 4 s of a 4 s gap
    gaps = r["breakdown"]["idle_gaps"]
    assert [round(g[1], 6) for g in gaps] == [4.0, 2.0, 1.0]
    assert gaps[0][0] == "task.run_x1.0_threads_chip_0"
    assert gaps[1][0] == "no_program_span_chip_0"
    assert gaps[2][0] == "task.run_x1.0_threads_chip_0"
    # one chip asked for: chip 0 alone
    assert tr.reduce_planes(planes, 1, spans, marks)["busy_s_mean"] == \
        pytest.approx(3.0)


def test_self_intervals():
    got = sorted(tr.self_intervals(synthetic()[2]))
    assert got == [("device.d2h", 104.0, 107.0), ("task.run", 100.0, 104.0),
                   ("task.run", 103.0, 106.0), ("task.run", 107.0, 108.0)]


def test_no_device_operation_reads_nothing():
    planes, marks, spans = synthetic()
    planes["planes"] = planes["planes"][:1]
    assert tr.reduce_planes(planes, 1, spans, marks) is None


def test_trace_without_the_mark_is_an_error():
    planes, marks, spans = synthetic()
    with pytest.raises(RuntimeError, match=tr.MARK):
        tr.reduce_planes({"planes": planes["planes"][1:]}, 1, spans, marks)


def test_recorded_v5e_trace():
    fix = json.load(open(os.path.join(TESTS, "v5e_trace_cut.json")))
    r = tr.reduce_planes(fix["planes"], 1,
                         [tuple(s) for s in fix["spans"]], fix["marks"])
    # by hand from the XLA Modules events of the cut, in ns: four sorts
    # 11613848 + 11600628 + 11615632 + 11602216; eight slice_to_bucket
    # 4 x ~1313877 + 4 x ~2460844 = 15098883; seven merge_path_prep 17381;
    # one merge_path_pair that starts 1207137 ns before the cut ends
    ops = dict(r["breakdown"]["device_ops"])
    assert list(ops) == ["_fused_resident_hash_sort_impl",
                         "_slice_to_bucket_impl", "_merge_path_pair_impl",
                         "_merge_path_prep_impl"]
    assert ops["_fused_resident_hash_sort_impl"] == pytest.approx(
        0.046432324, abs=1e-9)
    assert ops["_slice_to_bucket_impl"] == pytest.approx(0.015098883,
                                                         abs=1e-9)
    assert ops["_merge_path_prep_impl"] == pytest.approx(0.000017381,
                                                         abs=1e-9)
    assert ops["_merge_path_pair_impl"] == pytest.approx(0.001207137,
                                                         abs=1e-7)
    # the union of the XLA Ops events, checked once against a 10 ns raster
    assert r["busy_s_fullest"] == pytest.approx(0.06274034, abs=1e-7)
    assert r["window_s"] == pytest.approx(0.22087407, abs=1e-7)
    # longest gaps: window start to the first sort's first op (tokenizers
    # run), then the last sort's end to the first slice (shuffle)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["task.run_x2.9_threads_chip_0",
                       pytest.approx(0.09780812, abs=1e-6)]
    assert gaps[1] == ["shuffle.wait_x2.7_threads_chip_0",
                       pytest.approx(0.05702932, abs=1e-7)]
    assert len(gaps) == tr.TOP


def test_roofline():
    # 819e9 B/s; 1,000,000 rows x 3 lanes x 4 B x 2 = 24e6 B = 29.304 us
    assert tr.least_hbm_bytes(1_000_000, 3) == 24_000_000
    pct = tr.hbm_roofline_pct(1_000_000, 3, 0.0029304029304, "TPU v5 lite")
    assert pct == pytest.approx(1.0, rel=1e-6)
    assert tr.hbm_roofline_pct(0, 3, 1.0, "TPU v5 lite") is None


def test_roofline_over_105_raises_and_unknown_device_is_an_error():
    with pytest.raises(ValueError, match="105"):
        tr.hbm_roofline_pct(1_000_000, 3, 0.0000277, "TPU v5 lite")
    assert tr.hbm_roofline_pct(1_000_000, 3, 0.0000280, "TPU v5 lite") > 100
    with pytest.raises(KeyError, match="peaks.json"):
        tr.hbm_roofline_pct(1, 3, 1.0, "TPU v9")
