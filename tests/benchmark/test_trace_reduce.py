"""benchmark/trace_reduce.py on hand-made events and on a small recorded
TPU trace kept beside this file, and benchmark/roofline.py's shape
functions against hand-worked sizes. CPU only; nothing is timed."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import roofline, trace_reduce    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000      # ns


def test_union_merges_overlapping_and_touching_intervals():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) \
        == [(0, 4), (5, 7), (9, 9)]
    assert trace_reduce.union([]) == []


def test_reduction_of_hand_made_events():
    # one device, a 10 ms window: ops cover [0,2) [1,3) [5,6) [8,9) ms
    events = [("fusion.1", 0, 2 * MS), ("copy.2", 1 * MS, 3 * MS),
              ("fusion.1", 5 * MS, 6 * MS), ("sort.3", 8 * MS, 9 * MS)]
    r = trace_reduce.Reduction({"/device:TPU:0": events}, window_s=0.010)
    assert r.busy_s == pytest.approx(0.005)         # 3 + 1 + 1 ms
    assert r.idle_share == pytest.approx(0.5)
    assert r.device_ops[0] == ("fusion.1", pytest.approx(0.003))
    assert dict(r.device_ops)["copy.2"] == pytest.approx(0.002)
    # two gaps of 2 ms: after copy.2 ended at 3, after fusion.1 at 6
    assert dict(r.idle_gaps) == {
        "unattributed_after_copy.2": pytest.approx(0.002),
        "unattributed_after_fusion.1": pytest.approx(0.002)}
    assert r.longest_gap_s == pytest.approx(0.002)
    b = r.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) == 3 and len(b["idle_gaps"]) == 2


def test_busy_is_averaged_over_the_chips():
    planes = {"/device:TPU:0": [("a", 0, 4 * MS)],
              "/device:TPU:1": [("a", 0, 2 * MS)]}
    r = trace_reduce.Reduction(planes, window_s=0.004)
    assert r.busy_s == pytest.approx(0.003)
    assert dict(r.device_ops)["a"] == pytest.approx(0.003)


def test_no_device_plane_reads_zero_busy():
    r = trace_reduce.Reduction({}, window_s=1.0)
    assert r.busy_s == 0.0 and r.breakdown() == {"device_ops": [],
                                                 "idle_gaps": []}


def test_op_name_shortens_the_hlo_text_a_tpu_event_is_named_by():
    assert trace_reduce.op_name(
        "fusion.2 = f32[16777216]{0:T(1024)} fusion(f32[256]{0:T(256)S(1)} "
        "%copy-done.2, s32[16777216]{0:T(1024)S(1)} %fusion.13), "
        "kind=kCustom, calls=%fused_computation.2") == "fusion.2_f32_16777216"
    assert trace_reduce.op_name(
        "sort.4 = (s32[16777216]{0:T(1024)}, f32[16777216]{0:T(1024)}) "
        "sort(s32[16777216]{0:T(1024)S(1)} %b)") == "sort.4_s32_16777216"
    assert trace_reduce.op_name(
        "broadcast.42 = s32[64,16777216]{1,0:T(8,128)} broadcast(s32[1] %x)"
    ) == "broadcast.42_s32_64x16777216"
    assert trace_reduce.op_name("%fusion.1") == "fusion.1"


# ------------------------------------------------------- shape functions

V5E = roofline.peaks("TPU v5 lite")


def test_peaks_table_is_keyed_by_device_kind_and_has_no_default():
    assert V5E == (197e12, 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_knn_shape_function_sift_1m():
    # d_pad 2^20 x 128 f32: 536,870,912 B read, 268,435,456 flops
    nbytes, flops = roofline.knn_exact(
        {"d_pad": 1 << 20, "dimension": 128}, {})
    assert nbytes == 536_870_912 and flops == 268_435_456
    t, bound = roofline.least_seconds(nbytes, flops, V5E)
    assert bound == "memory"
    assert t == pytest.approx(536_870_912 / 819e9)      # 0.6555 ms
    assert t * 1e3 == pytest.approx(0.6555, abs=1e-4)


def test_bm25_dense_shape_function_msmarco():
    # a query whose terms hold 100,000 blocks: 12.8M lanes x 12 B, and
    # three passes over the [16,777,216] f32 score vector
    lanes = 100_000 * 128
    nbytes, flops = roofline.bm25_dense({"d_pad": 1 << 24},
                                        {"lanes": lanes})
    assert nbytes == 12 * lanes + 12 * (1 << 24) == 354_926_592
    assert flops == 8 * lanes
    t, bound = roofline.least_seconds(nbytes, flops, V5E)
    assert bound == "memory" and t * 1e3 == pytest.approx(0.4334, abs=1e-4)


def test_bm25_candidate_shape_function():
    # two rare terms, 40 blocks together: 5,120 lanes x 12 B
    nbytes, flops = roofline.bm25_candidate({"d_pad": 1 << 24},
                                            {"lanes": 40 * 128})
    assert nbytes == 61_440 and flops == 40_960
    t, bound = roofline.least_seconds(nbytes, flops, V5E)
    assert bound == "memory" and t == pytest.approx(61_440 / 819e9)


def test_compute_bound_is_named():
    assert roofline.least_seconds(1.0, 1e9, V5E)[1] == "compute"
