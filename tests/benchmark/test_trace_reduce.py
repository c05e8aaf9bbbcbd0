"""benchmark/trace_reduce.py on hand-made events and on the two small
recorded TPU v5e traces kept beside this file (tests/benchmark/recorded),
and benchmark/roofline.py's shape functions against hand-worked sizes.
CPU only; nothing is timed.

The recorded traces (PR 27, cut down to what the reduction reads: the
/device:TPU:n planes, their "XLA Ops" and "XLA Modules" lines, event
names, starts and durations; every stat, host plane and other line
dropped, which changes no event the reduction sees):

- `msmarco-1chip.xplane.pb`, `msmarco-1chip.slice.json`: a traced slice
  of `msmarco-natural-closed` on one chip (`benchmark/run.py --trace 1
  --keep-trace`), with the span ring's rows and the client's samples of
  the same slice;
- `spmd-4chip.xplane.pb`, `spmd-4chip.slice.json`: two rounds of
  chip_smoke.py's five `sharded` bodies on a four-chip host, the SPMD
  program `jit_local_query_phase` on a mesh of 4 (1,000,000 documents
  over 8 shards, 2 rows a chip), with the ring's rows; that route
  records `http.request` and `rest.search` and no `dispatch` yet.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import roofline, trace_reduce    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded")
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
    # the window is what the plane recorded, its first op to its last,
    # 9 ms, not the 10 the host's clock read around the profiler
    assert r.plane_recorded_ns == {"/device:TPU:0": (0, 9 * MS)}
    assert r.window_s == pytest.approx(0.009)
    assert r.idle_share == pytest.approx(4 / 9)
    # a run that cannot lay the plane on the host's clock counts its
    # requests over the host's slice, and reads the window over it too
    r.keep_host_window()
    assert r.window_s == pytest.approx(0.010)
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


def test_every_plane_keeps_its_own_busy_time_and_recorded_interval():
    # four chips whose planes begin and end at different times
    planes = {"/device:TPU:0": [("a", 0, 4 * MS), ("b", 8 * MS, 10 * MS)],
              "/device:TPU:1": [("a", 1 * MS, 3 * MS), ("b", 8 * MS, 9 * MS)],
              "/device:TPU:2": [("a", 2 * MS, 4 * MS), ("b", 6 * MS, 8 * MS)],
              "/device:TPU:3": [("a", 0, 2 * MS), ("b", 3 * MS, 5 * MS)]}
    r = trace_reduce.Reduction(planes, window_s=0.012)
    assert r.plane_busy_s == {
        "/device:TPU:0": pytest.approx(0.006),
        "/device:TPU:1": pytest.approx(0.003),
        "/device:TPU:2": pytest.approx(0.004),
        "/device:TPU:3": pytest.approx(0.004)}
    assert r.plane_recorded_ns == {
        "/device:TPU:0": (0, 10 * MS), "/device:TPU:1": (1 * MS, 9 * MS),
        "/device:TPU:2": (2 * MS, 8 * MS), "/device:TPU:3": (0, 5 * MS)}
    assert r.busy_s == pytest.approx(0.00425)
    assert r.window_s == pytest.approx((10 + 8 + 6 + 5) / 4 / 1e3)
    assert r.idle_share == pytest.approx(
        1 - (6 / 10 + 3 / 8 + 4 / 6 + 4 / 5) / 4)
    # (6 - 3) / 4.25 of the mean
    assert r.busy_skew == pytest.approx(3 / 4.25)
    assert trace_reduce.Reduction(
        {"/device:TPU:0": planes["/device:TPU:0"]}, 0.012).busy_skew is None
    # module events ride beside the ops, [] where a plane has none
    r = trace_reduce.Reduction(
        planes, 0.012, {"/device:TPU:1": [("jit_x(1)", 1 * MS, 3 * MS)]})
    assert r.modules["/device:TPU:1"] == [("jit_x(1)", 1 * MS, 3 * MS)]
    assert r.modules["/device:TPU:0"] == []


def test_no_device_plane_reads_zero_busy():
    r = trace_reduce.Reduction({}, window_s=1.0)
    assert r.busy_s == 0.0 and r.breakdown() == {"device_ops": [],
                                                 "idle_gaps": []}
    assert r.window_s == 1.0 and r.idle_share == 1.0
    assert r.busy_skew is None


# ------------------------------------------------- the recorded traces

def recorded(name: str):
    return trace_reduce.device_events(
        os.path.join(RECORDED, name + ".xplane.pb"))


def test_both_recorded_traces_are_small():
    for name in ("msmarco-1chip", "spmd-4chip"):
        for ext in (".xplane.pb", ".slice.json"):
            size = os.path.getsize(os.path.join(RECORDED, name + ext))
            assert 0 < size < 1_000_000, (name, ext, size)


def test_the_one_chip_trace_reduces_to_one_plane():
    ops, modules = recorded("msmarco-1chip")
    assert list(ops) == list(modules) == ["/device:TPU:0"]
    assert len(ops["/device:TPU:0"]) == 1243
    # 22 program runs of the dense kernel, two executables
    names = [m[0] for m in modules["/device:TPU:0"]]
    assert len(names) == 22 and len(set(names)) == 2
    assert {n.split("(")[0] for n in names} == {"jit_bm25_dense"}
    r = trace_reduce.Reduction(ops, 6.000430408, modules)
    assert r.busy_s == pytest.approx(5.346826479)
    assert r.plane_recorded_ns == {"/device:TPU:0": (112145211, 6029172689)}
    assert r.window_s == pytest.approx(5.917027478)
    assert r.idle_share == pytest.approx(1 - 5.346826479 / 5.917027478)
    assert r.busy_skew is None
    # the two scatter-adds into [16,777,216] lead, as in PERF.md
    assert [k for k, _ in r.device_ops[:2]] == [
        "fusion.4_f32_16777216", "fusion.3_s32_16777216"]
    # the profiler cut the plane's last program short: its module event
    # runs past the last op the plane holds, so the window ends at the
    # op, which the busy time is the union of
    assert max(m[2] for m in modules["/device:TPU:0"]) > 6029172689


def test_the_four_chip_trace_reduces_to_four_planes():
    ops, modules = recorded("spmd-4chip")
    planes = [f"/device:TPU:{k}" for k in range(4)]
    assert sorted(ops) == sorted(modules) == planes
    # two rounds of five bodies: ten runs of the SPMD program a plane
    assert [len(modules[p]) for p in planes] == [10] * 4
    assert {m[0].split("(")[0] for p in planes for m in modules[p]} \
        == {"jit_local_query_phase"}
    assert [len(ops[p]) for p in planes] == [798] * 4
    r = trace_reduce.Reduction(ops, 0.24247826999999234, modules)
    assert sorted(r.plane_busy_s) == planes
    # every chip ran its two rows of every program: 12.6 ms of 174.5
    for p in planes:
        assert r.plane_busy_s[p] == pytest.approx(0.01262, rel=2e-3)
        assert r.plane_window_s(p) == pytest.approx(0.174485, rel=1e-4)
    # the planes of one trace count from one zero: the same program
    # starts within 2 us on the four of them
    firsts = [r.plane_recorded_ns[p][0] for p in planes]
    assert max(firsts) - min(firsts) < 2_000
    # the device recorded 174.5 ms of the 242.5 between the host's two
    # clock reads: the rest is not idle time, it is not in the trace
    assert r.window_s == pytest.approx(0.174485, rel=1e-4)
    assert r.host_window_s - r.window_s > 0.06
    assert r.idle_share == pytest.approx(0.9277, abs=1e-3)
    assert 0 < r.busy_skew < 0.01
    assert r.busy_skew == pytest.approx(
        (max(r.plane_busy_s.values()) - min(r.plane_busy_s.values()))
        / r.busy_s)
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10


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
