"""The join-free stage reader (`benchmark/metrics/readers/
stage_ms_slice.py`, ISSUE 33) on hand-made spans and device events: a
slice of one served executable reads its stages with no join of waves
and runs at all, the three k-NN stage metrics sum to the slice's
device-op time, and it returns None (it does not guess) where two
dispatched executables put an instruction that ran in different stages,
where a dispatched executable has no scope map, and where there is no
device plane or no span ring.
"""

import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run as bench_run      # noqa: E402
from benchmark import spans                 # noqa: E402
from benchmark import trace_reduce          # noqa: E402

MS = 1_000_000
SLICE = (10_000 * MS, 10_100 * MS)          # host ns
STAGE_METRICS = ["knn_distance_ms.knn", "knn_topk_ms.knn", "knn_other_ms"]
SCOPES = {"fp-a": {"multiply_reduce_fusion": "distance",
                   "custom-call": "~top_k", "sort.3": "~top_k",
                   "fusion.4": "top_k", "pad_add_fusion": "pack_row"}}


def ring_of(fingerprints):
    """Eight waves in flight at once, their spans overlapping as a
    closed loop of eight clients leaves them: wave i dispatched at
    10,000 + 10 i ms and answered 80 ms later."""
    out, sid = [], 1
    for i, fp in enumerate(fingerprints):
        t = SLICE[0] + i * 10 * MS
        http, env = sid, sid + 1
        out += [
            {"trace_id": i + 1, "span_id": http, "parent_id": 0,
             "name": "http.request", "start_ns": t - MS,
             "end_ns": t + 81 * MS, "attributes": {"route": "_search"}},
            {"trace_id": i + 1, "span_id": env, "parent_id": http,
             "name": "envelope", "start_ns": t - MS // 2,
             "end_ns": t + 80 * MS + MS // 2, "attributes": {}},
            {"trace_id": i + 1, "span_id": sid + 2, "parent_id": env,
             "name": "dispatch", "start_ns": t, "end_ns": t + MS // 2,
             "attributes": {"wave": 0, "programs": 1, "family": "knn",
                            "fingerprint": fp}},
            {"trace_id": i + 1, "span_id": sid + 3, "parent_id": env,
             "name": "device_wait", "start_ns": t + MS // 2,
             "end_ns": t + 80 * MS, "attributes": {"wave": 0}}]
        sid += 4
    return {"spans": out, "dropped": 0}


def program(t0):
    """One run of the served program on the device's clock: 8 ms of
    scan, two selections of 1 ms with a sort each, 0.1 ms of packing."""
    ops = [("%multiply_reduce_fusion = f32[2097152]", 8 * MS),
           ("%custom-call = (f32[128,100])", MS), ("%sort.3 = f32", MS // 10),
           ("%fusion.4 = f32[2097152]", MS // 5),
           ("%pad_add_fusion = s32[1,201]", MS // 10)]
    out, t = [], t0
    for name, dur in ops:
        out.append((name, t, t + dur))
        t += dur
    return out, t


class FakeRun:
    """What the readers take from a run, with a canned node."""

    def __init__(self, fingerprints, scopes=SCOPES, planes=1, ring=True):
        self.window = self.trace_slice = (SLICE[0] / 1e9, SLICE[1] / 1e9)
        self.trace_called = self.window[0] - 0.05
        self.drained = self.window[1]
        # ten requests, each wholly inside the slice
        self.all_samples = [SimpleNamespace(
            index=i, sent=self.window[0] + 0.001 * i,
            done=self.window[0] + 0.09 + 0.001 * i) for i in range(10)]
        self.requests = [["q"]] * 10
        events, t = [], 5 * MS              # the device's own zero
        for _ in range(10):
            ops, t = program(t)
            events += ops
        names = [f"/device:TPU:{i}" for i in range(planes)]
        self.trace = trace_reduce.Reduction(
            {n: list(events) for n in names}, 0.1) if planes else None
        self.canned_ring = ring_of(fingerprints) if ring else None
        self.canned_scopes = scopes

    def call(self, method, path, body=None):
        if path.startswith("/_telemetry/spans") and self.canned_ring:
            return self.canned_ring
        if path.startswith("/_telemetry/kernels") and self.canned_scopes:
            return {"kernels": {"census": {"executables": [
                {"fingerprint": fp, "scopes": m}
                for fp, m in self.canned_scopes.items()]}}}
        raise RuntimeError(f"GET {path} -> 400: no handler")


def read(run, metric):
    fn, params = bench_run.Files(REPO).reader(metric)
    return fn(run, params)


@pytest.mark.parametrize("planes", [1, 4])
def test_one_executable_reads_its_stages_with_no_join(planes):
    run = FakeRun(["fp-a"] * 12, planes=planes)
    # the waves' spans do not bracket these runs at all (the device's
    # clock starts at 5 ms): whatever the join makes of them, the
    # stages are read
    got = {m: read(run, m) for m in STAGE_METRICS}
    assert got["knn_distance_ms.knn"] == pytest.approx(8.0)
    assert got["knn_topk_ms.knn"] == pytest.approx(1.0 + 0.1 + 0.2)
    assert got["knn_other_ms"] == pytest.approx(0.1)
    # sums over ops: the three are the slice's device-op time a query,
    # a chip (the mean over the planes)
    assert sum(got.values()) == pytest.approx(
        run.trace.busy_s * 1e3 / 10)
    fn, params = bench_run.Files(REPO).reader("device_ms_per_query.knn")
    assert fn(run, params) == pytest.approx(sum(got.values()))


def test_the_three_entries_name_the_join_free_reader():
    import json
    for m in STAGE_METRICS:
        spec = json.load(open(os.path.join(
            REPO, "benchmark", "metrics", m + ".json")))
        assert spec["reader"] == "stage_ms_slice"
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = "vectorsearch-knn-closed-8"
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", [])}
    assert set(STAGE_METRICS) <= listed
    # what only the join can read is not listed for this cell (PERF.md,
    # section 7): idle by span, the clock bracket, runs_agree
    assert not [n for n in listed if n.startswith(
        ("idle_", "span_clock_bracket", "device_runs_agree"))]


def test_executables_that_disagree_on_an_op_that_ran_read_nothing():
    scopes = dict(SCOPES)
    scopes["fp-b"] = {**SCOPES["fp-a"], "fusion.4": "eligible_total"}
    run = FakeRun(["fp-a", "fp-b"] * 6, scopes=scopes)
    assert [read(run, m) for m in STAGE_METRICS] == [None] * 3
    # they agree on every op that ran: read as one
    scopes["fp-b"] = {**SCOPES["fp-a"], "never_ran": "scatter"}
    run = FakeRun(["fp-a", "fp-b"] * 6, scopes=scopes)
    assert read(run, "knn_distance_ms.knn") == pytest.approx(8.0)


def test_nothing_to_read_is_none_not_zero():
    # an executable dispatched near the slice without a scope map
    assert read(FakeRun(["fp-a", "fp-x"] * 6), "knn_topk_ms.knn") is None
    # no scope maps at all (a node older than the census' maps)
    assert read(FakeRun(["fp-a"] * 12, scopes=None),
                "knn_topk_ms.knn") is None
    # no span ring, no device plane
    assert read(FakeRun(["fp-a"] * 12, ring=False),
                "knn_distance_ms.knn") is None
    assert read(FakeRun(["fp-a"] * 12, planes=0), "knn_other_ms") is None


def test_an_instruction_is_read_as_the_trace_names_it():
    assert spans.instruction("%fusion.4 = f32[2097152]{0} fusion(...)") \
        == "fusion.4"
