"""The benchmark's reading of the program's spans (benchmark/spans.py and
the four readers of ISSUE 25) on hand-made spans and device events: the
causal bracket of the clock offset (a feasible interval, an empty one, a
match found one wave along), the four idle parts that sum to the
slice's idle time, the stages that sum to its busy time; then the
committed cell's traced dry run, which prints the six span metrics and
leaves the device ones out; and the new entries' files.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import readings              # noqa: E402
from benchmark import run as bench_run      # noqa: E402
from benchmark import spans                 # noqa: E402
from benchmark import trace_reduce          # noqa: E402

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL = "msmarco-natural-closed"
SPAN_METRICS = ["http_self_ms.closed", "rest_self_ms.closed",
                "envelope_host_ms.closed", "enqueue_ms.closed",
                "device_wait_ms.closed", "respond_ms.closed"]
IDLE_METRICS = [f"idle_{p}_ms.closed" for p in spans.IDLE_PARTS]
ALIGN_METRICS = ["span_clock_bracket_us.closed",
                 "device_runs_agree.closed"]
STAGE_METRICS = ["dense_gather_ms.closed", "dense_scatter_ms.closed",
                 "dense_topk_ms.closed", "dense_other_ms.closed"]
DEVICE_METRICS = IDLE_METRICS + ALIGN_METRICS + STAGE_METRICS
NEW = SPAN_METRICS + DEVICE_METRICS

MS = 1_000_000
SLICE = (10_000 * MS, 10_060 * MS)      # host ns
OFFSET = -9_900 * MS                    # device = host + OFFSET
FP = "0123abcd"
# an executable takes the same time in every run: a request open 5, 7
# or 9 ms dispatches the executable of that cost
FPS = {5: FP, 7: "7777bbbb", 9: "9999cccc"}
SCOPES = {fp: {"fusion.1": "postings_gather", "fusion.2": "scatter",
               "sort.1": "~top_k"} for fp in FPS.values()}
# one request: how long it is open, ms; the requests follow one another
# with 2 ms between them (the client's turnaround)
OPEN_MS = (5, 9, 5, 7, 9, 5)
TURNAROUND = 2 * MS
TO_FIRST_OP = MS // 2           # dispatch.start -> first op
AFTER_LAST_OP = 7 * MS // 10    # last op -> device_wait.end


def make(open_ms=OPEN_MS, first=10_002 * MS, drop_ops_of=(), fps=None):
    """(ring body, device events, client samples) of a closed loop;
    request k dispatches the executable `fps[k]` (else the one of its
    cost, `FPS`)."""
    ids = iter(range(1, 10_000))
    rows, events, samples = [], [], []
    t = first
    for k, dur in enumerate(open_ms):
        s, e = t, t + dur * MS
        trace = next(ids)
        rest, env = next(ids), next(ids)

        def span(name, parent, a, b, attrs=None, span_id=None):
            row = {"trace_id": trace, "span_id": span_id or next(ids),
                   "parent_id": parent, "name": name, "start_ns": a,
                   "end_ns": b}
            if attrs:
                row["attributes"] = attrs
            rows.append(row)

        span("http.read_decode", trace, s, s + 100_000)
        span("envelope.parse", env, s + 300_000, s + 400_000)
        span("envelope.compile_group", env, s + 400_000, s + 900_000,
             {"wave": 0})
        span("envelope.pack", env, s + 900_000, s + MS, {"wave": 0})
        span("dispatch", env, s + MS, s + MS + 200_000,
             {"wave": 0, "programs": 1, "nbytes": 64, "family":
              "bm25_dense",
              "fingerprint": fps[k] if fps else FPS.get(dur, FP),
              "shape": "b1/k10/d128"})
        wait_end = e - 300_000
        span("device_wait", env, s + MS + 200_000, wait_end,
             {"wave": 0, "nbytes": 84, "programs": 0})
        span("respond", env, wait_end, wait_end + 50_000, {"wave": 0})
        span("envelope", rest, s + 250_000, e - 200_000,
             {"bodies": 1, "waves": 1}, span_id=env)
        span("rest.search", trace, s + 200_000, e - 150_000,
             span_id=rest)
        span("http.encode_write", trace, e - 100_000, e - 10_000)
        span("http.request", 0, s, e,
             {"method": "POST", "route": "_search", "status": 200,
              "request_bytes": 110, "response_bytes": 900},
             span_id=trace)
        samples.append(SimpleNamespace(index=k, sent=(s - 50_000) / 1e9,
                                       done=(e + 50_000) / 1e9))
        if k not in drop_ops_of:
            op0 = s + MS + TO_FIRST_OP + OFFSET
            op3 = wait_end - AFTER_LAST_OP + OFFSET
            third = (op3 - op0) // 3
            # one program: three ops, a 1 us stall before the last
            events += [
                ("%fusion.1 = f32[128]{0} fusion(%p0, %p1), kind=kCustom",
                 op0, op0 + third),
                ("%fusion.2 = s32[128]{0} fusion(%fusion.1), kind=kLoop",
                 op0 + third + 2, op0 + 2 * third),
                ("%sort.1 = (f32[128]{0}, s32[128]{0}) sort(%fusion.2)",
                 op0 + 2 * third + 1_000, op3)]
        t = e + TURNAROUND
    body = {"clock": "monotonic_ns", "dropped": 0, "spans": rows,
            "anchor": {"monotonic_ns": t, "time_ns": t + 10**18}}
    return body, events, samples


def joined(body, events, **kw):
    ring = spans.Spans(body)
    return ring, spans.join(spans.waves_of(ring), events, SLICE, **kw)


# ------------------------------------------------------------ the ring

def test_self_time_is_a_span_less_what_its_children_cover():
    body, _, _ = make(open_ms=(5,))
    ring = spans.Spans(body)
    named = {s["name"]: s for s in ring.spans}
    # http.request 5 ms: read_decode 0.1, rest.search 4.65, write 0.09
    assert ring.self_ns(named["http.request"]) == 5 * MS - 100_000 \
        - (5 * MS - 350_000) - 90_000
    assert ring.self_ns(named["dispatch"]) == 200_000
    assert ring.self_ns(named["rest.search"]) == 100_000
    # children that overlap are covered once
    ring.children[named["dispatch"]["span_id"]] = [
        {"start_ns": named["dispatch"]["start_ns"],
         "end_ns": named["dispatch"]["start_ns"] + 150_000},
        {"start_ns": named["dispatch"]["start_ns"] + 100_000,
         "end_ns": named["dispatch"]["end_ns"] + 999}]
    assert ring.self_ns(named["dispatch"]) == 0


def test_requests_are_the_served_routes_inside_the_window():
    body, _, _ = make()
    body["spans"].append({
        "trace_id": 9001, "span_id": 9001, "parent_id": 0,
        "name": "http.request", "start_ns": 10_010 * MS,
        "end_ns": 10_011 * MS,
        "attributes": {"route": "other", "status": 200}})
    ring = spans.Spans(body)
    assert len(ring.requests(*SLICE)) == len(OPEN_MS)
    assert len(ring.requests(10_008 * MS, 10_030 * MS)) == 2
    assert [w.programs for w in spans.waves_of(ring)] == [1] * 6


# ---------------------------------------------------------- the bracket

def test_program_runs_are_cut_at_the_gap_not_at_a_stall():
    _, events, _ = make()
    runs = spans.program_runs(events)
    assert [len(r.events) for r in runs] == [3] * len(OPEN_MS)
    # a gap over RUN_GAP_NS inside a program would cut it in two
    name, lo, hi = events[2]
    events[2] = (name, lo + spans.RUN_GAP_NS, hi)
    assert len(spans.program_runs(events)) == len(OPEN_MS) + 1
    assert spans.instruction("%fusion.5 = f32[16]{0} fusion(%a)") \
        == "fusion.5"
    assert spans.instruction("copy-done.1") == "copy-done.1"


def modules_of(events, name="jit_bm25_dense(1234567890123)"):
    """One module event a program of `make()`'s ops (three ops each),
    as a TPU plane's "XLA Modules" line has them: from a little before
    the program's first op to its last op's end."""
    by_start = sorted(events, key=lambda e: e[1])
    return [(name, by_start[i][1] - 400, by_start[i + 2][2])
            for i in range(0, len(by_start), 3)]


def test_program_runs_come_from_module_events_where_the_plane_has_them():
    # two programs queued back to back: the second's first op starts
    # the nanosecond the first's last op ends
    a = [("%fusion.1 = f32[8]{0} fusion(%p)", 1_000, 2_000),
         ("%sort.1 = f32[8]{0} sort(%fusion.1)", 2_000, 3_000)]
    b = [("%fusion.1 = f32[8]{0} fusion(%p)", 3_000, 4_500),
         ("%sort.1 = f32[8]{0} sort(%fusion.1)", 4_500, 5_000)]
    modules = [("jit_bm25_candidate(11)", 900, 3_000),
               ("jit_concat_rows(12)", 3_000, 5_000)]
    # cut at the gap they are one run; by their module events, two
    assert [len(r.events) for r in spans.program_runs(a + b)] == [4]
    runs = spans.program_runs(b + a, modules)
    assert [(r.name, r.start, r.end) for r in runs] == [
        ("jit_bm25_candidate(11)", 1_000, 3_000),
        ("jit_concat_rows(12)", 3_000, 5_000)]
    assert [r.events for r in runs] == [a, b]
    # which way is decided by what the trace holds: a plane without the
    # line is cut at the gap, and so are the ops whose module event the
    # profiler cut away at an edge of the trace
    assert [r.name for r in spans.program_runs(a + b, [])] == [None]
    early = [("%fusion.9 = f32[8]{0} fusion(%p)", -40_000, -39_000),
             ("%fusion.9 = f32[8]{0} fusion(%p)",
              -39_000 + spans.RUN_GAP_NS + 1, -20_000)]
    runs = spans.program_runs(a + b + early, modules)
    assert [(r.name, len(r.events)) for r in runs] == [
        (None, 1), (None, 1), ("jit_bm25_candidate(11)", 2),
        ("jit_concat_rows(12)", 2)]
    # a stall inside a program, longer than the gap, no longer cuts it
    _, events, _ = make()
    name, lo, hi = events[2]
    events[2] = (name, lo + 2 * spans.RUN_GAP_NS, hi)
    assert len(spans.program_runs(events)) == len(OPEN_MS) + 1
    runs = spans.program_runs(events, modules_of(events))
    assert [len(r.events) for r in runs] == [3] * len(OPEN_MS)
    assert {r.name for r in runs} == {"jit_bm25_dense(1234567890123)"}


def test_back_to_back_programs_of_one_wave_join_by_their_module_events():
    """A wave of two programs (the kernel, then the row concat of its
    `device_wait`) whose runs touch: cut at the gap the plane has one
    run a wave and the wave wants two, by module events it has both."""
    body, events, _ = make()
    for row in body["spans"]:
        if row["name"] == "device_wait":
            row["attributes"]["programs"] = 1
    by_start = sorted(events, key=lambda e: e[1])
    modules = []
    for i in range(0, len(by_start), 3):
        first, second, third = by_start[i:i + 3]
        modules += [("jit_bm25_dense(1)", first[1] - 400, second[2]),
                    ("jit_concat_rows(2)", third[1], third[2])]
        # no gap between the programs
        by_start[i + 2] = (third[0], second[2], third[2])
        modules[-1] = ("jit_concat_rows(2)", second[2], third[2])
    ring = spans.Spans(body)
    waves = spans.waves_of(ring)
    assert [w.programs for w in waves] == [2] * len(OPEN_MS)
    # one run a wave where two are due: the gap cut sees the waves'
    # programs run together and matches half the waves, wrongly
    by_gap = spans.join(waves, by_start, SLICE)
    assert by_gap is None or len(by_gap.waves) < len(OPEN_MS)
    jn = spans.join(waves, by_start, SLICE, modules=modules)
    assert jn is not None and jn.lo <= OFFSET <= jn.hi
    assert len(jn.waves) == len(OPEN_MS)
    assert jn.matched_runs == 2 * len(OPEN_MS)
    assert [[r.name for r in w.runs] for w in jn.waves] \
        == [["jit_bm25_dense(1)", "jit_concat_rows(2)"]] * len(OPEN_MS)


PLANES = [f"/device:TPU:{k}" for k in range(4)]


def four_planes(skew_ns=(0, 200_000, -100_000, 300_000)):
    """`make()`'s ops on four planes of one trace, as an SPMD program
    runs on every chip of the mesh; plane k runs `skew_ns[k]` late, and
    the profiler came up on every plane at another time, so the planes'
    edges differ: plane 1 lost the first op of its first program, plane
    2 the whole first program, plane 3 the last op of its last."""
    body, events, samples = make()
    events = sorted(events, key=lambda e: e[1])
    cut = [events, events[1:], events[3:], events[:-1]]
    planes = {name: [(n, lo + d, hi + d) for n, lo, hi in evs]
              for name, evs, d in zip(PLANES, cut, skew_ns)}
    return body, events, samples, planes


def test_a_four_plane_join_intersects_the_planes_intervals():
    body, events, samples, planes = four_planes()
    ring = spans.Spans(body)
    waves = spans.waves_of(ring)
    mesh = spans.join_planes(waves, planes, SLICE)
    assert mesh is not None and list(mesh.planes) == PLANES
    # every plane's own interval holds its own offset; the true offset
    # of the trace's zero lies in all of them, so in their intersection
    for jn, d in zip(mesh.planes.values(), (0, 200_000, -100_000, 300_000)):
        assert jn.lo <= OFFSET + d <= jn.hi
        assert jn.offset == mesh.offset     # one offset for all
    assert mesh.lo == max(jn.lo for jn in mesh.planes.values())
    assert mesh.hi == min(jn.hi for jn in mesh.planes.values())
    assert mesh.lo <= OFFSET <= mesh.hi
    # plane 3 ran 0.3 ms late and bounds the offset from below, plane 2
    # ran 0.1 ms early and bounds it from above: narrower than any one
    assert mesh.lo == OFFSET + 300_000 - AFTER_LAST_OP
    assert mesh.hi == OFFSET - 100_000 + TO_FIRST_OP
    assert mesh.bracket_ns == 800_000 < min(
        jn.bracket_ns for jn in mesh.planes.values())
    # the waves of one plane's join are not another's
    assert [len(jn.waves) for jn in mesh.planes.values()] == [6, 6, 5, 6]
    assert [len(w.runs[0].events)
            for w in mesh.planes[PLANES[1]].waves][:2] == [2, 3]
    assert all(not hasattr(w, "runs") or not w.runs for w in waves)
    # the planes' recorded intervals differ, each on the host's clock
    run = FakeRun(body, planes, samples)
    intervals = spans.recorded_intervals(run)
    assert len(set(intervals)) == 4
    assert intervals[2][0] - intervals[0][0] == pytest.approx(
        (events[3][1] - 100_000 - events[0][1]) / 1e9)
    # a request counts by its mean share over the planes: the first one
    # is cut by planes 0 and 1 and was over before plane 2 began
    shares = dict((s.index, share)
                  for s, share in readings.slice_shares(run))
    want = sum(cut_by(samples[0], a * 1e9, b * 1e9)
               for a, b in intervals) / 4
    assert shares[0] == pytest.approx(want) and 0 < want < 0.7
    # window, busy, idle and its parts are means over the planes
    trace = run.trace
    assert trace.window_s == pytest.approx(
        sum(b - a for a, b in intervals) / 4)
    parts = spans.mesh_idle_parts(spans.fetch(run), mesh)
    assert sum(parts.values()) == pytest.approx(
        (trace.window_s - trace.busy_s) * 1e9)
    assert read(run, "span_clock_bracket_us.closed") == pytest.approx(800.0)
    assert read(run, "device_runs_agree.closed") == pytest.approx(100.0)
    n = readings.queries_in_slice(run)
    assert sum(read(run, m) for m in STAGE_METRICS) == pytest.approx(
        trace.busy_s * 1e3 / n)
    assert sum(read(run, m) for m in IDLE_METRICS) == pytest.approx(
        (trace.window_s - trace.busy_s) * 1e3 / n)


def test_an_empty_intersection_of_the_planes_intervals_is_no_join():
    # plane 3 two milliseconds late: it joins alone, and with no other
    body, _, samples, planes = four_planes((0, 200_000, -100_000, 2 * MS))
    ring = spans.Spans(body)
    waves = spans.waves_of(ring)
    alone = spans.join(waves, planes[PLANES[3]], SLICE)
    assert alone is not None and alone.lo <= OFFSET + 2 * MS <= alone.hi
    assert spans.join_planes(waves, planes, SLICE) is None
    # the readers then read nothing from the device side, and the window
    # and its requests keep the host's slice
    run = FakeRun(body, planes, samples)
    assert all(read(run, m) is None for m in DEVICE_METRICS)
    assert run.trace.window_s == pytest.approx((SLICE[1] - SLICE[0]) / 1e9)
    assert readings.queries_in_slice(run) == pytest.approx(len(OPEN_MS))
    # a plane on which nothing ran is left out of the join, not a
    # reason for none
    body, _, samples, planes = four_planes()
    planes["/device:TPU:4"] = []
    mesh = spans.join_planes(waves, planes, SLICE)
    assert mesh is not None and list(mesh.planes) == PLANES


def test_device_busy_skew_reads_the_planes_apart():
    ops = {name: [("%fusion.1 = f32[8]{0} fusion(%p)", 0, busy_ms * MS),
                  ("%fusion.2 = f32[8]{0} fusion(%p)", 9 * MS, 10 * MS)]
           for name, busy_ms in zip(PLANES, (4, 2, 3, 3))}
    run = FakeRun(None, ops, [])
    assert run.trace.plane_busy_s == {
        name: pytest.approx(ms / 1e3)
        for name, ms in zip(PLANES, (5, 3, 4, 4))}
    assert run.trace.busy_s == pytest.approx(0.004)
    # (5 - 3) / 4
    assert read(run, "device_busy_skew") == pytest.approx(50.0)
    # one plane has nothing to be uneven with: no number, not 0
    run = FakeRun(None, ops[PLANES[0]], [])
    assert read(run, "device_busy_skew") is None
    assert read(FakeRun(None, None, []), "device_busy_skew") is None
    # found by name like every metric, and listed by no cell until a
    # four-chip cell lists it
    assert "device_busy_skew" not in {m["name"] for m in BENCH["per_layer"]}


def test_a_feasible_interval_holds_the_true_offset():
    body, events, _ = make()
    _, jn = joined(body, events)
    assert jn is not None and jn.lo <= OFFSET <= jn.hi
    assert jn.hi - OFFSET == TO_FIRST_OP
    assert OFFSET - jn.lo == AFTER_LAST_OP
    assert jn.bracket_ns == TO_FIRST_OP + AFTER_LAST_OP
    assert len(jn.waves) == jn.matched_runs == len(OPEN_MS)


def test_an_empty_interval_is_no_join():
    body, events, _ = make()
    # stretch one program past the wave that would own it
    name, lo, hi = events[-1]
    events[-1] = (name, lo, hi + 3 * MS)
    _, jn = joined(body, events)
    assert jn is None
    ring = spans.Spans(body)
    assert spans.join(spans.waves_of(ring), [], SLICE) is None
    assert spans.join([], events, SLICE) is None


def test_a_match_one_wave_along_is_found():
    # the profiler came up after the first request's program: its wave
    # is there, its ops are not
    body, events, _ = make(drop_ops_of=(0,))
    _, jn = joined(body, events)
    assert jn is not None and jn.lo <= OFFSET <= jn.hi
    assert len(jn.waves) == len(OPEN_MS) - 1
    assert jn.waves[0].dispatch["start_ns"] > 10_007 * MS
    # and the other way round: a first run whose wave fell off the ring
    body, events, _ = make()
    gone = body["spans"][0]["trace_id"]
    body["spans"] = [s for s in body["spans"] if s["trace_id"] != gone]
    _, jn = joined(body, events)
    assert jn is not None and jn.lo <= OFFSET <= jn.hi
    assert jn.matched_runs == len(OPEN_MS) - 1
    assert len(jn.runs) == len(OPEN_MS)


def test_runs_agree_checks_the_join_by_what_an_executable_takes():
    # two executables: a request open 4 ms runs 1.5 ms on the device,
    # one open 10 ms runs 7.5 ms
    open_ms = (4, 10, 10, 4, 10, 10)
    fps = ["short" if ms == 4 else "long" for ms in open_ms]
    body, events, _ = make(open_ms=open_ms, fps=fps)
    _, jn = joined(body, events)
    assert [w.fingerprints for w in jn.waves] == [[fp] for fp in fps]
    # the plane's first and last run are left out (the profiler cuts
    # them); every other run takes what its executable takes
    assert spans.runs_agree(jn) == 1.0
    # the k-th wave given the (k+1)-th run: a `long` wave now owns a
    # 1.5 ms run beside a 7.5 ms one. (This bracket would be empty; one
    # whose waves are open long enough would not be.)
    for w, nxt in zip(jn.waves, jn.waves[1:]):
        w.runs = nxt.runs
    jn.waves[-1].runs = []
    assert spans.runs_agree(jn) == pytest.approx(15 / 24)
    # nothing to compare: one run an executable
    body, events, _ = make(fps=list("abcdef"))
    _, jn = joined(body, events)
    assert spans.runs_agree(jn) is None


# ---------------------------------------------------- idle, by the span

def test_the_four_idle_parts_sum_to_the_slices_idle():
    body, events, _ = make()
    ring, jn = joined(body, events)
    parts = spans.idle_parts(ring, jn, SLICE)
    assert set(parts) == set(spans.IDLE_PARTS)
    busy = sum(hi - lo for lo, hi in spans.union(
        [(lo, hi) for _, lo, hi in events]))
    assert sum(parts.values()) == (SLICE[1] - SLICE[0]) - busy
    # no request open: before the first, the client's turnarounds, and
    # after the last, to the slice's end
    last_end = 10_002 * MS + sum(OPEN_MS) * MS + 5 * TURNAROUND
    assert parts["between_requests"] == 2 * MS + 5 * TURNAROUND \
        + (SLICE[1] - last_end)
    # the offset sits mid-bracket, 0.1 ms from the truth: what it moves
    # goes from one neighbour to the other, request by request
    err = jn.offset - OFFSET
    assert abs(err) == 100_000
    assert parts["before_first_op"] == 6 * (MS + TO_FIRST_OP - err)
    assert parts["after_last_op"] == 6 * (AFTER_LAST_OP + 300_000 + err)
    # the 1 us stall and the 2 ns seam inside each program
    assert parts["inside_request"] == 6 * 1_002


# --------------------------------------------------- stages, by the map

def test_the_stages_sum_to_busy_time():
    body, events, _ = make()
    _, jn = joined(body, events)
    by_stage = spans.stage_ns(jn, SCOPES)
    assert set(by_stage) == {"postings_gather", "scatter", "~top_k"}
    assert sum(by_stage.values()) == sum(hi - lo for _, lo, hi in events)
    # an op the map does not know is unnamed, not lost
    events.append(("%mystery.9 = f32[4]{0} add(%a, %b)",
                   events[-1][2] + 5, events[-1][2] + 105))
    _, jn = joined(body, events)
    assert spans.stage_ns(jn, SCOPES)[None] == 100
    # an executable of the slice without a map: no stage is read
    assert spans.stage_ns(jn, {"another": SCOPES[FP]}) is None


class FakeRun:
    """What the readers take from a run, with a canned node. `events`:
    one plane's device ops, or {plane name -> ops}; `modules`: the same
    for the planes' module events."""

    def __init__(self, body, events, samples, scopes=SCOPES, modules=None):
        self.window = (SLICE[0] / 1e9, SLICE[1] / 1e9)
        self.drained = SLICE[1] / 1e9
        self.trace_slice = self.window
        self.all_samples = samples
        self.requests = [["q"]] * len(samples)
        if events is not None and not isinstance(events, dict):
            events = {"/device:TPU:0": events} if events else {}
            modules = {"/device:TPU:0": modules} if modules else None
        self.trace = None if events is None else trace_reduce.Reduction(
            events, (SLICE[1] - SLICE[0]) / 1e9, modules)
        self.canned_body, self.canned_scopes = body, scopes
        self.calls = []

    def call(self, method, path, body=None):
        self.calls.append(path)
        if path.startswith("/_telemetry/spans") \
                and self.canned_body is not None:
            return self.canned_body
        if path.startswith("/_telemetry/kernels") and self.canned_scopes:
            return {"kernels": {"census": {"executables": [
                {"fingerprint": fp, "scopes": m}
                for fp, m in self.canned_scopes.items()]}}}
        raise RuntimeError(f"GET {path} -> 400: no handler")


def read(run, metric):
    fn, params = bench_run.Files(REPO).reader(metric)
    return fn(run, params)


def cut_by(sample, a_ns: int, b_ns: int) -> float:
    """The share of a request's service interval inside [a, b] ns."""
    lo, hi = max(sample.sent, a_ns / 1e9), min(sample.done, b_ns / 1e9)
    return max(hi - lo, 0.0) / (sample.done - sample.sent)


def test_the_readers_on_a_hand_made_run():
    body, events, samples = make()
    run = FakeRun(body, events, samples)
    got = {m: read(run, m) for m in NEW}
    assert all(v is not None for v in got.values()), got
    mean_open = sum(OPEN_MS) / len(OPEN_MS)
    assert sum(got[m] for m in SPAN_METRICS) == pytest.approx(mean_open)
    assert got["enqueue_ms.closed"] == pytest.approx(0.2)
    assert got["respond_ms.closed"] == pytest.approx(0.05)
    assert got["rest_self_ms.closed"] == pytest.approx(0.1)
    # the window is what the plane recorded, first op to last, and the
    # requests are counted over that interval: the first and the last
    # are cut by it
    n = readings.queries_in_slice(run)
    assert len(OPEN_MS) - 2 < n < len(OPEN_MS)
    first, last = events[0][1], events[-1][2]
    assert run.trace.window_s == pytest.approx((last - first) / 1e9)
    assert run.trace.window_s < (SLICE[1] - SLICE[0]) / 1e9
    idle_ms = (run.trace.window_s - run.trace.busy_s) * 1e3
    assert sum(got[m] for m in IDLE_METRICS) == pytest.approx(idle_ms / n)
    assert sum(got[m] for m in STAGE_METRICS) == pytest.approx(
        run.trace.busy_s * 1e3 / n)
    assert got["dense_other_ms.closed"] == pytest.approx(0.0, abs=1e-9)
    assert got["span_clock_bracket_us.closed"] == pytest.approx(1200.0)
    assert got["device_runs_agree.closed"] == pytest.approx(100.0)
    # a stage the census inferred (`~top_k`) counts in its stage
    assert got["dense_topk_ms.closed"] > 0
    # the ring and the census are fetched once a run
    assert len([c for c in run.calls if "spans" in c]) == 1
    assert len([c for c in run.calls if "kernels" in c]) == 1


def test_the_window_and_the_requests_are_read_over_one_interval():
    """What the device recorded, not the host's clock around the
    profiler's start and stop: the plane's first op to its last, put on
    the host's clock by the join's offset; a request that the plane's
    first op cuts counts by its share."""
    body, events, samples = make()
    run = FakeRun(body, events, samples)
    mesh = spans.device_join(run)
    (a, b), = spans.recorded_intervals(run)
    assert a * 1e9 == pytest.approx(events[0][1] - mesh.offset)
    assert b * 1e9 == pytest.approx(events[-1][2] - mesh.offset)
    assert run.trace.window_s == pytest.approx(b - a)
    assert SLICE[0] / 1e9 < a and b < SLICE[1] / 1e9
    shares = dict((s.index, share)
                  for s, share in readings.slice_shares(run))
    # request 0 was open 5.1 ms for the client; its first op started
    # 1.5 ms into the span (+-0.1 ms of offset), 1.55 ms after the send
    assert shares[0] == pytest.approx(cut_by(samples[0], a * 1e9, b * 1e9))
    assert shares[0] == pytest.approx((5.1 - 1.55) / 5.1, abs=0.11 / 5.1)
    assert all(shares[k] == 1.0 for k in range(1, len(OPEN_MS) - 1))
    assert 0 < shares[len(OPEN_MS) - 1] < 1
    assert readings.queries_in_slice(run) == pytest.approx(
        sum(shares.values()))
    assert run.trace.idle_share == pytest.approx(
        1 - run.trace.busy_s / (b - a))
    # idle is attributed inside that interval only: nothing is idle
    # before the first op or after the last
    parts = spans.mesh_idle_parts(spans.fetch(run), mesh)
    assert sum(parts.values()) == pytest.approx(
        (run.trace.window_s - run.trace.busy_s) * 1e9)
    # without a join both keep the host's slice
    name, lo, hi = events[-1]
    events[-1] = (name, lo, hi + 3 * MS)
    run = FakeRun(body, events, samples)
    assert spans.device_join(run) is None
    assert spans.recorded_intervals(run) is None
    assert run.trace.window_s == pytest.approx((SLICE[1] - SLICE[0]) / 1e9)
    assert readings.queries_in_slice(run) == pytest.approx(len(OPEN_MS))


@pytest.mark.parametrize("what", ["no span ring", "no device plane",
                                  "no scope map", "a refused scope map",
                                  "no join"])
def test_a_reader_with_nothing_to_read_returns_none(what):
    body, events, samples = make()
    if what == "no span ring":      # the parent commit's node
        run = FakeRun(None, events, samples)
        assert all(read(run, m) is None for m in NEW)
    elif what == "no device plane":  # the CPU dry run
        run = FakeRun(body, [], samples)
        assert all(read(run, m) is not None for m in SPAN_METRICS)
        assert all(read(run, m) is None for m in DEVICE_METRICS)
    elif what == "no scope map":
        run = FakeRun(body, events, samples, scopes=None)
        assert all(read(run, m) is None for m in STAGE_METRICS)
        assert all(read(run, m) is not None for m in IDLE_METRICS)
    elif what == "a refused scope map":
        # the node loaded the executable from a compile cache filled by
        # another stage layout: no stage rather than a stale one
        run = FakeRun(body, events, samples, scopes={
            FP: {"_error": "loaded from the compile cache, compiled "
                           "from another stage layout"},
            "unused": SCOPES[FP]})
        assert all(read(run, m) is None for m in STAGE_METRICS)
        assert all(read(run, m) is not None for m in IDLE_METRICS)
    else:
        name, lo, hi = events[-1]
        events[-1] = (name, lo, hi + 3 * MS)
        run = FakeRun(body, events, samples)
        assert all(read(run, m) is None for m in DEVICE_METRICS)
        assert all(read(run, m) is not None for m in SPAN_METRICS)


# -------------------------------------------------- the recorded traces

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded")


def recorded(name: str):
    """(ops, modules, the kept slice) of a recorded trace: see
    test_trace_reduce.py's docstring for what each holds."""
    ops, modules = trace_reduce.device_events(
        os.path.join(RECORDED, name + ".xplane.pb"))
    kept = json.load(open(os.path.join(RECORDED, name + ".slice.json")))
    return ops, modules, kept


def recorded_run(name: str) -> "FakeRun":
    """A recorded slice as the readers see a run: its planes, the ring's
    rows and the client's samples, no scope map (none was kept)."""
    ops, modules, kept = recorded(name)
    samples = [SimpleNamespace(index=s["index"], sent=s["sent"],
                               done=s["done"]) for s in kept["samples"]]
    run = FakeRun(kept["ring"], ops, samples, scopes=None, modules=modules)
    run.trace_slice = run.window = tuple(kept["trace_slice"])
    run.trace_called = kept["trace_called"]
    run.drained = max(s.done for s in samples)
    run.trace = trace_reduce.Reduction(
        ops, run.trace_slice[1] - run.trace_slice[0], modules)
    run.requests = {s.index: ["q"] for s in samples}
    return run


def test_the_one_chip_trace_reads_as_the_run_that_recorded_it_read():
    """`msmarco-natural-closed` on the v5e, seed 2147510001, a 10 s
    window (my chip run, PR 27): what that run printed comes out of its
    kept trace and ring again, off the chip."""
    run = recorded_run("msmarco-1chip")
    a, b = run.trace_slice
    mesh = spans.device_join(run)
    jn, = mesh.planes.values()
    # one module event a run, each named for the family its wave
    # dispatched; by the gap the same 22 runs (the programs of one
    # client lie 20 ms apart)
    assert len(jn.runs) == jn.matched_runs == len(jn.waves) == 22
    ops = run.trace.planes["/device:TPU:0"]
    assert [(r.start, r.end) for r in spans.program_runs(ops)] \
        == [(r.start, r.end) for r in jn.runs]
    for w in jn.waves:
        family = w.dispatch["attributes"]["family"]
        assert [r.name.split("(")[0] for r in w.runs] == ["jit_" + family]
    # two XLA modules (the QB buckets) under the census' six
    # fingerprints (terms x QB bucket): a fingerprint's runs all carry
    # one module's name, which a matching shifted by a wave would break
    by_name = {}
    for w in jn.waves:
        by_name.setdefault(w.runs[0].name, set()).update(w.fingerprints)
    assert sorted(len(v) for v in by_name.values()) == [3, 3]
    assert not set.intersection(*by_name.values())
    # the plane began 64.9 ms after start_trace returned and ended
    # 18.6 ms before stop_trace was called: 5.917 of the host's 6.000 s
    (lo, hi), = spans.recorded_intervals(run)
    assert lo - a == pytest.approx(0.0649, abs=1e-3)
    assert hi - b == pytest.approx(-0.0186, abs=1e-3)
    assert run.trace.window_s == pytest.approx(5.917027478)
    assert run.trace.host_window_s == pytest.approx(6.000430408)
    assert run.trace.busy_s == pytest.approx(5.346826479)
    n = readings.queries_in_slice(run)
    assert n == pytest.approx(20.0936154)
    got = {m: read(run, m) for m in IDLE_METRICS + ALIGN_METRICS
           + ["device_ms_per_query.closed"]}
    assert got == {
        "idle_between_requests_ms.closed": pytest.approx(0.54473313),
        "idle_before_first_op_ms.closed": pytest.approx(26.01070044),
        "idle_inside_request_ms.closed": pytest.approx(0.00155721),
        "idle_after_last_op_ms.closed": pytest.approx(1.82023191),
        "span_clock_bracket_us.closed": pytest.approx(1690.472),
        "device_runs_agree.closed": pytest.approx(100.0),
        "device_ms_per_query.closed": pytest.approx(266.09579068)}
    assert sum(got[m] for m in IDLE_METRICS) == pytest.approx(
        (run.trace.window_s - run.trace.busy_s) * 1e3 / n)
    # a device cannot have worked longer for a query than the host
    # waited for it
    assert got["device_ms_per_query.closed"] \
        <= read(run, "enqueue_ms.closed") + read(run, "device_wait_ms.closed")
    # no scope map was kept: no stage is read
    assert all(read(run, m) is None for m in STAGE_METRICS)
    # over the host's slice, as before PR 27, the same trace read 1.1%
    # fewer device milliseconds a query and 1.26 points more idle:
    # 83 ms of the host's 6 s were never in the trace
    n_host = sum(max(min(s.done, b) - max(s.sent, a), 0.0)
                 / (s.done - s.sent) for s in run.all_samples)
    assert run.trace.busy_s * 1e3 / n_host == pytest.approx(263.20104697)
    assert 1 - run.trace.busy_s / (b - a) == pytest.approx(0.10892617)
    assert run.trace.idle_share == pytest.approx(0.09636612)


def test_the_four_chip_trace_joins_on_every_plane():
    ops, modules, kept = recorded("spmd-4chip")
    a, b = (int(t * 1e9) for t in kept["trace_slice"])
    ring = spans.Spans(kept["ring"])
    # by their module events and by the gap alike: ten runs a plane,
    # each named for the SPMD program
    for plane in ops:
        runs = spans.program_runs(ops[plane], modules[plane])
        assert len(runs) == len(spans.program_runs(ops[plane])) == 10
        assert {r.name.split("(")[0] for r in runs} \
            == {"jit_local_query_phase"}
        # five bodies, five executables, each run twice
        assert len({r.name for r in runs}) == 5
        assert [r.name for r in runs[:5]] == [r.name for r in runs[5:]]
    # that route records no `dispatch` and no `device_wait` yet (ROADMAP
    # R0 row 1 has it to do): the wave of a body is the `rest.search`
    # that encloses its one program run
    inside = ring.requests(a, b)
    assert len(inside) == 10
    waves = []
    for req in inside:
        rest, = ring.named(req["trace_id"], "rest.search")
        waves.append(spans.Wave(
            {**rest, "attributes": {"programs": 1}},
            {**rest, "attributes": {"programs": 0}}))
    mesh = spans.join_planes(waves, ops, (a, b), modules)
    assert mesh is not None and len(mesh.planes) == 4
    assert mesh.lo <= mesh.hi
    for jn in mesh.planes.values():
        assert len(jn.waves) == jn.matched_runs == len(jn.runs) == 10
        assert jn.lo <= mesh.lo <= mesh.hi <= jn.hi
        # a body's program starts after its `rest.search` began and is
        # over before it ended, on the host's clock
        for w in jn.waves:
            assert w.start_ns <= w.runs[0].start - mesh.offset
            assert w.runs[-1].end - mesh.offset <= w.end_ns
    # the bracket is as wide as the host side of the shortest body
    assert 1_000_000 < mesh.bracket_ns < 12_000_000
    # the planes' recorded intervals agree to microseconds, and lie
    # inside the host's slice: 174.5 ms of its 242.5
    intervals = [jn.recorded_ns for jn in mesh.planes.values()]
    assert max(lo for lo, _ in intervals) - min(lo for lo, _ in intervals) \
        < 2_000
    assert all(a < lo and hi < b for lo, hi in intervals)
    assert all(hi - lo == pytest.approx(174_485_000, rel=1e-4)
               for lo, hi in intervals)
    # and the skew reader reads a number on it
    run = FakeRun(None, ops, [], modules=modules)
    assert 0 < read(run, "device_busy_skew") < 1.0
    # one plane pushed 20 ms off the others: no common offset is left
    shifted = dict(ops)
    shifted["/device:TPU:3"] = [(n, lo + 20 * MS, hi + 20 * MS)
                                for n, lo, hi in ops["/device:TPU:3"]]
    assert spans.join_planes(waves, shifted, (a, b)) is None


# --------------------------------------------------- the committed cell

def test_traced_dry_run_prints_the_span_metrics_only():
    assert os.environ["JAX_PLATFORMS"] == "cpu"     # conftest pinned it
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483777", "--seconds", "3",
         "--trace", "1", "--dry-run"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    metrics = out["metrics"]
    for name in SPAN_METRICS:
        assert metrics[name]["unit"] == "ms" and metrics[name]["value"] > 0
    assert not set(DEVICE_METRICS) & set(metrics)
    # a request is its layers: the six sum to what the client saw, less
    # the socket
    total = sum(metrics[name]["value"] for name in SPAN_METRICS)
    assert 0 < total < 1000
    # `query_phase_ms.closed` is retired: `enqueue_ms.closed` and
    # `device_wait_ms.closed` say it in two parts
    assert "query_phase_ms.closed" not in metrics
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "metrics", "query_phase_ms.closed.json"))
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "metrics", "query_phase_ms.json"))


def test_the_new_entries_name_files_that_exist():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == NEW
    mdir = os.path.join(REPO, "benchmark", "metrics")
    for name in NEW:
        e = entries[name]
        assert e["workloads"] == [CELL]
        assert e["moves"] == "closed_search_p50_ms"
        assert e["source"] == ("program_span" if name in SPAN_METRICS
                               else "device_trace")
        spec = json.load(open(os.path.join(mdir, name + ".json")))
        assert set(spec) == {"reader", "params", "what"}
        assert os.path.exists(os.path.join(
            mdir, "readers", spec["reader"] + ".py"))
    parts = {json.load(open(os.path.join(mdir, m + ".json")))[
        "params"]["part"] for m in IDLE_METRICS}
    assert parts == set(spans.IDLE_PARTS)
    from opensearch_tpu.telemetry.kernels import STAGES
    for m in STAGE_METRICS:
        params = json.load(open(os.path.join(mdir, m + ".json")))["params"]
        named = params.get("stages") or params["rest_of"]
        assert {st.lstrip("~") for st in named} <= set(STAGES)
        # a stage and its inferred part are read together
        assert {"~" + st for st in named if st[0] != "~"} <= set(named)


def test_a_reader_that_trips_fails_the_run():
    # only absence is expected (no ring, no plane, no map, no join);
    # a ring of another shape is a bug, and a bug is not a None
    body, events, samples = make()
    body["spans"][3].pop("end_ns")
    run = FakeRun(body, events, samples)
    with pytest.raises(KeyError):
        read(run, "envelope_host_ms.closed")
