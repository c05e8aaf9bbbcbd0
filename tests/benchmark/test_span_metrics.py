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

from benchmark import run as bench_run      # noqa: E402
from benchmark import spans                 # noqa: E402

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
    """What the readers take from a run, with a canned node."""

    def __init__(self, body, events, samples, scopes=SCOPES):
        self.window = (SLICE[0] / 1e9, SLICE[1] / 1e9)
        self.drained = SLICE[1] / 1e9
        self.trace_slice = self.window
        self.all_samples = samples
        self.requests = [["q"]] * len(samples)
        busy = sum(hi - lo for lo, hi in spans.union(
            [(lo, hi) for _, lo, hi in events]))
        self.trace = None if events is None else SimpleNamespace(
            planes={"/device:TPU:0": events} if events else {},
            busy_s=busy / 1e9, window_s=(SLICE[1] - SLICE[0]) / 1e9)
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


def test_the_readers_on_a_hand_made_run():
    body, events, samples = make()
    run = FakeRun(body, events, samples)
    got = {m: read(run, m) for m in NEW}
    assert all(v is not None for v in got.values()), got
    n = len(OPEN_MS)        # every request lies inside the slice
    mean_open = sum(OPEN_MS) / n
    assert sum(got[m] for m in SPAN_METRICS) == pytest.approx(mean_open)
    assert got["enqueue_ms.closed"] == pytest.approx(0.2)
    assert got["respond_ms.closed"] == pytest.approx(0.05)
    assert got["rest_self_ms.closed"] == pytest.approx(0.1)
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
    assert metrics["device_wait_ms.closed"]["value"] \
        < metrics["query_phase_ms.closed"]["value"] + 1.0


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
