"""The readers of ISSUE 37 (`span_percentile`, `process_span_ms`,
`idle_under_span`) and its twenty metric files, on hand-made spans and
device events: a percentile of a span over the window's requests; a
process span across the window's edge, and one before it; device idle
wholly, partly and not under a span, checked against a microsecond
raster of the same slice; None against a node without a process track,
without a plane and without a ring; every new entry names a reader that
exists and a layer the benchmark already had; and a traced dry run of
each cell prints its new metrics.
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

from benchmark import process_track         # noqa: E402
from benchmark import readings              # noqa: E402
from benchmark import run as bench_run      # noqa: E402
from benchmark import spans                 # noqa: E402
from benchmark import trace_reduce          # noqa: E402

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
MDIR = os.path.join(REPO, "benchmark", "metrics")
CLOSED, DASH = "msmarco-natural-closed", "http_logs-4chip-dashboards"
KNN, TAXI = "vectorsearch-knn-closed-8", "nyc_taxis-aggs-closed-1"
SUFFIX = {"closed": CLOSED, "dash": DASH, "knn": KNN, "taxi": TAXI}
# the new entries, by the cells that list them
NEW = {
    "compile_bundle_ms.closed": [CLOSED],
    "compile_text_clause_ms.closed": [CLOSED],
    "compile_scan_note_ms.closed": [CLOSED],
    "respond_decode_aggs_ms.taxi": [TAXI],
    "respond_reduce_aggs_ms.taxi": [TAXI],
    "respond_render_ms.taxi": [TAXI],
    "respond_p95_ms.taxi": [TAXI],
    "spmd_plan_compile_ms.dash": [DASH],
    "spmd_plan_align_ms.dash": [DASH],
    "spmd_plan_stack_ms.dash": [DASH],
    "spmd_reduce_decode_ms.dash": [DASH],
    "spmd_reduce_aggs_ms.dash": [DASH],
    "gc_pause_ms": [CLOSED, DASH, KNN, TAXI],
    "gc_full_in_window": [CLOSED, DASH, KNN, TAXI],
    "install_host_pad_s": [CLOSED, DASH, KNN, TAXI],
    "install_device_put_s": [CLOSED, DASH, KNN, TAXI],
    "install_shard_set_s": [DASH],
    "idle_under_compile_bundle_ms.closed": [CLOSED],
    "idle_under_reduce_aggs_ms.taxi": [TAXI],
    "idle_under_gc_ms": [CLOSED, TAXI],
    # and one for each span that had no reader of its own
    "respond_unpack_ms.taxi": [TAXI],
    "spmd_reduce_scan_note_ms.dash": [DASH],
    "spmd_reduce_candidates_ms.dash": [DASH],
    "install_upload_segment_s": [CLOSED, DASH, KNN, TAXI],
    "install_shard_set_host_images_s": [DASH],
    "install_shard_set_stack_s": [DASH],
    "install_shard_set_device_put_s": [DASH],
}
NEW_READERS = {"span_percentile", "process_span_ms", "idle_under_span"}

MS = 1_000_000
US = 1_000
SLICE = (10_000 * MS, 10_060 * MS)      # host ns
OFFSET = -9_900 * MS                    # device = host + OFFSET
# one request: open 8 ms; 1.5 ms on the host before its first op
# (`compile.bundle` 0.5 ms of them), ops for 5 ms, then 1.5 ms after
# the last op, of which `respond` is 1 ms and `respond.reduce_aggs`
# 0.6 ms; the client's turnaround between requests is 2 ms
OPEN, TURNAROUND = 8 * MS, 2 * MS
N_REQUESTS = 5


def make(respond_ms=(1,) * N_REQUESTS):
    """(ring body with a process track, one plane's device events,
    client samples) of a one-client closed loop."""
    ids = iter(range(8, 100_000, 8))
    rows, events, samples = [], [], []
    t = SLICE[0] + 2 * MS
    for k in range(N_REQUESTS):
        s, e = t, t + OPEN
        trace = next(ids)
        rest, env, group, respond = (next(ids) for _ in range(4))

        def span(name, parent, a, b, attrs=None, span_id=None):
            row = {"trace_id": trace, "span_id": span_id or next(ids),
                   "parent_id": parent, "name": name, "start_ns": a,
                   "end_ns": b}
            if attrs:
                row["attributes"] = attrs
            rows.append(row)

        span("http.request", 0, s, e, {"route": "_search"}, trace)
        span("rest.search", trace, s + 100 * US, e - 100 * US, None, rest)
        span("envelope", rest, s + 200 * US, e - 200 * US,
             {"bodies": 1, "waves": 1}, env)
        span("envelope.compile_group", env, s + 300 * US, s + 900 * US,
             {"wave": 0}, group)
        span("compile.bundle", group, s + 350 * US, s + 850 * US,
             {"memo": "miss", "nbytes": 64})
        span("dispatch", env, s + MS, s + MS + 200 * US,
             {"wave": 0, "programs": 1, "nbytes": 64,
              "family": "agg_env", "fingerprint": "0123abcd",
              "shape": "b1/d128/bins50"})
        wait_end = e - 1200 * US
        span("device_wait", env, s + MS + 200 * US, wait_end,
             {"wave": 0, "nbytes": 84, "programs": 0})
        r_end = wait_end + respond_ms[k] * MS
        span("respond", env, wait_end, r_end, {"wave": 0}, respond)
        span("respond.reduce_aggs", respond, wait_end + 100 * US,
             wait_end + 700 * US, {"buckets": 50})
        samples.append(SimpleNamespace(index=k, sent=(s - 50 * US) / 1e9,
                                       done=(e + 50 * US) / 1e9))
        op0, op1 = s + 1500 * US + OFFSET, e - 1500 * US + OFFSET
        mid = (op0 + op1) // 2
        events += [("%fusion.1 = s32[128]{0} fusion(%p0)", op0, mid),
                   ("%fusion.2 = f32[128]{0} fusion(%fusion.1)", mid, op1)]
        t = e + TURNAROUND
    body = {"clock": "monotonic_ns", "dropped": 0, "spans": rows,
            "anchor": {"monotonic_ns": t, "time_ns": t + 10**18},
            "process": []}
    return body, events, samples


def gc_span(a, b, generation=2, span_id=7):
    return {"trace_id": 0, "span_id": span_id, "parent_id": 0,
            "name": "gc.collect", "start_ns": a, "end_ns": b,
            "attributes": {"generation": generation, "collected": 0,
                           "uncollectable": 0}}


class FakeRun:
    """What the readers take from a run, with a canned node that
    filters its process track as the node does."""

    def __init__(self, body, events, samples,
                 window=(SLICE[0] / 1e9, SLICE[1] / 1e9)):
        self.window = window
        self.drained = window[1]
        self.trace_slice = (SLICE[0] / 1e9, SLICE[1] / 1e9)
        self.all_samples = samples
        self.requests = [["q"]] * len(samples)
        self.trace = None if events is None else trace_reduce.Reduction(
            {"/device:TPU:0": events} if events else {},
            (SLICE[1] - SLICE[0]) / 1e9)
        self.canned_body = body
        self.calls = []

    def call(self, method, path, body=None):
        self.calls.append(path)
        if not path.startswith("/_telemetry/spans") \
                or self.canned_body is None:
            raise RuntimeError(f"GET {path} -> 400: no handler")
        out = dict(self.canned_body)
        if "process" in out:
            query = dict(p.split("=") for p in
                         path.partition("?")[2].split("&") if p)
            since = int(query.get("since_ns", -1))
            until = int(query.get("until_ns", 10**30))
            out["process"] = [s for s in out["process"]
                              if s["end_ns"] >= since
                              and s["start_ns"] <= until]
        return out


def read(run, metric):
    fn, params = bench_run.Files(REPO).reader(metric)
    return fn(run, params)


def reader(name):
    return bench_run.load_module("reader", os.path.join(
        MDIR, "readers", name + ".py")).read


# ---------------------------------------------------- span_percentile

def test_a_percentile_of_a_span_over_the_windows_requests():
    body, events, samples = make(respond_ms=(1, 1, 1, 1, 50))
    run = FakeRun(body, events, samples)
    # linear interpolation between order statistics, as numpy's: the
    # fifth of five values at 0.95 is 0.8 of the way from the fourth
    assert read(run, "respond_p95_ms.taxi") == pytest.approx(
        1 + 0.8 * 49)
    assert reader("span_percentile")(
        run, {"name": "respond", "q": 0.5}) == pytest.approx(1.0)
    # the mean beside it, by the reader the benchmark already had
    assert read(run, "respond_ms.taxi") == pytest.approx(54 / 5)
    # several spans of the name in one request are added
    extra = dict(body["spans"][-2], span_id=99_999,
                 start_ns=body["spans"][-2]["end_ns"],
                 end_ns=body["spans"][-2]["end_ns"] + 10 * MS)
    assert extra["name"] == "respond"
    run = FakeRun({**body, "spans": body["spans"] + [extra]}, events,
                  samples)
    assert reader("span_percentile")(
        run, {"name": "respond", "q": 1.0}) == pytest.approx(60.0)


def test_a_percentile_with_nothing_to_read_is_none():
    body, events, samples = make()
    assert reader("span_percentile")(
        FakeRun(body, events, samples),
        {"name": "no.such.span", "q": 0.95}) is None
    assert read(FakeRun(None, events, samples),
                "respond_p95_ms.taxi") is None
    # a window that holds no request
    late = (SLICE[1] / 1e9 + 1.0, SLICE[1] / 1e9 + 2.0)
    assert read(FakeRun(body, events, samples, window=late),
                "respond_p95_ms.taxi") is None


# ---------------------------------------------------- process_span_ms

def test_a_process_span_across_the_windows_edge_counts_its_part_inside():
    body, events, samples = make()
    w0 = SLICE[0] + 5 * MS
    window = (w0 / 1e9, SLICE[1] / 1e9)
    body["process"] = [
        gc_span(w0 - 3 * MS, w0 + 2 * MS, span_id=1),    # 2 ms inside
        gc_span(w0 + 20 * MS, w0 + 21 * MS, generation=1, span_id=2),
        gc_span(w0 - 9 * MS, w0 - 8 * MS, span_id=3),    # before it
        {**gc_span(w0 + 30 * MS, w0 + 39 * MS, span_id=4),
         "name": "install.host_pad"}]                    # another name
    run = FakeRun(body, events, samples, window=window)
    served = len(spans.fetch(run).requests(*spans.window_ns(run)))
    assert served == 4          # the first request began before it
    assert read(run, "gc_pause_ms") == pytest.approx(3.0 / served)
    # asked of the node with the window's own bounds
    assert any(f"since_ns={w0}" in c and "until_ns=" in c
               for c in run.calls)


def test_the_spans_before_the_window_are_the_runs_set_up():
    body, events, samples = make()
    w0 = SLICE[0] + 5 * MS
    up = {"trace_id": 0, "parent_id": 0, "attributes": {}}
    body["process"] = [
        {**up, "span_id": 1, "name": "install.upload_segment",
         "start_ns": w0 - 900 * MS, "end_ns": w0 - 100 * MS},
        {**up, "span_id": 2, "parent_id": 1, "name": "install.host_pad",
         "start_ns": w0 - 900 * MS, "end_ns": w0 - 400 * MS},
        {**up, "span_id": 3, "parent_id": 1, "name": "install.device_put",
         "start_ns": w0 - 400 * MS, "end_ns": w0 - 100 * MS},
        {**up, "span_id": 4, "name": "install.host_pad",
         "start_ns": w0 - 80 * MS, "end_ns": w0 - 60 * MS},
        # still running when the window began: not set-up's
        {**up, "span_id": 5, "name": "install.host_pad",
         "start_ns": w0 - 10 * MS, "end_ns": w0 + 10 * MS}]
    run = FakeRun(body, events, samples,
                  window=(w0 / 1e9, SLICE[1] / 1e9))
    assert read(run, "install_host_pad_s") == pytest.approx(0.52)
    assert read(run, "install_device_put_s") == pytest.approx(0.3)
    assert read(run, "install_upload_segment_s") == pytest.approx(0.8)
    assert read(run, "install_shard_set_s") == pytest.approx(0.0)
    assert read(run, "install_shard_set_stack_s") == pytest.approx(0.0)
    assert any(c.endswith(f"until_ns={w0}") for c in run.calls)
    # fetched once an interval, however many metrics read it
    assert len(run.calls) == 1


def test_a_node_without_a_process_track_reads_none():
    body, events, samples = make()
    body.pop("process")                 # the parent commit's export
    run = FakeRun(body, events, samples)
    for name in ("gc_pause_ms", "install_host_pad_s",
                 "install_device_put_s", "install_shard_set_s",
                 "idle_under_gc_ms"):
        assert read(run, name) is None, name
    assert process_track.fetch(run) is None
    # nor one without the endpoint at all; nothing raises
    run = FakeRun(None, events, samples)
    assert read(run, "gc_pause_ms") is None
    assert read(run, "install_host_pad_s") is None
    # a track and no request in the window: nothing to divide by
    body, events, samples = make()
    late = (SLICE[1] / 1e9 + 1.0, SLICE[1] / 1e9 + 2.0)
    assert read(FakeRun(body, events, samples, window=late),
                "gc_pause_ms") is None


def test_the_counter_of_full_collections_is_read_over_the_window():
    def stats(n):
        return {"telemetry": {"metrics": {"counters": {
            "process.gc.collections.gen2": n}}}}

    run = SimpleNamespace(stats={"before": stats(7), "after": stats(12)})
    assert read(run, "gc_full_in_window") == 5
    older = {"telemetry": {"metrics": {"counters": {}}}}
    run = SimpleNamespace(stats={"before": older, "after": older})
    assert read(run, "gc_full_in_window") is None


# ---------------------------------------------------- idle_under_span

def raster_under(run, cover, step=US):
    """Idle microseconds of the plane's recorded interval under
    `cover` [(start, end)], by looking at every microsecond: the
    reader's answer found another way."""
    jn = next(iter(spans.device_join(run).planes.values()))
    a, b = jn.recorded_ns
    busy = [(lo - jn.offset, hi - jn.offset)
            for _, lo, hi in run.trace.planes["/device:TPU:0"]]
    under = 0
    for t in range(a, b, step):
        mid = t + step // 2
        if any(lo <= mid < hi for lo, hi in busy):
            continue
        under += any(lo <= mid < hi for lo, hi in cover)
    return under * step


def test_idle_wholly_partly_and_not_under_a_span():
    body, events, samples = make()
    # the plane's recorded interval begins at the first request's first
    # op: the spans below lie in and around the second request
    second = body["spans"][0]["start_ns"] + OPEN + TURNAROUND
    op0 = second + 1500 * US
    body["process"] = [
        # wholly inside the request's idle before its first op
        gc_span(second + 100 * US, second + 300 * US, span_id=1),
        # partly: it ends 0.4 ms into the request's ops
        gc_span(op0 - 300 * US, op0 + 400 * US, span_id=2),
        # not at all: while the device runs
        gc_span(op0 + MS, op0 + 2 * MS, span_id=3),
        # wholly, in the client's turnaround after the request
        gc_span(second + OPEN + 200 * US, second + OPEN + 900 * US,
                span_id=4)]
    run = FakeRun(body, events, samples)
    mesh = spans.device_join(run)
    assert mesh is not None
    n = readings.queries_in_slice(run)
    assert N_REQUESTS - 2 < n <= N_REQUESTS
    err = mesh.offset - OFFSET      # the join's offset is mid-bracket
    assert abs(err) == 100 * US
    got = read(run, "idle_under_gc_ms")
    cover = [(s["start_ns"], s["end_ns"]) for s in body["process"]]
    assert got * n * MS == pytest.approx(raster_under(run, cover),
                                         abs=4 * US)
    # 0.2 ms wholly, the 0.3 ms of the second that lie before the ops
    # (as the offset puts them: 0.1 ms from the truth), none of the
    # third, the fourth's 0.7 ms
    assert got * n * MS == pytest.approx(
        200 * US + 300 * US - err + 700 * US, abs=US)
    # a request's own spans: `compile.bundle` is 0.5 ms of every
    # request's 1.5 ms before its first op, `respond.reduce_aggs` 0.6
    # of the 1.5 after its last; the recorded interval starts at the
    # first request's first op and ends at the last one's last
    bundles = [(s["start_ns"], s["end_ns"]) for s in body["spans"]
               if s["name"] == "compile.bundle"]
    got = read(run, "idle_under_compile_bundle_ms.closed")
    assert got * n * MS == pytest.approx(raster_under(run, bundles),
                                         abs=6 * US)
    assert got * n == pytest.approx(0.5 * (N_REQUESTS - 1), abs=0.01)
    got = read(run, "idle_under_reduce_aggs_ms.taxi")
    assert got * n == pytest.approx(0.6 * (N_REQUESTS - 1), abs=0.01)
    # a part of the four idle parts, not a fifth beside them
    parts = spans.mesh_idle_parts(spans.fetch(run), mesh)
    assert got * n * MS <= parts["after_last_op"]


def test_idle_under_a_span_with_nothing_to_read_is_none():
    body, events, samples = make()
    for name in ("idle_under_compile_bundle_ms.closed",
                 "idle_under_reduce_aggs_ms.taxi", "idle_under_gc_ms"):
        # no device plane; no trace at all; no span ring
        assert read(FakeRun(body, [], samples), name) is None, name
        assert read(FakeRun(body, None, samples), name) is None, name
        assert read(FakeRun(None, events, samples), name) is None, name
    # no feasible join: the ops lie an hour from the spans
    far = [(n, lo + 3600 * 1000 * MS, hi + 3600 * 1000 * MS)
           for n, lo, hi in events]
    assert read(FakeRun(body, far, samples), "idle_under_gc_ms") is None
    # a process track that held no collection: no idle under one; a
    # ring without one request span of the name (the parent commit's):
    # nothing to read
    run = FakeRun(body, events, samples)
    assert read(run, "idle_under_gc_ms") == 0.0
    fn = reader("idle_under_span")
    assert fn(run, {"name": "no.such.span", "track": "spans"}) is None


def test_overlap_is_counted_over_sorted_disjoint_intervals():
    mod = bench_run.load_module("reader", os.path.join(
        MDIR, "readers", "idle_under_span.py"))
    gaps = [(0, 10), (20, 30), (40, 50)]
    assert mod.overlap_ns(gaps, []) == 0
    assert mod.overlap_ns(gaps, [(0, 50)]) == 30
    assert mod.overlap_ns(gaps, [(5, 25)]) == 10
    assert mod.overlap_ns(gaps, [(2, 4), (6, 8), (29, 41)]) == 6
    assert mod.overlap_ns(gaps, [(10, 20), (30, 40)]) == 0
    assert mod.overlap_ns([], [(0, 5)]) == 0


# ----------------------------------------------------- the new entries

def test_every_new_entry_names_a_reader_and_a_layer_that_exist():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    assert set(NEW) <= set(names)
    # appended: the entries the benchmark had keep their places
    first_new = min(names.index(n) for n in NEW)
    assert set(names[first_new:]) == set(NEW)
    old_layers = {m["layer"] for m in BENCH["per_layer"][:first_new]}
    readers = set()
    for name, cells in NEW.items():
        e = entries[name]
        assert e["workloads"] == cells, name
        assert e["layer"] in old_layers, name
        assert e["better"] == "lower"
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        suffix = name.rpartition(".")[2]
        if suffix in SUFFIX:
            assert cells == [SUFFIX[suffix]], name
        installs = name.startswith("install_")
        assert e["moves"] == ("setup_s" if installs
                              else "closed_search_p50_ms"), name
        assert e["unit"] == ("s" if installs else "count"
                             if name == "gc_full_in_window" else "ms")
        spec = json.load(open(os.path.join(MDIR, name + ".json")))
        assert set(spec) == {"reader", "params", "what"}
        assert os.path.exists(os.path.join(
            MDIR, "readers", spec["reader"] + ".py")), name
        readers.add(spec["reader"])
        assert e["source"] == {
            "span_self_mean": "program_span",
            "span_percentile": "program_span",
            "process_span_ms": "program_span",
            "counter_in_window": "program_counter",
            "idle_under_span": "device_trace"}[spec["reader"]], name
    assert readers == NEW_READERS | {"span_self_mean", "counter_in_window"}
    # the device's idle is read against a span only where the join of
    # waves and runs is trusted (PERF.md, section 7)
    for name, e in entries.items():
        if name.startswith("idle_under_"):
            assert set(e["workloads"]) <= {CLOSED, TAXI}


def test_the_spans_the_new_entries_read_are_spans_the_program_records():
    """Every span name in a new entry's parameters is written somewhere
    under opensearch_tpu/, as a string the ring is given."""
    named = set()
    for name in NEW:
        params = json.load(open(os.path.join(
            MDIR, name + ".json")))["params"]
        named |= set(params.get("plus", []))
        if "name" in params:
            named.add(params["name"])
    assert len(named) == 23
    source = ""
    for root, _, files in os.walk(os.path.join(REPO, "opensearch_tpu")):
        for f in files:
            if f.endswith(".py"):
                source += open(os.path.join(root, f)).read()
    for span_name in named:
        assert f'"{span_name}"' in source, span_name
    assert '"process.gc.collections.gen2"' in source
    # and the other way: nothing is recorded below a boundary or on the
    # process track that no entry reads (ISSUE 37's acceptance)
    import re
    written = set(re.findall(
        r'"((?:compile|respond|spmd\.plan|spmd\.reduce|install|gc)'
        r'\.[a-z_.]+)"', source))
    # (`respond`: the boundary span `respond_p95_ms.taxi` reads)
    assert written == named - {"respond"}, written ^ named


# ------------------------------------------------- a traced dry run each

def dry_run(cell, devices=None):
    assert os.environ["JAX_PLATFORMS"] == "cpu"     # conftest pinned it
    env = dict(os.environ)
    if devices:
        env["XLA_FLAGS"] = " ".join(
            [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
            + [f"--xla_force_host_platform_device_count={devices}"])
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483777", "--seconds", "3",
         "--trace", "1", "--dry-run"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0
    return line["metrics"]


@pytest.mark.parametrize("cell,devices", [
    (CLOSED, None), (DASH, 4), (KNN, None), (TAXI, None)])
def test_a_traced_dry_run_of_the_cell_prints_its_new_metrics(cell,
                                                             devices):
    metrics = dry_run(cell, devices)
    mine = [n for n, cells in NEW.items() if cell in cells]
    # the CPU backend leaves no device plane: what reads the device's
    # idle is left out, everything else of the cell prints a number
    for name in mine:
        if name.startswith("idle_under_"):
            assert name not in metrics
            continue
        assert name in metrics, name
        assert metrics[name]["value"] >= 0
        assert metrics[name]["unit"] == ("s" if name.startswith("install_")
                                         else "count" if name
                                         == "gc_full_in_window" else "ms")
    assert not [n for n in NEW if n in metrics and cell not in NEW[n]]
    # children are parts of their parents: they sum to no more
    def total(names):
        return sum(metrics[n]["value"] for n in names)

    if cell == CLOSED:
        assert metrics["compile_bundle_ms.closed"]["value"] > 0
        assert metrics["compile_text_clause_ms.closed"]["value"] \
            <= metrics["compile_bundle_ms.closed"]["value"]
        assert total(["compile_bundle_ms.closed",
                      "compile_scan_note_ms.closed"]) \
            <= metrics["envelope_host_ms.closed"]["value"]
    if cell == TAXI:
        assert metrics["respond_reduce_aggs_ms.taxi"]["value"] > 0
        assert total(["respond_unpack_ms.taxi",
                      "respond_decode_aggs_ms.taxi",
                      "respond_reduce_aggs_ms.taxi",
                      "respond_render_ms.taxi"]) \
            <= metrics["respond_ms.taxi"]["value"]
        assert metrics["respond_p95_ms.taxi"]["value"] \
            >= 0.5 * metrics["respond_ms.taxi"]["value"]
    if cell == DASH:
        assert total(["spmd_plan_compile_ms.dash",
                      "spmd_plan_align_ms.dash",
                      "spmd_plan_stack_ms.dash"]) \
            <= metrics["spmd_plan_ms.dash"]["value"]
        assert total(["spmd_reduce_scan_note_ms.dash",
                      "spmd_reduce_candidates_ms.dash",
                      "spmd_reduce_decode_ms.dash",
                      "spmd_reduce_aggs_ms.dash"]) \
            <= metrics["spmd_reduce_ms.dash"]["value"]
        assert metrics["install_shard_set_stack_s"]["value"] > 0
        assert 0 < total(["install_shard_set_host_images_s",
                          "install_shard_set_stack_s",
                          "install_shard_set_device_put_s"]) \
            <= metrics["install_shard_set_s"]["value"]
    assert metrics["install_host_pad_s"]["value"] > 0
    assert metrics["install_device_put_s"]["value"] > 0
    assert metrics["install_host_pad_s"]["value"] \
        + metrics["install_device_put_s"]["value"] \
        <= metrics["install_upload_segment_s"]["value"] * (1 + 1e-6) \
        <= metrics["install_upload_s"]["value"] * (1 + 1e-6)
