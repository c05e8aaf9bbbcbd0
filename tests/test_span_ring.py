"""The always-on span ring (telemetry/tracer.py SpanRing, ISSUE 25): one
trace id along a request from the socket to the device and back, parent
links that form a tree, children inside their parents on one clock,
every span closed on error, rejection and timeout, a bounded ring that
drops its oldest and counts, and a budget of records a request. The
verbose tree stays gated: NOOP_SPAN with tracing off.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from opensearch_tpu.launcher import start_node
from opensearch_tpu.search import executor as executor_mod
from opensearch_tpu.telemetry import TELEMETRY
from opensearch_tpu.telemetry.tracer import (NOOP_SPAN, SPAN_RING_SIZE,
                                             Span, SpanRing)

RING = TELEMETRY.tracer.spans
SEARCH_TREE = {
    "http.request": None,
    "http.read_decode": "http.request",
    "http.encode_write": "http.request",
    "rest.search": "http.request",
    "envelope": "rest.search",
    "envelope.parse": "envelope",
    "envelope.compile_group": "envelope",
    "envelope.pack": "envelope",
    "dispatch": "envelope",
    "device_wait": "envelope",
    "respond": "envelope",
}
# a warm B=1 `_search` writes the eleven records above, and one
# `xla.compile` more the first time its program is called
SEARCH_BUDGET = len(SEARCH_TREE)


@pytest.fixture(scope="module")
def served():
    node, server = start_node({"http.port": 0, "node.name": "span-ring",
                               "search.backpressure.max_concurrent": 512})
    base = f"http://127.0.0.1:{server.port}"

    def call(method, path, body=None, ndjson=False):
        data = None
        if body is not None:
            data = body.encode() if isinstance(body, str) \
                else json.dumps(body).encode()
        req = urllib.request.Request(
            base + path, data=data, method=method,
            headers={"Content-Type": "application/x-ndjson" if ndjson
                     else "application/json"})
        try:
            with urllib.request.urlopen(req) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    call("PUT", "/ring", {"mappings": {"properties": {
        "t": {"type": "text"}}}})
    bulk = "".join(
        json.dumps({"index": {"_index": "ring", "_id": str(i)}}) + "\n"
        + json.dumps({"t": f"hello world w{i % 7} x{i % 13}"}) + "\n"
        for i in range(400))
    call("POST", "/_bulk", bulk, ndjson=True)
    call("POST", "/ring/_refresh")
    # compile the B=1 program once, so later requests are warm
    call("POST", "/ring/_search", {"query": {"match": {"t": "hello w1"}}})
    yield node, call
    server.close()


def request_spans(call, method, path, body=None, ndjson=False):
    """(status, response, the spans of that one request).
    `http.request` is written after the last byte has gone out, so the
    reader waits for it; the tail of an earlier request, written late
    for the same reason, is of another trace and is left out."""
    RING.clear()
    t0 = time.monotonic_ns()
    status, resp = call(method, path, body, ndjson)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        spans = RING.export()["spans"]
        root = [s for s in spans if s["name"] == "http.request"
                and s["start_ns"] >= t0]
        if root:
            return status, resp, [s for s in spans
                                  if s["trace_id"] == root[0]["trace_id"]]
        time.sleep(0.005)
    raise AssertionError(f"no http.request span: {spans}")


def assert_tree(spans):
    """One trace; every parent link lands on a span of it; one root;
    start <= end; a child lies inside its parent."""
    assert len({s["trace_id"] for s in spans}) == 1
    by_id = {s["span_id"]: s for s in spans}
    assert len(by_id) == len(spans)
    roots = [s for s in spans if s["parent_id"] == 0]
    assert [r["name"] for r in roots] == ["http.request"]
    assert roots[0]["span_id"] == roots[0]["trace_id"]
    for s in spans:
        assert isinstance(s["start_ns"], int) and isinstance(
            s["end_ns"], int)
        assert s["start_ns"] <= s["end_ns"], s
        if s["parent_id"]:
            parent = by_id[s["parent_id"]]
            assert parent["start_ns"] <= s["start_ns"], (s, parent)
            assert s["end_ns"] <= parent["end_ns"], (s, parent)
    return by_id


def msearch_body(n):
    return "".join(
        json.dumps({"index": "ring"}) + "\n"
        + json.dumps({"query": {"match": {"t": f"w{i % 7} x{i % 13}"}}})
        + "\n" for i in range(n))


# ------------------------------------------------------------ one request

def test_one_search_is_one_trace_with_the_documented_tree(served):
    _, call = served
    assert TELEMETRY.tracer.enabled is False
    status, resp, spans = request_spans(
        call, "POST", "/ring/_search",
        {"query": {"match": {"t": "hello w3"}}})
    assert status == 200 and resp["hits"]["total"]["value"] > 0
    by_id = assert_tree(spans)
    parent_name = {s["name"]: (by_id[s["parent_id"]]["name"]
                               if s["parent_id"] else None) for s in spans}
    assert parent_name == SEARCH_TREE
    assert len(spans) <= SEARCH_BUDGET
    named = {s["name"]: s for s in spans}
    assert named["http.request"]["attributes"] == {
        "method": "POST", "route": "_search", "status": 200,
        "request_bytes": named["http.request"]["attributes"][
            "request_bytes"],
        "response_bytes": named["http.request"]["attributes"][
            "response_bytes"]}
    assert named["http.request"]["attributes"]["request_bytes"] > 0
    assert named["http.request"]["attributes"]["response_bytes"] > 0
    assert named["envelope"]["attributes"] == {"bodies": 1, "waves": 1}
    d = named["dispatch"]["attributes"]
    assert d["wave"] == 0 and d["programs"] == 1 and d["nbytes"] > 0
    assert d["family"] in ("bm25_candidate", "bm25_dense")
    assert len(d["fingerprint"]) == 8 and d["shape"].startswith("b1/")
    assert named["device_wait"]["attributes"]["nbytes"] > 0
    # pack ends where dispatch begins: one clock read, two spans
    assert named["envelope.pack"]["end_ns"] == named["dispatch"]["start_ns"]
    assert named["envelope.compile_group"]["end_ns"] \
        == named["envelope.pack"]["start_ns"]


def test_the_export_is_on_the_monotonic_clock_with_one_wall_anchor(served):
    _, call = served
    t0 = time.monotonic_ns()
    _, _, spans = request_spans(call, "POST", "/ring/_search",
                                {"query": {"match": {"t": "hello w2"}}})
    t1 = time.monotonic_ns()
    for s in spans:
        assert t0 <= s["start_ns"] <= s["end_ns"] <= t1
    status, body = call("GET", "/_telemetry/spans")
    assert status == 200 and body["clock"] == "monotonic_ns"
    anchor = body["anchor"]
    assert abs((anchor["time_ns"] - anchor["monotonic_ns"])
               - (time.time_ns() - time.monotonic_ns())) < 50_000_000
    assert isinstance(body["dropped"], int)


def test_since_and_until_select_by_overlap(served):
    _, call = served
    _, _, first = request_spans(call, "POST", "/ring/_search",
                                {"query": {"match": {"t": "w1"}}})
    cut = time.monotonic_ns()
    call("POST", "/ring/_search", {"query": {"match": {"t": "w2"}}})
    time.sleep(0.05)
    _, late = call("GET", f"/_telemetry/spans?since_ns={cut}")
    _, early = call("GET", f"/_telemetry/spans?until_ns={cut}")
    first_ids = {s["span_id"] for s in first}
    assert not first_ids & {s["span_id"] for s in late["spans"]}
    assert first_ids <= {s["span_id"] for s in early["spans"]}
    assert all(s["end_ns"] >= cut for s in late["spans"])
    assert all(s["start_ns"] <= cut for s in early["spans"])
    status, err = call("GET", "/_telemetry/spans?since_ns=soon")
    assert status == 400 and "since_ns" in json.dumps(err)


def test_a_256_body_msearch_keeps_its_trace_on_the_collector(
        served, monkeypatch):
    _, call = served
    monkeypatch.setattr(executor_mod, "FORCED_WAVES", 2)
    finishers = []
    finish = executor_mod.SearchExecutor._msearch_finish

    def spy(self, *args, **kwargs):
        finishers.append(threading.current_thread().name)
        return finish(self, *args, **kwargs)

    monkeypatch.setattr(executor_mod.SearchExecutor, "_msearch_finish",
                        spy)
    status, resp, spans = request_spans(call, "POST", "/_msearch",
                                        msearch_body(256), ndjson=True)
    assert status == 200 and len(resp["responses"]) == 256
    assert all("hits" in r for r in resp["responses"])
    by_id = assert_tree(spans)
    names = [s["name"] for s in spans]
    assert names.count("rest.msearch") == 1 and "rest.search" not in names
    env = next(s for s in spans if s["name"] == "envelope")
    assert env["attributes"] == {"bodies": 256, "waves": 2}
    for name in ("envelope.compile_group", "envelope.pack", "dispatch",
                 "device_wait", "respond"):
        waves = sorted(s["attributes"]["wave"] for s in spans
                       if s["name"] == name)
        assert waves == [0, 1], (name, waves)
        assert all(by_id[s["parent_id"]]["name"] == "envelope"
                   for s in spans if s["name"] == name)
    # the collect half ran on the collector thread and still wrote into
    # the request's trace (both waves' `device_wait` and `respond` are
    # in the tree above): the trace rides the wave, not the thread
    assert "msearch-wave-collector" in finishers
    # the items of a batch get no spans of their own
    assert len(spans) < 40


def test_scheduler_coalesced_envelope_lists_the_traces_it_serves(served):
    node, _ = served
    RING.clear()
    ex = node.indices.get("ring").shards[0].executor
    bodies = [{"query": {"match": {"t": "w1"}}},
              {"query": {"match": {"t": "w2"}}},
              {"query": {"match": {"t": "w3"}}}]
    ctx, _, parent = RING.enter()       # the scheduler's own thread
    try:
        ex.multi_search(bodies, trace_ids=[101, 202, 101])
    finally:
        RING.leave(ctx, parent)
    spans = RING.export()["spans"]
    env = next(s for s in spans if s["name"] == "envelope")
    disp = next(s for s in spans if s["name"] == "dispatch")
    assert env["trace_id"] == ctx.trace_id
    assert env["attributes"]["trace_ids"] == [101, 202]
    assert disp["attributes"]["trace_ids"] == [101, 202]


def test_a_queued_item_carries_its_requests_trace():
    from opensearch_tpu.search.scheduler import _SchedItem
    ctx, _, parent = RING.enter()
    try:
        item = _SchedItem(None, [{}], None, None, None, None, 0.0)
    finally:
        RING.leave(ctx, parent)
    assert item.trace_id == ctx.trace_id
    assert _SchedItem(None, [{}], None, None, None, None,
                      0.0).trace_id is None


# ------------------------------------------------ every exit closes spans

def names_and_status(spans):
    http = next(s for s in spans if s["name"] == "http.request")
    return {s["name"] for s in spans}, http["attributes"]["status"]


def test_spans_close_on_error(served):
    _, call = served
    status, _, spans = request_spans(
        call, "POST", "/ring/_search", {"query": {"match_all": {}},
                                        "size": -2})
    assert status == 400
    assert_tree(spans)
    names, http_status = names_and_status(spans)
    assert http_status == 400
    assert {"http.request", "http.read_decode", "rest.search",
            "http.encode_write"} <= names
    status, _, spans = request_spans(call, "POST", "/nowhere/_search",
                                     {"query": {"match_all": {}}})
    assert status == 404
    assert_tree(spans)
    assert names_and_status(spans)[1] == 404


def test_spans_close_on_rejection(served):
    node, call = served
    old = node.search_backpressure.max_concurrent
    node.search_backpressure.max_concurrent = 0
    try:
        status, _, spans = request_spans(
            call, "POST", "/ring/_search",
            {"query": {"match": {"t": "hello"}}})
    finally:
        node.search_backpressure.max_concurrent = old
    assert status == 429
    assert_tree(spans)
    names, http_status = names_and_status(spans)
    assert http_status == 429
    assert "rest.search" in names and "envelope" not in names


def test_spans_close_on_timeout(served):
    node, call = served
    # a budget that is gone on arrival: answered `timed_out` before the
    # envelope is entered
    status, resp, spans = request_spans(
        call, "POST", "/ring/_search",
        {"query": {"match": {"t": "hello w5"}}, "timeout": "1nanos"})
    assert status == 200 and resp["timed_out"] is True
    assert_tree(spans)
    names, _ = names_and_status(spans)
    assert {"http.request", "rest.search"} <= names
    assert "device_wait" not in names
    # and one that runs out inside the envelope, before its wave
    ex = node.indices.get("ring").shards[0].executor
    RING.clear()
    ctx, _, parent = RING.enter()
    try:
        res = ex.multi_search([{"query": {"match": {"t": "hello w5"}}}],
                              deadline=time.monotonic() - 1.0)
    finally:
        RING.leave(ctx, parent)
    assert res["responses"][0]["timed_out"] is True
    spans = RING.export()["spans"]
    names = {s["name"] for s in spans}
    assert {"envelope", "envelope.parse"} <= names
    # nothing was dispatched, so nothing claims to have waited
    assert not {"dispatch", "device_wait"} & names
    assert all(s["trace_id"] == ctx.trace_id
               and s["start_ns"] <= s["end_ns"] for s in spans)


def test_a_failed_dispatch_still_closes_the_envelope(served, monkeypatch):
    node, _ = served
    ex = node.indices.get("ring").shards[0].executor

    def boom(*a, **kw):
        raise RuntimeError("prepare failed")

    monkeypatch.setattr(ex, "_msearch_prepare", boom)
    RING.clear()
    ctx, _, parent = RING.enter()
    try:
        with pytest.raises(RuntimeError):
            ex.multi_search([{"query": {"match": {"t": "w1"}}}],
                            _raise_item_errors=True)
    finally:
        RING.leave(ctx, parent)
    spans = RING.export()["spans"]
    env = [s for s in spans if s["name"] == "envelope"]
    assert len(env) == 1 and env[0]["end_ns"] >= env[0]["start_ns"]
    assert RING.current() is None


# -------------------------------------------------------- the ring itself

def one_request(ring, name, start, end):
    """A request of one span, served and completed on this thread."""
    trace, sid, parent = ring.enter()
    trace.spans.append((sid, parent, name, start, end, None))
    ring.leave(trace, parent)
    return trace


def test_the_ring_drops_its_oldest_and_counts():
    ring = SpanRing(size=4)
    for i in range(7):
        one_request(ring, f"r{i}", i, i + 1)
    out = ring.export()
    assert [s["name"] for s in out["spans"]] == ["r3", "r4", "r5", "r6"]
    assert out["dropped"] == ring.dropped == 3
    assert ring.stats() == {"size": 4, "retained": 4, "recorded": 7,
                            "dropped": 3}
    ring.clear()
    assert ring.stats() == {"size": 4, "retained": 0, "recorded": 0,
                            "dropped": 0}


def test_a_request_is_one_row_of_the_spans_that_closed_in_it():
    ring = SpanRing(size=8)
    trace, sid, parent = ring.enter()
    assert parent == 0 and trace.trace_id == sid == trace.top
    inner_trace, inner, inner_parent = ring.enter()
    assert inner_trace is trace and inner_parent == sid
    assert trace.top == inner
    # ids come eight apart, so a span names its leaves without a draw;
    # times are monotonic_ns, or monotonic seconds as read
    assert inner == sid + 8
    ring.child("leaf", 1_100_000_000, 1_200_000_000)   # under `inner`
    trace.spans.append((inner, inner_parent, "half", 1_000_000_000,
                        1_500_000_000, None))
    ring.leave(trace, inner_parent)
    assert trace.top == sid and ring.current() is trace
    # nothing is in the ring before the root has ended
    assert ring.export()["spans"] == [] and ring.stats()["retained"] == 0
    trace.spans.append((sid, parent, "whole", 1.0, 2.5, {"k": 1}))
    ring.leave(trace, parent)
    assert ring.current() is None
    out = ring.export()["spans"]
    assert [(s["name"], s["parent_id"], s["start_ns"], s["end_ns"])
            for s in out] == [
        ("leaf", inner, 1_100_000_000, 1_200_000_000),
        ("half", sid, 1_000_000_000, 1_500_000_000),
        ("whole", 0, 1_000_000_000, 2_500_000_000)]
    assert {s["trace_id"] for s in out} == {sid}
    assert out[2]["attributes"] == {"k": 1} and "attributes" not in out[1]
    assert ring.stats()["retained"] == 1       # one row, three spans
    assert ring.export(since_ns=2_000_000_000)["spans"] == [out[2]]


def test_attributes_are_built_when_the_ring_is_exported():
    ring = SpanRing(size=8)
    built = []

    def build(wave, nbytes):
        built.append(wave)
        return {"wave": wave, "nbytes": nbytes}

    trace, sid, parent = ring.enter()
    trace.spans.append((sid, parent, "dispatch", 1, 2, (build, 3, 64)))
    ring.leave(trace, parent)
    assert built == []                  # the serving path built nothing
    assert ring.export()["spans"][0]["attributes"] == {"wave": 3,
                                                       "nbytes": 64}
    assert built == [3]


def test_the_ring_holds_the_busiest_cells_window():
    # a served request is eight spans at the least (ISSUE 25: 65,536
    # records); the busiest queued cell sends 140 requests/s for 30 s
    assert SPAN_RING_SIZE * 8 >= 65536 and SPAN_RING_SIZE >= 140 * 30
    assert RING.stats()["size"] == SPAN_RING_SIZE


def test_a_span_without_a_request_is_not_recorded():
    ring = SpanRing(size=8)
    ring.child("orphan", 1, 2)
    assert ring.export()["spans"] == [] and ring.current() is None


def test_traces_bind_per_thread():
    ring = SpanRing(size=8)
    trace, sid, parent = ring.enter()
    seen = []
    t = threading.Thread(target=lambda: seen.append(ring.current()))
    t.start()
    t.join()
    assert seen == [None] and ring.current() is trace
    ring.leave(trace, parent)
    assert ring.current() is None


def test_nodes_stats_gets_the_rings_counts_only(served):
    _, call = served
    call("POST", "/ring/_search", {"query": {"match": {"t": "w4"}}})
    _, stats = call("GET", "/_nodes/stats")
    tracing = next(iter(stats["nodes"].values()))["telemetry"]["tracing"]
    assert set(tracing["spans"]) == {"size", "retained", "recorded",
                                     "dropped"}
    assert tracing["spans"]["retained"] > 0
    status, ack = call("POST", "/_telemetry/spans/_clear")
    assert status == 200 and ack == {"acknowledged": True}


# ------------------------------------------------------ the verbose tree

def test_noop_span_is_still_returned_with_tracing_off(served):
    _, call = served
    TELEMETRY.disable()
    assert TELEMETRY.tracer.start_trace("rest.search") is NOOP_SPAN
    before = TELEMETRY.tracer.stats()["started"]
    _, _, spans = request_spans(call, "POST", "/ring/_search",
                                {"query": {"match": {"t": "w6"}}})
    assert len(spans) == SEARCH_BUDGET
    assert TELEMETRY.tracer.stats()["started"] == before
    assert TELEMETRY.tracer.traces() == []


def test_the_tree_when_on_is_on_the_same_clock_and_trace(served):
    _, call = served
    TELEMETRY.enable()
    TELEMETRY.tracer.clear()
    try:
        _, _, spans = request_spans(call, "POST", "/ring/_search",
                                    {"query": {"match": {"t": "w0"}}})
        trees = TELEMETRY.tracer.traces()
    finally:
        TELEMETRY.disable()
        TELEMETRY.tracer.clear()
    tree = next(t["trace"] for t in trees
                if t["trace"]["name"] == "rest.search")
    flat = next(s for s in spans if s["name"] == "rest.search")
    assert tree["trace_id"] == flat["trace_id"]
    # the tree's root opens a moment before and closes a moment after
    # the interval `rest.search_ms` times
    assert abs(tree["start_ns"] - flat["start_ns"]) < 5_000_000
    assert abs(tree["end_ns"] - flat["end_ns"]) < 5_000_000
    assert tree["start_ns"] <= tree["end_ns"]
    assert tree["duration_ms"] == round(
        (tree["end_ns"] - tree["start_ns"]) / 1e6, 3)
    d = Span("x").to_dict()
    assert d["end_ns"] is None and "trace_id" not in d


# ------------------------------------------------ search.* through the B=1

def test_a_search_through_the_envelope_counts_in_search_metrics(served):
    _, call = served

    def snapshot():
        m = TELEMETRY.metrics.to_dict()
        return (m["counters"].get("search.queries", 0),
                {k: v["count"] for k, v in m["histograms"].items()
                 if k == "search.took_ms"
                 or k.startswith("search.phase.")})

    q0, h0 = snapshot()
    status, resp = call("POST", "/ring/_search",
                        {"query": {"match": {"t": "hello w1"}}})
    assert status == 200
    q1, h1 = snapshot()
    assert q1 == q0 + 1
    for name in ("search.took_ms", "search.phase.parse_ms",
                 "search.phase.query_ms", "search.phase.render_ms"):
        assert h1[name] == h0.get(name, 0) + 1, name
    # an `_msearch` batch does not count as searches
    status, _ = call("POST", "/_msearch", msearch_body(4), ndjson=True)
    assert status == 200
    q2, h2 = snapshot()
    assert q2 == q1 and h2 == h1
