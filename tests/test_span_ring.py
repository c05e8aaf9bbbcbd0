"""The always-on span ring (telemetry/tracer.py SpanRing, ISSUE 25): one
trace id along a request from the socket to the device and back, parent
links that form a tree, children inside their parents on one clock,
every span closed on error, rejection and timeout, a bounded ring that
drops its oldest and counts, and a budget of records a request. The
verbose tree stays gated: NOOP_SPAN with tracing off.

Since ISSUE 37: child spans below the four boundary spans that are wide
on the host (and none directly under `http.request`, `rest.*` or
`envelope`, whose self time the benchmark reads), and the ring's
process track: the heap's collections and an index's install.
"""

import gc
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from opensearch_tpu.launcher import start_node
from opensearch_tpu.search import executor as executor_mod
from opensearch_tpu.search.compile import COMPILE_SPANS_A_WAVE, Compiler
from opensearch_tpu.telemetry import TELEMETRY
from opensearch_tpu.telemetry.tracer import (GC_SPAN_MIN_NS, NOOP_SPAN,
                                             PROCESS_RING_SIZE,
                                             SPAN_RING_SIZE, GcSpans, Span,
                                             SpanRing)

RING = TELEMETRY.tracer.spans
# the layer boundaries of a B=1 `_search`: what PR 25 recorded and the
# benchmark's `*_self_ms` and `envelope_host_ms` are read from
BOUNDARY_TREE = {
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
# below the boundaries (ISSUE 37): a BM25 `_search` whose body the
# bundle memo does not hold
SEARCH_TREE = {
    **BOUNDARY_TREE,
    "compile.bundle": "envelope.compile_group",
    "compile.text_clause": "compile.bundle",
    "compile.scan_note": "envelope.compile_group",
    "respond.unpack": "respond",
    "respond.render": "respond",
}
AGG_CHILDREN = {"respond.decode_aggs": "respond",
                "respond.reduce_aggs": "respond"}
SPMD_TREE = {
    "http.request": None,
    "http.read_decode": "http.request",
    "http.encode_write": "http.request",
    "rest.search": "http.request",
    "spmd.plan": "rest.search",
    "dispatch": "rest.search",
    "device_wait": "rest.search",
    "spmd.reduce": "rest.search",
    "respond": "rest.search",
    "spmd.plan.compile_rows": "spmd.plan",
    "spmd.plan.align": "spmd.plan",
    "spmd.plan.stack": "spmd.plan",
    "spmd.reduce.scan_note": "spmd.reduce",
    "spmd.reduce.candidates": "spmd.reduce",
    "spmd.reduce.decode_aggs": "spmd.reduce",
    "spmd.reduce.reduce_aggs": "spmd.reduce",
}
# a warm B=1 `_search` writes the sixteen records of SEARCH_TREE (eleven
# boundaries, five children; a body the bundle memo holds two fewer),
# and one `xla.compile` more the first time its program is called
SEARCH_BUDGET = len(SEARCH_TREE)
AGG_BODY = {"size": 0, "query": {"range": {"n": {"gte": 3}}},
            "aggs": {"h": {"histogram": {"field": "n", "interval": 5},
                           "aggs": {"s": {"stats": {"field": "n"}}}}}}


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

    properties = {"t": {"type": "text"}, "n": {"type": "integer"}}
    call("PUT", "/ring", {"mappings": {"properties": properties}})
    # four shards: its rows take the SPMD program on the virtual devices
    call("PUT", "/rows", {"settings": {"number_of_shards": 4},
                          "mappings": {"properties": properties}})
    for index in ("ring", "rows"):
        bulk = "".join(
            json.dumps({"index": {"_index": index, "_id": str(i)}}) + "\n"
            + json.dumps({"t": f"hello world w{i % 7} x{i % 13}",
                          "n": i % 50}) + "\n"
            for i in range(400))
        call("POST", "/_bulk", bulk, ndjson=True)
        call("POST", f"/{index}/_refresh")
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
    # the items of a batch get no spans of their own: below
    # `envelope.compile_group` a wave records the first
    # `COMPILE_SPANS_A_WAVE` of its compiles (91 distinct bodies here)
    assert len(spans) < 40 + 2 * COMPILE_SPANS_A_WAVE
    compiles = [s for s in spans if s["name"] in ("compile.bundle",
                                                  "compile.text_clause")]
    assert 0 < len(compiles) <= 2 * COMPILE_SPANS_A_WAVE
    bundles = [s for s in compiles if s["name"] == "compile.bundle"]
    assert bundles and all(
        by_id[s["parent_id"]]["name"] == "envelope.compile_group"
        for s in bundles)
    assert all(by_id[s["parent_id"]]["name"] == "compile.bundle"
               for s in compiles if s["name"] == "compile.text_clause")


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
                            "dropped": 3, "process": 0}
    ring.clear()
    assert ring.stats() == {"size": 4, "retained": 0, "recorded": 0,
                            "dropped": 0, "process": 0}


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
    # a served request is sixteen spans and more (ISSUE 37: 131,072
    # records); the busiest queued cell sends 140 requests/s for 30 s
    assert SPAN_RING_SIZE * len(SEARCH_TREE) >= 131072
    assert SPAN_RING_SIZE >= 140 * 30
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
                                     "dropped", "process"}
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
                                {"query": {"match": {"t": "hello w6"}}})
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


# ------------------------------------- below the boundaries (ISSUE 37)

def tree_of(spans, by_id):
    return {s["name"]: (by_id[s["parent_id"]]["name"] if s["parent_id"]
                        else None) for s in spans}


def route_request(call, route):
    """(spans, the tree they should form) of one request of a route; a
    body no memo or cache holds, so every child is written."""
    route_request.n += 1
    lo = 3 + route_request.n
    if route == "bm25":
        body = {"query": {"match": {"t": f"hello w{lo % 7} x{lo % 13} "
                                         f"n{lo}"}}}
        index, tree = "ring", SEARCH_TREE
    elif route == "aggs":
        body = json.loads(json.dumps(AGG_BODY))
        body["query"]["range"]["n"]["gte"] = lo
        index = "ring"
        tree = {k: v for k, v in {**SEARCH_TREE, **AGG_CHILDREN}.items()
                if k != "compile.text_clause"}
    else:
        body = json.loads(json.dumps(AGG_BODY))
        body["query"]["range"]["n"]["gte"] = lo
        index, tree = "rows", SPMD_TREE
    status, resp, spans = request_spans(call, "POST", f"/{index}/_search",
                                        body)
    assert status == 200 and resp["_shards"]["failed"] == 0
    return [s for s in spans if s["name"] != "xla.compile"], tree


route_request.n = 0
ROUTES = ("bm25", "aggs", "spmd")


@pytest.mark.parametrize("route", ROUTES)
def test_a_route_carries_the_children_of_its_wide_spans(served, route):
    _, call = served
    spans, tree = route_request(call, route)
    by_id = assert_tree(spans)      # every child inside its parent
    assert tree_of(spans, by_id) == tree
    if route == "spmd":
        # two `spmd.reduce` a request: the per-row half has the scan
        # note, the candidates and the decode, the cross-row half the
        # reduce
        halves = sorted((s for s in spans if s["name"] == "spmd.reduce"),
                        key=lambda s: s["start_ns"])
        assert len(halves) == 2
        under = [sorted(s["name"] for s in spans
                        if s["parent_id"] == h["span_id"]) for h in halves]
        assert under == [["spmd.reduce.candidates",
                          "spmd.reduce.decode_aggs",
                          "spmd.reduce.scan_note"],
                         ["spmd.reduce.reduce_aggs"]]


@pytest.mark.parametrize("route", ROUTES)
def test_the_children_of_one_parent_do_not_overlap(served, route):
    _, call = served
    spans, _ = route_request(call, route)
    kids = {}
    for s in spans:
        kids.setdefault(s["parent_id"], []).append(s)
    for siblings in kids.values():
        siblings.sort(key=lambda s: (s["start_ns"], s["end_ns"]))
        for a, b in zip(siblings, siblings[1:]):
            assert a["end_ns"] <= b["start_ns"], (a, b)


@pytest.mark.parametrize("route", ROUTES)
def test_the_spans_read_for_their_self_time_get_no_new_child(served,
                                                             route):
    """`http_self_ms`, `rest_self_ms` and `envelope_host_ms` read the
    SELF time of `http.request`, `rest.search` and `envelope`: what lies
    directly under them is what PR 25 (PR 28 on the SPMD route) put
    there, so their self time is the interval it was."""
    _, call = served
    spans, _ = route_request(call, route)
    by_id = {s["span_id"]: s for s in spans}
    direct = {}
    for s in spans:
        if s["parent_id"]:
            direct.setdefault(by_id[s["parent_id"]]["name"],
                              set()).add(s["name"])
    assert direct["http.request"] == {"http.read_decode", "rest.search",
                                      "http.encode_write"}
    if route == "spmd":
        assert direct["rest.search"] == {"spmd.plan", "dispatch",
                                         "device_wait", "spmd.reduce",
                                         "respond"}
        assert "envelope" not in direct
    else:
        assert direct["rest.search"] == {"envelope"}
        assert direct["envelope"] == {
            "envelope.parse", "envelope.compile_group", "envelope.pack",
            "dispatch", "device_wait", "respond"}


@pytest.mark.parametrize("route", ("bm25", "aggs"))
def test_the_boundary_spans_are_what_pr_25_recorded(served, route):
    """Names, parents and attributes of the spans the benchmark reads,
    with the children taken away: the tree and the attribute keys of
    the parent commit."""
    _, call = served
    spans, _ = route_request(call, route)
    by_id = {s["span_id"]: s for s in spans}
    boundary = [s for s in spans if s["name"] in BOUNDARY_TREE]
    assert tree_of(boundary, by_id) == BOUNDARY_TREE
    keys = {s["name"]: sorted(s.get("attributes", {})) for s in boundary}
    assert keys == {
        "http.request": ["method", "request_bytes", "response_bytes",
                         "route", "status"],
        "http.read_decode": [], "http.encode_write": [],
        "rest.search": [], "envelope": ["bodies", "waves"],
        "envelope.parse": [],
        "envelope.compile_group": ["wave"], "envelope.pack": ["wave"],
        "dispatch": ["family", "fingerprint", "nbytes", "programs",
                     "shape", "wave"],
        "device_wait": ["nbytes", "programs", "wave"],
        "respond": ["wave"]}
    named = {s["name"]: s for s in boundary}
    # the reads the phase histograms share: one read, two spans
    assert named["envelope.compile_group"]["end_ns"] \
        == named["envelope.pack"]["start_ns"]
    assert named["envelope.pack"]["end_ns"] == named["dispatch"]["start_ns"]


def test_the_childrens_attributes(served):
    _, call = served
    spans, _ = route_request(call, "bm25")
    named = {s["name"]: s for s in spans}
    b = named["compile.bundle"]["attributes"]
    assert b["memo"] == "miss" and b["nbytes"] > 0
    c = named["compile.text_clause"]["attributes"]
    assert c["terms"] == 4 and c["blocks"] >= 1
    spans, _ = route_request(call, "aggs")
    named = {s["name"]: s for s in spans}
    # 50 values of `n` in buckets of 5 from `gte`: the row carries the
    # histogram's bins and `stats`' four arrays back, the page its
    # collected buckets
    shape = named["dispatch"]["attributes"]["shape"]
    assert named["respond.decode_aggs"]["attributes"]["buckets"] \
        == int(shape.rsplit("bins", 1)[1])
    assert 1 <= named["respond.reduce_aggs"]["attributes"]["buckets"] <= 10
    spans, _ = route_request(call, "spmd")
    named = {s["name"]: s for s in spans}
    assert named["spmd.plan.compile_rows"]["attributes"] == {"rows": 4}
    assert named["spmd.reduce.decode_aggs"]["attributes"] == {"rows": 4}
    assert named["spmd.reduce.reduce_aggs"]["attributes"] == {"rows": 4}


def test_a_body_the_bundle_memo_holds_records_no_bundle(served):
    _, call = served
    body = {"query": {"match": {"t": "hello w5 x7 again"}}}
    _, _, first = request_spans(call, "POST", "/ring/_search", body)
    _, _, second = request_spans(call, "POST", "/ring/_search", body)
    assert "compile.bundle" in {s["name"] for s in first}
    names = {s["name"] for s in second}
    assert not {"compile.bundle", "compile.text_clause"} & names
    assert "compile.scan_note" in names and len(second) == len(first) - 2


def test_a_failed_item_still_closes_every_span(served, monkeypatch):
    """One body of a batch whose compile raises is answered by the
    general path; its `compile.bundle` is closed all the same, below a
    `compile_group` that is, and the thread's open span is the
    request's again."""
    node, call = served
    compile_bundle = executor_mod.SearchExecutor._compile_msearch_bundle
    seen = []

    def flaky(self, *args, **kwargs):
        seen.append(len(seen))
        if len(seen) == 2:
            raise RuntimeError("no plan for this one")
        return compile_bundle(self, *args, **kwargs)

    monkeypatch.setattr(executor_mod.SearchExecutor,
                        "_compile_msearch_bundle", flaky)
    batch = "".join(
        json.dumps({"index": "ring"}) + "\n"
        + json.dumps({"query": {"match": {"t": f"failed item w{i}"}}})
        + "\n" for i in range(3))
    status, resp, spans = request_spans(call, "POST", "/_msearch", batch,
                                        ndjson=True)
    assert status == 200 and len(resp["responses"]) == 3
    assert all("hits" in r for r in resp["responses"])
    assert len(seen) >= 3
    by_id = assert_tree(spans)
    groups = [s for s in spans if s["name"] == "envelope.compile_group"
              and by_id[s["parent_id"]]["name"] == "envelope"
              and by_id[by_id[s["parent_id"]]["parent_id"]]["name"]
              == "rest.msearch"]
    assert len(groups) == 1
    bundles = [s for s in spans if s["name"] == "compile.bundle"
               and s["parent_id"] == groups[0]["span_id"]]
    assert len(bundles) == 3
    assert sorted(s["attributes"]["nbytes"] > 0 for s in bundles) \
        == [False, True, True]
    assert RING.current() is None
    # and a fault of the compile loop that raises out of the envelope
    from opensearch_tpu.telemetry.scan import SCAN

    def boom(*a, **kw):
        raise RuntimeError("no note")

    monkeypatch.setattr(SCAN, "note_batch", boom)
    ex = node.indices.get("ring").shards[0].executor
    RING.clear()
    ctx, _, parent = RING.enter()
    try:
        with pytest.raises(RuntimeError):
            ex.multi_search([{"query": {"match": {"t": "raised item"}}}],
                            _raise_item_errors=True)
        assert ctx.top == ctx.trace_id
    finally:
        RING.leave(ctx, parent)
    spans = RING.export()["spans"]
    by_id = {s["span_id"]: s for s in spans}
    assert all(s["parent_id"] in by_id or s["parent_id"] == 0
               or s["parent_id"] == ctx.trace_id for s in spans)
    names = [s["name"] for s in spans]
    assert names.count("compile.bundle") == 1 \
        and names.count("envelope.compile_group") == 1 \
        and names.count("envelope") == 1


def test_a_child_recorded_under_an_entered_span_names_it_as_parent():
    ring = SpanRing(size=8)
    trace, root, _ = ring.enter()
    _, outer, outer_parent = ring.enter()
    leaf = ring.child("leaf", 10, 20)
    assert leaf and trace.spans[-1][:3] == (leaf, outer, "leaf")
    # after the fact, under a span recorded before: its id, handed on
    late = ring.child("late", 30, 60)
    assert ring.child("late.part", 30, 40, None, late)
    assert [s[:3] for s in trace.spans[-2:]] == [
        (late, outer, "late"), (trace.spans[-1][0], late, "late.part")]
    ring.leave(trace, outer_parent)
    assert ring.child("back", 70, 80) and trace.spans[-1][1] == root
    ring.leave(trace, 0)
    assert ring.current() is None
    assert SpanRing(size=8).child("orphan", 1, 2) == 0


def test_a_waves_compiler_records_so_many_spans_and_no_more():
    ring = SpanRing(size=8)
    compiler = Compiler(None, None, spans=ring)
    assert [compiler.span_ring() for _ in range(COMPILE_SPANS_A_WAVE)] \
        == [ring] * COMPILE_SPANS_A_WAVE
    assert compiler.span_ring() is None and compiler.span_ring() is None
    # the next wave's compiler starts from none; one without a ring
    # records nothing
    assert Compiler(None, None, spans=ring).span_ring() is ring
    assert Compiler(None, None).span_ring() is None


def test_the_ring_itself_caps_no_request():
    # `child` is what the boundary spans of the SPMD route and
    # `xla.compile` go through: it keeps every one
    ring = SpanRing(size=8)
    trace, sid, parent = ring.enter()
    assert all(ring.child("dispatch", i, i + 1) for i in range(500))
    trace.spans.append((sid, parent, "rest.msearch", 0, 999, None))
    ring.leave(trace, parent)
    assert len(ring.export()["spans"]) == 501


def test_the_process_track_is_bounded_and_drops_its_oldest():
    ring = SpanRing(size=4, process_size=3)
    ids = [ring.process(f"p{i}", i * 10, i * 10 + 5) for i in range(5)]
    assert ids == sorted(ids) and len(set(ids)) == 5
    out = ring.export()
    assert [s["name"] for s in out["process"]] == ["p2", "p3", "p4"]
    assert all(s["trace_id"] == 0 and s["parent_id"] == 0
               for s in out["process"])
    assert out["spans"] == [] and ring.stats()["process"] == 3
    assert PROCESS_RING_SIZE >= 3000 // 8      # section 6 of PERF.md
    assert RING.stats()["process"] <= PROCESS_RING_SIZE


def test_the_process_track_is_filtered_as_the_requests_are():
    ring = SpanRing(size=4)
    ring.process("early", 1.0, 2.0)             # seconds, as read
    ring.process("across", 1_500_000_000, 3_500_000_000)
    ring.process("late", 4_000_000_000, 5_000_000_000, {"k": 1})
    names = lambda **kw: [s["name"] for s in ring.export(**kw)["process"]]
    assert names() == ["early", "across", "late"]
    assert names(since_ns=3_000_000_000) == ["across", "late"]
    assert names(until_ns=3_000_000_000) == ["early", "across"]
    assert names(since_ns=2_500_000_000, until_ns=3_000_000_000) \
        == ["across"]
    row = ring.export()["process"]
    assert row[0]["start_ns"] == 1_000_000_000 and "attributes" not in row[0]
    assert row[2]["attributes"] == {"k": 1}


def test_process_spans_name_their_parent_and_outlive_a_clear():
    ring = SpanRing(size=4)
    up = ring.process("install.upload_segment", 10, 90, {"segment": "s0"})
    ring.process("install.host_pad", 10, 40, None, up)
    ring.process("install.device_put", 40, 90, None, up)
    one_request(ring, "r", 1, 2)
    ring.clear()
    out = ring.export()
    assert out["spans"] == []
    assert [(s["name"], s["parent_id"]) for s in out["process"]] == [
        ("install.upload_segment", 0), ("install.host_pad", up),
        ("install.device_put", up)]


def test_spans_is_what_it_was_beside_the_process_track():
    """A request without the new children is exported as PR 25 exported
    it: the same rows under `spans`, the same keys before `process`."""
    ring = SpanRing(size=4)
    trace, sid, parent = ring.enter()
    trace.spans.append((sid + 1, sid, "http.read_decode", 100, 200, None))
    trace.spans.append((sid, parent, "http.request", 100, 900,
                        {"route": "_search"}))
    ring.leave(trace, parent)
    before = json.dumps(ring.export()["spans"])
    ring.process("gc.collect", 300, 400, {"generation": 2})
    out = ring.export()
    assert json.dumps(out["spans"]) == before == json.dumps([
        {"trace_id": sid, "span_id": sid + 1, "parent_id": sid,
         "name": "http.read_decode", "start_ns": 100, "end_ns": 200},
        {"trace_id": sid, "span_id": sid, "parent_id": 0,
         "name": "http.request", "start_ns": 100, "end_ns": 900,
         "attributes": {"route": "_search"}}])
    assert list(out) == ["clock", "anchor", "dropped", "spans", "process"]
    # and the benchmark's reader of `spans` sees what it saw
    from benchmark import spans as bench_spans
    fetched = bench_spans.Spans(out)
    assert [s["name"] for s in fetched.spans] == ["http.read_decode",
                                                  "http.request"]


# ------------------------------------------------ the heap's collections

@pytest.fixture()
def gc_spans():
    ring = SpanRing(size=4)
    watch = GcSpans(ring)
    watch.install()
    watch.install()         # once, however often it is asked
    assert gc.callbacks.count(watch) == 1
    try:
        yield ring, watch
    finally:
        gc.callbacks.remove(watch)


def test_a_forced_full_collection_is_one_span_and_moves_both_counters(
        gc_spans):
    ring, watch = gc_spans
    t0 = time.monotonic_ns()
    full0, pause0 = watch.full, watch.pause_ns
    gc.collect(2)
    t1 = time.monotonic_ns()
    spans = [s for s in ring.export()["process"]
             if s["attributes"]["generation"] == 2]
    assert len(spans) == 1 and spans[0]["name"] == "gc.collect"
    s = spans[0]
    assert t0 <= s["start_ns"] <= s["end_ns"] <= t1
    assert set(s["attributes"]) == {"generation", "collected",
                                    "uncollectable"}
    assert watch.full == full0 + 1
    assert watch.pause_ns - pause0 >= s["end_ns"] - s["start_ns"] > 0


def test_a_young_collection_is_counted_and_kept_only_if_it_was_slow():
    ring = SpanRing(size=4)
    watch = GcSpans(ring)           # driven by hand: not installed
    info = {"generation": 0, "collected": 3, "uncollectable": 0}
    watch("start", info)
    watch("stop", info)
    pause1 = watch.pause_ns
    assert watch.full == 0 and pause1 > 0 \
        and ring.export()["process"] == []
    watch("start", info)
    time.sleep(GC_SPAN_MIN_NS / 1e9 * 1.5)
    watch("stop", info)
    assert watch.full == 0
    kept = ring.export()["process"]
    assert len(kept) == 1 and kept[0]["attributes"] == info
    assert kept[0]["end_ns"] - kept[0]["start_ns"] >= GC_SPAN_MIN_NS
    pause2 = watch.pause_ns
    assert pause2 - pause1 >= GC_SPAN_MIN_NS
    # a stop whose start was before the watch: nothing, and no raise
    watch("stop", {"generation": 2})
    watch("stop", {})
    assert (watch.full, watch.pause_ns) == (0, pause2) \
        and len(ring.export()["process"]) == 1


def test_the_nodes_collections_are_spans_and_counters(served):
    _, call = served
    assert gc.callbacks.count(TELEMETRY.tracer.gc) == 1

    def counters():
        _, stats = call("GET", "/_nodes/stats")
        c = next(iter(stats["nodes"].values()))["telemetry"]["metrics"][
            "counters"]
        return c["process.gc.collections.gen2"], c["process.gc.pause_us"]

    full0, pause0 = counters()
    cut = time.monotonic_ns()
    gc.collect(2)
    full1, pause1 = counters()
    assert full1 >= full0 + 1 and pause1 > pause0
    status, body = call("GET", f"/_telemetry/spans?since_ns={cut}")
    assert status == 200
    mine = [s for s in body["process"] if s["name"] == "gc.collect"
            and s["attributes"]["generation"] == 2]
    assert mine and all(s["trace_id"] == 0 and s["start_ns"] >= cut
                        for s in mine)
    # `reset` zeroes the counters the registry owns, not these
    TELEMETRY.metrics.reset()
    assert counters()[0] == full1 or counters()[0] > full1


# ------------------------------------------------------ an index's install

def test_an_installed_segment_is_three_process_spans(served):
    _, call = served
    cut = time.monotonic_ns()
    call("PUT", "/fresh", {"mappings": {"properties": {
        "t": {"type": "text"}}}})
    call("POST", "/_bulk", json.dumps({"index": {
        "_index": "fresh", "_id": "1"}}) + "\n" + json.dumps(
            {"t": "just installed"}) + "\n", ndjson=True)
    call("POST", "/fresh/_refresh")
    _, body = call("GET", f"/_telemetry/spans?since_ns={cut}")
    ups = [s for s in body["process"]
           if s["name"] == "install.upload_segment"]
    assert len(ups) == 1
    up = ups[0]
    assert set(up["attributes"]) == {"segment", "d_pad", "nbytes"}
    assert up["attributes"]["d_pad"] >= 1 and up["attributes"]["nbytes"] > 0
    kids = sorted((s for s in body["process"]
                   if s["parent_id"] == up["span_id"]),
                  key=lambda s: s["start_ns"])
    assert [s["name"] for s in kids] == ["install.host_pad",
                                         "install.device_put"]
    assert up["start_ns"] == kids[0]["start_ns"] \
        and kids[0]["end_ns"] == kids[1]["start_ns"] \
        and kids[1]["end_ns"] == up["end_ns"]
    # before the cut: the two indices of the fixture, a segment a shard
    _, body = call("GET", f"/_telemetry/spans?until_ns={cut}")
    assert sum(1 for s in body["process"]
               if s["name"] == "install.upload_segment") >= 5


def test_the_shard_set_of_an_spmd_index_is_a_span_and_its_three_parts(
        served):
    _, call = served
    call("POST", "/rows/_search", AGG_BODY)      # built by now, if not yet
    _, body = call("GET", "/_telemetry/spans")
    sets = [s for s in body["process"] if s["name"] == "install.shard_set"]
    assert len(sets) >= 1
    assert sets[0]["attributes"]["rows"] == 4 \
        and sets[0]["attributes"]["devices"] == 4 \
        and sets[0]["attributes"]["nbytes"] > 0 \
        and set(sets[0]["attributes"]) == {"rows", "devices", "nbytes"}
    # the rows' host images, their stack, the put over the mesh: end to
    # end, the first from the span's start and the last to its end
    kids = [s for s in body["process"]
            if s["parent_id"] == sets[0]["span_id"]]
    assert [s["name"] for s in kids] == [
        "install.shard_set.host_images", "install.shard_set.stack",
        "install.shard_set.device_put"]
    assert all("attributes" not in s for s in kids)
    assert kids[0]["start_ns"] == sets[0]["start_ns"] \
        and kids[2]["end_ns"] == sets[0]["end_ns"] \
        and kids[1]["end_ns"] == kids[2]["start_ns"] \
        and kids[0]["end_ns"] <= kids[1]["start_ns"]
    # the rows' host images are no uploads: one a shard, from the bulk
    assert sum(1 for s in body["process"]
               if s["name"] == "install.upload_segment"
               and s["start_ns"] >= sets[0]["start_ns"]
               and s["end_ns"] <= sets[0]["end_ns"]) == 0


@pytest.mark.parametrize("faulty", (0, 1))
def test_one_items_failed_reduce_leaves_its_siblings_their_answers(
        served, monkeypatch, faulty):
    # the wave's aggregations are reduced before its pages are rendered
    # (`respond.reduce_aggs`, one interval a wave): per item all the same
    _, call = served
    reduce_aggs = executor_mod.reduce_aggs
    seen = []

    def flaky(partials):
        seen.append(len(seen))
        if seen[-1] == faulty:
            raise RuntimeError("no reduce for this one")
        return reduce_aggs(partials)

    monkeypatch.setattr(executor_mod, "reduce_aggs", flaky)
    bodies = []
    for i in range(2):
        body = json.loads(json.dumps(AGG_BODY))
        body["query"]["range"]["n"]["gte"] = 40 + 2 * faulty + i
        bodies.append(body)
    batch = "".join(json.dumps({"index": "ring"}) + "\n"
                    + json.dumps(b) + "\n" for b in bodies)
    status, resp, spans = request_spans(call, "POST", "/_msearch", batch,
                                        ndjson=True)
    assert status == 200 and len(seen) == 2
    bad, good = resp["responses"][faulty], resp["responses"][1 - faulty]
    assert bad["status"] == 500 and "no reduce" in json.dumps(bad["error"])
    assert good["aggregations"]["h"]["buckets"] and "error" not in good
    # and the wave's spans closed as ever
    by_id = assert_tree(spans)
    assert {"respond.reduce_aggs", "respond.render"} <= {
        s["name"] for s in spans
        if by_id.get(s["parent_id"], {}).get("name") == "respond"}
