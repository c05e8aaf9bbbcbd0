"""The B=1 hybrid `_search` route in the always-on span ring and the
node's counters: one request over HTTP leaves one trace whose
`rest.search` holds `hybrid.compile`, `dispatch` (family `hybrid_env`,
named by its census record), `device_wait`, `hybrid.merge` and
`respond`, in that order on one clock; a request over several shards
dispatches a wave a shard; `search.hybrid.queries` rises by one a
request on `_search` and on `_msearch`'s hybrid waves, and
`search.hybrid.candidates` by the normalization pool `hybrid.merge`
names. No timing is asserted.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from opensearch_tpu.launcher import start_node
from opensearch_tpu.telemetry import TELEMETRY

RING = TELEMETRY.tracer.spans
DIMS = 4
ROUTE_TREE = {
    "http.request": None,
    "http.read_decode": "http.request",
    "http.encode_write": "http.request",
    "rest.search": "http.request",
    "hybrid.compile": "rest.search",
    "dispatch": "rest.search",
    "device_wait": "rest.search",
    "hybrid.merge": "rest.search",
    "respond": "rest.search",
}
PIPELINE = {"phase_results_processors": [{"normalization-processor": {
    "normalization": {"technique": "min_max"},
    "combination": {"technique": "arithmetic_mean",
                    "parameters": {"weights": [0.3, 0.7]}}}}]}


def hybrid_body(i: int, size: int = 10) -> dict:
    rng = np.random.default_rng(i)
    return {"query": {"hybrid": {"queries": [
                {"match": {"t": f"hello w{i % 7}"}},
                {"knn": {"v": {"vector": rng.random(DIMS).round(3).tolist(),
                               "k": 5}}}]}},
            "size": size, "_source": False, "search_pipeline": PIPELINE}


@pytest.fixture(scope="module")
def served():
    node, server = start_node({"http.port": 0, "node.name": "hybrid-ring"})
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

    rng = np.random.default_rng(7)
    properties = {"t": {"type": "text"},
                  "v": {"type": "knn_vector", "dimension": DIMS,
                        "method": {"space_type": "innerproduct"}}}
    for index, shards in (("hyb", 1), ("hyb2", 2)):
        call("PUT", f"/{index}", {"settings": {"number_of_shards": shards},
                                  "mappings": {"properties": properties}})
        bulk = "".join(
            json.dumps({"index": {"_index": index, "_id": str(i)}}) + "\n"
            + json.dumps({"t": f"hello world w{i % 7} x{i % 13}",
                          "v": rng.random(DIMS).round(3).tolist()}) + "\n"
            for i in range(120))
        call("POST", "/_bulk", bulk, ndjson=True)
        call("POST", f"/{index}/_refresh")
        # compile the fused program once, so later requests are warm
        assert call("POST", f"/{index}/_search", hybrid_body(0))[0] == 200
    yield node, call
    server.close()


def request_spans(call, method, path, body=None, ndjson=False):
    """(status, response, the spans of that one request), waiting for
    `http.request`, which is written after the last byte has gone out."""
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


def counter(call, name: str) -> int:
    stats = call("GET", "/_nodes/stats")[1]
    node = next(iter(stats["nodes"].values()))
    return node["telemetry"]["metrics"]["counters"].get(name, 0)


def parents(spans) -> dict:
    by_id = {s["span_id"]: s for s in spans}
    assert len({s["trace_id"] for s in spans}) == 1
    for s in spans:
        assert s["start_ns"] <= s["end_ns"], s
        if s["parent_id"]:
            p = by_id[s["parent_id"]]
            assert p["start_ns"] <= s["start_ns"] <= s["end_ns"] \
                <= p["end_ns"], (s, p)
    return {s["span_id"]: (s["name"], by_id[s["parent_id"]]["name"]
                           if s["parent_id"] else None) for s in spans}


def test_one_hybrid_search_is_one_trace_with_the_route_tree(served):
    _, call = served
    status, resp, spans = request_spans(call, "POST", "/hyb/_search",
                                        hybrid_body(3))
    assert status == 200 and resp["hits"]["hits"]
    assert sorted(parents(spans).values()) == sorted(ROUTE_TREE.items())
    named = {s["name"]: s for s in spans}
    d = named["dispatch"]["attributes"]
    assert d["family"] == "hybrid_env" and d["wave"] == 0
    assert d["programs"] == 1 and d["nbytes"] > 0
    assert len(d["fingerprint"]) == 8
    # the batch, d_pad, each window's k, the sub-queries, the width
    assert d["shape"] == "b1/d128/k10/sub2/dim4"
    w = named["device_wait"]["attributes"]
    assert w["wave"] == 0 and w["nbytes"] > 0 and w["programs"] == 0
    # one clock: each span begins where the route's last read left off
    assert named["hybrid.compile"]["end_ns"] \
        == named["dispatch"]["start_ns"]
    assert named["dispatch"]["end_ns"] <= named["device_wait"]["start_ns"]
    assert named["device_wait"]["end_ns"] \
        <= named["hybrid.merge"]["start_ns"]
    assert named["hybrid.merge"]["end_ns"] == named["respond"]["start_ns"]
    # the normalization pool: both sub-queries' windows
    assert named["hybrid.merge"]["attributes"]["candidates"] == 10 + 5
    # the census names the executable the span names
    census = call("GET", "/_telemetry/kernels")[1]["kernels"]["census"]
    rec = [e for e in census["executables"]
           if e["fingerprint"] == d["fingerprint"]]
    assert rec and rec[0]["family"] == "hybrid_env"
    assert rec[0]["shape"] == d["shape"]


def test_a_search_over_two_shards_dispatches_a_wave_a_shard(served):
    _, call = served
    status, _, spans = request_spans(call, "POST", "/hyb2/_search",
                                     hybrid_body(4))
    assert status == 200
    tree = sorted(parents(spans).values())
    assert tree.count(("dispatch", "rest.search")) == 2
    assert tree.count(("device_wait", "rest.search")) == 2
    assert tree.count(("hybrid.compile", "rest.search")) == 2
    assert tree.count(("hybrid.merge", "rest.search")) == 1
    assert tree.count(("respond", "rest.search")) == 1
    waves = sorted((s["name"], s["attributes"]["wave"]) for s in spans
                   if s["name"] in ("dispatch", "device_wait"))
    assert waves == [("device_wait", 0), ("device_wait", 1),
                     ("dispatch", 0), ("dispatch", 1)]


def test_the_counters_rise_once_a_request_on_search_and_msearch(served):
    _, call = served
    q0 = counter(call, "search.hybrid.queries")
    c0 = counter(call, "search.hybrid.candidates")
    pools = 0
    for i in range(3):
        status, _, spans = request_spans(call, "POST", "/hyb/_search",
                                         hybrid_body(10 + i))
        assert status == 200
        pools += sum(s["attributes"]["candidates"] for s in spans
                     if s["name"] == "hybrid.merge")
    assert counter(call, "search.hybrid.queries") == q0 + 3
    assert counter(call, "search.hybrid.candidates") == c0 + pools
    # `_msearch`'s hybrid waves: the default pipeline, once an item
    items = [hybrid_body(20 + i) for i in range(4)]
    for body in items:
        body.pop("search_pipeline")
    ndjson = "".join(json.dumps({"index": "hyb"}) + "\n"
                     + json.dumps(body) + "\n" for body in items)
    status, resp = call("POST", "/_msearch", ndjson, ndjson=True)
    assert status == 200
    assert all(r.get("status", 200) == 200 and r["hits"]["hits"]
               for r in resp["responses"])
    assert counter(call, "search.hybrid.queries") == q0 + 3 + 4
    assert counter(call, "search.hybrid.candidates") > c0 + pools
