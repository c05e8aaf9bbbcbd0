"""The SPMD route as a served path (ISSUE 28): a request the SPMD
program answers leaves `spmd.plan`, `dispatch`, `device_wait`,
`spmd.reduce` and `respond` under `rest.search` in the always-on span
ring, `dispatch` naming the executable; the program is an XLA module
`jit_spmd_query_phase` whose ops the census maps to stages; a request
that `spmd.eligible` admitted and that ends in the host loop is counted
(`search.spmd_fallbacks`, by reason), and rows whose `date_histogram`s
differ in bin count are no such request; a `Segment` over a lazy id
sequence answers what one over a list answers.
"""

import http.client
import json
import time

import numpy as np
import pytest

from opensearch_tpu.index.segment import (PrefixedIds, Segment,
                                          SegmentBuilder)
from opensearch_tpu.launcher import start_node
from opensearch_tpu.parallel.distributed import DistributedSearcher
from opensearch_tpu.search import spmd
from opensearch_tpu.telemetry import TELEMETRY

T0 = 893894400000       # 1998-04-30T00:00:00Z
HOUR = 3600000
MAPPING = {"properties": {"@timestamp": {"type": "date"},
                          "status": {"type": "integer"},
                          "size": {"type": "integer"}}}
BODY = {"size": 0, "track_total_hits": True,
        "query": {"range": {"@timestamp": {"gte": T0 + 2 * HOUR,
                                           "lt": T0 + 30 * HOUR}}},
        "aggs": {"by_hour": {
            "date_histogram": {"field": "@timestamp",
                               "calendar_interval": "hour"},
            "aggs": {"by_status": {
                "terms": {"field": "status"},
                "aggs": {"bytes": {"sum": {"field": "size"}}}}}}}}


def docs_of(shard: int, hours: int, per_hour: int = 6):
    """A shard's documents: `hours` hours of them from the span's
    start, so that shards differ in how many hourly bins they hold."""
    rng = np.random.default_rng(shard)
    out = []
    for h in range(hours):
        for j in range(per_hour):
            out.append({"@timestamp": T0 + h * HOUR + j * 60000 * 7,
                        "status": int(rng.choice([200, 200, 304, 404])),
                        "size": int(rng.integers(1, 50000))})
    return out


@pytest.fixture(scope="module")
def served():
    """A node with one index of 8 shards whose rows span 20..34 hours
    (every row another bin count), installed as the benchmark installs
    a corpus, and every document beside it."""
    node, server = start_node({"http.port": 0, "node.name": "spmd-route"})
    node.request("PUT", "/logs", {"settings": {"number_of_shards": 8},
                                  "mappings": MAPPING})
    svc = node.indices.get("logs")
    everything = []
    for s, shard in enumerate(svc.shards):
        b = SegmentBuilder(svc.mapper, "s0")
        for i, doc in enumerate(docs_of(s, 20 + 2 * s)):
            b.add(svc.mapper.parse_document(f"s{s}-{i}", doc))
            everything.append(doc)
        seg = b.seal()
        shard.engine.install_segments([seg], max_seq_no=seg.num_docs,
                                      local_checkpoint=seg.num_docs)
        shard._sync_reader()
    yield node, server, everything
    server.close()


def expected(docs):
    lo = BODY["query"]["range"]["@timestamp"]["gte"]
    hi = BODY["query"]["range"]["@timestamp"]["lt"]
    sel = [d for d in docs if lo <= d["@timestamp"] < hi]
    hours = {}
    for d in sel:
        by = hours.setdefault(d["@timestamp"] // HOUR * HOUR, {})
        c, s = by.get(d["status"], (0, 0))
        by[d["status"]] = (c + 1, s + d["size"])
    return len(sel), hours


def check_response(resp, docs):
    total, hours = expected(docs)
    assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
    assert resp["_shards"]["failed"] == 0 and resp["timed_out"] is False
    buckets = resp["aggregations"]["by_hour"]["buckets"]
    assert [b["key"] for b in buckets] == sorted(hours)
    for b in buckets:
        want = hours[b["key"]]
        got = {t["key"]: (t["doc_count"], t["bytes"]["value"])
               for t in b["by_status"]["buckets"]}
        assert got == {k: (c, float(s)) for k, (c, s) in want.items()}


def counters():
    return {k: v for k, v in
            TELEMETRY.metrics.to_dict()["counters"].items()
            if k.startswith("search.spmd_")}


def post(server, body):
    conn = http.client.HTTPConnection("127.0.0.1", server.port)
    try:
        conn.request("POST", "/logs/_search", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        return json.loads(resp.read())
    finally:
        conn.close()


def test_rows_that_differ_in_bin_count_take_the_spmd_program(served):
    node, server, docs = served
    svc = node.indices.get("logs")
    firsts = {len(np.unique(sh.engine.segments[0].numeric_dv["@timestamp"]
                            .unique // HOUR)) for sh in svc.shards}
    assert len(firsts) == 8         # every row another bin count
    before = counters()
    check_response(post(server, BODY), docs)
    after = counters()
    assert after["search.spmd_queries"] == before["search.spmd_queries"] + 1
    assert after["search.spmd_fallbacks"] == before["search.spmd_fallbacks"]
    stats = next(iter(node.request("GET", "/_nodes/stats")["nodes"]
                      .values()))["telemetry"]["metrics"]["counters"]
    assert stats["search.spmd_fallbacks"] == after["search.spmd_fallbacks"]
    assert "search.spmd_queries" in stats


def moved(body, minutes):
    """The panel over another window: the request caches never hit."""
    body = json.loads(json.dumps(body))
    r = body["query"]["range"]["@timestamp"]
    r["gte"] += minutes * 60000
    r["lt"] += minutes * 60000
    return body


@pytest.mark.parametrize("raised,reason", [(ValueError, "searcher"),
                                           (KeyError, "searcher"),
                                           (RuntimeError, "error")])
def test_a_forced_fallback_is_counted_by_reason(served, monkeypatch,
                                                raised, reason):
    node, server, docs = served

    def refuse(self, *a, **kw):
        raise raised("refused for the test")
    monkeypatch.setattr(DistributedSearcher, "search_resident", refuse)
    before = counters()
    body = moved(BODY, {ValueError: 1, KeyError: 2, RuntimeError: 3}[raised])
    resp = post(server, body)       # the host loop answers it
    assert resp["_shards"]["failed"] == 0
    assert resp["hits"]["total"]["value"] > 0
    after = counters()
    assert after["search.spmd_fallbacks"] \
        == before["search.spmd_fallbacks"] + 1
    key = f"search.spmd_fallbacks.{reason}"
    assert after[key] == before[key] + 1
    assert after["search.spmd_queries"] == before["search.spmd_queries"]


def test_force_host_loop_is_no_fallback(served):
    node, server, docs = served
    before = counters()
    with spmd.force_host_loop():
        check_response(node.request("POST", "/logs/_search", BODY), docs)
    assert counters() == before


@pytest.fixture(scope="module")
def notes():
    """A text index of 8 shards whose rows differ in everything a text
    plan's traced inputs hold: document count, field length (avgdl), each
    term's df (`gamma` is in the even shards alone)."""
    node = start_node({"http.port": 0, "node.name": "spmd-text"})[0]
    node.request("PUT", "/notes", {
        "settings": {"number_of_shards": 8},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    svc = node.indices.get("notes")
    for s, shard in enumerate(svc.shards):
        rng = np.random.default_rng(100 + s)
        b = SegmentBuilder(svc.mapper, "s0")
        for i in range(30 + 9 * s):
            words = [w for w, p in (("alpha", 0.6), ("beta", 0.3),
                                    ("gamma", 0.2 * (s % 2 == 0)))
                     if rng.random() < p]
            words += [f"w{int(x)}" for x in rng.integers(0, 40, 2 + 3 * s)]
            b.add(svc.mapper.parse_document(f"s{s}-{i}",
                                            {"body": " ".join(words)}))
        seg = b.seal()
        shard.engine.install_segments([seg], max_seq_no=seg.num_docs,
                                      local_checkpoint=seg.num_docs)
        shard._sync_reader()
    return node


@pytest.mark.parametrize("match,score_only", [
    ("alpha gamma beta", True),
    ({"query": "alpha beta", "operator": "and"}, False),
    ({"query": "alpha gamma w3", "minimum_should_match": 2}, False)],
    ids=["default-match", "operator-and", "minimum-should-match-2"])
def test_a_text_query_over_rows_takes_the_spmd_program(notes, match,
                                                       score_only):
    """The `score_only` flag of a text clause (ISSUE 30) is in the rows'
    plan structure and rests on nothing of a row: every row plans the
    same flag, so the request is one SPMD program, and its page is the
    host loop's."""
    body = {"query": {"match": {"body": match}}, "size": 10}
    flag = "score_only" if score_only else "counted"
    planned = TELEMETRY.metrics.counter(f"search.text_clause.{flag}")
    other = TELEMETRY.metrics.counter(
        "search.text_clause." + ("counted" if score_only else "score_only"))
    n_flag, n_other, before = planned.value, other.value, counters()
    got = notes.request("POST", "/notes/_search", body)
    after = counters()
    assert after["search.spmd_queries"] == before["search.spmd_queries"] + 1
    assert after["search.spmd_fallbacks"] == before["search.spmd_fallbacks"]
    assert planned.value == n_flag + 8 and other.value == n_other
    with spmd.force_host_loop():
        want = notes.request("POST", "/notes/_search", body)
    assert counters() == after
    assert got["hits"]["total"] == want["hits"]["total"]
    assert got["hits"]["total"]["value"] > 10
    assert [h["_id"] for h in got["hits"]["hits"]] \
        == [h["_id"] for h in want["hits"]["hits"]]
    assert [h["_score"] for h in got["hits"]["hits"]] \
        == pytest.approx([h["_score"] for h in want["hits"]["hits"]],
                         rel=1e-6)


def test_an_spmd_served_request_is_one_row_of_route_spans(served):
    node, server, docs = served
    TELEMETRY.tracer.spans.clear()
    post(server, moved(BODY, 11))
    post(server, moved(BODY, 5))
    # a request is in the ring once its http.request has ended, which
    # is after the client has read the response
    for _ in range(200):
        ring = node.request("GET", "/_telemetry/spans")["spans"]
        roots = [s for s in ring if s["name"] == "http.request"
                 and s["attributes"]["route"] == "_search"]
        if len(roots) == 2:
            break
        time.sleep(0.01)
    assert len(roots) == 2
    for root in roots:
        row = [s for s in ring if s["trace_id"] == root["trace_id"]]
        rest = [s for s in row if s["name"] == "rest.search"]
        assert len(rest) == 1 and rest[0]["parent_id"] == root["span_id"]
        under = [s for s in row if s["parent_id"] == rest[0]["span_id"]]
        names = [s["name"] for s in sorted(under,
                                           key=lambda s: s["start_ns"])
                 if s["name"] != "xla.compile"]
        assert names == ["spmd.plan", "dispatch", "device_wait",
                         "spmd.reduce", "spmd.reduce", "respond"]
        assert not [s for s in row if s["name"].startswith("envelope")]
        by = {s["name"]: s for s in under}
        d = by["dispatch"]["attributes"]
        assert d["family"] == "spmd_query_phase" and d["programs"] == 1
        assert len(d["fingerprint"]) == 8 and d["wave"] == 0
        assert d["nbytes"] > 0 and d["shape"].startswith("r8x1xd")
        w = by["device_wait"]["attributes"]
        assert w["wave"] == 0 and w["nbytes"] > 0 and w["programs"] == 0
        # one after another on one clock, inside rest.search
        ordered = sorted((s for s in under if s["name"] != "xla.compile"),
                         key=lambda s: s["start_ns"])
        for a, b in zip(ordered, ordered[1:]):
            assert a["end_ns"] <= b["start_ns"]
        assert rest[0]["start_ns"] <= ordered[0]["start_ns"]
        assert ordered[-1]["end_ns"] <= rest[0]["end_ns"]
    # the benchmark's join pairs them as one wave a request
    from benchmark import spans as bench_spans
    waves = bench_spans.waves_of(bench_spans.Spans({"spans": ring}))
    assert len(waves) == 2 and all(w.programs == 1 for w in waves)
    assert {w.fingerprints[0] for w in waves} \
        == {s["attributes"]["fingerprint"] for s in ring
            if s["name"] == "dispatch"}


def test_the_program_is_named_and_its_ops_map_to_stages(served):
    node, server, docs = served
    post(server, moved(BODY, 7))
    census = node.request("GET", "/_telemetry/kernels", scopes="true")[
        "kernels"]["census"]["executables"]
    mine = [e for e in census if e["family"] == "spmd_query_phase"]
    # the census is the process's: other tests' SPMD programs (a match
    # with no aggregation) are in it too; the panel's has every stage
    want = {"filter_mask", "agg_bins", "collective_merge",
            "eligible_total", "top_k"}
    mine = [e for e in mine if "_error" not in e["scopes"]
            and want <= {s.lstrip("~") for s in e["scopes"].values()}]
    assert mine
    fn, structs = TELEMETRY.kernels._lowerable[mine[0]["fingerprint"]]
    assert "module @jit_spmd_query_phase" in fn.lower(*structs).as_text()


# ------------------------------------------------------------- lazy ids

def segment_over(ids):
    n = len(ids)
    return Segment("s0", n, ids, [None] * n, {},
                   np.full((1, 128), -1, np.int32),
                   np.zeros((1, 128), np.float32), {}, {}, {}, {}, {})


def test_a_segment_over_lazy_ids_answers_as_over_a_list():
    lazy = segment_over(PrefixedIds("s3-", 50))
    listed = segment_over([f"s3-{i}" for i in range(50)])
    assert "_id_ords" in lazy.__dict__ and lazy._id_ords is None
    assert listed._id_ords is None      # built on first use, both
    probes = ["s3-0", "s3-7", "s3-49", "s3-50", "s3-07", "s3--1", "s3-",
              "s3-1x", "x3-1", "s3-٣", "", "7", None]
    for p in probes:
        assert lazy.ord_of(p) == listed.ord_of(p), p
        assert lazy._id_to_ord.get(p) == listed._id_to_ord.get(p), p
        assert (p in lazy._id_to_ord) == (p in listed._id_to_ord), p
    assert isinstance(listed._id_ords, dict) and len(listed._id_ords) == 50
    assert lazy._id_to_ord.get("s3-9") == 9
    assert list(lazy.doc_ids) == listed.doc_ids
    assert lazy.doc_ids[3:6] == listed.doc_ids[3:6]
    assert lazy.doc_ids[-1] == "s3-49" and lazy.doc_ids == listed.doc_ids
    with pytest.raises(IndexError):
        lazy.doc_ids[50]
    # a delete goes through the same map, and a copy keeps its own
    clone = lazy.clone_for_copy()
    assert lazy.delete("s3-7") and not lazy.delete("s3-7")
    assert lazy.ord_of("s3-7") is None and clone.ord_of("s3-7") == 7
    assert listed.delete("s3-7") and listed.ord_of("s3-7") is None


def test_a_repeated_id_in_a_list_keeps_its_last_row():
    seg = segment_over(["a", "b", "a", None])
    assert seg.ord_of("a") == 2 and seg.ord_of("b") == 1
    assert seg.ord_of(None) is None
