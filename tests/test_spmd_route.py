"""The SPMD route as a served path (ISSUE 28): a request the SPMD
program answers leaves `spmd.plan`, `dispatch`, `device_wait`,
`spmd.reduce` and `respond` under `rest.search` in the always-on span
ring, `dispatch` naming the executable; the program is an XLA module
`jit_spmd_query_phase` whose ops the census maps to stages; a request
that `spmd.eligible` admitted and that ends in the host loop is counted
(`search.spmd_fallbacks`, by reason), and rows whose `date_histogram`s
differ in bin count are no such request; a `Segment` over a lazy id
sequence answers what one over a list answers. A `bucket_num` level's
lane -> bin vector leaves the request (ISSUE 32): derived once a (shard
set, field, bucketing), kept on the mesh, counted
(`search.agg_lane_bins.*`), released with its shard set; an identity
table reads the rank column.
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


def expected(docs, body=BODY, step=HOUR, shift=0):
    lo = body["query"]["range"]["@timestamp"]["gte"]
    hi = body["query"]["range"]["@timestamp"]["lt"]
    sel = [d for d in docs if lo <= d["@timestamp"] < hi]
    hours = {}
    for d in sel:
        key = (d["@timestamp"] + shift) // step * step - shift
        by = hours.setdefault(key, {})
        c, s = by.get(d["status"], (0, 0))
        by[d["status"]] = (c + 1, s + d["size"])
    return len(sel), hours


def check_response(resp, docs, body=BODY, step=HOUR, shift=0):
    total, hours = expected(docs, body, step, shift)
    assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
    assert resp["_shards"]["failed"] == 0 and resp["timed_out"] is False
    buckets = resp["aggregations"]["by_hour"]["buckets"]
    assert [b["key"] for b in buckets] == sorted(hours)
    for b in buckets:
        want = hours[b["key"]]
        got = {t["key"]: (t["doc_count"], t["bytes"]["value"])
               for t in b["by_status"]["buckets"]}
        assert got == {k: (c, float(s)) for k, (c, s) in want.items()}


def counters(prefix="search.spmd_"):
    return {k: v for k, v in
            TELEMETRY.metrics.to_dict()["counters"].items()
            if k.startswith(prefix)}


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


# --------------------------------------------- resident lane -> bin vectors

def lane_bins():
    return {k.rsplit(".", 1)[1]: v
            for k, v in counters("search.agg_lane_bins.").items()}


def delta(after, before):
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def drop_shard_sets():
    """What a refresh does to every resident set at once."""
    with spmd._SPMD_LOCK:
        sets = list(spmd._SHARD_SETS.values())
        spmd._SHARD_SETS.clear()
    for shard_set in sets:
        shard_set.release()


def resident_bytes():
    return TELEMETRY.device_memory.stats()["classes"].get(
        "spmd_shard_sets", {"live_bytes": 0})["live_bytes"]


def the_shard_set():
    (shard_set,) = spmd._SHARD_SETS.values()
    return shard_set


def with_hist(body, **hist):
    body = json.loads(json.dumps(body))
    body["aggs"]["by_hour"]["date_histogram"] = dict(
        {"field": "@timestamp"}, **hist)
    return body


def test_the_panel_on_the_miss_and_on_the_hit(served):
    """The panel over rows of unequal `card`: the first request of a
    shard set derives the hourly level's vector (a miss), every later
    one finds it (a hit), `terms(status)` reads the rank column in both
    (identity), and all of them answer what the host loop and the
    references answer."""
    from reference_impl import ref_date_histogram
    node, server, docs = served
    drop_shard_sets()
    assert resident_bytes() == 0
    before, seen = lane_bins(), []
    for n, minutes in enumerate([21, 22, 23, 24]):
        body = moved(BODY, minutes)
        resp = post(server, body)
        check_response(resp, docs, body)
        r = body["query"]["range"]["@timestamp"]
        assert {b["key"]: b["doc_count"]
                for b in resp["aggregations"]["by_hour"]["buckets"]} \
            == ref_date_histogram(
                [d["@timestamp"] for d in docs
                 if r["gte"] <= d["@timestamp"] < r["lt"]], fixed_ms=HOUR)
        assert delta(lane_bins(), before) == dict(
            {"miss": 1, "identity": n + 1}, **({"hit": n} if n else {}))
        mark = lane_bins()
        with spmd.force_host_loop():
            want = node.request("POST", "/logs/_search", body)
        # the host loop's rows find or derive their segments' own
        # vectors, counted by the same class: not this route's
        before = {k: v + lane_bins()[k] - mark[k] for k, v in before.items()}
        assert resp["aggregations"] == want["aggregations"]
        assert resp["hits"]["total"] == want["hits"]["total"]
        seen.append(the_shard_set().lane_bins.vectors[
            ("@timestamp", "date_histogram", HOUR, 0)])
    # one vector, the same array on the miss and on every hit: int32, a
    # row a row of the image and a lane a lane of the rank column,
    # sharded like it, on the device-memory ledger with the image
    assert all(v is seen[0] for v in seen)
    shard_set = the_shard_set()
    ranks = shard_set.seg_stack["numeric"]["@timestamp"]["val_ords"]
    assert seen[0].shape == ranks.shape and seen[0].dtype == np.int32
    assert seen[0].sharding == ranks.sharding
    assert resident_bytes() == shard_set.nbytes + seen[0].nbytes
    # the same integers in the same lanes as the gather a request made
    svc = node.indices.get("logs")
    for row, shard in enumerate(svc.shards):
        col = shard.engine.segments[0].numeric_dv["@timestamp"]
        hours = col.unique.astype(np.int64) // HOUR
        want = (hours - hours[0])[col.value_ords]
        got = np.asarray(seen[0][row])
        assert (got[:len(want)] == want).all() and (got[len(want):] == -1).all()
    stats = next(iter(node.request("GET", "/_nodes/stats")["nodes"]
                      .values()))["telemetry"]["metrics"]["counters"]
    assert {k for k in stats if k.startswith("search.agg_lane_bins.")} \
        == {f"search.agg_lane_bins.{k}"
            for k in ("hit", "miss", "identity", "evicted")}


def test_a_hit_builds_no_table(served, monkeypatch):
    """The rows' rank -> bucket tables are built for the derivation, one
    a row, and by no request that finds the vector."""
    from opensearch_tpu.search.aggs import engine
    node, server, docs = served
    built = []
    rank_table = engine._rank_table
    monkeypatch.setattr(engine, "_rank_table",
                        lambda b: built.append(len(b)) or rank_table(b))
    drop_shard_sets()
    check_response(post(server, moved(BODY, 26)), docs, moved(BODY, 26))
    assert len(built) == len(node.indices.get("logs").shards)
    for minutes in (27, 28):
        check_response(post(server, moved(BODY, minutes)), docs,
                       moved(BODY, minutes))
    assert len(built) == len(node.indices.get("logs").shards)


def test_another_bucketing_of_the_field_gets_its_own_vector(served):
    """Keyed by the bucketing's scalars, not by the field alone; and the
    memo is bounded: the least recently used vector goes, with its bytes."""
    from opensearch_tpu.search.aggs.lane_bins import MAX_LANE_BINS
    node, server, docs = served
    drop_shard_sets()
    check_response(post(server, moved(BODY, 31)), docs, moved(BODY, 31))
    shard_set = the_shard_set()
    one = next(iter(shard_set.lane_bins.vectors.values())).nbytes
    before = lane_bins()
    offsets = [10, 20, 30, 40][:MAX_LANE_BINS]
    for n, minutes in enumerate(offsets):
        body = with_hist(moved(BODY, 32 + n), fixed_interval="1h",
                         offset=f"+{minutes}m")
        for again in (False, True):
            check_response(post(server, moved(body, 7 * again)), docs,
                           moved(body, 7 * again), shift=-minutes * 60000)
        assert ("@timestamp", "date_histogram", HOUR, -minutes * 60000) \
            in shard_set.lane_bins.vectors
    # each offset missed once and hit once; the hourly vector without an
    # offset, the least recently used, made room for the last of them
    assert delta(lane_bins(), before) == {
        "miss": len(offsets), "hit": len(offsets), "evicted": 1,
        "identity": 2 * len(offsets)}
    assert len(shard_set.lane_bins.vectors) == MAX_LANE_BINS
    assert ("@timestamp", "date_histogram", HOUR, 0) \
        not in shard_set.lane_bins.vectors
    assert resident_bytes() == shard_set.nbytes + MAX_LANE_BINS * one


def test_a_dropped_shard_set_takes_its_vectors_along(served, notes,
                                                     monkeypatch):
    """The residency cache's eviction releases the set's image and its
    vectors from the device-memory ledger in one; the set that replaces
    it (a refresh) starts with none."""
    node, server, docs = served
    drop_shard_sets()
    post(server, moved(BODY, 41))
    old = the_shard_set()
    assert len(old.lane_bins.vectors) == 1 and resident_bytes() > old.nbytes
    monkeypatch.setattr(spmd, "_MAX_SHARD_SETS", 1)
    before = lane_bins()
    notes.request("POST", "/notes/_search",
                  {"query": {"match": {"body": "alpha"}}})
    new = the_shard_set()
    assert new is not old and not old.lane_bins.vectors
    assert resident_bytes() == new.nbytes
    assert delta(lane_bins(), before) == {"evicted": 1}
    post(server, moved(BODY, 42))       # evicts `new`, builds the set again
    assert delta(lane_bins(), before) == {"evicted": 1, "miss": 1,
                                          "identity": 1}
    assert resident_bytes() == the_shard_set().nbytes \
        + sum(v.nbytes for v in the_shard_set().lane_bins.vectors.values())


DAY = 86400000
# a row whose every day holds one distinct timestamp (its daily table is
# the identity) beside a row with two in one day (its table is not): by
# which of them holds more unique values than the other's table has
# entries (`u_pad` 8)
MIXED_ROWS = {
    "the-identity-row-is-the-narrower": [
        [T0, T0 + DAY, T0 + 2 * DAY],
        [T0 + 5, T0 + 7, T0 + 2 * DAY + 1, T0 + 3 * DAY]],
    "the-identity-row-is-the-wider": [
        [T0 + d * DAY for d in range(24)],
        [T0 + 3 * DAY + 5, T0 + 3 * DAY + 7, T0 + 17 * DAY]],
}
BY_DAY = {"size": 0, "aggs": {"by_day": {
    "date_histogram": {"field": "@timestamp", "fixed_interval": "1d"},
    "aggs": {"bytes": {"sum": {"field": "size"}}}}}}


def mixed_node(name, stamps):
    node = start_node({"http.port": 0, "node.name": name})[0]
    node.request("PUT", "/days", {"settings": {"number_of_shards": 2},
                                  "mappings": MAPPING})
    svc = node.indices.get("days")
    for s, shard in enumerate(svc.shards):
        b = SegmentBuilder(svc.mapper, "s0")
        for i, t in enumerate(stamps[s]):
            b.add(svc.mapper.parse_document(
                f"s{s}-{i}", {"@timestamp": t, "status": 200 + s,
                              "size": 10 * (i + 1)}))
        seg = b.seal()
        shard.engine.install_segments([seg], max_seq_no=seg.num_docs,
                                      local_checkpoint=seg.num_docs)
        shard._sync_reader()
    return node, svc


@pytest.mark.parametrize("stamps", list(MIXED_ROWS.values()),
                         ids=list(MIXED_ROWS))
def test_rows_that_differ_in_whether_their_table_is_the_identity(stamps):
    """The identity row takes its own table back, an entry a rank of its
    own column however narrow the other row's table, so the rows keep
    one structure, the request is the SPMD program's, and every day of
    both rows is counted as the host loop counts it."""
    node, svc = mixed_node("spmd-mixed", stamps)
    before, lanes = counters(), lane_bins()
    got = node.request("POST", "/days/_search", BY_DAY)
    after = counters()
    assert after["search.spmd_queries"] == before["search.spmd_queries"] + 1
    assert after["search.spmd_fallbacks"] == before["search.spmd_fallbacks"]
    assert delta(lane_bins(), lanes) == {"miss": 1}
    days = {}
    for row in stamps:
        for i, t in enumerate(row):
            c, b = days.get(t // DAY * DAY, (0, 0.0))
            days[t // DAY * DAY] = (c + 1, b + 10.0 * (i + 1))
    assert {b["key"]: (b["doc_count"], b["bytes"]["value"])
            for b in got["aggregations"]["by_day"]["buckets"]
            if b["doc_count"]} == days
    with spmd.force_host_loop():
        want = node.request("POST", "/days/_search", BY_DAY)
    assert got["aggregations"] == want["aggregations"]
    # the host loop's rows are programs of their own: the row whose
    # table is not the identity derived its segment's vector, the other
    # reads its rank column
    assert delta(lane_bins(), lanes) == {"miss": 2}


@pytest.mark.parametrize("stamps", list(MIXED_ROWS.values()),
                         ids=list(MIXED_ROWS))
def test_aligned_rows_build_their_own_tables_when_asked(stamps):
    """`align_agg_plans` on rows compiled across rows: none carries a
    table among its inputs, the identity row names BINS_TABLE like the
    other, and the table it builds for the derivation is the identity
    over its own ranks, padded like any other."""
    from opensearch_tpu.parallel.distributed import align_agg_plans
    from opensearch_tpu.search.aggs.engine import BINS_TABLE, compile_aggs
    from opensearch_tpu.search.aggs.parse import parse_aggs
    from opensearch_tpu.search.compile import Compiler
    node, svc = mixed_node("spmd-mixed-eager", stamps)
    rows = []
    for shard in svc.shards:
        reader = shard.reader
        (seg,), ((_, meta),) = reader.segments, reader.device
        rows.append(compile_aggs(
            parse_aggs(BY_DAY["aggs"]), svc.mapper, seg, meta,
            Compiler(svc.mapper, reader.stats()), allow_fused=False))
    align_agg_plans(rows)
    for (plan,), row in zip(rows, stamps):
        assert plan.static[3] == BINS_TABLE and "table" not in plan.inputs
        table = plan.table_of()
        assert table.shape == (max(8, 1 << (len(set(row)) - 1).bit_length()),)
    identity = rows[0][0].table_of()
    n = len(stamps[0])
    assert (identity[:n] == np.arange(n)).all() and (identity[n:] == -1).all()


def test_a_range_bucket_keeps_its_table_and_the_memo_its_vectors(served):
    """A `range`/`date_range` bucket's bounds may move with every request
    (`now`-relative ones do), so it brings its table as it did and is no
    entry of the shard set's memo: no vector derived, none dropped,
    whatever the bounds, and the answer is the host loop's."""
    node, server, docs = served
    drop_shard_sets()
    post(server, moved(BODY, 61))
    shard_set = the_shard_set()
    keys, before = list(shard_set.lane_bins.vectors), lane_bins()
    for n in range(6):
        body = {"size": 0, "aggs": {"spans": {
            "date_range": {"field": "@timestamp", "ranges": [
                {"from": T0 + (n + i) * HOUR + 1,
                 "to": T0 + (n + i + 9) * HOUR} for i in range(5)]},
            "aggs": {"bytes": {"sum": {"field": "size"}}}}}}
        got = post(server, body)
        for b in got["aggregations"]["spans"]["buckets"]:
            sel = [d for d in docs if b["from"] <= d["@timestamp"] < b["to"]]
            assert (b["doc_count"], b["bytes"]["value"]) \
                == (len(sel), float(sum(d["size"] for d in sel)))
        with spmd.force_host_loop():
            want = node.request("POST", "/logs/_search", body)
        assert got["aggregations"] == want["aggregations"]
    assert the_shard_set() is shard_set
    assert list(shard_set.lane_bins.vectors) == keys
    assert delta(lane_bins(), before) == {}


def dispatched(node, server, body):
    """The fingerprint of the one program a request dispatched."""
    TELEMETRY.tracer.spans.clear()
    post(server, body)
    for _ in range(200):
        ring = node.request("GET", "/_telemetry/spans")["spans"]
        if any(s["name"] == "http.request" for s in ring):
            break
        time.sleep(0.01)
    (fp,) = [s["attributes"]["fingerprint"] for s in ring
             if s["name"] == "dispatch"]
    return fp


def lane_gathers(fp, lanes):
    """The lowered program's `gather`s that produce a value a lane of a
    row, and its flat request inputs' sizes in bytes."""
    import re
    import jax
    fn, structs = TELEMETRY.kernels._lowerable[fp]
    text = fn.lower(*structs).as_text()
    results = re.findall(r'"?stablehlo\.gather"?\(.*-> tensor<([0-9x]+)x\w+>',
                         text)
    assert results      # top-k's gathers: the pattern finds gathers
    wide = [r for r in results if str(lanes) in r.split("x")]
    sizes = [int(np.prod(s.shape)) * s.dtype.itemsize
             for s in jax.tree_util.tree_leaves(structs[1])]
    return wide, sizes


def test_the_served_program_gathers_no_lane_and_takes_no_table(
        served, monkeypatch):
    """The lowered `jit_spmd_query_phase` of the panel holds no gather
    with a result a lane (the two 16.8M-lane passes of the benchmark's
    four-chip cell), and a request's flat inputs are the range's bounds
    alone; the same panel through the tables (what a request did before)
    holds both, so the reading can tell them apart."""
    from opensearch_tpu.parallel import distributed
    from opensearch_tpu.search.aggs.engine import BINS_TABLE
    node, server, docs = served
    drop_shard_sets()
    fp = dispatched(node, server, moved(BODY, 51))
    lanes = the_shard_set().seg_stack["numeric"]["@timestamp"][
        "val_ords"].shape[-1]
    wide, sizes = lane_gathers(fp, lanes)
    assert wide == [] and max(sizes) <= 64
    # the hourly level through its table; `terms(status)` stays the rank
    def through_tables(searcher, shard_set, per_shard):
        def walk(plans):
            for p in plans:
                if p.table_of is not None and p.static[3] == BINS_TABLE:
                    p.inputs = dict(p.inputs, table=p.table_of())
                walk(p.children)
        for plans in per_shard:
            walk(plans)
        return []
    monkeypatch.setattr(distributed, "resident_lane_bins", through_tables)
    fp_tables = dispatched(node, server, moved(BODY, 52))
    assert fp_tables != fp
    wide, sizes = lane_gathers(fp_tables, lanes)
    assert len(wide) == 1 and max(sizes) > 64
