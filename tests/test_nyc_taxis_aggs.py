"""The one-chip aggregation route as OpenSearch Benchmark's `nyc_taxis`
workload drives it (ISSUE 35): `distance_amount_agg` and
`date_histogram_agg`, bodies verbatim, served over the real socket
against the plain reference on a corpus with the out-of-filter distance
tail and negative amounts; a `dd/MM/yyyy` range whose `lte` is the end
of its day; BASELINE.json config 2's shape; the index's
`index.requests.cache.enable: false` and `?request_cache=` honoured on
the envelope, the host loop and the SPMD route; the stages of
`jit_agg_env`, its `dispatch` span's shape and the counters; and (ISSUE
36) the lane -> bin vector of a `histogram`/`date_histogram` level kept
resident beside its segment's image on the one-chip routes."""

import calendar
import http.client
import json
import math
import re
import time

import numpy as np
import pytest

from opensearch_tpu.index.mapper import parse_date_millis
from opensearch_tpu.index.segment import SegmentBuilder
from opensearch_tpu.indices.request_cache import REQUEST_CACHE
from opensearch_tpu.launcher import start_node
from opensearch_tpu.telemetry import TELEMETRY

from reference_impl import (ref_day_counts, ref_histogram_stats,
                            ref_terms_avg)

RTOL = 1e-6
FMT = "yyyy-MM-dd HH:mm:ss"
MAPPING = {"properties": {
    "pickup_datetime": {"type": "date", "format": FMT},
    "dropoff_datetime": {"type": "date", "format": FMT},
    "trip_distance": {"type": "scaled_float", "scaling_factor": 100},
    "total_amount": {"type": "scaled_float", "scaling_factor": 100},
    "tip_amount": {"type": "scaled_float", "scaling_factor": 100},
    "passenger_count": {"type": "integer"},
    "payment_type": {"type": "keyword"},
    "pickup_location": {"type": "geo_point"},
    "vendor_name": {"type": "text"}}}
SOURCE_SETTINGS = {"index": {"number_of_shards": 1,
                             "number_of_replicas": 0,
                             "requests.cache.enable": False}}
T0 = calendar.timegm((2015, 1, 1, 0, 0, 0))

DISTANCE = {
    "size": 0,
    "query": {"bool": {"filter": {"range": {"trip_distance": {
        "lt": 50, "gte": 0}}}}},
    "aggs": {"distance_histo": {
        "histogram": {"field": "trip_distance", "interval": 1},
        "aggs": {"total_amount_stats": {
            "stats": {"field": "total_amount"}}}}}}
DATES = {
    "size": 0,
    "query": {"range": {"dropoff_datetime": {
        "gte": "01/01/2015", "lte": "21/01/2015",
        "format": "dd/MM/yyyy"}}},
    "aggs": {"dropoffs_over_time": {"date_histogram": {
        "field": "dropoff_datetime", "calendar_interval": "day"}}}}
# BASELINE.json config 2: bool query + filter + terms aggregation
BASELINE_2 = {
    "size": 0,
    "query": {"bool": {"filter": [
        {"range": {"dropoff_datetime": {
            "gte": "01/01/2015", "lte": "21/01/2015",
            "format": "dd/MM/yyyy"}}},
        {"range": {"total_amount": {"gte": 5, "lt": 15}}}]}},
    "aggs": {"by_payment": {
        "terms": {"field": "payment_type"},
        "aggs": {"tip": {"avg": {"field": "tip_amount"}}}}}}


def stamp(seconds: int) -> str:
    return time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime(seconds))


def rides(n: int, seed: int):
    """Rides over 40 days from 2015-01-01: distances with zeros, a gap
    of empty buckets and a tail past the workload's `lt 50`; amounts
    with negatives; dropoffs on both sides of the range's last second."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        pickup = T0 + int(rng.integers(0, 40 * 86400))
        miles = float(np.round(np.exp(rng.normal(0.5, 0.8)), 2))
        if i % 97 == 0:
            miles = 0.0
        elif i % 53 == 0:
            miles = float(np.round(rng.uniform(50, 9000), 2))
        elif i % 41 == 0:
            miles = 37.5 + (i % 3)      # far from the body: empties between
        amount = float(np.round(2.5 + 2.5 * miles + rng.uniform(0, 9), 2))
        if i % 61 == 0:
            amount = -amount
        out.append({
            "pickup_datetime": stamp(pickup),
            "dropoff_datetime": stamp(pickup + int(rng.integers(60, 7200))),
            "trip_distance": miles, "total_amount": amount,
            "tip_amount": float(np.round(rng.uniform(0, 6), 2)),
            "passenger_count": int(rng.integers(1, 7)),
            "payment_type": str(rng.choice(["1", "1", "1", "2", "2", "3"]))})
    # the range's edges, to the second
    for j, at in enumerate(("2015-01-21 23:59:59", "2015-01-22 00:00:00",
                            "2015-01-01 00:00:00")):
        out.append({**out[j], "pickup_datetime": "2015-01-01 00:00:00",
                    "dropoff_datetime": at})
    return out


def install(node, index: str, docs, settings):
    node.request("PUT", f"/{index}", {"settings": settings,
                                      "mappings": MAPPING})
    svc = node.indices.get(index)
    for s, shard in enumerate(svc.shards):
        b = SegmentBuilder(svc.mapper, "s0")
        for i, doc in enumerate(docs[s::len(svc.shards)]):
            b.add(svc.mapper.parse_document(f"s{s}-{i}", doc))
        seg = b.seal()
        shard.engine.install_segments([seg], max_seq_no=seg.num_docs,
                                      local_checkpoint=seg.num_docs)
        shard._sync_reader()


@pytest.fixture(scope="module")
def served():
    node, server = start_node({"http.port": 0, "node.name": "taxis"})
    docs = rides(3000, 35)
    install(node, "nyc_taxis", docs, SOURCE_SETTINGS)
    install(node, "taxis_cached", docs[:400],
            {"index": {"number_of_shards": 1}})
    install(node, "taxis_rows", docs[:800],
            {"index": {"number_of_shards": 2,
                       "requests.cache.enable": False}})
    yield node, server, docs
    server.close()


def post(server, index, body, query=""):
    conn = http.client.HTTPConnection("127.0.0.1", server.port)
    try:
        conn.request("POST", f"/{index}/_search{query}", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        assert resp.status == 200, raw[:400]
        return json.loads(raw)
    finally:
        conn.close()


def counters(*prefixes):
    return {k: v for k, v in
            TELEMETRY.metrics.to_dict()["counters"].items()
            if k.startswith(prefixes)}


def ms(text: str) -> int:
    return calendar.timegm(time.strptime(text, "%Y-%m-%d %H:%M:%S")) * 1000


def close(got, want):
    return got is not None and math.isclose(got, want, rel_tol=RTOL,
                                            abs_tol=1e-9)


# ------------------------------------------------------ the operations

def check_distance(resp, docs):
    total, want = ref_histogram_stats(docs, "trip_distance", 1.0, 0.0,
                                      50.0, "total_amount")
    assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
    got = resp["aggregations"]["distance_histo"]["buckets"]
    assert [b["key"] for b in got] == [k for k, _, _ in want]
    assert any(n == 0 for _, n, _ in want)      # empties between
    assert want[-1][0] < 50 and len(want) <= 50
    for b, (key, n, stats) in zip(got, want):
        st = b["total_amount_stats"]
        assert b["doc_count"] == n == st["count"]
        if stats is None:
            assert (st["min"], st["max"], st["avg"], st["sum"]) \
                == (None, None, None, 0)
            continue
        for name, ref in zip(("min", "max", "avg", "sum"), stats[1:]):
            assert close(st[name], ref), (key, name, st[name], ref)
    assert min(st["min"] for st in
               (b["total_amount_stats"] for b in got)
               if st["min"] is not None) < 0     # the negative amounts


def check_dates(resp, docs):
    total, want = ref_day_counts(
        [ms(d["dropoff_datetime"]) for d in docs],
        ms("2015-01-01 00:00:00"), ms("2015-01-21 23:59:59") + 999)
    assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
    got = resp["aggregations"]["dropoffs_over_time"]["buckets"]
    assert [(b["key"], b["doc_count"]) for b in got] == want
    assert len(want) == 21


def check_baseline_2(resp, docs):
    lo, hi = ms("2015-01-01 00:00:00"), ms("2015-01-21 23:59:59") + 999
    total, want = ref_terms_avg(
        docs, lambda d: lo <= ms(d["dropoff_datetime"]) <= hi
        and 5 <= round(d["total_amount"] * 100) / 100 < 15,
        "payment_type", "tip_amount")
    assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
    got = resp["aggregations"]["by_payment"]["buckets"]
    assert [(b["key"], b["doc_count"]) for b in got] \
        == [(k, n) for k, n, _ in want]
    for b, (_, _, avg) in zip(got, want):
        assert close(b["tip"]["value"], avg)


OPERATIONS = {"distance_amount_agg": (DISTANCE, check_distance),
              "date_histogram_agg": (DATES, check_dates),
              "baseline_config_2": (BASELINE_2, check_baseline_2)}


@pytest.mark.parametrize("op", sorted(OPERATIONS))
def test_an_operation_served_over_the_socket_matches_the_reference(
        served, op):
    node, server, docs = served
    body, check = OPERATIONS[op]
    before = counters("search.agg_env.")
    resp = post(server, "nyc_taxis", body)
    assert resp["_shards"]["failed"] == 0 and resp["timed_out"] is False
    check(resp, docs)
    # an index of one shard takes the B=1 envelope: jit_agg_env
    after = counters("search.agg_env.")
    assert after["search.agg_env.queries"] \
        == before.get("search.agg_env.queries", 0) + 1


# ------------------------------------------------------- date bounds

DAY = 86_400_000
JAN21 = ms("2015-01-21 00:00:00")


@pytest.mark.parametrize("text,fmt,round_up,want", [
    ("21/01/2015", "dd/MM/yyyy", False, JAN21),
    ("21/01/2015", "dd/MM/yyyy", True, JAN21 + DAY - 1),
    ("2015-01-21 07:08:09", FMT, False, JAN21 + 25_689_000),
    ("2015-01-21 07:08:09", FMT, True, JAN21 + 25_689_999),
    ("01/2015", "MM/yyyy", True, ms("2015-02-01 00:00:00") - 1),
    ("2015", "yyyy", True, ms("2016-01-01 00:00:00") - 1),
    ("21/01/2015", "yyyy-MM-dd||dd/MM/yyyy", True, JAN21 + DAY - 1),
    ("2015-01-21", None, False, JAN21),
    ("2015-01-21", None, True, JAN21 + DAY - 1),
    ("2015-01-21T07", None, True, JAN21 + 8 * 3_600_000 - 1),
    ("2015-01-21T07:08:09.123Z", None, True, JAN21 + 25_689_123),
    ("2015-01", "strict_date_optional_time", True,
     ms("2015-02-01 00:00:00") - 1),
    (str(JAN21), "epoch_millis", True, JAN21),
])
def test_a_date_is_parsed_by_its_format_and_rounds_up_to_its_units_end(
        text, fmt, round_up, want):
    assert parse_date_millis(text, fmt, round_up=round_up) == want


@pytest.mark.parametrize("bound,keeps_last_second,keeps_midnight", [
    ({"lte": "21/01/2015"}, True, False),   # the end of the day
    ({"lt": "21/01/2015"}, False, False),   # its start
    ({"lt": "22/01/2015"}, True, False),
    ({"lte": "22/01/2015"}, True, True),
    ({"gt": "21/01/2015"}, False, True),    # after the day's end
    ({"gte": "21/01/2015"}, True, True),
])
def test_a_range_bound_in_its_own_format_rounds_as_upstream(
        served, bound, keeps_last_second, keeps_midnight):
    node, server, docs = served
    body = {"size": 0, "track_total_hits": True,
            "query": {"range": {"dropoff_datetime": {
                **bound, "format": "dd/MM/yyyy"}}}}
    total = post(server, "nyc_taxis", body)["hits"]["total"]["value"]

    def kept(t):
        lo = hi = None
        for op, v in bound.items():
            d = calendar.timegm(time.strptime(v, "%d/%m/%Y")) * 1000
            if op == "lte":
                hi = d + DAY - 1
            elif op == "lt":
                hi = d - 1
            elif op == "gt":
                lo = d + DAY
            else:
                lo = d
        return (lo is None or t >= lo) and (hi is None or t <= hi)
    times = [ms(d["dropoff_datetime"]) for d in docs]
    assert total == sum(kept(t) for t in times)
    assert kept(JAN21 + DAY - 1000) is keeps_last_second
    assert kept(JAN21 + DAY) is keeps_midnight


# ---------------------------------------------------- request cache

HOST_LOOP = {**DISTANCE, "track_total_hits": True}  # not an envelope body


@pytest.mark.parametrize("route,index,body,ran", [
    ("envelope", "nyc_taxis", DISTANCE, "search.agg_env.queries"),
    ("host_loop", "nyc_taxis", HOST_LOOP, "search.agg_bins.level.resident"),
    ("spmd", "taxis_rows", DISTANCE, "search.spmd_queries"),
])
def test_an_index_that_turns_its_request_cache_off_runs_every_request(
        served, route, index, body, ran):
    node, server, docs = served
    prefixes = ("search.request_cache.", "search.agg_env.",
                "search.agg_bins.", "search.spmd_queries")
    post(server, index, body)                   # whatever compiles
    stats0, c0 = REQUEST_CACHE.stats(), counters(*prefixes)
    first = post(server, index, body)
    second = post(server, index, body)
    stats1, c1 = REQUEST_CACHE.stats(), counters(*prefixes)
    assert second["aggregations"] == first["aggregations"]
    # nothing looked up, nothing stored, and the second one ran too
    assert stats1 == stats0
    assert c1[ran] - c0.get(ran, 0) == 2
    assert c1["search.request_cache.bypassed"] \
        - c0.get("search.request_cache.bypassed", 0) == 2
    if route == "envelope":
        assert "search.spmd_queries" not in c1 \
            or c1["search.spmd_queries"] == c0.get("search.spmd_queries", 0)
    # the request's word beats the index's: cached, then served from it
    post(server, index, body, "?request_cache=true")
    h0 = REQUEST_CACHE.stats()["hit_count"]
    c2 = counters(*prefixes)
    third = post(server, index, body, "?request_cache=true")
    assert REQUEST_CACHE.stats()["hit_count"] == h0 + 1
    assert counters(*prefixes) == c2            # nothing ran, none bypassed
    assert third["aggregations"] == first["aggregations"]


def test_a_request_may_turn_the_cache_off_and_the_setting_is_dynamic(
        served):
    node, server, docs = served
    post(server, "taxis_cached", DATES)
    h0 = REQUEST_CACHE.stats()["hit_count"]
    post(server, "taxis_cached", DATES)         # the default: cached
    assert REQUEST_CACHE.stats()["hit_count"] == h0 + 1
    b0 = counters("search.request_cache.").get(
        "search.request_cache.bypassed", 0)
    post(server, "taxis_cached", DATES, "?request_cache=false")
    assert REQUEST_CACHE.stats()["hit_count"] == h0 + 1
    node.request("PUT", "/taxis_cached/_settings",
                 {"index.requests.cache.enable": False})
    post(server, "taxis_cached", DATES)
    assert REQUEST_CACHE.stats()["hit_count"] == h0 + 1
    assert counters("search.request_cache.")[
        "search.request_cache.bypassed"] == b0 + 2
    node.request("PUT", "/taxis_cached/_settings",
                 {"index": {"requests": {"cache": {"enable": True}}}})
    post(server, "taxis_cached", DATES)
    assert REQUEST_CACHE.stats()["hit_count"] == h0 + 2
    # a body with hits is never cached, so never counted as bypassed
    post(server, "nyc_taxis", {"size": 3, "query": {"match_all": {}}})
    assert counters("search.request_cache.")[
        "search.request_cache.bypassed"] == b0 + 2


# ------------------------------------------- stages, shape, counters

def test_the_agg_envelope_names_its_stages_its_shape_and_its_levels(
        served):
    node, server, docs = served
    TELEMETRY.tracer.spans.clear()
    before = counters("search.agg_bins.level.")
    post(server, "nyc_taxis", DISTANCE)
    post(server, "nyc_taxis", DATES)
    after = counters("search.agg_bins.level.")
    # histogram > stats reads its segment's resident lane -> bin vector
    # (ISSUE 36: no table gathered through a request); a bare
    # date_histogram of few buckets at this size closes over its lane
    # bitmasks
    assert after["search.agg_bins.level.resident"] \
        == before.get("search.agg_bins.level.resident", 0) + 1
    assert after.get("search.agg_bins.level.table", 0) \
        == before.get("search.agg_bins.level.table", 0)
    assert after["search.agg_bins.level.bits"] \
        == before.get("search.agg_bins.level.bits", 0) + 1
    post(server, "nyc_taxis", {"size": 0, "aggs": {"p": {"terms": {
        "field": "passenger_count"}}}})
    assert counters("search.agg_bins.level.")[
        "search.agg_bins.level.rank"] \
        == before.get("search.agg_bins.level.rank", 0) + 1
    census = node.request("GET", "/_telemetry/kernels", scopes="true")[
        "kernels"]["census"]["executables"]
    mine = [e for e in census if e["family"] == "agg_env"
            and "_error" not in e["scopes"]]
    assert mine
    want = {"filter_mask", "agg_bins", "eligible_total", "pack_row",
            "unpack_envelope"}
    assert any(want <= {s.lstrip("~") for s in e["scopes"].values()}
               for e in mine)
    for _ in range(200):
        ring = node.request("GET", "/_telemetry/spans")["spans"]
        shapes = [s["attributes"]["shape"] for s in ring
                  if s["name"] == "dispatch"
                  and s["attributes"].get("family") == "agg_env"]
        if len(shapes) >= 3:
            break
        time.sleep(0.01)
    assert len(shapes) >= 3
    assert all(re.fullmatch(r"b1/d\d+/bins\d+", s) for s in shapes)
    stats = next(iter(node.request("GET", "/_nodes/stats")["nodes"]
                      .values()))["telemetry"]["metrics"]["counters"]
    for name in ("search.agg_env.queries", "search.agg_bins.level.rank",
                 "search.agg_bins.level.resident",
                 "search.agg_bins.level.bits",
                 "search.request_cache.bypassed"):
        assert stats[name] >= 1


# ------------------- resident lane -> bin vectors on one chip (ISSUE 36)
#
# A `histogram`/`date_histogram` level's lane -> bin vector is derived
# once a (segment image, field, bucketing), kept beside that image
# (`ShardReader._lane_bins`, search/aggs/lane_bins.py) and read by the
# envelope's and the host loop's programs as `seg["lane_bins"][slot]`;
# no table is gathered through, built, packed or uploaded a request.

LANES = ("search.agg_lane_bins.", "search.agg_bins.level.",
         "search.agg_env.queries", "search.xla_cache_miss")


def moved(after, before):
    return {k.replace("search.", ""): after[k] - before.get(k, 0)
            for k in after if after[k] != before.get(k, 0)}


def distance(interval, **extra):
    body = json.loads(json.dumps(DISTANCE))
    body["aggs"]["distance_histo"]["histogram"]["interval"] = interval
    return {**body, **extra}


def by_day(unit="day", lte="21/01/2015"):
    """`date_histogram_agg` with a sub-aggregation, which keeps the level
    off the fused root-leaf kind at this size."""
    body = json.loads(json.dumps(DATES))
    body["query"]["range"]["dropoff_datetime"]["lte"] = lte
    body["aggs"]["dropoffs_over_time"] = {
        "date_histogram": {"field": "dropoff_datetime",
                           "calendar_interval": unit},
        "aggs": {"paid": {"sum": {"field": "total_amount"}}}}
    return body


def check_distance_at(resp, docs, interval):
    total, want = ref_histogram_stats(docs, "trip_distance", float(interval),
                                      0.0, 50.0, "total_amount")
    assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
    got = resp["aggregations"]["distance_histo"]["buckets"]
    assert [b["key"] for b in got] == [k for k, _, _ in want]
    for b, (key, n, stats) in zip(got, want):
        st = b["total_amount_stats"]
        assert b["doc_count"] == n == st["count"]
        for name, ref in zip(("min", "max", "avg", "sum"),
                             stats[1:] if stats else ()):
            assert close(st[name], ref), (key, name, st[name], ref)


def check_by_day(resp, docs):
    lo, hi = ms("2015-01-01 00:00:00"), ms("2015-01-21 23:59:59") + 999
    total, want = ref_day_counts(
        [ms(d["dropoff_datetime"]) for d in docs], lo, hi)
    assert resp["hits"]["total"] == {"value": total, "relation": "eq"}
    got = resp["aggregations"]["dropoffs_over_time"]["buckets"]
    assert [(b["key"], b["doc_count"]) for b in got] == want
    for b in got:
        paid = math.fsum(round(d["total_amount"] * 100) / 100 for d in docs
                         if b["key"] <= ms(d["dropoff_datetime"])
                         < b["key"] + DAY
                         and lo <= ms(d["dropoff_datetime"]) <= hi)
        assert close(b["paid"]["value"], paid)


def put_docs(node, index, docs, start=0):
    lines = []
    for i, doc in enumerate(docs, start):
        lines += [json.dumps({"index": {"_index": index, "_id": f"r{i}"}}),
                  json.dumps(doc)]
    resp = node.request("POST", "/_bulk", "\n".join(lines) + "\n")
    assert resp["errors"] is False
    node.request("POST", f"/{index}/_refresh")


@pytest.fixture
def live():
    """A node of its own with a writable one-shard index `t`: its
    segments' memos start empty."""
    from opensearch_tpu.node import Node
    node = Node()
    node.request("PUT", "/t", {"settings": SOURCE_SETTINGS,
                               "mappings": MAPPING})
    docs = rides(240, 36)
    put_docs(node, "t", docs)
    return node, docs


def search(node, body, index="t"):
    resp = node.request("POST", f"/{index}/_search", body)
    assert resp["_status"] == 200 and resp["_shards"]["failed"] == 0
    return resp


def the_reader(node, index="t"):
    (shard,) = node.indices.get(index).shards
    return shard.reader


def memos(reader):
    return [memo for _, memo in reader._lane_bins.values()]


def corpus_bytes():
    import gc
    gc.collect()        # readers of nodes other tests dropped
    return TELEMETRY.device_memory.stats()["classes"]["corpus_columns"][
        "live_bytes"]


@pytest.mark.parametrize("route", ["envelope", "host_loop"])
def test_a_level_is_derived_once_and_found_by_every_request_after(
        live, route):
    """A miss, then hits: every answer is the reference's, the level is
    counted `resident` a request and `table` never, one vector is held
    (int32, a lane a lane of the rank column, in the corpus-columns
    gauge with the image), and the second request compiles nothing."""
    node, docs = live
    extra = {"track_total_hits": True} if route == "host_loop" else {}
    body = distance(2, **extra)
    reader = the_reader(node)
    (memo,) = memos(reader)
    image = corpus_bytes()
    assert memo.nbytes == 0 and reader.device_bytes == sum(
        reader._seg_bytes.values())
    seen = []
    for n in range(3):
        before = counters(*LANES)
        resp = search(node, body)
        check_distance_at(resp, docs, 2)
        got = moved(counters(*LANES), before)
        compiles = got.pop("xla_cache_miss", 0)
        want = {"agg_lane_bins.hit" if n else "agg_lane_bins.miss": 1,
                "agg_bins.level.resident": 1}
        if route == "envelope":
            want["agg_env.queries"] = 1
        assert got == want
        # the derive program and the served one compile with the first
        # request of a body (unless another test's index had their
        # shapes); the served executable is the same on the miss and on
        # the hit: the second request compiles nothing
        assert compiles == 0 or n != 1
        (vec,) = memo.vectors.values()
        seen.append(vec)
    assert all(v is seen[0] for v in seen)
    (seg,), ((arrays, meta),) = reader.snapshot()
    col = seg.numeric_dv["trip_distance"]
    ranks = arrays["numeric"]["trip_distance"]["val_ords"]
    assert seen[0].shape == ranks.shape and seen[0].dtype == np.int32
    # the same integers in the same lanes as the gather a request made
    want = np.floor(col.unique / 2).astype(np.int64)
    want = (want - want[0])[col.value_ords]
    got = np.asarray(seen[0])
    assert (got[:len(want)] == want).all() and (got[len(want):] == -1).all()
    assert memo.nbytes == seen[0].nbytes
    assert corpus_bytes() == image + seen[0].nbytes
    # the other route's program finds the same vector
    before = counters(*LANES)
    search(node, distance(2, **({} if extra else
                                {"track_total_hits": True})))
    assert moved(counters("search.agg_lane_bins."), before) \
        == {"agg_lane_bins.hit": 1}


@pytest.mark.parametrize("kind", ["interval", "calendar_interval"])
def test_another_bucketing_is_another_vector_and_a_fifth_evicts_the_lru(
        live, kind):
    from opensearch_tpu.search.aggs.lane_bins import MAX_LANE_BINS
    node, docs = live
    if kind == "interval":
        bodies = [distance(i) for i in (2, 3, 4, 5, 7)]
    else:
        bodies = [by_day(u) for u in ("day", "week", "month", "quarter",
                                      "year")]
    assert len(bodies) == MAX_LANE_BINS + 1
    (memo,) = memos(the_reader(node))
    image = corpus_bytes()
    before = counters("search.agg_lane_bins.")
    for n, body in enumerate(bodies[:MAX_LANE_BINS]):
        for _ in range(2):                      # a miss, then a hit
            resp = search(node, body)
        (check_distance_at(resp, docs, (2, 3, 4, 5)[n])
         if kind == "interval" else n or check_by_day(resp, docs))
        assert len(memo.vectors) == n + 1
    first, second = list(memo.vectors)[:2]
    one = next(iter(memo.vectors.values())).nbytes
    assert memo.nbytes == MAX_LANE_BINS * one
    search(node, bodies[0])                     # the first is used again
    search(node, bodies[-1])                    # a fifth: the second goes
    assert moved(counters("search.agg_lane_bins."), before) == {
        "agg_lane_bins.miss": MAX_LANE_BINS + 1,
        "agg_lane_bins.hit": MAX_LANE_BINS + 1,
        "agg_lane_bins.evicted": 1}
    assert len(memo.vectors) == MAX_LANE_BINS
    assert first in memo.vectors and second not in memo.vectors
    assert memo.nbytes == MAX_LANE_BINS * one
    assert corpus_bytes() == image + MAX_LANE_BINS * one


def reupload(node, docs):
    """A genuinely different segment under the id the reader holds."""
    reader = the_reader(node)
    svc = node.indices.get("t")
    b = SegmentBuilder(svc.mapper, reader.segments[0].seg_id)
    for i, doc in enumerate(docs[:100]):
        b.add(svc.mapper.parse_document(f"r{i}", doc))
    reader.update_segment(b.seal())
    return docs[:100]


def remove(node, docs):
    reader = the_reader(node)
    reader.remove_segment(reader.segments[0].seg_id)
    return []


def merge(node, docs):
    more = rides(60, 37)
    put_docs(node, "t", more, start=len(docs))
    assert len(the_reader(node).segments) == 2
    assert node.request("POST", "/t/_forcemerge")["_status"] == 200
    assert len(the_reader(node).segments) == 1
    return docs + more


@pytest.mark.parametrize("drop", [remove, merge, reupload],
                         ids=["remove_segment", "merge", "re-upload"])
def test_the_vectors_leave_with_their_segments_image(live, drop):
    """Derived from the image's rank column, so gone with it: out of the
    memo, out of the device-memory gauge, counted evicted; the image
    that takes its place starts with none and answers for its own
    documents."""
    node, docs = live
    reader = the_reader(node)
    search(node, distance(2))
    search(node, by_day())
    (old,) = memos(reader)
    assert len(old.vectors) == 2 and old.nbytes > 0
    assert reader.device_bytes \
        == sum(reader._seg_bytes.values()) + old.nbytes
    before = counters("search.agg_lane_bins.")
    docs = drop(node, docs)
    assert moved(counters("search.agg_lane_bins."), before) \
        == {"agg_lane_bins.evicted": 2}
    assert not old.vectors and old.nbytes == 0
    assert all(memo is not old and not memo.vectors
               for memo in memos(reader))
    assert set(reader._lane_bins) == set(reader._seg_bytes) \
        == {seg.seg_id for seg in reader.segments}
    assert reader.device_bytes == sum(reader._seg_bytes.values())
    if docs:
        before = counters("search.agg_lane_bins.")
        check_distance_at(search(node, distance(2)), docs, 2)
        assert moved(counters("search.agg_lane_bins."), before) \
            == {"agg_lane_bins.miss": 1}


def test_a_delete_leaves_the_vector_and_changes_the_counts(live):
    """A delete moves `live`, not the bins: the same vector, a hit, and
    one ride fewer in its bucket."""
    node, docs = live
    first = search(node, distance(2))
    (memo,) = memos(the_reader(node))
    (vec,) = memo.vectors.values()
    gone = next(i for i, d in enumerate(docs)
                if 2 <= d["trip_distance"] < 4 and d["total_amount"] > 0)
    assert node.request("DELETE", f"/t/_doc/r{gone}")["result"] == "deleted"
    node.request("POST", "/t/_refresh")
    before = counters("search.agg_lane_bins.")
    resp = search(node, distance(2))
    assert moved(counters("search.agg_lane_bins."), before) \
        == {"agg_lane_bins.hit": 1}
    assert list(memo.vectors.values()) == [vec] \
        and memos(the_reader(node)) == [memo]
    check_distance_at(resp, docs[:gone] + docs[gone + 1:], 2)

    def counts(r):
        return {b["key"]: b["doc_count"]
                for b in r["aggregations"]["distance_histo"]["buckets"]}
    was, now = counts(first), counts(resp)
    assert now[2.0] == was[2.0] - 1
    assert {k: v for k, v in now.items() if k != 2.0} \
        == {k: v for k, v in was.items() if k != 2.0}


@pytest.mark.parametrize("route", ["envelope", "host_loop"])
def test_every_segment_of_an_index_keeps_its_own_vector(live, route):
    node, docs = live
    more = rides(90, 38)
    put_docs(node, "t", more, start=len(docs))
    reader = the_reader(node)
    assert len(reader.segments) == 2
    # two segments are two rows to the SPMD route, which would take a
    # body the envelope does not
    from contextlib import nullcontext
    from opensearch_tpu.search import spmd
    extra, way = ({"track_total_hits": True}, spmd.force_host_loop) \
        if route == "host_loop" else ({}, nullcontext)
    for n in range(2):
        before = counters(*LANES)
        for body, check in ((distance(3, **extra),
                             lambda r: check_distance_at(r, docs + more, 3)),
                            (dict(by_day(), **extra),
                             lambda r: check_by_day(r, docs + more))):
            with way():
                check(search(node, body))
        got = moved(counters(*LANES), before)
        got.pop("xla_cache_miss", None)
        got.pop("agg_env.queries", None)
        # two bodies x two segments: a program, a level and a vector each
        assert got == {"agg_lane_bins.hit" if n else "agg_lane_bins.miss": 4,
                       "agg_bins.level.resident": 4}
    assert [len(memo.vectors) for memo in memos(reader)] == [2, 2]
    assert [v.shape for memo in memos(reader)
            for v in memo.vectors.values()] \
        == [arrays["numeric"][f]["val_ords"].shape
            for arrays, _ in reader.device
            for f in ("trip_distance", "dropoff_datetime")]


def test_a_range_bucket_rides_its_table_and_an_identity_terms_its_ranks(
        live):
    """What the choice rests on is a static fact of the plan: a `range`
    bucket's bounds may move with every request, so it brings its table
    (BINS_TABLE) and is no entry of the memo; `terms` on a numeric
    column reads the rank column itself (BINS_RANK)."""
    node, docs = live
    (memo,) = memos(the_reader(node))
    ranges = [{"to": 2}, {"from": 2, "to": 10}, {"from": 10}]
    for shift in (0, 1):
        before = counters(*LANES)
        resp = search(node, {"size": 0, "aggs": {
            "far": {"range": {"field": "trip_distance", "ranges": [
                {k: v + shift for k, v in r.items()} for r in ranges]},
                "aggs": {"paid": {"sum": {"field": "total_amount"}}}},
            "seats": {"terms": {"field": "passenger_count"},
                      "aggs": {"paid": {"sum": {"field": "total_amount"}}}}}})
        got = moved(counters(*LANES), before)
        got.pop("xla_cache_miss", None)
        assert got == {"agg_bins.level.table": 3, "agg_bins.level.rank": 1,
                       "agg_env.queries": 1}
        for b, r in zip(resp["aggregations"]["far"]["buckets"], ranges):
            sel = [d for d in docs
                   if r.get("from", -1) + shift * ("from" in r)
                   <= round(d["trip_distance"] * 100) / 100
                   < r.get("to", 1e9) + shift * ("to" in r)]
            assert b["doc_count"] == len(sel)
            assert close(b["paid"]["value"], math.fsum(
                round(d["total_amount"] * 100) / 100 for d in sel))
        seats = {b["key"]: b["doc_count"]
                 for b in resp["aggregations"]["seats"]["buckets"]}
        assert seats == {n: sum(d["passenger_count"] == n for d in docs)
                         for n in seats} and sum(seats.values()) == len(docs)
    assert not memo.vectors


def test_a_batch_of_aggregations_shares_one_vector(live):
    """A B>1 `_msearch` group is one program run over one vector a
    level (unbatched under `vmap`): one lookup for the group, a level
    counted an item, every item's answer its own `_search`'s."""
    node, docs = live
    bodies = []
    for lt in (50, 40, 30, 20):
        body = distance(2)
        body["query"]["bool"]["filter"]["range"]["trip_distance"]["lt"] = lt
        bodies.append(body)
    singles = [search(node, b) for b in bodies]
    lines = []
    for b in bodies:
        lines += [json.dumps({"index": "t"}), json.dumps(b)]
    payload = "\n".join(lines) + "\n"
    node.request("POST", "/_msearch", payload)      # whatever compiles
    (memo,) = memos(the_reader(node))
    before = counters(*LANES)
    resp = node.request("POST", "/_msearch", payload)
    assert moved(counters(*LANES), before) == {
        "agg_lane_bins.hit": 1, "agg_bins.level.resident": 4,
        "agg_env.queries": 4}
    assert len(memo.vectors) == 1
    for got, want in zip(resp["responses"], singles):
        assert got["aggregations"] == want["aggregations"]
        assert got["hits"]["total"] == want["hits"]["total"]
    # another interval in the same batch is another group: its own
    # vector, never the first item's
    mixed = "\n".join([json.dumps({"index": "t"}), json.dumps(distance(2)),
                       json.dumps({"index": "t"}), json.dumps(distance(5))])
    resp = node.request("POST", "/_msearch", mixed + "\n")
    check_distance_at(resp["responses"][0], docs, 2)
    check_distance_at(resp["responses"][1], docs, 5)
    assert len(memo.vectors) == 2


def test_a_pinned_reader_reads_its_sources_vectors(live):
    """A scroll or PIT context holds the reader's images, not copies:
    its aggregating programs find the vectors the reader keeps."""
    from opensearch_tpu.search.executor import PinnedReader, SearchExecutor
    node, docs = live
    want = search(node, distance(2, track_total_hits=True))
    pinned = SearchExecutor(PinnedReader(the_reader(node)))
    pinned.request_cache_enabled = False
    before = counters("search.agg_lane_bins.")
    got = pinned.search(distance(2, track_total_hits=True))
    assert moved(counters("search.agg_lane_bins."), before) \
        == {"agg_lane_bins.hit": 1}
    assert got["aggregations"] == want["aggregations"]
    # once the reader has let the image go, the pinned one derives for
    # itself and keeps nothing
    remove(node, docs)
    before = counters("search.agg_lane_bins.")
    got = pinned.search(distance(2, track_total_hits=True))
    assert moved(counters("search.agg_lane_bins."), before) \
        == {"agg_lane_bins.miss": 1}
    assert got["aggregations"] == want["aggregations"]
    assert the_reader(node).device_bytes == 0
