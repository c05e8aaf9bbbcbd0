"""Builder `nyc_taxis`: yellow-cab rides in the shape of OpenSearch
Benchmark's `nyc_taxis` workload, generated from the seed, and the plain
reference of its two aggregation operations.

The source's documents (the 2015 yellow-cab trip records) are not in
the tree, so the corpus is synthetic in the workload's shape: the
source's fields and types (dates in `yyyy-MM-dd HH:mm:ss`, money and
distance as `scaled_float` factor 100, keyword codes), its arrival rate
(rides a day), and a span cut to what the configuration's ride count
fills at that rate, so that the source's own 01/01-21/01 range holds
what it holds upstream. What the source does not fix is listed under
`assumed` in the configuration's file and read from there: the weekly
and diurnal arrival curve, the trip duration, the distance law with its
zeros and its tail beyond the workload's `lt 50`, the fare law with its
negative amounts, the shares of the keyword codes.

Layout. One shard, one sealed `Segment`, rows in pickup order (so the
aggregated `dropoff_datetime` is NOT sorted), built as arrays: seven
numeric doc-value columns, three keyword ordinal columns, `PrefixedIds`
for the `_id`s. The mapping holds every field of the source; the
geo_points, the text field and the columns no operation reads have no
column (`reduced` in the configuration's file).

Traffic. Two query classes, the source's operations verbatim and the
same in every request: `distance_amount_agg` (`bool.filter` range on
`trip_distance`, `histogram(trip_distance, 1) > stats(total_amount)`)
and `date_histogram_agg` (a `dd/MM/yyyy` range on `dropoff_datetime`
whose `lte` is the end of its day, `date_histogram(day)`). The index
sets `index.requests.cache.enable: false`, as the workload's does, so
every request runs.

Reference (`reference_distance_amount`, `reference_dropoff_days`):
numpy, nothing of `opensearch_tpu`, over every row of the seed's
columns: the range on the raw hundredths / seconds, the bucket by
integer floor, counts, and per bucket `min`, `max` and the sum of the
amounts as INTEGER cents in int64 (exact), divided by 100 in float64 at
the end. `Corpus.judge` holds a served response to the configuration's
guarantees: keys and their order, empty buckets between, every
`doc_count`, `stats.count` and `hits.total` exact, no failed shard or
time-out, and every `sum`, `avg`, `min` and `max` within `sum_rtol`
(1e-6) relative. **In this configuration the "score" of the harness's
`compared` line is a bucket's `sum`, `avg`, `min` or `max`**:
`score_rel_err_max` is the widest relative gap of a served one from the
reference's, `score_rel_err_limit` the configuration's `sum_rtol`.
"""

from __future__ import annotations

import calendar
import json
import math
import time

import numpy as np

from benchmark import oracle

DISTANCE, DATES = "distance_amount_agg", "date_histogram_agg"
HISTO, STATS, DAYS = "distance_histo", "total_amount_stats", \
    "dropoffs_over_time"
DAY_S = 86400
DATE_FORMAT = "%Y-%m-%d %H:%M:%S"

# the source's operations, verbatim (operations/default.json, written
# from recollection: `assumed.operations` in the configuration's file)
BODIES = {
    DISTANCE: {
        "size": 0,
        "query": {"bool": {"filter": {"range": {"trip_distance": {
            "lt": 50, "gte": 0}}}}},
        "aggs": {HISTO: {
            "histogram": {"field": "trip_distance", "interval": 1},
            "aggs": {STATS: {"stats": {"field": "total_amount"}}}}}},
    DATES: {
        "size": 0,
        "query": {"range": {"dropoff_datetime": {
            "gte": "01/01/2015", "lte": "21/01/2015",
            "format": "dd/MM/yyyy"}}},
        "aggs": {DAYS: {"date_histogram": {
            "field": "dropoff_datetime", "calendar_interval": "day"}}}},
}
# what the ALGORITHM reads a document and writes a request, for the
# roofline (benchmark/metrics/readers/agg_env_roofline.py)
WORK = {DISTANCE: {"op": DISTANCE, "value_columns": 2, "bins": 50},
        DATES: {"op": DATES, "value_columns": 1, "bins": 21}}

MAPPING = {"properties": {
    "pickup_datetime": {"type": "date", "format": "yyyy-MM-dd HH:mm:ss"},
    "dropoff_datetime": {"type": "date", "format": "yyyy-MM-dd HH:mm:ss"},
    **{f: {"type": "scaled_float", "scaling_factor": 100}
       for f in ("trip_distance", "total_amount", "fare_amount",
                 "tip_amount", "tolls_amount", "extra", "mta_tax",
                 "improvement_surcharge", "ehail_fee")},
    "passenger_count": {"type": "integer"},
    **{f: {"type": "keyword"}
       for f in ("payment_type", "vendor_id", "rate_code_id",
                 "store_and_fwd_flag", "trip_type", "cab_color")},
    "pickup_location": {"type": "geo_point"},
    "dropoff_location": {"type": "geo_point"},
    "vendor_name": {"type": "text"}}}


# ---------------------------------------------------------------- reference

def by_bucket(bucket: np.ndarray, n_buckets: int):
    """(order, bounds): the rows of buckets 0..n_buckets-1 gathered
    bucket by bucket (`bucket` uint8, `n_buckets` for a row of none);
    bucket b's rows are order[bounds[b]:bounds[b + 1]], in row order."""
    order = np.argsort(bucket, kind="stable")
    bounds = np.searchsorted(bucket[order], np.arange(n_buckets + 1))
    return order[:bounds[-1]], bounds


def reference_distance_amount(dist_h: np.ndarray, cents: np.ndarray,
                              lo: float, hi: float, interval: float):
    """`range trip_distance {gte lo, lt hi}` >
    `histogram(trip_distance, interval)` > `stats(total_amount)` over
    every row, exactly: `dist_h` is the distance in hundredths, `cents`
    the amount in cents, both integers. Returns (rows in the range,
    first bucket, counts int64, sums of cents int64, min cents, max
    cents) over the buckets from floor(lo / interval) up to the one
    below hi; an empty bucket's min and max are 0 and mean nothing."""
    lo_h, hi_h = int(round(lo * 100)), int(round(hi * 100))
    step_h = int(round(interval * 100))
    first = lo_h // step_h
    n_buckets = -(-hi_h // step_h) - first
    if n_buckets > 254:
        raise ValueError("the reference sorts buckets as uint8")
    inside = (dist_h >= lo_h) & (dist_h < hi_h)
    bucket = np.where(inside, dist_h // step_h - first,
                      n_buckets).astype(np.uint8)
    order, bounds = by_bucket(bucket, n_buckets)
    counts = np.diff(bounds).astype(np.int64)
    sums = np.zeros(n_buckets, dtype=np.int64)
    lows = np.zeros(n_buckets, dtype=np.int64)
    highs = np.zeros(n_buckets, dtype=np.int64)
    filled = np.flatnonzero(counts)
    if len(filled):
        c = cents[order].astype(np.int64)
        starts = bounds[:-1][filled]
        sums[filled] = np.add.reduceat(c, starts)
        lows[filled] = np.minimum.reduceat(c, starts)
        highs[filled] = np.maximum.reduceat(c, starts)
    return int(inside.sum()), first, counts, sums, lows, highs, \
        (order, bounds)


def reference_dropoff_days(dropoff_s: np.ndarray, lo_s: int, hi_s: int):
    """`range dropoff_datetime {gte lo_s, lte hi_s}` >
    `date_histogram(day)` over every row: (rows in the range, first UTC
    day, counts a day int64), `dropoff_s` and the bounds epoch seconds."""
    inside = (dropoff_s >= lo_s) & (dropoff_s <= hi_s)
    day0 = lo_s // DAY_S
    days = dropoff_s[inside] // DAY_S - day0
    return int(inside.sum()), day0, \
        np.bincount(days, minlength=hi_s // DAY_S - day0 + 1) \
        .astype(np.int64)


def filled_span(counts: np.ndarray):
    """The buckets a `min_doc_count` 0 histogram renders: from the first
    non-empty one to the last (both inclusive), or none."""
    filled = np.flatnonzero(counts)
    return range(int(filled[0]), int(filled[-1]) + 1) if len(filled) \
        else range(0)


def sequential_float32(values: np.ndarray) -> float:
    """One float32 accumulator fed one addend after another: what the
    `sum_rtol` guarantee is there to refuse (a control of `correct`)."""
    if not len(values):
        return 0.0
    return float(np.cumsum(values.astype(np.float32),
                           dtype=np.float32)[-1])


# ------------------------------------------------------------------- corpus

class Query:
    __slots__ = ("klass", "work")

    def __init__(self, klass: str):
        self.klass = klass
        self.work = WORK[klass]


def _rank_encode(values: np.ndarray):
    """(sorted distinct values, rank of each value among them) of an
    integer column: by presence bitmap where the domain is small beside
    the column, else by sorting."""
    lo = int(values.min())
    domain = int(values.max()) - lo + 1
    if domain <= 8 * len(values):
        shifted = values - lo
        present = np.zeros(domain, dtype=bool)
        present[shifted] = True
        rank = np.cumsum(present, dtype=np.int32) - 1
        return np.flatnonzero(present) + lo, rank[shifted]
    unique, inverse = np.unique(values, return_inverse=True)
    return unique, inverse.astype(np.int32)


def _by_share(rng, shares: dict, n: int):
    """(keys in the dictionary's order, code of each row) drawn by
    share."""
    keys = sorted(shares)
    p = np.array([float(shares[k]) for k in keys])
    code = np.searchsorted(np.cumsum(p / p.sum()),
                           rng.random(n, dtype=np.float32), side="right")
    return keys, np.minimum(code, len(keys) - 1).astype(np.int32)


def _lognormal(rng, n: int, median: float, sigma: float) -> np.ndarray:
    x = rng.standard_normal(n, dtype=np.float32)
    x *= np.float32(sigma)
    x += np.float32(math.log(median))
    return np.exp(x, out=x)


class Corpus:
    def __init__(self, config: dict, seed: int, dry_run: bool):
        from opensearch_tpu.index.segment import (DocValuesColumn,
                                                  OrdinalsColumn,
                                                  PrefixedIds, Segment,
                                                  _hash64, pad_bucket)
        # a program that does not know the index's request-cache setting
        # cannot serve this deployment (every request after the first
        # would be a cache hit): fail here, before a minute of set-up
        from opensearch_tpu.indices.request_cache import admits  # noqa: F401
        cfg = {**config, **(config["dry_run"] if dry_run else {})}
        self.config = config
        self.index = config["index"]
        n = self.n = int(cfg["documents"])
        self.rtol = float(config["sum_rtol"])
        a = config["assumed"]
        rng = np.random.default_rng([seed, 0x74617869])

        # pickups: the source's rate, a weekly and a diurnal curve,
        # whole seconds, rows in pickup order
        self.t0 = calendar.timegm(time.strptime(config["start"],
                                                DATE_FORMAT))
        span_s = int(math.ceil(n / float(cfg["rides_per_day"]) * DAY_S))
        self.span_s = span_s
        sec = np.arange(span_s, dtype=np.float64)
        curve = a["arrivals"]
        rate = 1.0 + float(curve["diurnal_amplitude"]) * np.cos(
            2.0 * np.pi * (sec / DAY_S
                           - float(curve["peak_hour_utc"]) / 24.0))
        # 1970-01-01 was a Thursday: day 0 of the week below is Monday
        weekday = ((self.t0 + sec) // DAY_S + 3) % 7
        rate *= np.asarray(curve["weekday_factor"],
                           dtype=np.float64)[weekday.astype(np.int64)]
        pickup = np.repeat(np.arange(span_s, dtype=np.int32),
                           rng.multinomial(n, rate / rate.sum()))
        del sec, rate, weekday

        # duration lognormal; dropoff = pickup + duration, not sorted
        d = a["duration_s"]
        duration = np.clip(_lognormal(rng, n, d["median"], d["sigma"]),
                           d["min"], d["max"]).astype(np.int32)
        dropoff = pickup + duration

        # distance in hundredths of a mile: lognormal under 50 miles,
        # some zeros, and the tail the workload's `lt 50` cuts
        t = a["trip_distance"]
        dist_h = np.minimum(_lognormal(rng, n, t["median_miles"],
                                       t["sigma"]) * 100.0,
                            4999.0).astype(np.int32)
        u = rng.random(n, dtype=np.float32)
        dist_h[u < float(t["zero_share"])] = 0
        tail = u > 1.0 - float(t["tail_share"])
        lo_t, hi_t = (math.log(float(x)) for x in t["tail_miles"])
        dist_h[tail] = (np.exp(rng.uniform(lo_t, hi_t, int(tail.sum())))
                        * 100.0).astype(np.int32)
        del u, tail

        # the keyword codes
        dictionaries = {}
        codes = {}
        for field in ("payment_type", "vendor_id", "rate_code_id"):
            dictionaries[field], codes[field] = _by_share(
                rng, a[field]["shares"], n)
        passengers_keys, passengers = _by_share(
            rng, a["passenger_count"]["shares"], n)
        passengers = np.asarray([int(k) for k in passengers_keys],
                                dtype=np.int32)[passengers]

        # the fare law: flag drop + per mile + per minute, to 50 cents;
        # a tip on card rides; surcharges; noise; a few negated
        f = a["fare"]
        fare = (float(f["flag_drop"]) + float(f["per_mile"])
                * (dist_h.astype(np.float32) / 100.0)
                + float(f["per_minute"])
                * (duration.astype(np.float32) / 60.0))
        fare_c = (np.round(fare * 2.0) * 50.0).astype(np.int32)
        card = codes["payment_type"] == dictionaries[
            "payment_type"].index(str(f["card_payment_type"]))
        tip_c = np.where(
            card, fare_c * np.clip(
                rng.normal(float(f["tip_mean"]), float(f["tip_sigma"]), n),
                0.0, 1.0), 0.0).astype(np.int32)
        total_c = fare_c + tip_c + int(f["surcharges_cents"]) \
            + np.abs(rng.normal(0.0, float(f["noise_cents"]), n)) \
            .astype(np.int32)
        negated = rng.random(n, dtype=np.float32) \
            < float(f["negative_share"])
        total_c[negated] = -total_c[negated]
        del fare, card, negated, duration

        # the reference's view: the columns as generated, integers
        self.dropoff_s = dropoff.astype(np.int64) + self.t0
        self.dist_h = dist_h
        self.total_c = total_c
        self._memo = {}

        ident = np.arange(n, dtype=np.int32)
        ones = np.ones(n, dtype=bool)
        one_each = np.ones(n, dtype=np.int32)

        def column(values: np.ndarray, scale: float = 1.0,
                   offset: float = 0.0):
            unique, ords = _rank_encode(values)
            unique = unique.astype(np.float64) * scale + offset
            return DocValuesColumn(ident, unique[ords], ones, one_each,
                                   ords.astype(np.int32), unique)
        t0_ms = float(self.t0) * 1000.0
        numeric = {
            "pickup_datetime": column(pickup, 1000.0, t0_ms),
            "dropoff_datetime": column(dropoff, 1000.0, t0_ms),
            # a scaled_float column holds round(v * 100) / 100
            "trip_distance": column(dist_h, 0.01),
            "total_amount": column(total_c, 0.01),
            "fare_amount": column(fare_c, 0.01),
            "tip_amount": column(tip_c, 0.01),
            "passenger_count": column(passengers)}
        ordinal = {
            field: OrdinalsColumn(
                ident, codes[field], ones, dictionaries[field],
                np.array([_hash64(s) for s in dictionaries[field]],
                         dtype=np.uint64))
            for field in dictionaries}
        self.segments = [Segment(
            "s0", n, PrefixedIds("r-", n), [None] * n, {},
            np.full((1, 128), -1, dtype=np.int32),
            np.zeros((1, 128), dtype=np.float32), {}, {}, numeric,
            ordinal, {})]
        self.index_settings = {
            "number_of_shards": int(config["shards"]),
            "number_of_replicas": 0,
            "index.requests.cache.enable": False}
        self.mapping = MAPPING
        self.sizes = {"d_pad": pad_bucket(n), "num_docs": n, "rows": 1}

    # ------------------------------------------------------------ queries

    def draw(self, spec: dict, classes: list, seed: int) -> list:
        """One request for each entry of `classes`: the operation its
        `id` names. Nothing is drawn: the source's bodies are fixed."""
        return [Query(c["id"]) for c in classes]

    def payload(self, query: Query) -> bytes:
        return json.dumps(BODIES[query.klass],
                          separators=(",", ":")).encode()

    # ------------------------------------------------------------- oracle

    def reference(self, klass: str):
        """What the reference finds for one operation (the bodies are
        fixed, so once a corpus)."""
        if klass not in self._memo:
            if klass == DISTANCE:
                r = BODIES[DISTANCE]["query"]["bool"]["filter"]["range"][
                    "trip_distance"]
                interval = BODIES[DISTANCE]["aggs"][HISTO]["histogram"][
                    "interval"]
                self._memo[klass] = reference_distance_amount(
                    self.dist_h, self.total_c, float(r["gte"]),
                    float(r["lt"]), float(interval))
            else:
                r = BODIES[DATES]["query"]["range"]["dropoff_datetime"]
                lo = calendar.timegm(time.strptime(r["gte"], "%d/%m/%Y"))
                hi = calendar.timegm(time.strptime(r["lte"], "%d/%m/%Y")) \
                    + DAY_S - 1     # `lte` of a day is that day's end
                self._memo[klass] = reference_dropoff_days(
                    self.dropoff_s, lo, hi)
        return self._memo[klass]

    def expected(self, klass: str, sums_as=None):
        """(hits.total, the buckets upstream renders). For
        `distance_amount_agg` [(key, doc_count, stats or None)], stats
        (count, min, max, avg, sum) in float64 from the exact integer
        cents; `sums_as(bucket's amounts in dollars, float64) -> sum`
        puts a control's sum (and the avg that follows from it) in the
        reference's place. For `date_histogram_agg` [(key ms,
        doc_count)]."""
        if klass == DATES:
            total, day0, counts = self.reference(DATES)
            return total, [((day0 + d) * DAY_S * 1000, int(counts[d]))
                           for d in filled_span(counts)]
        total, first, counts, sums, lows, highs, (order, bounds) = \
            self.reference(DISTANCE)
        interval = float(BODIES[DISTANCE]["aggs"][HISTO]["histogram"][
            "interval"])
        out = []
        for b in filled_span(counts):
            n = int(counts[b])
            if n == 0:
                out.append(((first + b) * interval, 0, None))
                continue
            s = sums[b] / 100.0
            if sums_as is not None:
                s = sums_as(self.total_c[order[bounds[b]:bounds[b + 1]]]
                            / 100.0)
            out.append(((first + b) * interval, n,
                        (n, lows[b] / 100.0, highs[b] / 100.0, s / n, s)))
        return total, out

    def reference_response(self, query: Query, sums_as=None) -> dict:
        """The response the plain reference itself would serve, its sums
        as `sums_as` leaves them (the controls of `correct`)."""
        total, buckets = self.expected(query.klass, sums_as)
        if query.klass == DATES:
            aggs = {DAYS: {"buckets": [{"key": k, "doc_count": n}
                                       for k, n in buckets]}}
        else:
            aggs = {HISTO: {"buckets": [
                {"key": k, "doc_count": n, STATS: dict(zip(
                    ("count", "min", "max", "avg", "sum"),
                    st if st is not None else (0, None, None, None, 0.0)))}
                for k, n, st in buckets]}}
        return {"timed_out": False, "_shards": {"failed": 0},
                "hits": {"total": {"value": total, "relation": "eq"},
                         "hits": []},
                "aggregations": aggs}

    def judge(self, pairs: list, seen: dict = None) -> list:
        bad = []
        seen = {} if seen is None else seen
        seen["score_rel_err_limit"] = self.rtol
        seen.setdefault("score_rel_err_max", 0.0)
        seen.setdefault("values_compared", 0)
        for query, resp in pairs:
            what = query.klass
            try:
                oracle.check_clean(resp, what)
                total, want = self.expected(query.klass)
                # hits.total: the program counts every hit of a body
                # without track_total_hits where upstream stops at
                # 10,000 (PERF.md section 7); held to the exact count
                oracle.check_total(what, resp, total)
                if query.klass == DATES:
                    self._check_days(what, resp, want)
                else:
                    self._check_distance(what, resp, want, seen)
            except oracle.Mismatch as e:
                bad.append(str(e))
            except (KeyError, TypeError, IndexError) as e:
                bad.append(f"{what}: malformed response "
                           f"({type(e).__name__}: {e})")
        return bad

    def _check_days(self, what: str, resp: dict, want: list) -> None:
        got = resp["aggregations"][DAYS]["buckets"]
        oracle.require(
            [b["key"] for b in got] == [k for k, _ in want],
            f"{what}: {len(got)} day buckets, keys differ from the "
            f"reference's {len(want)}")
        for b, (key, n) in zip(got, want):
            oracle.require(b["doc_count"] == n,
                           f"{what}: day {key} doc_count "
                           f"{b['doc_count']}, reference {n}")

    def _check_distance(self, what: str, resp: dict, want: list,
                        seen: dict) -> None:
        got = resp["aggregations"][HISTO]["buckets"]
        oracle.require(
            [b["key"] for b in got] == [k for k, _, _ in want],
            f"{what}: {len(got)} distance buckets "
            f"{[b['key'] for b in got][:4]}.., keys differ from the "
            f"reference's {len(want)}")
        worst = None
        for b, (key, n, stats) in zip(got, want):
            st = b[STATS]
            oracle.require(
                b["doc_count"] == n and st["count"] == n,
                f"{what}: bucket {key} doc_count {b['doc_count']}, "
                f"stats.count {st['count']}, reference {n}")
            if stats is None:
                oracle.require(
                    st["min"] is None and st["max"] is None
                    and st["avg"] is None and st["sum"] == 0,
                    f"{what}: empty bucket {key} renders {st}")
                continue
            for name, ref in zip(("min", "max", "avg", "sum"), stats[1:]):
                value = st[name]
                oracle.require(
                    value is not None and math.isfinite(value),
                    f"{what}: bucket {key} {name} {value}")
                gap = float(abs(value - ref)
                            / max(abs(value), abs(ref), 1e-300))
                seen["values_compared"] += 1
                seen["score_rel_err_max"] = max(seen["score_rel_err_max"],
                                                gap)
                if gap > self.rtol and (worst is None or gap > worst[4]):
                    worst = (key, name, value, ref, gap)
        # every value is compared before the page fails, so that the gap
        # the run prints is the widest of the page, not the first
        if worst is not None:
            key, name, value, ref, gap = worst
            raise oracle.Mismatch(
                f"{what}: {name} of bucket {key} {value!r} != reference "
                f"{ref!r} (relative gap {gap:.3g} > {self.rtol})")


def build(config: dict, seed: int, dry_run: bool) -> Corpus:
    return Corpus(config, seed, dry_run)
