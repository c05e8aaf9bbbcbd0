"""Builder `http_logs`: web-server log documents in the shape of
OpenSearch Benchmark's `http_logs` workload, generated from the seed,
and the plain reference of its Dashboards panel.

The source's documents (the 1998 World Cup site's access log) are not in
the tree, so the corpus is synthetic in the workload's shape: the
source's fields (`@timestamp` date, `clientip` ip, `request` text,
`status` integer, `size` integer), its arrival rate (documents a day),
and a span cut to what the configuration's document count fills at that
rate, so that an hourly bucket holds what it holds upstream. What the
source does not fix is listed under `assumed` in the configuration's
file and read from there: the diurnal arrival curve, the shares of the
eight `status` values, the lognormal `size` (0 for a 304), the Zipf draw
of `clientip`.

Layout. Documents arrive in time order; each run of `shards`
consecutive arrivals goes one to each shard in a seeded rotation, which
is what `_id`-hash routing does to auto-generated ids and keeps every
shard at exactly documents / shards (`d_pad` a power of two, no padding
row). A shard is one sealed `Segment` of four numeric doc-value columns,
built as arrays (no document is ever parsed) with `PrefixedIds` for its
`_id`s; `request` is in the mapping and has no postings, `clientip` has
its numeric column (the address as a long) and neither term postings
nor an ordinal dictionary: the cell's operations read none of them
(`reduced` in the configuration's file).

Traffic. One query class, the panel: `size` 0, `track_total_hits`, a
`range` on `@timestamp` over `window_days` from a start that is a whole
number of `start_granularity_s` from the span's beginning, drawn from
the seed WITHOUT REPEAT (so neither cache of the program ever hits),
and `date_histogram(hour) > terms(status) > sum(size)`.

Reference (`hour_status_keys`, `reference_panel`, `expected_buckets`):
numpy, float64,
nothing of `opensearch_tpu`: the range on the arrival-ordered
timestamps, the hour floor, the `status` group, counts and sums over
every row of the seed's columns, in blocks. `Corpus.judge` holds a
served response to the configuration's guarantees: bucket keys and
their order, every `doc_count` and `hits.total` exact, no failed shard
or time-out, and every `sum` within `sum_rtol` (1e-6) relative of the
reference's. **In this configuration the "score" of the harness's
`compared` line is a bucket's `sum`**: `score_rel_err_max` is the
widest relative gap of a served `sum` from the reference's, and
`score_rel_err_limit` the configuration's `sum_rtol` (run.py prints
those two keys; it reads no others).
"""

from __future__ import annotations

import calendar
import concurrent.futures
import json
import math
import os
import time

import numpy as np

from benchmark import oracle

HIST, TERMS, SUM = "by_hour", "by_status", "bytes"
HOUR_S = 3600


# ---------------------------------------------------------------- reference

def hour_status_keys(ts_s: np.ndarray, status_code: np.ndarray,
                     n_status: int) -> np.ndarray:
    """hour floor x status group of every row, one pass when the corpus
    is built: (ts // 3600) * n_status + status_code, int32."""
    key = ts_s // HOUR_S
    key *= n_status
    key += status_code
    return key


def reference_panel(ts_s: np.ndarray, keys: np.ndarray, size: np.ndarray,
                    lo_s: int, hi_s: int, n_status: int,
                    block: int = 1 << 22):
    """The panel over rows with lo_s <= ts < hi_s, in float64 and in
    blocks: (first hour, counts [hours, statuses] int64, sums [hours,
    statuses] float64, rows in the range). `ts_s` is seconds in arrival
    order (ascending), so the range is a slice; `keys` is
    `hour_status_keys` of the same rows."""
    a = int(np.searchsorted(ts_s, lo_s, side="left"))
    b = int(np.searchsorted(ts_s, hi_s, side="left"))
    h0 = lo_s // HOUR_S
    nh = (hi_s - 1) // HOUR_S - h0 + 1
    counts = np.zeros(nh * n_status, dtype=np.int64)
    sums = np.zeros(nh * n_status, dtype=np.float64)
    for i in range(a, b, block):
        j = min(i + block, b)
        key = keys[i:j] - h0 * n_status
        counts += np.bincount(key, minlength=nh * n_status)
        sums += np.bincount(key, weights=size[i:j].astype(np.float64),
                            minlength=nh * n_status)
    return h0, counts.reshape(nh, n_status), sums.reshape(nh, n_status), \
        b - a


def expected_buckets(h0: int, counts: np.ndarray, sums: np.ndarray,
                     statuses) -> list:
    """What `date_histogram > terms > sum` renders of the reference:
    [(hour key in epoch ms, doc_count, [(status, doc_count, sum), ...])]
    for every hour from the first non-empty one to the last
    (`min_doc_count` 0 fills the gaps between them), the statuses of an
    hour by doc_count descending, then key ascending, empty ones left
    out (`terms` has `min_doc_count` 1)."""
    per_hour = counts.sum(axis=1)
    filled = np.flatnonzero(per_hour)
    if len(filled) == 0:
        return []
    out = []
    for h in range(int(filled[0]), int(filled[-1]) + 1):
        order = sorted((s for s in range(len(statuses)) if counts[h, s]),
                       key=lambda s: (-int(counts[h, s]), statuses[s]))
        out.append(((h0 + h) * HOUR_S * 1000, int(per_hour[h]),
                    [(statuses[s], int(counts[h, s]), float(sums[h, s]))
                     for s in order]))
    return out


# ------------------------------------------------------------------- corpus

class Query:
    __slots__ = ("start_s", "klass", "work")

    def __init__(self, start_s: int, klass, work: dict):
        self.start_s = start_s      # epoch seconds, a whole minute
        self.klass = klass
        self.work = work


def _rank_encode(values: np.ndarray, domain: int):
    """(sorted distinct values, rank of each value among them), values
    non-negative integers below `domain`: by presence bitmap where the
    domain is small beside the column, else by sorting."""
    if domain <= 8 * len(values):
        present = np.zeros(domain, dtype=bool)
        present[values] = True
        rank = np.cumsum(present, dtype=np.int32) - 1
        return np.flatnonzero(present), rank[values]
    unique, inverse = np.unique(values, return_inverse=True)
    return unique, inverse.astype(np.int32)


def iso(seconds: int) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(seconds))


class Corpus:
    def __init__(self, config: dict, seed: int, dry_run: bool):
        from opensearch_tpu.index.segment import (DocValuesColumn,
                                                  PrefixedIds, Segment,
                                                  pad_bucket)
        cfg = {**config, **(config["dry_run"] if dry_run else {})}
        self.config = config
        self.index = config["index"]
        n = int(cfg["documents"])
        shards = int(config["shards"])
        if n % shards:
            raise ValueError("documents must divide over the shards")
        self.n, self.shards = n, shards
        self.rtol = float(config["sum_rtol"])
        rng = np.random.default_rng([seed, 0x6c6f6773])

        # arrivals: the source's rate, a diurnal curve, whole seconds
        self.t0 = calendar.timegm(time.strptime(
            config["start"], "%Y-%m-%dT%H:%M:%SZ"))
        if self.t0 % HOUR_S:
            raise ValueError("the span starts on a whole hour")
        span_s = int(math.ceil(n / float(cfg["docs_per_day"]) * 86400.0))
        self.span_s = span_s
        sec = np.arange(span_s, dtype=np.float64)
        d = config["diurnal"]
        rate = 1.0 + float(d["amplitude"]) * np.cos(
            2.0 * np.pi * (sec / 86400.0 - float(d["peak_hour_utc"]) / 24.0))
        per_second = rng.multinomial(n, rate / rate.sum())
        ts = np.repeat(np.arange(span_s, dtype=np.int32), per_second)
        del sec, rate, per_second

        # status: eight values, by share; size: lognormal, 0 for a 304
        shares = config["status_shares"]
        self.statuses = sorted(int(s) for s in shares)
        p = np.array([float(shares[str(s)]) for s in self.statuses])
        status_code = np.searchsorted(
            np.cumsum(p / p.sum()), rng.random(n, dtype=np.float32),
            side="right").astype(np.uint8)
        np.minimum(status_code, len(self.statuses) - 1, out=status_code)
        z = config["size_lognormal"]
        size = rng.standard_normal(n, dtype=np.float32)
        size *= np.float32(z["sigma"])
        size += np.float32(math.log(float(z["median"])))
        np.exp(size, out=size)
        np.clip(size, 1.0, float(int(z["below"]) - 1), out=size)
        size = size.astype(np.int32)
        size[status_code == self.statuses.index(304)] = 0

        # clientip: Zipf(1) over K addresses, rank by a log-uniform draw
        k = int(cfg["client_addresses"])
        ip_rank = np.power(np.float32(k + 1),
                           rng.random(n, dtype=np.float32)).astype(np.int32)
        ip_rank -= 1
        np.clip(ip_rank, 0, k - 1, out=ip_rank)
        addresses = np.unique(rng.integers(1 << 24, 1 << 32, int(k * 1.05),
                                           dtype=np.int64))
        if len(addresses) < k:
            raise RuntimeError("too few distinct addresses drawn")
        # value order of an address, by Zipf rank (ranks are scattered
        # over the address space)
        pos_of_rank = rng.permutation(k).astype(np.int32)
        addresses = addresses[:k]

        # routing: each run of `shards` arrivals, one to each shard
        rot = rng.integers(0, shards, n // shards, dtype=np.uint8)
        shard_of = (np.arange(n, dtype=np.uint32) % shards).astype(np.uint8)
        shard_of += np.repeat(rot, shards)
        shard_of %= shards
        del rot

        # the reference's view: the columns as generated, arrival order
        self.ts, self.status_code, self.size = ts, status_code, size
        self.keys = hour_status_keys(ts, status_code, len(self.statuses))

        def build(s: int):
            idx = np.flatnonzero(shard_of == s)
            m = len(idx)
            ident = np.arange(m, dtype=np.int32)
            ones = np.ones(m, dtype=bool)

            def column(unique, ords):
                unique = unique.astype(np.float64)
                return DocValuesColumn(ident, unique[ords], ones,
                                       np.ones(m, dtype=np.int32),
                                       ords.astype(np.int32), unique)
            secs, t_ord = _rank_encode(ts[idx], span_s)
            codes, s_ord = _rank_encode(status_code[idx],
                                        len(self.statuses))
            sizes, z_ord = _rank_encode(size[idx], int(z["below"]))
            pos, c_ord = _rank_encode(pos_of_rank[ip_rank[idx]], k)
            numeric = {
                "@timestamp": column(
                    (secs.astype(np.int64) + self.t0) * 1000, t_ord),
                "status": column(np.asarray(self.statuses)[codes], s_ord),
                "size": column(sizes, z_ord),
                "clientip": column(addresses[pos], c_ord)}
            return Segment(
                "s0", m, PrefixedIds(f"s{s}-", m), [None] * m, {},
                np.full((1, 128), -1, dtype=np.int32),
                np.zeros((1, 128), dtype=np.float32), {}, {}, numeric,
                {}, {})

        workers = min(shards, os.cpu_count() or 1)
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            self.segments = list(pool.map(build, range(shards)))
        self.index_settings = {"number_of_shards": shards,
                               "number_of_replicas": 0}
        self.mapping = {"properties": {
            "@timestamp": {"type": "date"}, "clientip": {"type": "ip"},
            "request": {"type": "text"}, "status": {"type": "integer"},
            "size": {"type": "integer"}}}
        self.sizes = {"d_pad": pad_bucket(n // shards),
                      "num_docs": n, "rows": shards}

    # ------------------------------------------------------------ queries

    def draw(self, spec: dict, classes: list, seed: int) -> list:
        """One panel for each entry of `classes` (one class: the traffic
        file's `query`): window starts a whole number of
        `start_granularity_s` from the span's beginning, such that the
        window stays inside the span, drawn without repeat."""
        step = int(spec["start_granularity_s"])
        window_s = int(spec["window_days"]) * 86400
        starts = (self.span_s - window_s) // step + 1
        if starts < len(classes):
            raise RuntimeError(
                f"{len(classes)} panels and only {starts} window starts "
                f"in the span: the traffic file is wrong")
        rng = np.random.default_rng([seed, 0x70616e65])
        hours = -(-window_s // HOUR_S) + 1
        return [Query(self.t0 + int(i) * step, c.get("id", "panel"),
                      {"window_s": window_s, "hour_buckets": hours,
                       "status_values": len(self.statuses)})
                for i, c in zip(rng.choice(starts, size=len(classes),
                                           replace=False), classes)]

    def payload(self, query: Query) -> bytes:
        lo, hi = query.start_s, query.start_s + query.work["window_s"]
        return json.dumps({
            "size": 0, "track_total_hits": True,
            "query": {"range": {"@timestamp": {"gte": iso(lo),
                                               "lt": iso(hi)}}},
            "aggs": {HIST: {
                "date_histogram": {"field": "@timestamp",
                                   "calendar_interval": "hour"},
                "aggs": {TERMS: {
                    "terms": {"field": "status"},
                    "aggs": {SUM: {"sum": {"field": "size"}}}}}}}},
            separators=(",", ":")).encode()

    # ------------------------------------------------------------- oracle

    def reference(self, query: Query):
        """(rows in the window, the buckets `expected_buckets` renders)."""
        lo = query.start_s - self.t0
        h0, counts, sums, total = reference_panel(
            self.ts, self.keys, self.size, lo,
            lo + query.work["window_s"], len(self.statuses))
        buckets = expected_buckets(h0, counts, sums, self.statuses)
        return total, [(key + self.t0 * 1000, n, rows)
                       for key, n, rows in buckets]

    def reference_response(self, query: Query, sums_as=lambda s: s) -> dict:
        """The response the plain reference itself would serve, its sums
        in the precision `sums_as` leaves them in (the control of
        `correct`: `oracle.lower_precision`)."""
        total, buckets = self.reference(query)
        served = sums_as(np.array([v for _, _, rows in buckets
                                   for _, _, v in rows], dtype=np.float64))
        it = iter(served.tolist())
        return {"timed_out": False, "_shards": {"failed": 0},
                "hits": {"total": {"value": total, "relation": "eq"},
                         "hits": []},
                "aggregations": {HIST: {"buckets": [
                    {"key": key, "doc_count": n, TERMS: {"buckets": [
                        {"key": st, "doc_count": c, SUM: {"value": next(it)}}
                        for st, c, _ in rows]}}
                    for key, n, rows in buckets]}}}

    def judge(self, pairs: list, seen: dict = None) -> list:
        bad = []
        seen = {} if seen is None else seen
        seen["score_rel_err_limit"] = self.rtol
        seen.setdefault("score_rel_err_max", 0.0)
        seen.setdefault("sums_compared", 0)
        for query, resp in pairs:
            what = f"panel from {iso(query.start_s)}"
            try:
                oracle.check_clean(resp, what)
                total, want = self.reference(query)
                oracle.check_total(what, resp, total)
                self._check_buckets(what, resp, want, seen)
            except oracle.Mismatch as e:
                bad.append(str(e))
            except (KeyError, TypeError, IndexError) as e:
                bad.append(f"{what}: malformed response "
                           f"({type(e).__name__}: {e})")
        return bad

    def _check_buckets(self, what: str, resp: dict, want: list,
                       seen: dict) -> None:
        got = resp["aggregations"][HIST]["buckets"]
        oracle.require(
            [b["key"] for b in got] == [key for key, _, _ in want],
            f"{what}: {len(got)} hour buckets, keys differ from the "
            f"reference's {len(want)}")
        worst = None
        for b, (key, n, rows) in zip(got, want):
            oracle.require(b["doc_count"] == n,
                           f"{what}: hour {key} doc_count {b['doc_count']},"
                           f" reference {n}")
            inner = b[TERMS]["buckets"]
            oracle.require(
                [(t["key"], t["doc_count"]) for t in inner]
                == [(st, c) for st, c, _ in rows],
                f"{what}: hour {key} status buckets "
                f"{[(t['key'], t['doc_count']) for t in inner]}, "
                f"reference {[(st, c) for st, c, _ in rows]}")
            for t, (st, _, ref_sum) in zip(inner, rows):
                value = t[SUM]["value"]
                oracle.require(
                    value is not None and math.isfinite(value),
                    f"{what}: hour {key} status {st} sum {value}")
                gap = abs(value - ref_sum) / max(abs(value), abs(ref_sum),
                                                 1e-300)
                seen["sums_compared"] += 1
                seen["score_rel_err_max"] = max(seen["score_rel_err_max"],
                                                gap)
                if gap > self.rtol and (worst is None or gap > worst[4]):
                    worst = (key, st, value, ref_sum, gap)
        # every sum is compared before the page fails, so that the gap
        # the run prints is the widest of the page, not the first
        if worst is not None:
            key, st, value, ref_sum, gap = worst
            raise oracle.Mismatch(
                f"{what}: sum of hour {key} status {st} {value!r} != "
                f"reference {ref_sum!r} (relative gap {gap:.3g} > "
                f"{self.rtol})")


def build(config: dict, seed: int, dry_run: bool) -> Corpus:
    return Corpus(config, seed, dry_run)
