"""The `nyc_taxis-1shard` configuration and its cell off the chip (ISSUE
35): the generator is fixed by its seed and lays the corpus out as the
configuration says (one full segment, rows in pickup order, the tail
past the workload's `lt 50`, negative amounts); the plain reference is
numpy alone and agrees with a row-by-row reckoning; the judge's
controls come out NOT correct at dry-run size on three seeds (the
reference's sums in bfloat16, and one sequential float32 accumulation a
bucket: what `sum_rtol` is there to refuse), as do a wrong `doc_count`,
a missing empty bucket, a wrong `min` and a failed shard; a whole
`main()` dry run of the cell ends `correct`; and the roofline's shape
function. No timing is asserted.
"""

import inspect
import json
import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import oracle                # noqa: E402
from benchmark import run as bench_run      # noqa: E402

FILES = bench_run.Files(REPO)
CELL = "nyc_taxis-aggs-closed-1"
CONFIG = FILES.config("nyc_taxis-1shard")
BUILDER = FILES.builder(CONFIG)
TRAFFIC = FILES.traffic("taxi-aggs-closed-1")
SEEDS = [5, 2147483659, 3000000019]
DISTANCE, DATES = BUILDER.DISTANCE, BUILDER.DATES


@pytest.fixture(scope="module")
def corpora():
    return {seed: BUILDER.build(CONFIG, seed, True) for seed in SEEDS}


def bfloat16_sum(dollars: np.ndarray) -> float:
    return float(oracle.lower_precision(np.array([dollars.sum()]))[0])


# ------------------------------------------------------------ generator

def test_the_corpus_is_fixed_by_its_seed_and_laid_out_as_stated(corpora):
    from opensearch_tpu.index.segment import ident_pairs, pad_bucket
    a = corpora[SEEDS[0]]
    again = BUILDER.build(CONFIG, SEEDS[0], True)
    other = corpora[SEEDS[1]]
    for name in ("dropoff_s", "dist_h", "total_c"):
        assert np.array_equal(getattr(a, name), getattr(again, name))
        assert not np.array_equal(getattr(a, name), getattr(other, name))
    n = CONFIG["dry_run"]["documents"]
    (seg,) = a.segments
    assert seg.num_docs == n == pad_bucket(n) == a.sizes["d_pad"]
    assert set(seg.numeric_dv) == {
        "pickup_datetime", "dropoff_datetime", "trip_distance",
        "total_amount", "fare_amount", "tip_amount", "passenger_count"}
    assert set(seg.ordinal_dv) == {"payment_type", "vendor_id",
                                   "rate_code_id"}
    for col in seg.numeric_dv.values():
        assert ident_pairs(col) and col.exists.all()
        assert np.array_equal(col.unique[col.value_ords], col.values)
        assert (np.diff(col.unique) > 0).all()
    # rows in pickup order, so NOT in the aggregated field's order
    pickup = seg.numeric_dv["pickup_datetime"].values
    dropoff = seg.numeric_dv["dropoff_datetime"].values
    assert (np.diff(pickup) >= 0).all() and (np.diff(dropoff) < 0).any()
    assert np.array_equal(dropoff, a.dropoff_s * 1000.0)
    assert ((dropoff - pickup) >= 60_000).all()
    # the span: the source's rate, ~74 days from 2015-01-01 (30 days at
    # dry-run size, so that the 21-day range still lies inside it)
    assert 29.9 < a.span_s / 86400 < 30.1
    assert 74.0 < CONFIG["documents"] / CONFIG["rides_per_day"] < 74.2
    assert CONFIG["rides_per_day"] == CONFIG["source_documents"] // 365
    assert CONFIG["documents"] == 1 << 25 and CONFIG["shards"] == 1
    # a scaled_float column holds hundredths; the tail and the negatives
    dist = seg.numeric_dv["trip_distance"].values
    assert np.allclose(dist * 100, a.dist_h) and dist.min() == 0.0
    tail = (a.dist_h >= 5000).mean()
    assert 0.5e-4 < tail < 4e-4 and a.dist_h.max() > 100_000
    assert 0.001 < (a.dist_h == 0).mean() < 0.01
    assert 1e-4 < (a.total_c < 0).mean() < 1.5e-3
    amount = seg.numeric_dv["total_amount"].values
    assert np.allclose(amount * 100, a.total_c)
    assert seg.ordinal_dv["payment_type"].dictionary \
        == ["1", "2", "3", "4", "5"]
    two = np.bincount(seg.ordinal_dv["payment_type"].ords)[:2].sum() / n
    assert two > 0.97
    assert a.index_settings["index.requests.cache.enable"] is False
    assert a.index_settings["number_of_shards"] == 1
    fields = a.mapping["properties"]
    assert len(fields) == 21 and fields["pickup_location"] == {
        "type": "geo_point"}
    assert fields["total_amount"] == {"type": "scaled_float",
                                      "scaling_factor": 100}


def test_the_traffic_is_the_sources_bodies_in_a_cycle_of_three(corpora):
    corpus = corpora[SEEDS[0]]
    cycle = bench_run.class_cycle(TRAFFIC["classes"])
    assert [c["id"] for c in cycle] == [DISTANCE, DATES, DISTANCE]
    assert TRAFFIC["clients"] == 1 and TRAFFIC["loop"] == "closed"
    qs = corpus.draw(TRAFFIC["query"], cycle * 4, 7)
    assert [q.klass for q in qs] == [DISTANCE, DATES, DISTANCE] * 4
    bodies = {q.klass: json.loads(corpus.payload(q)) for q in qs}
    assert bodies[DISTANCE] == {
        "size": 0,
        "query": {"bool": {"filter": {"range": {"trip_distance": {
            "lt": 50, "gte": 0}}}}},
        "aggs": {"distance_histo": {
            "histogram": {"field": "trip_distance", "interval": 1},
            "aggs": {"total_amount_stats": {
                "stats": {"field": "total_amount"}}}}}}
    assert bodies[DATES] == {
        "size": 0,
        "query": {"range": {"dropoff_datetime": {
            "gte": "01/01/2015", "lte": "21/01/2015",
            "format": "dd/MM/yyyy"}}},
        "aggs": {"dropoffs_over_time": {"date_histogram": {
            "field": "dropoff_datetime", "calendar_interval": "day"}}}}
    # the same bytes in every request of a class
    assert len({corpus.payload(q) for q in qs}) == 2
    assert {q.klass: q.work for q in qs} == {
        DISTANCE: {"op": DISTANCE, "value_columns": 2, "bins": 50},
        DATES: {"op": DATES, "value_columns": 1, "bins": 21}}


# ------------------------------------------------------------- reference

def test_the_reference_is_plain_numpy_and_agrees_with_a_row_by_row_one():
    for fn in (BUILDER.by_bucket, BUILDER.reference_distance_amount,
               BUILDER.reference_dropoff_days, BUILDER.filled_span,
               BUILDER.sequential_float32):
        assert "opensearch_tpu" not in inspect.getsource(fn)
    rng = np.random.default_rng(11)
    n = 30000
    dist_h = rng.integers(0, 3000, n).astype(np.int32)
    dist_h[dist_h % 7 == 0] += 4000         # some past the filter
    dist_h[(dist_h >= 1200) & (dist_h < 1500)] = 100    # empties between
    cents = rng.integers(-3000, 90000, n).astype(np.int32)
    total, first, counts, sums, lows, highs, _ = \
        BUILDER.reference_distance_amount(dist_h, cents, 0.0, 50.0, 1.0)
    inside = (dist_h >= 0) & (dist_h < 5000)
    assert total == int(inside.sum()) and first == 0
    assert counts.shape == (50,) and sums.dtype == np.int64
    for b in range(50):
        sel = inside & (dist_h // 100 == b)
        assert counts[b] == sel.sum()
        if counts[b]:
            assert sums[b] == int(cents[sel].astype(np.int64).sum())
            assert (lows[b], highs[b]) == (cents[sel].min(),
                                           cents[sel].max())
    assert counts[12] == counts[13] == counts[14] == 0
    assert list(BUILDER.filled_span(counts))[0] == 0
    assert 12 in BUILDER.filled_span(counts)
    t = rng.integers(1420070400 - 5000, 1420070400 + 30 * 86400, n)
    lo, hi = 1420070400, 1420070400 + 21 * 86400 - 1
    total, day0, days = BUILDER.reference_dropoff_days(t, lo, hi)
    assert total == int(((t >= lo) & (t <= hi)).sum())
    assert len(days) == 21 and days.sum() == total
    assert days[20] == ((t // 86400 == day0 + 20) & (t <= hi)).sum()
    # one float32 accumulator loses what float64 keeps
    v = np.full(1 << 20, 12.35)
    assert abs(BUILDER.sequential_float32(v) - v.sum()) / v.sum() > 1e-5


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_passes_and_both_controls_fail(corpora, seed):
    corpus = corpora[seed]
    queries = corpus.draw({}, bench_run.class_cycle(TRAFFIC["classes"]),
                          seed)
    # the reference in the program's place, served as float32: correct
    seen = {}
    sound = [(q, corpus.reference_response(
        q, lambda d: float(np.float32(math.fsum(d.tolist())))))
        for q in queries]
    assert corpus.judge(sound, seen) == []
    assert seen["values_compared"] >= 2 * 4 * 30
    lower = seen["score_rel_err_max"]
    assert lower <= 2.0 ** -24 < seen["score_rel_err_limit"] == 1e-6
    # the controls: every distance page has to differ: bfloat16 by
    # 1,000 times the limit; one sequential float32 accumulation by as
    # much as a bucket of this size lets it drift (1.5e-6 to 3e-6 over
    # the ~20,000 addends here; at the cell's 10^7 it reads 4e-3, as
    # tests/test_agg_sum_precision.py and PERF.md section 2 have it)
    for control, least in ((bfloat16_sum, 1000e-6),
                           (BUILDER.sequential_float32, 1e-6)):
        seen = {}
        bad = corpus.judge([(q, corpus.reference_response(q, control))
                            for q in queries], seen)
        assert len(bad) == 2 and all("relative gap" in b for b in bad)
        assert seen["score_rel_err_max"] > least
        assert seen["score_rel_err_max"] > 3 * max(lower, 1e-12)


def test_a_wrong_count_a_missing_bucket_a_wrong_min_and_a_failed_shard(
        corpora):
    corpus = corpora[SEEDS[0]]
    q_dist, q_days = BUILDER.Query(DISTANCE), BUILDER.Query(DATES)

    def judged(q, change):
        resp = corpus.reference_response(q)
        change(resp)
        return corpus.judge([(q, resp)])

    def dist(r):
        return r["aggregations"]["distance_histo"]["buckets"]

    def days(r):
        return r["aggregations"]["dropoffs_over_time"]["buckets"]
    assert judged(q_dist, lambda r: None) == []
    assert judged(q_days, lambda r: None) == []
    empty = next(i for i, b in enumerate(dist(
        corpus.reference_response(q_dist))) if b["doc_count"] == 0)
    assert judged(q_dist, lambda r: dist(r)[3].__setitem__(
        "doc_count", dist(r)[3]["doc_count"] + 1))
    assert judged(q_dist, lambda r: dist(r)[3]["total_amount_stats"]
                  .__setitem__("count", 0))
    assert judged(q_dist, lambda r: dist(r).pop(empty))
    assert judged(q_dist, lambda r: dist(r)[empty]["total_amount_stats"]
                  .__setitem__("min", 0.0))
    assert judged(q_dist, lambda r: dist(r)[2]["total_amount_stats"]
                  .__setitem__("min", dist(r)[2]["total_amount_stats"]
                               ["min"] + 0.01))
    assert judged(q_dist, lambda r: dist(r)[2]["total_amount_stats"]
                  .__setitem__("avg", None))
    assert judged(q_dist, lambda r: dist(r).append(
        {"key": 5000.0, "doc_count": 1, "total_amount_stats": {
            "count": 1, "min": 1.0, "max": 1.0, "avg": 1.0, "sum": 1.0}}))
    assert judged(q_days, lambda r: days(r)[4].__setitem__(
        "doc_count", days(r)[4]["doc_count"] - 1))
    assert judged(q_days, lambda r: days(r).pop())
    for q in (q_dist, q_days):
        assert judged(q, lambda r: r["hits"]["total"].__setitem__(
            "relation", "gte"))
        assert judged(q, lambda r: r["_shards"].__setitem__("failed", 1))
        assert judged(q, lambda r: r.__setitem__("timed_out", True))
        assert judged(q, lambda r: r.pop("aggregations"))


# ---------------------------------------------------------------- a run

def test_a_whole_dry_run_of_the_cell_ends_correct(capfd):
    """`main()` with the look for a chip skipped (`--dry-run`): both
    classes served through the socket and the B=1 envelope, judged, the
    index's request cache off all the way."""
    assert os.environ["JAX_PLATFORMS"] == "cpu"     # conftest pinned it
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    cache0 = REQUEST_CACHE.stats()
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483693",
                         "--seconds", "2", "--trace", "1", "--dry-run"])
    out, err = capfd.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    compared = line["compared"]
    assert compared["pages_differing"]["value"] == 0
    assert compared["pages_judged"]["value"] \
        == TRAFFIC["dry_run"]["judge"]["sample"]
    gap = compared["score_rel_err_max"]
    assert 0 < gap["value"] <= gap["limit"] == 1e-6
    m = line["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    for name in ("http_self_ms.taxi", "rest_self_ms.taxi",
                 "envelope_host_ms.taxi", "enqueue_ms.taxi",
                 "device_wait_ms.taxi", "respond_ms.taxi"):
        assert m[name]["value"] > 0, name
    # every histogram > stats request gathers through a table
    assert m["agg_table_levels_in_window"]["value"] > 0
    # no device plane on the CPU: no device metric, under any name
    assert not {"device_ms_per_query.taxi", "agg_bins_ms.taxi",
                "agg_env_roofline", "taxi_other_ms"} & set(m)
    # every request ran: nothing was looked up in or stored to the cache
    assert REQUEST_CACHE.stats() == cache0
    assert any("score_rel_err_max" in ln and "<= 1e-06" in ln
               for ln in err.strip().splitlines()[-3:])


# ------------------------------------------------------------- roofline

def test_the_rooflines_shape_function_counts_what_the_algorithm_needs():
    reader = bench_run.load_module("reader", os.path.join(
        REPO, "benchmark", "metrics", "readers", "agg_env_roofline.py"))
    sizes = {"d_pad": 1 << 25, "num_docs": 1 << 25, "rows": 1}
    nbytes, flops = reader.agg_env(sizes, BUILDER.WORK[DISTANCE])
    assert nbytes == (1 << 25) * 8.125 + 50 * 5 * 4
    assert flops == (1 << 25) * 8.0
    nbytes_d, _ = reader.agg_env(sizes, BUILDER.WORK[DATES])
    assert nbytes_d == (1 << 25) * 4.125 + 21 * 4
    from benchmark import roofline
    t, bound = roofline.least_seconds(nbytes, flops,
                                      roofline.peaks("TPU v5 lite"))
    assert bound == "memory" and 0.3e-3 < t < 0.4e-3
