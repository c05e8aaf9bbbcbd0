"""Aggregations framework tests.

Contract model: reference agg semantics (search/aggregations/) — bucket
counts, metric values, nesting via bucketOrd composition, two-level reduce
across segments, pipeline aggs on the reduced tree.
"""

import json

import numpy as np
import pytest

from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import SegmentBuilder
from opensearch_tpu.search.executor import SearchExecutor, ShardReader

MAPPING = {"properties": {
    "cat": {"type": "keyword"},
    "brand": {"type": "keyword"},
    "price": {"type": "double"},
    "qty": {"type": "integer"},
    "day": {"type": "date"},
    "desc": {"type": "text"},
}}

DOCS = [
    {"cat": "a", "brand": "x", "price": 10.0, "qty": 1, "day": "2024-01-05", "desc": "red fox"},
    {"cat": "a", "brand": "y", "price": 20.0, "qty": 2, "day": "2024-01-15", "desc": "blue fox"},
    {"cat": "b", "brand": "x", "price": 30.0, "qty": 3, "day": "2024-02-10", "desc": "red dog"},
    {"cat": "b", "brand": "y", "price": 40.0, "qty": 4, "day": "2024-02-20", "desc": "lazy dog"},
    {"cat": "b", "brand": "x", "price": 50.0, "qty": 5, "day": "2024-03-01", "desc": "red cat"},
    {"cat": "c", "price": 60.0, "qty": 6, "day": "2024-03-15", "desc": "gray cat"},
    {"qty": 7, "desc": "no cat field"},
]


def build_executor(split=None):
    mapper = MapperService(MAPPING)
    if split is None:
        split = [len(DOCS)]
    segs = []
    i = 0
    for si, n in enumerate(split):
        b = SegmentBuilder(mapper, f"s{si}")
        for d in DOCS[i:i + n]:
            b.add(mapper.parse_document(f"d{i}", d))
            i += 1
        segs.append(b.seal())
    return SearchExecutor(ShardReader(mapper, segs))


@pytest.fixture(scope="module", params=[(7,), (3, 4), (2, 2, 3)],
                ids=["1seg", "2seg", "3seg"])
def executor(request):
    return build_executor(list(request.param))


def agg(executor, aggs, query=None, **kw):
    body = {"size": 0, "aggs": aggs}
    if query is not None:
        body["query"] = query
    body.update(kw)
    return executor.search(body)["aggregations"]


def test_terms_basic(executor):
    out = agg(executor, {"cats": {"terms": {"field": "cat"}}})
    buckets = out["cats"]["buckets"]
    assert [(b["key"], b["doc_count"]) for b in buckets] == [
        ("b", 3), ("a", 2), ("c", 1)]
    assert out["cats"]["sum_other_doc_count"] == 0
    assert out["cats"]["doc_count_error_upper_bound"] == 0


def test_terms_size_and_order(executor):
    out = agg(executor, {"cats": {"terms": {"field": "cat", "size": 1}}})
    assert [b["key"] for b in out["cats"]["buckets"]] == ["b"]
    assert out["cats"]["sum_other_doc_count"] == 3
    out = agg(executor, {"cats": {"terms": {"field": "cat",
                                            "order": {"_key": "asc"}}}})
    assert [b["key"] for b in out["cats"]["buckets"]] == ["a", "b", "c"]


def test_terms_with_query_filter(executor):
    out = agg(executor, {"cats": {"terms": {"field": "cat"}}},
              query={"match": {"desc": "red"}})
    assert {b["key"]: b["doc_count"] for b in out["cats"]["buckets"]} == {
        "a": 1, "b": 2}


def test_terms_numeric(executor):
    out = agg(executor, {"qtys": {"terms": {"field": "qty", "size": 20}}})
    assert {b["key"]: b["doc_count"] for b in out["qtys"]["buckets"]} == {
        i: 1 for i in range(1, 8)}


def test_terms_nested_sub_metric(executor):
    out = agg(executor, {"cats": {"terms": {"field": "cat"},
                                  "aggs": {"avg_price": {"avg": {"field": "price"}}}}})
    by_key = {b["key"]: b for b in out["cats"]["buckets"]}
    assert by_key["a"]["avg_price"]["value"] == pytest.approx(15.0)
    assert by_key["b"]["avg_price"]["value"] == pytest.approx(40.0)
    assert by_key["c"]["avg_price"]["value"] == pytest.approx(60.0)


def test_terms_nested_terms(executor):
    out = agg(executor, {"cats": {"terms": {"field": "cat"},
                                  "aggs": {"brands": {"terms": {"field": "brand"}}}}})
    by_key = {b["key"]: b for b in out["cats"]["buckets"]}
    assert {b["key"]: b["doc_count"] for b in by_key["b"]["brands"]["buckets"]} \
        == {"x": 2, "y": 1}
    assert {b["key"]: b["doc_count"] for b in by_key["a"]["brands"]["buckets"]} \
        == {"x": 1, "y": 1}


def test_metrics(executor):
    out = agg(executor, {
        "mn": {"min": {"field": "price"}}, "mx": {"max": {"field": "price"}},
        "sm": {"sum": {"field": "price"}}, "av": {"avg": {"field": "price"}},
        "vc": {"value_count": {"field": "price"}},
        "st": {"stats": {"field": "price"}},
        "xs": {"extended_stats": {"field": "price"}},
    })
    prices = [10, 20, 30, 40, 50, 60]
    assert out["mn"]["value"] == 10.0
    assert out["mx"]["value"] == 60.0
    assert out["sm"]["value"] == pytest.approx(sum(prices))
    assert out["av"]["value"] == pytest.approx(np.mean(prices))
    assert out["vc"]["value"] == 6
    assert out["st"]["count"] == 6
    assert out["st"]["avg"] == pytest.approx(35.0)
    assert out["xs"]["variance"] == pytest.approx(np.var(prices))
    assert out["xs"]["std_deviation"] == pytest.approx(np.std(prices))


def test_histogram(executor):
    out = agg(executor, {"h": {"histogram": {"field": "price", "interval": 25}}})
    assert [(b["key"], b["doc_count"]) for b in out["h"]["buckets"]] == [
        (0.0, 2), (25.0, 2), (50.0, 2)]


def test_histogram_empty_buckets_filled(executor):
    out = agg(executor, {"h": {"histogram": {"field": "qty", "interval": 2}}},
              query={"terms": {"qty": [1, 7]}})
    keys = [(b["key"], b["doc_count"]) for b in out["h"]["buckets"]]
    assert keys == [(0.0, 1), (2.0, 0), (4.0, 0), (6.0, 1)]


def test_date_histogram_month(executor):
    out = agg(executor, {"m": {"date_histogram": {"field": "day",
                                                  "calendar_interval": "month"}}})
    buckets = out["m"]["buckets"]
    assert [b["doc_count"] for b in buckets] == [2, 2, 2]
    assert buckets[0]["key_as_string"].startswith("2024-01-01")
    assert buckets[1]["key_as_string"].startswith("2024-02-01")


def test_date_histogram_fixed(executor):
    out = agg(executor, {"w": {"date_histogram": {"field": "day",
                                                  "fixed_interval": "30d"}}})
    total = sum(b["doc_count"] for b in out["w"]["buckets"])
    assert total == 6


def test_range_agg(executor):
    out = agg(executor, {"r": {"range": {"field": "price", "ranges": [
        {"to": 25}, {"from": 25, "to": 45}, {"from": 45}]}}})
    buckets = out["r"]["buckets"]
    assert [b["doc_count"] for b in buckets] == [2, 2, 2]
    assert buckets[0]["key"] == "*-25"
    assert buckets[1]["from"] == 25.0 and buckets[1]["to"] == 45.0


def test_range_agg_with_sub(executor):
    out = agg(executor, {"r": {"range": {"field": "price",
                                         "ranges": [{"from": 25}]},
                               "aggs": {"s": {"sum": {"field": "qty"}}}}})
    assert out["r"]["buckets"][0]["s"]["value"] == pytest.approx(3 + 4 + 5 + 6)


def test_filter_agg(executor):
    out = agg(executor, {"red": {"filter": {"match": {"desc": "red"}},
                                 "aggs": {"mx": {"max": {"field": "price"}}}}})
    assert out["red"]["doc_count"] == 3
    assert out["red"]["mx"]["value"] == 50.0


def test_filters_agg(executor):
    out = agg(executor, {"f": {"filters": {"filters": {
        "cheap": {"range": {"price": {"lt": 25}}},
        "foxy": {"match": {"desc": "fox"}}}}}})
    assert out["f"]["buckets"]["cheap"]["doc_count"] == 2
    assert out["f"]["buckets"]["foxy"]["doc_count"] == 2


def test_global_agg(executor):
    out = agg(executor, {"all": {"global": {},
                                 "aggs": {"c": {"value_count": {"field": "qty"}}}},
                         "local": {"value_count": {"field": "qty"}}},
              query={"term": {"cat": "a"}})
    assert out["all"]["doc_count"] == 7
    assert out["all"]["c"]["value"] == 7
    assert out["local"]["value"] == 2


def test_missing_agg(executor):
    out = agg(executor, {"nocat": {"missing": {"field": "cat"}}})
    assert out["nocat"]["doc_count"] == 1
    out = agg(executor, {"noprice": {"missing": {"field": "price"}}})
    assert out["noprice"]["doc_count"] == 1


def test_cardinality(executor):
    out = agg(executor, {"c": {"cardinality": {"field": "cat"}},
                         "n": {"cardinality": {"field": "qty"}}})
    assert out["c"]["value"] == 3
    assert out["n"]["value"] == 7


def test_cardinality_under_terms(executor):
    out = agg(executor, {"cats": {"terms": {"field": "cat"},
                                  "aggs": {"brands": {"cardinality": {"field": "brand"}}}}})
    by_key = {b["key"]: b["brands"]["value"] for b in out["cats"]["buckets"]}
    assert by_key == {"a": 2, "b": 2, "c": 0}


def test_percentiles_exact(executor):
    out = agg(executor, {"p": {"percentiles": {"field": "price",
                                               "percents": [50, 90]}}})
    prices = np.array([10, 20, 30, 40, 50, 60], dtype=float)
    assert out["p"]["values"]["50.0"] == pytest.approx(np.percentile(prices, 50))
    assert out["p"]["values"]["90.0"] == pytest.approx(np.percentile(prices, 90))


def test_percentile_ranks(executor):
    out = agg(executor, {"p": {"percentile_ranks": {"field": "price",
                                                    "values": [30, 60]}}})
    assert out["p"]["values"]["30.0"] == pytest.approx(100 * 3 / 6)
    assert out["p"]["values"]["60.0"] == pytest.approx(100.0)


def test_weighted_avg(executor):
    out = agg(executor, {"w": {"weighted_avg": {"value": {"field": "price"},
                                                "weight": {"field": "qty"}}}})
    prices = np.array([10, 20, 30, 40, 50, 60], dtype=float)
    qtys = np.array([1, 2, 3, 4, 5, 6], dtype=float)
    assert out["w"]["value"] == pytest.approx(float((prices * qtys).sum() / qtys.sum()))


def test_median_absolute_deviation(executor):
    out = agg(executor, {"m": {"median_absolute_deviation": {"field": "price"}}})
    prices = np.array([10, 20, 30, 40, 50, 60], dtype=float)
    med = np.median(prices)
    assert out["m"]["value"] == pytest.approx(np.median(np.abs(prices - med)))


def test_stats_under_date_histogram(executor):
    out = agg(executor, {"m": {"date_histogram": {"field": "day",
                                                  "calendar_interval": "month"},
                               "aggs": {"s": {"stats": {"field": "price"}}}}})
    first = out["m"]["buckets"][0]["s"]
    assert first["count"] == 2 and first["sum"] == pytest.approx(30.0)


# ----------------------------------------------------------------- pipelines

def test_cumulative_sum_and_derivative(executor):
    out = agg(executor, {"m": {
        "date_histogram": {"field": "day", "calendar_interval": "month"},
        "aggs": {
            "sales": {"sum": {"field": "price"}},
            "cum": {"cumulative_sum": {"buckets_path": "sales"}},
            "diff": {"derivative": {"buckets_path": "sales"}},
        }}})
    buckets = out["m"]["buckets"]
    sales = [b["sales"]["value"] for b in buckets]
    assert sales == [30.0, 70.0, 110.0]
    assert [b["cum"]["value"] for b in buckets] == [30.0, 100.0, 210.0]
    assert "diff" not in buckets[0]
    assert buckets[1]["diff"]["value"] == pytest.approx(40.0)
    assert buckets[2]["diff"]["value"] == pytest.approx(40.0)


def test_sibling_pipelines(executor):
    out = agg(executor, {
        "m": {"date_histogram": {"field": "day", "calendar_interval": "month"},
              "aggs": {"sales": {"sum": {"field": "price"}}}},
        "avg_sales": {"avg_bucket": {"buckets_path": "m>sales"}},
        "max_sales": {"max_bucket": {"buckets_path": "m>sales"}},
        "total": {"sum_bucket": {"buckets_path": "m>sales"}},
    })
    assert out["avg_sales"]["value"] == pytest.approx(70.0)
    assert out["max_sales"]["value"] == pytest.approx(110.0)
    assert out["total"]["value"] == pytest.approx(210.0)


def test_bucket_script_and_selector(executor):
    out = agg(executor, {"cats": {
        "terms": {"field": "cat"},
        "aggs": {
            "p": {"sum": {"field": "price"}},
            "q": {"sum": {"field": "qty"}},
            "ratio": {"bucket_script": {"buckets_path": {"p": "p", "q": "q"},
                                        "script": "p / q"}},
            "keep": {"bucket_selector": {"buckets_path": {"c": "_count"},
                                         "script": "c >= 2"}},
        }}})
    buckets = out["cats"]["buckets"]
    assert all(b["doc_count"] >= 2 for b in buckets)
    keys = {b["key"] for b in buckets}
    assert keys == {"a", "b"}
    by_key = {b["key"]: b for b in buckets}
    assert by_key["a"]["ratio"]["value"] == pytest.approx(30.0 / 3.0)


def test_bucket_sort(executor):
    out = agg(executor, {"cats": {
        "terms": {"field": "cat", "order": {"_key": "asc"}},
        "aggs": {
            "p": {"sum": {"field": "price"}},
            "srt": {"bucket_sort": {"sort": [{"p": {"order": "desc"}}],
                                    "size": 2}},
        }}})
    buckets = out["cats"]["buckets"]
    assert [b["key"] for b in buckets] == ["b", "c"]


def test_agg_on_unmapped_field(executor):
    out = agg(executor, {"x": {"terms": {"field": "ghost"}},
                         "y": {"sum": {"field": "ghost"}}})
    assert out["x"]["buckets"] == []
    assert out["y"]["value"] == 0


# -------------------------------------------- identity rank -> bucket table

def _compiled(executor, spec):
    """(segment, device arrays, the plans `compile_aggs` gives it)."""
    from opensearch_tpu.search.aggs.engine import compile_aggs
    from opensearch_tpu.search.aggs.parse import parse_aggs
    from opensearch_tpu.search.compile import Compiler
    reader = executor.reader
    compiler = Compiler(reader.mapper, reader.stats())
    for seg, (arrays, meta) in zip(reader.segments, reader.device):
        yield seg, arrays, compile_aggs(parse_aggs(spec), reader.mapper,
                                        seg, meta, compiler)


def test_terms_on_a_numeric_column_reads_the_rank_column(executor):
    """ISSUE 32: the rank -> bucket table of `terms` on a numeric column
    is the identity, so its plan says so, carries no table, and the
    program takes the rank column for the bin: the answer is what the
    same level gathered through its identity table gives (the parent's
    program), on every one-chip route."""
    import jax
    import jax.numpy as jnp
    from dataclasses import replace
    from opensearch_tpu.search.aggs.engine import (BINS_RANK, BINS_TABLE,
                                                   eval_aggs)
    spec = {"q": {"terms": {"field": "qty", "order": {"_key": "asc"}},
                  "aggs": {"p": {"sum": {"field": "price"}}}}}
    got = agg(executor, spec)["q"]["buckets"]
    assert [(b["key"], b["doc_count"], b["p"]["value"]) for b in got] \
        == [(d["qty"], 1, d.get("price", 0.0)) for d in DOCS]
    for seg, arrays, (plan,) in _compiled(executor, spec):
        assert plan.kind == "bucket_num" and plan.static[3] == BINS_RANK
        assert plan.inputs == {}
        n = len(seg.numeric_dv["qty"].unique)
        table = np.full(8, -1, np.int32)
        table[:n] = np.arange(n)
        parent = replace(plan, static=plan.static[:3] + (BINS_TABLE,),
                         inputs={"table": table})

        def run(p):
            outs = []
            flat = jax.tree_util.tree_map(jnp.asarray, p.flatten_inputs([]))
            eval_aggs([p], arrays, flat, [0], arrays["live"], outs)
            return outs
        mine, theirs = run(plan), run(parent)
        assert jax.tree_util.tree_structure(mine) \
            == jax.tree_util.tree_structure(theirs)
        for a, b in zip(jax.tree_util.tree_leaves(mine),
                        jax.tree_util.tree_leaves(theirs)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("spec,identity", [
    ({"histogram": {"field": "qty", "interval": 1}}, True),
    ({"histogram": {"field": "qty", "interval": 2}}, False),
    ({"date_histogram": {"field": "day", "calendar_interval": "month"}},
     False)],
    ids=["every-value-its-own-bucket", "two-values-a-bucket", "months"])
def test_a_table_is_the_identity_by_what_it_holds(spec, identity):
    """Decided from the table the compiler just built, not from the
    aggregation's type: a histogram whose every unique value opens its
    own bucket is the identity too; any other names the slot of its
    segment's resident lane -> bin vector and carries no table either (a
    sub-aggregation keeps these off the fused root-leaf kinds)."""
    from opensearch_tpu.search.aggs.engine import (BINS_RANK, bins_slot,
                                                   resident_levels)
    ex = build_executor()
    spec = {"h": dict(spec, aggs={"p": {"sum": {"field": "price"}}})}
    for seg, arrays, (plan,) in _compiled(ex, spec):
        assert plan.kind == "bucket_num" and plan.bins_key is not None
        assert plan.static[3] == (BINS_RANK if identity else 0)
        assert bins_slot(plan) == (None if identity else 0)
        assert list(resident_levels([plan])) == ([] if identity else [plan])
        assert plan.inputs == {}
    field = next(iter(spec["h"].values()))["field"]
    buckets = agg(ex, spec)["h"]["buckets"]
    assert sum(b["doc_count"] for b in buckets) \
        == sum(1 for d in DOCS if field in d)
    assert sum(b["p"]["value"] for b in buckets) \
        == sum(d.get("price", 0.0) for d in DOCS if field in d)


RESIDENT_SPECS = {
    "histogram>stats": {"h": {
        "histogram": {"field": "qty", "interval": 2},
        "aggs": {"p": {"stats": {"field": "price"}}}}},
    "months>sum": {"h": {
        "date_histogram": {"field": "day", "calendar_interval": "month"},
        "aggs": {"p": {"sum": {"field": "price"}}}}},
    "terms>histogram>avg+a-sibling": {
        "t": {"terms": {"field": "cat"}, "aggs": {"h": {
            "histogram": {"field": "qty", "interval": 3},
            "aggs": {"p": {"avg": {"field": "price"}}}}}},
        "m": {"date_histogram": {"field": "day", "fixed_interval": "2d"},
              "aggs": {"q": {"max": {"field": "qty"}}}}},
}


@pytest.mark.parametrize("spec", list(RESIDENT_SPECS.values()),
                         ids=list(RESIDENT_SPECS))
def test_a_resident_level_feeds_the_bins_its_table_gathered(spec):
    """ISSUE 36: the program that reads `seg["lane_bins"][slot]` returns,
    bit for bit, what the same level gathered through its table a request
    returns (the parent's program): same bins into the same reductions,
    float sums included; slots in the order the plans are walked."""
    import jax
    import jax.numpy as jnp
    from dataclasses import replace
    from opensearch_tpu.search.aggs.engine import (BINS_TABLE, bins_slot,
                                                   eval_aggs,
                                                   resident_levels)
    ex = build_executor()
    reader = ex.reader

    def through_tables(p):
        kids = [through_tables(c) for c in p.children]
        if bins_slot(p) is None:
            return replace(p, children=kids)
        return replace(p, children=kids, inputs={"table": p.table_of()},
                       static=p.static[:3] + (BINS_TABLE,) + p.static[4:])

    def run(plans, seg_in):
        outs = []
        flat = []
        for p in plans:
            p.flatten_inputs(flat)
        flat = jax.tree_util.tree_map(jnp.asarray, flat)
        eval_aggs(plans, seg_in, flat, [0], seg_in["live"], outs)
        return outs
    for (seg, arrays, plans), (_, meta) in zip(_compiled(ex, spec),
                                               reader.device):
        levels = list(resident_levels(plans))
        assert [bins_slot(p) for p in levels] == list(range(len(levels)))
        assert len(levels) == sum(
            "histogram" in k for k in json.dumps(spec).split('"'))
        seg_in = reader.with_lane_bins(arrays, meta, plans)
        assert len(seg_in["lane_bins"]) == len(levels)
        assert "lane_bins" not in arrays
        mine = run(plans, seg_in)
        theirs = run([through_tables(p) for p in plans], arrays)
        assert jax.tree_util.tree_structure(mine) \
            == jax.tree_util.tree_structure(theirs)
        for a, b in zip(jax.tree_util.tree_leaves(mine),
                        jax.tree_util.tree_leaves(theirs)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and served: the REST answer of the same tree
    out = agg(ex, spec)
    for name in spec:
        assert sum(b["doc_count"] for b in out[name]["buckets"]) > 0


def test_two_bucketings_of_a_field_do_not_share_a_signature():
    """A level that reads a resident vector carries no table whose
    content could tell two bucketings of one `card` apart: its signature
    holds what the vector is keyed by, so a `_msearch` group (one
    program run, one vector a slot) never mixes them."""
    ex = build_executor()

    def sigs(interval, offset):
        spec = {"h": {"histogram": {"field": "qty", "interval": interval,
                                    "offset": offset},
                      "aggs": {"p": {"sum": {"field": "price"}}}}}
        return [(plan.static, plan.sig())
                for _, _, (plan,) in _compiled(ex, spec)]
    for (static_a, sig_a), (static_b, sig_b) in zip(sigs(2, 0), sigs(2, 1)):
        assert static_a == static_b     # same card, same slot
        assert sig_a != sig_b
    assert sigs(2, 0) == sigs(2, 0)
