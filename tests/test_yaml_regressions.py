"""Pinned regressions for the three 500-class crashes the round-5 YAML
sweep surfaced. The reference checkout isn't present
in CI, so each failing suite's do-steps are reproduced in-process with
the reference's expected results asserted — these must stay green even
when /root/reference is absent (tools/sweep_delta.py re-runs the real
YAML files when it is).
"""

import json

import pytest

from opensearch_tpu.node import Node
from opensearch_tpu.rest.controller import RestRequest


def _dispatch(node, method, path, body, **params):
    """Hand a python dict straight to dispatch — the YAML runner's path,
    where pyyaml's unquoted numeric mapping keys arrive as ints."""
    return node.controller.dispatch(RestRequest(
        method=method, path=path,
        params={k: str(v) for k, v in params.items()}, body=body))


def _bulk(node, *pairs, **params):
    raw = "\n".join(json.dumps(p) for p in pairs) + "\n"
    return node.request("POST", "/_bulk", raw, **params)


# ------------------------- search.aggregation/70_adjacency_matrix.yml

def _adjacency_node():
    node = Node()
    node.request("PUT", "/test", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"num": {"type": "integer"}}}})
    _bulk(node,
          {"index": {"_index": "test", "_id": "1"}}, {"num": [1, 2]},
          {"index": {"_index": "test", "_id": "2"}}, {"num": [2, 3]},
          {"index": {"_index": "test", "_id": "3"}}, {"num": [3, 4]},
          refresh="true")
    return node


def test_adjacency_matrix_filters_intersections():
    node = _adjacency_node()
    res = node.request("POST", "/test/_search", {
        "size": 0, "aggs": {"conns": {"adjacency_matrix": {"filters": {
            "f1": {"term": {"num": 1}},
            "f2": {"term": {"num": 2}},
            "f4": {"term": {"num": 4}}}}}}})
    assert res["_status"] == 200
    assert res["hits"]["total"]["value"] == 3
    buckets = res["aggregations"]["conns"]["buckets"]
    assert buckets == [{"key": "f1", "doc_count": 1},
                       {"key": "f1&f2", "doc_count": 1},
                       {"key": "f2", "doc_count": 2},
                       {"key": "f4", "doc_count": 1}]


def test_adjacency_matrix_numeric_filter_names_no_500():
    """The crash shape: unquoted numeric YAML mapping keys reach the agg
    path as int dict keys → `TypeError: '<' not supported between
    instances of 'str' and 'int'` (a 500) before the fix. Keys must
    normalize to their JSON string forms."""
    node = _adjacency_node()
    out = _dispatch(node, "POST", "/test/_search", {
        "size": 0, "aggs": {"conns": {"adjacency_matrix": {"filters": {
            1: {"term": {"num": 1}},
            2: {"term": {"num": 2}},
            "f4": {"term": {"num": 4}}}}}}})
    assert out.status == 200, out.body
    buckets = out.body["aggregations"]["conns"]["buckets"]
    assert buckets == [{"key": "1", "doc_count": 1},
                       {"key": "1&2", "doc_count": 1},
                       {"key": "2", "doc_count": 2},
                       {"key": "f4", "doc_count": 1}]


def test_adjacency_matrix_terms_lookup_is_4xx():
    node = _adjacency_node()
    res = node.request("POST", "/test/_search", {
        "size": 0, "aggs": {"conns": {"adjacency_matrix": {"filters": {
            "lkp": {"terms": {"num": {"index": "lookup", "id": "1",
                                      "path": "nums"}}}}}}}})
    assert 400 <= res["_status"] < 500


# --------------------------------- search/110_field_collapsing.yml

def _collapsing_node():
    """The suite's setup: every doc indexed with version_type=external —
    this indexing path raised `TypeError: InternalEngine.index() got an
    unexpected keyword argument 'external_version'` before the fix."""
    node = Node()
    node.request("PUT", "/test", {"mappings": {"properties": {
        "numeric_group": {"type": "integer"}}}})
    docs = [("1", {"numeric_group": 1, "sort": 10}, 11),
            ("2", {"numeric_group": 1, "sort": 6}, 22),
            ("3", {"numeric_group": 1, "sort": 24}, 33),
            ("4", {"numeric_group": 25, "sort": 10}, 44),
            ("5", {"numeric_group": 25, "sort": 5}, 55),
            ("6", {"numeric_group": 25, "sort": 8}, 66)]
    for doc_id, body, version in docs:
        res = node.request("POST", f"/test/_doc/{doc_id}", body,
                           version=version, version_type="external")
        assert res["_status"] == 201, res
        assert res["_version"] == version
    node.request("POST", "/test/_refresh")
    return node


def test_field_collapsing_external_version_indexing_and_collapse():
    node = _collapsing_node()
    res = node.request("POST", "/test/_search", {
        "collapse": {"field": "numeric_group"},
        "sort": [{"sort": "desc"}], "version": True})
    assert res["_status"] == 200
    hits = res["hits"]["hits"]
    assert res["hits"]["total"]["value"] == 6
    # best (highest `sort`) doc of each numeric_group, page in sort order:
    # group 1 → d3 (24), group 25 → d4 (10)
    assert [h["_id"] for h in hits] == ["3", "4"]
    assert [h["sort"] for h in hits] == [[24], [10]]
    # external versions round-trip into the rendered hits
    assert [h["_version"] for h in hits] == [33, 44]


def test_field_collapsing_from():
    node = _collapsing_node()
    res = node.request("POST", "/test/_search", {
        "collapse": {"field": "numeric_group"},
        "sort": [{"sort": "desc"}], "from": 1, "size": 5})
    assert res["_status"] == 200
    assert [h["_id"] for h in res["hits"]["hits"]] == ["4"]


def test_external_version_conflict_and_update_rejection():
    node = _collapsing_node()
    res = node.request("POST", "/test/_doc/1", {"numeric_group": 9},
                       version=5, version_type="external")
    assert res["_status"] == 409
    # external versioning on _update is a 400 (reference: UpdateRequest
    # validation), not a 500
    res = node.request("POST", "/test/_update/1",
                       {"doc": {"numeric_group": 9}},
                       version=99, version_type="external")
    assert res["_status"] == 400


# --------------------------------- search/250_distance_feature.yml

def _distance_node():
    node = Node()
    node.request("PUT", "/index1", {"mappings": {"properties": {
        "location": {"type": "geo_point"},
        "population": {"type": "integer"}}}})
    _bulk(node,
          {"index": {"_index": "index1", "_id": "1"}},
          {"location": [-71.34, 41.12], "population": 1000},
          {"index": {"_index": "index1", "_id": "2"}},
          {"location": [-71.30, 41.15], "population": 3000},
          {"index": {"_index": "index1", "_id": "3"}},
          {"location": [-71.35, 41.12], "population": 2000},
          refresh="true")
    return node


@pytest.mark.parametrize("origin", [[-71.35, 41.12], "41.12,-71.35",
                                    {"lat": 41.12, "lon": -71.35}])
def test_distance_feature_on_geo_point(origin):
    """`TypeError: float() argument must be a string or a real number,
    not 'list'` (a 500) before the fix — every geo-point origin wire
    shape must work, ranked nearest-first."""
    node = _distance_node()
    res = node.request("POST", "/index1/_search", {
        "query": {"distance_feature": {
            "field": "location", "pivot": "1km", "origin": origin}}})
    assert res["_status"] == 200, res
    hits = res["hits"]["hits"]
    assert [h["_id"] for h in hits] == ["3", "1", "2"]
    # doc 3 sits exactly at the origin: score = boost·pivot/(pivot+0) = 1
    assert hits[0]["_score"] == pytest.approx(1.0, rel=1e-5)
    assert hits[0]["_score"] > hits[1]["_score"] > hits[2]["_score"]


def test_distance_feature_geo_in_bool_should():
    """The suite's other geo section: distance_feature as a should clause
    boosting an otherwise-constant filter ranking."""
    node = _distance_node()
    res = node.request("POST", "/index1/_search", {
        "query": {"bool": {
            "filter": [{"range": {"population": {"gte": 0}}}],
            "should": [{"distance_feature": {
                "field": "location", "pivot": "1km",
                "origin": [-71.35, 41.12]}}]}}})
    assert res["_status"] == 200
    assert [h["_id"] for h in res["hits"]["hits"]] == ["3", "1", "2"]
