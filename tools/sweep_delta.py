"""Sweep delta: re-run the crash-fixed YAML suites + the search-pipeline
suite and FAIL on any 5xx.

The full reference YAML sweep (tools/yaml_sweep.py) needs the reference
checkout at /root/reference; this tool pins the three suites whose
round-5 sweep failures were 500-class crashes:

  search.aggregation/70_adjacency_matrix.yml  — TypeError: '<' not
      supported (non-string agg/filter keys from YAML's unquoted numeric
      mapping keys)
  search/110_field_collapsing.yml             — TypeError: InternalEngine
      .index() got an unexpected keyword argument 'external_version'
      (the suite's setup indexes with ?version_type=external)
  search/250_distance_feature.yml             — TypeError: float() on a
      geo origin (distance_feature on geo_point)

Each suite below reproduces the reference suite's do-steps in-process
(the checkout is not required), plus a new search-pipeline suite covering
the subsystem end-to-end. Any response >= 500 fails the run. Wired into
tier-1 as tests/test_sweep_delta.py (non-slow). When /root/reference IS
present, the real YAML files for the three suites are executed as well
(5xx check only — match assertions stay tools/yaml_sweep.py's job).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "tests"))


def _fresh_node():
    from opensearch_tpu.node import Node
    return Node()


def _do(node, results, method, path, body=None, **params):
    """One do-step through the in-process REST dispatch (dict bodies pass
    through RestRequest exactly like the YAML runner hands them over)."""
    from opensearch_tpu.rest.controller import RestRequest
    raw = None
    if isinstance(body, (str, bytes)):
        raw = body.encode() if isinstance(body, str) else body
        body = None
    req = RestRequest(method=method, path=path,
                      params={k: str(v) for k, v in params.items()},
                      body=body, raw_body=raw)
    resp = node.controller.dispatch(req)
    results.append((f"{method} {path}", resp.status, resp.body))
    return resp


def _bulk_lines(*pairs):
    return "\n".join(json.dumps(line) for line in pairs) + "\n"


# --------------------------------------------------------------- suites

def suite_adjacency_matrix():
    """search.aggregation/70_adjacency_matrix.yml: filter intersections,
    including the unquoted-numeric-filter-name shape YAML produces."""
    node = _fresh_node()
    results = []
    _do(node, results, "PUT", "/test",
        {"settings": {"number_of_shards": 1},
         "mappings": {"properties": {"num": {"type": "integer"}}}})
    _do(node, results, "POST", "/_bulk", _bulk_lines(
        {"index": {"_index": "test", "_id": "1"}}, {"num": [1, 2]},
        {"index": {"_index": "test", "_id": "2"}}, {"num": [2, 3]},
        {"index": {"_index": "test", "_id": "3"}}, {"num": [3, 4]}),
        refresh="true")
    _do(node, results, "POST", "/test/_search",
        {"size": 0, "aggs": {"conns": {"adjacency_matrix": {"filters": {
            "f1": {"term": {"num": 1}},
            "f2": {"term": {"num": 2}},
            "f4": {"term": {"num": 4}}}}}}},
        rest_total_hits_as_int="true")
    # the crash shape: pyyaml parses unquoted numeric mapping keys as
    # ints, which reached the agg path as non-string dict keys
    _do(node, results, "POST", "/test/_search",
        {"size": 0, "aggs": {"conns": {"adjacency_matrix": {"filters": {
            1: {"term": {"num": 1}},
            2: {"term": {"num": 2}},
            "f4": {"term": {"num": 4}}}}}}})
    # "Terms lookup" section: the lookup shape is unsupported — must be a
    # 4xx parsing error, never a 500
    _do(node, results, "POST", "/test/_search",
        {"size": 0, "aggs": {"conns": {"adjacency_matrix": {"filters": {
            "lookup": {"terms": {"num": {"index": "lkp", "id": "1",
                                         "path": "nums"}}}}}}}})
    return results


def suite_field_collapsing():
    """search/110_field_collapsing.yml: the setup indexes every doc with
    an EXTERNAL version (?version_type=external) — the round-5 crash —
    then collapses on numeric_group."""
    node = _fresh_node()
    results = []
    _do(node, results, "PUT", "/test",
        {"mappings": {"properties": {"numeric_group": {"type":
                                                       "integer"}}}})
    docs = [("1", {"numeric_group": 1, "sort": 10}, 11),
            ("2", {"numeric_group": 1, "sort": 6}, 22),
            ("3", {"numeric_group": 1, "sort": 24}, 33),
            ("4", {"numeric_group": 25, "sort": 10}, 44),
            ("5", {"numeric_group": 25, "sort": 5}, 55),
            ("6", {"numeric_group": 25, "sort": 8}, 66)]
    for doc_id, body, version in docs:
        _do(node, results, "POST", f"/test/_doc/{doc_id}", body,
            version=version, version_type="external")
    _do(node, results, "POST", "/test/_refresh")
    _do(node, results, "POST", "/test/_search",
        {"collapse": {"field": "numeric_group"},
         "sort": [{"sort": "desc"}], "version": True})
    _do(node, results, "POST", "/test/_search",
        {"collapse": {"field": "numeric_group"},
         "sort": [{"sort": "desc"}], "from": 2})
    return results


def suite_distance_feature():
    """search/250_distance_feature.yml: the geo_point section (TypeError:
    float() on the [lon, lat] origin) plus the numeric/date sections."""
    node = _fresh_node()
    results = []
    _do(node, results, "PUT", "/index1",
        {"mappings": {"properties": {
            "location": {"type": "geo_point"},
            "date": {"type": "date"},
            "population": {"type": "integer"}}}})
    _do(node, results, "POST", "/_bulk", _bulk_lines(
        {"index": {"_index": "index1", "_id": "1"}},
        {"location": [-71.34, 41.12], "date": "2018-02-01",
         "population": 1000},
        {"index": {"_index": "index1", "_id": "2"}},
        {"location": [-71.30, 41.15], "date": "2018-03-01",
         "population": 3000},
        {"index": {"_index": "index1", "_id": "3"}},
        {"location": [-71.35, 41.12], "date": "2018-02-15",
         "population": 2000}), refresh="true")
    for origin in ([-71.35, 41.12], "41.12,-71.35",
                   {"lat": 41.12, "lon": -71.35}):
        _do(node, results, "POST", "/index1/_search",
            {"query": {"distance_feature": {
                "field": "location", "pivot": "1km", "origin": origin}}})
    _do(node, results, "POST", "/index1/_search",
        {"query": {"distance_feature": {
            "field": "population", "pivot": 500, "origin": 1000}}})
    _do(node, results, "POST", "/index1/_search",
        {"query": {"distance_feature": {
            "field": "date", "pivot": "7d", "origin": "2018-02-15"}}})
    return results


def suite_search_pipeline():
    """New subsystem suite: pipeline CRUD + processors + hybrid query
    through ?search_pipeline= and the index default setting."""
    node = _fresh_node()
    results = []
    _do(node, results, "PUT", "/sp",
        {"settings": {"number_of_shards": 2},
         "mappings": {"properties": {
             "title": {"type": "text"},
             "color": {"type": "keyword"},
             "vec": {"type": "knn_vector", "dimension": 4,
                     "method": {"space_type": "l2"}}}}})
    _do(node, results, "POST", "/_bulk", _bulk_lines(
        {"index": {"_index": "sp", "_id": "1"}},
        {"title": "red fox", "color": "red", "vec": [1, 0, 0, 0]},
        {"index": {"_index": "sp", "_id": "2"}},
        {"title": "brown dog", "color": "brown", "vec": [0, 1, 0, 0]},
        {"index": {"_index": "sp", "_id": "3"}},
        {"title": "red dog", "color": "red", "vec": [0.9, 0.2, 0, 0]},
        {"index": {"_index": "sp", "_id": "4"}},
        {"title": "blue cat", "color": "blue", "vec": [0, 0, 1, 0]}),
        refresh="true")
    _do(node, results, "PUT", "/_search/pipeline/hybrid-pipe", {
        "request_processors": [
            {"filter_query": {"query": {"terms": {
                "color": ["red", "brown", "blue"]}}}},
            {"oversample": {"sample_factor": 2.0}}],
        "phase_results_processors": [{"normalization-processor": {
            "normalization": {"technique": "min_max"},
            "combination": {"technique": "arithmetic_mean",
                            "parameters": {"weights": [0.4, 0.6]}}}}],
        "response_processors": [
            {"rename_field": {"field": "color",
                              "target_field": "colour"}},
            {"truncate_hits": {}}]})
    _do(node, results, "GET", "/_search/pipeline")
    _do(node, results, "GET", "/_search/pipeline/hybrid-pipe")
    hybrid_body = {"query": {"hybrid": {"queries": [
        {"match": {"title": "red"}},
        {"knn": {"vec": {"vector": [1, 0, 0, 0], "k": 3}}}]}},
        "size": 2}
    _do(node, results, "POST", "/sp/_search", hybrid_body,
        search_pipeline="hybrid-pipe")
    _do(node, results, "POST", "/sp/_search", hybrid_body)
    _do(node, results, "PUT", "/sp/_settings",
        {"index": {"search": {"default_pipeline": "hybrid-pipe"}}})
    _do(node, results, "POST", "/sp/_search", hybrid_body)
    # l2 + geometric variant, and an empty sub-query edge case
    _do(node, results, "PUT", "/_search/pipeline/l2-pipe", {
        "phase_results_processors": [{"normalization-processor": {
            "normalization": {"technique": "l2"},
            "combination": {"technique": "geometric_mean"}}}]})
    _do(node, results, "POST", "/sp/_search",
        {"query": {"hybrid": {"queries": [
            {"match": {"title": "nosuchterm"}},
            {"knn": {"vec": {"vector": [0, 0, 1, 0], "k": 2}}}]}}},
        search_pipeline="l2-pipe")
    # error contract: bad shapes must be 4xx, never 5xx
    _do(node, results, "POST", "/sp/_search",
        {"query": {"bool": {"must": [{"hybrid": {"queries": [
            {"match_all": {}}]}}]}}})
    _do(node, results, "POST", "/sp/_search",
        {"query": {"hybrid": {"queries": []}}})
    _do(node, results, "POST", "/sp/_search", hybrid_body,
        search_pipeline="missing-pipe")
    _do(node, results, "DELETE", "/_search/pipeline/l2-pipe")
    _do(node, results, "GET", "/_search/pipeline/l2-pipe")
    return results


SUITES = {
    "search.aggregation/70_adjacency_matrix.yml": suite_adjacency_matrix,
    "search/110_field_collapsing.yml": suite_field_collapsing,
    "search/250_distance_feature.yml": suite_distance_feature,
    "search.pipeline/10_pipeline_crud_and_hybrid.yml":
        suite_search_pipeline,
}


def run_reference_suites():
    """When the reference checkout is present, additionally run the real
    YAML files of the three fixed suites, checking 5xx only."""
    try:
        import yaml_rest_runner as yr
    except ImportError:
        return []
    if not yr.available():
        return []
    from opensearch_tpu.node import Node
    failures = []
    for suite in ("search.aggregation/70_adjacency_matrix.yml",
                  "search/110_field_collapsing.yml",
                  "search/250_distance_feature.yml"):
        path = os.path.join(yr.TEST_DIR, suite)
        if not os.path.exists(path):
            continue
        setup, _teardown, tests = yr.load_suite(path)
        for name, steps in tests:
            node = Node()
            try:
                yr.run_case(node, setup, steps)
            except yr.SkipTest:
                continue
            except Exception as e:
                msg = str(e)
                if "-> 5" in msg or "500" in msg.split(":")[0]:
                    failures.append(f"{suite}::{name}: {msg[:160]}")
    return failures


def run_all():
    """Returns (report dict, failures list). A failure is any response
    with status >= 500."""
    report = {}
    failures = []
    for suite, fn in SUITES.items():
        results = fn()
        statuses = [status for _, status, _ in results]
        report[suite] = statuses
        for step, status, body in results:
            if status >= 500:
                failures.append(
                    f"{suite} [{step}] -> {status}: "
                    f"{json.dumps(body, default=str)[:200]}")
    failures.extend(run_reference_suites())
    return report, failures


def main():
    # a correctness sweep: pinned to the CPU backend, before jax loads
    os.environ["JAX_PLATFORMS"] = "cpu"
    report, failures = run_all()
    for suite, statuses in report.items():
        print(f"{'FAIL' if any(s >= 500 for s in statuses) else 'OK  '} "
              f"{suite} statuses={statuses}")
    if failures:
        print(f"\n{len(failures)} 5xx failure(s):")
        for f in failures:
            print(" ", f)
        sys.exit(1)
    print("\nno 5xx — sweep delta clean")


if __name__ == "__main__":
    main()
