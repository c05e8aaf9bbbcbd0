"""Chaos matrix for the fault-injection subsystem (common/faults.py).

Seeded fault schedules × {single search, msearch B∈{1,32}, hybrid, aggs}
asserting the partial-failure contract end to end:

  - one shard's fault costs ONE `_shards.failures[]` entry, not the
    request (pinned regression: per-shard 500 → partial-200);
  - msearch faults downgrade only the affected items to per-item error
    objects — the envelope and sibling items are untouched;
  - transient faults recover through the bounded retry helper
    (`search.retry_success` accounting included);
  - timeouts render `timed_out: true` with accumulated hits and stop
    launching new phases; `_tasks/_cancel` terminates at a boundary;
  - with injection disabled the engine's behavior is BIT-IDENTICAL
    (differential check) and `faults.ENABLED` stays False.

The surviving-shard differential uses the actual shard partition (doc
ids read from shard segments) as the oracle: a partial response must
equal the unfaulted response restricted to surviving shards.
"""

import json
import threading
import time

import pytest

from opensearch_tpu.common import faults
from opensearch_tpu.common import retry as retry_mod
from opensearch_tpu.common.errors import TransientFault
from opensearch_tpu.node import Node
from opensearch_tpu.telemetry import TELEMETRY


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()


def _counter(name: str) -> int:
    return TELEMETRY.metrics.to_dict()["counters"].get(name, 0)


def _mk_node(n_shards=3, n_docs=30, index="logs"):
    node = Node()
    node.request("PUT", f"/{index}", {
        "settings": {"number_of_shards": n_shards},
        "mappings": {"properties": {
            "msg": {"type": "text"},
            "level": {"type": "keyword"},
            "code": {"type": "integer"},
        }}})
    lines = []
    for i in range(n_docs):
        lines.append(json.dumps({"index": {"_index": index,
                                           "_id": f"d{i}"}}))
        lines.append(json.dumps({
            "msg": f"error in module {i}" if i % 2 else f"ok module {i}",
            "level": "error" if i % 2 else "info", "code": i}))
    r = node.request("POST", "/_bulk", "\n".join(lines) + "\n",
                     refresh="true")
    assert r["_status"] == 200 and not r["errors"]
    return node


def _shard_ids(node, index="logs"):
    """Doc ids per shard, read from the actual shard segments."""
    out = []
    for shard in node.indices.get(index).shards:
        ids = []
        for seg in shard.executor.reader.segments:
            ids.extend(seg.doc_ids[o] for o in range(seg.num_docs)
                       if seg.live[o])
        out.append(ids)
    return out


def _hit_map(resp):
    return {h["_id"]: h["_score"] for h in resp["hits"]["hits"]}


QUERY = {"query": {"match": {"msg": "module"}}, "size": 30}


# ------------------------------------------------------------ REST control

def test_fault_rule_validation():
    node = Node()
    r = node.request("POST", "/_fault_injection",
                     {"site": "nope", "kind": "exception"})
    assert r["_status"] == 400
    r = node.request("POST", "/_fault_injection",
                     {"site": "query.shard", "kind": "nope"})
    assert r["_status"] == 400
    r = node.request("POST", "/_fault_injection",
                     {"site": "query.shard", "kind": "delay",
                      "bogus_key": 1})
    assert r["_status"] == 400
    assert faults.ENABLED is False      # nothing installed by rejects
    r = node.request("GET", "/_fault_injection")
    assert r["_status"] == 200 and r["enabled"] is False
    assert r["rules"] == [] and "query.shard" in r["sites"]


def test_fault_install_snapshot_clear():
    node = Node()
    r = node.request("POST", "/_fault_injection",
                     {"site": "query.shard", "kind": "exception",
                      "max_fires": 2})
    assert r["_status"] == 200 and r["enabled"] is True
    assert faults.ENABLED is True
    snap = node.request("GET", "/_fault_injection")
    assert snap["rules"][0]["site"] == "query.shard"
    assert snap["rules"][0]["fires"] == 0
    r = node.request("DELETE", "/_fault_injection/query.shard")
    assert r["removed"] == 1 and r["enabled"] is False
    assert faults.ENABLED is False


# ------------------------------------------- partial-failure isolation

def test_single_shard_query_fault_partial_200():
    """PINNED REGRESSION (ISSUE 6): one shard's query-phase exception used
    to 500 the whole request; it must now return 200 with that shard's
    slice missing, `_shards.failed == 1`, and a reference-shaped
    failures[] entry — hits from the surviving shards are bit-identical
    to the unfaulted run (the differential oracle)."""
    node = _mk_node(n_shards=3)
    clean = node.request("POST", "/logs/_search", QUERY)
    assert clean["_status"] == 200 and clean["_shards"]["failed"] == 0

    faults.install({"site": "query.shard", "kind": "exception",
                    "max_fires": 1})
    r = node.request("POST", "/logs/_search", QUERY)
    assert r["_status"] == 200
    assert r["_shards"]["total"] == 3
    assert r["_shards"]["failed"] == 1
    assert r["_shards"]["successful"] == 2
    (failure,) = r["_shards"]["failures"]
    assert failure["index"] == "logs"
    assert failure["reason"]["type"] == "injected_fault_exception"
    failed_shard = failure["shard"]
    surviving = set()
    for si, ids in enumerate(_shard_ids(node)):
        if si != failed_shard:
            surviving.update(ids)
    clean_hits = _hit_map(clean)
    want = {d: s for d, s in clean_hits.items() if d in surviving}
    assert _hit_map(r) == want
    assert r["hits"]["total"]["value"] < clean["hits"]["total"]["value"]


def test_all_shards_failed_is_typed_error():
    node = _mk_node(n_shards=3)
    faults.install({"site": "query.shard", "kind": "exception"})
    r = node.request("POST", "/logs/_search", QUERY)
    assert r["_status"] == 503
    assert r["error"]["type"] == "search_phase_execution_exception"
    assert "all shards failed" in r["error"]["reason"]
    assert len(r["error"]["failed_shards"]) == 3


def test_allow_partial_false_rejects_with_typed_error():
    node = _mk_node(n_shards=3)
    faults.install({"site": "query.shard", "kind": "exception",
                    "max_fires": 1})
    r = node.request("POST", "/logs/_search",
                     {**QUERY, "allow_partial_search_results": False})
    assert r["_status"] == 503
    assert r["error"]["type"] == "search_phase_execution_exception"
    assert "Partial shards failure" in r["error"]["reason"]


def test_allow_partial_cluster_setting_default():
    node = _mk_node(n_shards=3)
    node.request("PUT", "/_cluster/settings", {"transient": {
        "search.default_allow_partial_results": "false"}})
    faults.install({"site": "query.shard", "kind": "exception",
                    "max_fires": 1})
    r = node.request("POST", "/logs/_search", QUERY)
    assert r["_status"] == 503
    # per-request body key overrides the cluster default
    faults.clear()
    faults.install({"site": "query.shard", "kind": "exception",
                    "max_fires": 1})
    r = node.request("POST", "/logs/_search",
                     {**QUERY, "allow_partial_search_results": True})
    assert r["_status"] == 200 and r["_shards"]["failed"] == 1


def test_canmatch_fault_degrades_to_dont_skip():
    """A can-match failure is an optimization failure: the shard executes
    anyway and the response is identical to the unfaulted run."""
    node = _mk_node(n_shards=3)
    body = {"query": {"range": {"code": {"gte": 0}}}, "size": 30}
    clean = node.request("POST", "/logs/_search", body)
    faults.install({"site": "canmatch.shard", "kind": "exception"})
    r = node.request("POST", "/logs/_search", body)
    assert r["_status"] == 200 and r["_shards"]["failed"] == 0
    assert _hit_map(r) == _hit_map(clean)


def test_fetch_fault_drops_only_that_shards_page_hits():
    node = _mk_node(n_shards=3)
    clean = node.request("POST", "/logs/_search", QUERY)
    faults.install({"site": "fetch.gather", "kind": "exception",
                    "skip": 1, "max_fires": 1})
    r = node.request("POST", "/logs/_search", QUERY)
    assert r["_status"] == 200
    assert r["_shards"]["failed"] == 1
    assert len(r["_shards"]["failures"]) == 1
    # every hit that DID render matches the clean run exactly
    clean_hits = _hit_map(clean)
    for d, s in _hit_map(r).items():
        assert clean_hits[d] == s
    assert len(r["hits"]["hits"]) < len(clean["hits"]["hits"])


def test_aggs_reduce_fault_is_clean_typed_error():
    """Coordinator agg reduce has no per-shard slice to degrade to: the
    outcome must be a clean typed error, never a corrupt agg tree."""
    node = _mk_node(n_shards=3)
    body = {"query": {"match_all": {}}, "size": 0,
            "aggs": {"lv": {"terms": {"field": "level"}}}}
    faults.install({"site": "reduce.aggs", "kind": "exception"})
    r = node.request("POST", "/logs/_search", body)
    assert r["_status"] == 500
    assert r["error"]["type"] == "injected_fault_exception"
    assert "aggregations" not in r


def test_request_cache_faults_degrade_to_miss():
    node = _mk_node(n_shards=3)
    body = {"query": {"match": {"msg": "module"}}, "size": 0,
            "aggs": {"lv": {"terms": {"field": "level"}}}}
    clean = node.request("POST", "/logs/_search", body)
    faults.install({"site": "request_cache.get", "kind": "exception"})
    faults.install({"site": "request_cache.put", "kind": "exception"})
    r = node.request("POST", "/logs/_search", body)
    assert r["_status"] == 200 and r["_shards"]["failed"] == 0
    assert r["aggregations"] == clean["aggregations"]
    assert r["hits"]["total"] == clean["hits"]["total"]


# ---------------------------------------------------- transient + retry

def test_transient_fault_retried_to_full_response():
    node = _mk_node(n_shards=3)
    clean = node.request("POST", "/logs/_search", QUERY)
    before = _counter("search.retry_success")
    faults.install({"site": "query.dispatch", "kind": "transient"})
    r = node.request("POST", "/logs/_search", QUERY)
    assert r["_status"] == 200
    assert r["_shards"]["failed"] == 0
    assert _hit_map(r) == _hit_map(clean)
    assert _counter("search.retry_success") >= before + 1


def test_retry_helper_policy():
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise TransientFault("blip")
        return "ok"
    assert retry_mod.call_with_retry(flaky) == "ok"
    assert calls[0] == 3

    # non-transient exceptions never retry
    calls[0] = 0

    def hard():
        calls[0] += 1
        raise ValueError("bug")
    with pytest.raises(ValueError):
        retry_mod.call_with_retry(hard)
    assert calls[0] == 1

    # budget exhaustion propagates the last transient failure
    calls[0] = 0

    def always():
        calls[0] += 1
        raise TransientFault("down")
    with pytest.raises(TransientFault):
        retry_mod.call_with_retry(always, retries=2)
    assert calls[0] == 3


def test_is_transient_only_the_injected_class():
    # a JAX runtime status is never retried: on an attached chip
    # RESOURCE_EXHAUSTED is an HBM allocation that fails every time
    class XlaRuntimeError(Exception):
        pass
    assert retry_mod.is_transient(TransientFault("blip"))
    assert not retry_mod.is_transient(
        XlaRuntimeError("RESOURCE_EXHAUSTED: hbm"))
    assert not retry_mod.is_transient(XlaRuntimeError("UNAVAILABLE: socket"))
    assert not retry_mod.is_transient(ValueError("UNAVAILABLE"))


# ------------------------------------------------ timeout + cancellation

def test_timeout_renders_timed_out_with_partial_hits():
    node = _mk_node(n_shards=3)
    node.request("POST", "/logs/_search", QUERY)        # warm executables
    faults.install({"site": "query.shard", "kind": "delay",
                    "delay_ms": 80, "max_fires": 1})
    r = node.request("POST", "/logs/_search",
                     {**QUERY, "timeout": "10ms"})
    assert r["_status"] == 200
    assert r["timed_out"] is True
    # the delayed shard still completed (delay, not failure); shards
    # after the deadline were never launched, so the page is partial
    assert r["_shards"]["failed"] == 0
    assert 0 < len(r["hits"]["hits"]) < 30


def test_timeout_disabled_values_and_rest_param():
    node = _mk_node(n_shards=2)
    r = node.request("POST", "/logs/_search", {**QUERY, "timeout": "-1"})
    assert r["_status"] == 200 and r["timed_out"] is False
    r = node.request("GET", "/logs/_search", q="module", timeout="10s")
    assert r["_status"] == 200 and r["timed_out"] is False
    r = node.request("POST", "/logs/_search",
                     {**QUERY, "timeout": "not-a-time"})
    assert r["_status"] == 400


def test_cancel_terminates_at_phase_boundary():
    node = _mk_node(n_shards=3)
    node.request("POST", "/logs/_search", QUERY)        # warm executables
    faults.install({"site": "query.shard", "kind": "delay",
                    "delay_ms": 150})
    out = {}

    def run():
        out["r"] = node.request("POST", "/logs/_search", QUERY)
    t = threading.Thread(target=run)
    t.start()
    deadline = time.monotonic() + 5.0
    cancelled = False
    while time.monotonic() < deadline and not cancelled:
        tasks = node.request("GET", "/_tasks",
                             actions="indices:data/read/search")
        for tid in tasks.get("tasks", {}):
            c = node.request("POST", f"/_tasks/{tid}/_cancel")
            cancelled = c["_status"] == 200
        time.sleep(0.01)
    t.join()
    assert cancelled, "search task never observed"
    r = out["r"]
    assert r["_status"] == 400
    assert r["error"]["type"] == "task_cancelled_exception"


# ----------------------------------------------------- msearch isolation

def _msearch(node, bodies, index="logs", **params):
    lines = []
    for b in bodies:
        lines.append(json.dumps({"index": index}))
        lines.append(json.dumps(b))
    resp = node.handle("POST", "/_msearch",
                       params={k: str(v) for k, v in params.items()},
                       body="\n".join(lines) + "\n")
    return resp.status, resp.body


def test_msearch_b1_runtime_fault_is_per_item_error():
    node = _mk_node(n_shards=1)
    faults.install({"site": "query.dispatch", "kind": "exception"})
    status, body = _msearch(node, [dict(QUERY)])
    assert status == 200                        # the envelope survives
    (item,) = body["responses"]
    assert item["status"] == 500
    assert item["error"]["type"] == "injected_fault_exception"


def test_msearch_b32_group_fault_isolated_to_items():
    """A device fault in one wave group downgrades only that group's
    items; siblings in other groups return results identical to the
    unfaulted run."""
    node = _mk_node(n_shards=1)
    # two wave groups: the k window is max(from+size, 10), so sizes 5
    # and 20 land in distinct (struct, shape, k) group signatures
    bodies = []
    for i in range(32):
        bodies.append({"query": {"match": {"msg": "module"}},
                       "size": 5 if i % 2 else 20})
    status, clean = _msearch(node, bodies)
    assert status == 200
    assert all("error" not in it for it in clean["responses"])

    faults.install({"site": "query.dispatch", "kind": "exception",
                    "max_fires": 1})
    status, body = _msearch(node, bodies)
    assert status == 200
    failed = [i for i, it in enumerate(body["responses"])
              if "error" in it]
    ok = [i for i, it in enumerate(body["responses"])
          if "error" not in it]
    assert failed and ok, "expected one group failed, one survived"
    # the failed group is exactly one of the two shape groups (16 items)
    assert len(failed) == 16
    for i in failed:
        assert body["responses"][i]["status"] == 500
        assert body["responses"][i]["error"]["type"] == \
            "injected_fault_exception"
    for i in ok:
        assert body["responses"][i]["hits"] == \
            clean["responses"][i]["hits"]


def test_msearch_transient_fault_retried_envelope_clean():
    node = _mk_node(n_shards=1)
    bodies = [{"query": {"match": {"msg": "module"}}, "size": 4}
              for _ in range(8)]
    status, clean = _msearch(node, bodies)
    before = _counter("search.retry_success")
    faults.install({"site": "query.dispatch", "kind": "transient"})
    status, body = _msearch(node, bodies)
    assert status == 200
    assert all("error" not in it for it in body["responses"])
    for got, want in zip(body["responses"], clean["responses"]):
        assert got["hits"] == want["hits"]
    assert _counter("search.retry_success") >= before + 1


def test_msearch_deadline_renders_timed_out_tail():
    node = _mk_node(n_shards=1)
    bodies = []
    for i in range(8):
        # one group per distinct k window (k = max(from+size, 10)) → one
        # wave dispatch per group, so the deadline checkpoint between
        # waves has boundaries to hit
        bodies.append({"query": {"match": {"msg": "module"}},
                       "size": 10 * (i + 1)})
    _msearch(node, bodies)                      # warm executables
    faults.install({"site": "query.dispatch", "kind": "delay",
                    "delay_ms": 120, "max_fires": 1})
    status, body = _msearch(node, bodies, timeout="20ms")
    assert status == 200
    timed_out = [it for it in body["responses"] if it.get("timed_out")]
    finished = [it for it in body["responses"]
                if not it.get("timed_out") and "error" not in it]
    assert timed_out, "expected the post-deadline tail to time out"
    assert finished, "expected the pre-deadline wave to finish"
    for it in timed_out:
        assert it["hits"]["hits"] == []


# --------------------------------------------------------------- hybrid

def test_hybrid_single_shard_fault_partial_200():
    node = Node()
    node.request("PUT", "/hyb", {
        "settings": {"number_of_shards": 2},
        "mappings": {"properties": {
            "title": {"type": "text"},
            "vec": {"type": "knn_vector", "dimension": 4,
                    "method": {"space_type": "l2"}}}}})
    lines = []
    for i in range(16):
        lines.append(json.dumps({"index": {"_index": "hyb",
                                           "_id": f"d{i}"}}))
        lines.append(json.dumps({
            "title": "red dog" if i % 2 else "blue cat",
            "vec": [0.1 * i, 0.2, 0.3, 0.4]}))
    r = node.request("POST", "/_bulk", "\n".join(lines) + "\n",
                     refresh="true")
    assert not r["errors"]
    body = {"query": {"hybrid": {"queries": [
        {"match": {"title": "red dog"}},
        {"knn": {"vec": {"vector": [0.5, 0.2, 0.3, 0.4], "k": 4}}}]}},
        "size": 16, "_source": False}
    clean = node.request("POST", "/hyb/_search", body)
    assert clean["_status"] == 200

    faults.install({"site": "query.shard", "kind": "exception",
                    "max_fires": 1})
    r = node.request("POST", "/hyb/_search", body)
    assert r["_status"] == 200
    assert r["_shards"]["failed"] == 1
    (failure,) = r["_shards"]["failures"]
    assert failure["reason"]["type"] == "injected_fault_exception"
    # candidate generation is shard-local, so with a page wide enough to
    # hold every match the faulted id set is exactly the clean id set
    # restricted to surviving shards (scores shift — the normalization
    # bounds are now computed over one shard — but membership must not)
    surviving = set()
    for si, ids in enumerate(_shard_ids(node, "hyb")):
        if si != failure["shard"]:
            surviving.update(ids)
    clean_ids = {h["_id"] for h in clean["hits"]["hits"]}
    assert {h["_id"] for h in r["hits"]["hits"]} == clean_ids & surviving

    faults.clear()
    faults.install({"site": "query.shard", "kind": "exception"})
    r = node.request("POST", "/hyb/_search", body)
    assert r["_status"] == 503
    assert "all shards failed" in r["error"]["reason"]


# --------------------------------------- backpressure batch admission

def test_msearch_backpressure_rejects_per_item():
    node = _mk_node(n_shards=1)
    bodies = [{"query": {"match": {"msg": "module"}}, "size": 3}
              for _ in range(5)]
    node.search_backpressure.max_concurrent = 2
    try:
        status, body = _msearch(node, bodies)
    finally:
        node.search_backpressure.max_concurrent = 100
    assert status == 200                        # envelope survives
    errs = [it for it in body["responses"] if "error" in it]
    ok = [it for it in body["responses"] if "error" not in it]
    assert len(ok) == 2 and len(errs) == 3
    for it in errs:
        assert it["status"] == 429
        assert it["error"]["type"] == "circuit_breaking_exception"
    assert node.search_backpressure.current == 0    # fully released
    stats = node.request("GET", "/_nodes/stats")
    node_stats = next(iter(stats["nodes"].values()))
    assert node_stats["search_backpressure"]["search_task"][
        "rejections"] >= 3


# ----------------------------------------------------- warmup isolation

def test_warmup_replay_fault_costs_only_that_entry():
    from opensearch_tpu.search.warmup import WarmupRegistry
    node = _mk_node(n_shards=1)
    executor = node.indices.get("logs").shards[0].executor
    reg = WarmupRegistry()
    reg.record("logs", {"query": {"match": {"msg": "module"}},
                        "size": 3}, 1, ("sig", "logs", 3))
    assert reg.entries()
    faults.install({"site": "warmup.replay", "kind": "exception"})
    out = reg.warm_executor(executor)
    assert out["errors"] == len(reg.entries()) and out["warmed"] == 0
    faults.clear()
    faults.install({"site": "warmup.replay", "kind": "transient"})
    out = reg.warm_executor(executor)
    assert out["warmed"] == len(reg.entries()) and out["errors"] == 0


# --------------------------------------- determinism + disabled no-op

def test_seeded_schedule_is_reproducible():
    node = _mk_node(n_shards=3)

    def run_schedule():
        faults.clear()
        faults.install({"site": "query.shard", "kind": "exception",
                        "probability": 0.5, "seed": 42})
        outcomes = []
        for _ in range(6):
            r = node.request("POST", "/logs/_search", QUERY)
            outcomes.append((r["_status"],
                             r.get("_shards", {}).get("failed")))
        fires = faults.snapshot()[0]["fires"]
        return outcomes, fires
    a, fires_a = run_schedule()
    b, fires_b = run_schedule()
    assert a == b
    assert fires_a == fires_b > 0


def test_disabled_injector_zero_behavior_change():
    node = _mk_node(n_shards=3)
    assert faults.ENABLED is False
    clean = node.request("POST", "/logs/_search", QUERY)
    faults.install({"site": "query.shard", "kind": "exception"})
    assert faults.ENABLED is True
    faults.clear()
    assert faults.ENABLED is False
    again = node.request("POST", "/logs/_search", QUERY)
    clean.pop("took"), again.pop("took")
    assert clean == again
