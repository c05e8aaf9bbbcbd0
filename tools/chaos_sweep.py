"""Chaos sweep: enumerate every (fault site × fault kind) in
common/faults.py against a small corpus and verify the engine's
fault-tolerance contract (ISSUE 6):

  every injected-fault outcome is either
    - a differential-oracle-correct PARTIAL result with accurate
      `_shards.failures[]` (surviving shards' hits bit-identical to the
      unfaulted run), or
    - a clean TYPED error object —
  never an uncaught 500, never a corrupt page.

For each site the sweep picks the workload that actually reaches it
(single search, size=0 aggs, B=8 msearch envelope, hybrid, warmup
replay), installs one seeded rule, runs, classifies the outcome against
the site×kind expectation table, and re-checks the rendered page hit by
hit against the clean run (score equality — the corrupt-page check).
Two extra scenario rows cover the timeout contract (delayed shard +
timeout=10ms → `timed_out: true` partial) and per-item msearch
isolation (device fault downgrades one wave group's items only).

Exit 1 on any violated expectation; the site→outcome table prints
either way. `--fast` runs the exception+transient kinds only (the delay
rows add wall-clock, not coverage) — that subset is wired into tier-1
as tests/test_chaos_sweep.py (the sweep_delta pattern).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N_DOCS = 24

# site → the workload that reaches it (see WORKLOADS)
SITE_WORKLOAD = {
    "canmatch.shard": "search",
    "query.shard": "search",
    "query.dispatch": "search",
    "fetch.gather": "search",
    "request_cache.get": "aggs",
    "request_cache.put": "aggs",
    "reduce.aggs": "aggs",
    "warmup.replay": "warmup",
}

# (site, kind) → expected outcome class:
#   full        200, zero failed shards, page bit-identical to clean
#   partial     200, failed >= 1 with failures[], surviving-shard
#               differential holds (the oracle check)
#   typed_error 5xx allowed, but body.error.type must be present (a
#               clean typed error, never a raw stack-trace 500)
#   isolated    warmup replay: the faulted entry costs errors += 1,
#               never a raise out of warm_executor
# kind=delay expects "full" everywhere: a slow site is not a failed one.
EXPECT = {
    ("canmatch.shard", "exception"): "full",      # degrade: don't skip
    ("canmatch.shard", "transient"): "full",
    ("query.shard", "exception"): "partial",
    ("query.shard", "transient"): "partial",      # site not retry-wrapped
    ("query.dispatch", "exception"): "partial",
    ("query.dispatch", "transient"): "full",      # absorbed by retry
    ("fetch.gather", "exception"): "partial",
    ("fetch.gather", "transient"): "full",        # absorbed by retry
    ("request_cache.get", "exception"): "full",   # degrade to MISS
    ("request_cache.get", "transient"): "full",
    ("request_cache.put", "exception"): "full",   # dropped write
    ("request_cache.put", "transient"): "full",
    ("reduce.aggs", "exception"): "typed_error",  # no per-shard slice
    ("reduce.aggs", "transient"): "typed_error",
    ("warmup.replay", "exception"): "isolated",
    ("warmup.replay", "transient"): "full",       # absorbed by retry
}

SEARCH_BODY = {"query": {"match": {"msg": "module"}}, "size": N_DOCS}
AGGS_BODY = {"query": {"match": {"msg": "module"}}, "size": 0,
             "aggs": {"lv": {"terms": {"field": "level"}}}}


def build_corpus():
    """One node, two indices: logs (3 shards, text/keyword/integer) and
    hyb (2 shards, text + knn_vector) — small enough that the full sweep
    is tier-1-speed, sharded enough that partial results exist."""
    from opensearch_tpu.node import Node
    node = Node()
    node.request("PUT", "/logs", {
        "settings": {"number_of_shards": 3},
        "mappings": {"properties": {
            "msg": {"type": "text"}, "level": {"type": "keyword"},
            "code": {"type": "integer"}}}})
    lines = []
    for i in range(N_DOCS):
        lines.append(json.dumps({"index": {"_index": "logs",
                                           "_id": f"d{i}"}}))
        lines.append(json.dumps({
            "msg": f"error in module {i}" if i % 2 else f"ok module {i}",
            "level": "error" if i % 2 else "info", "code": i}))
    # single-shard twin of logs: the batched _msearch envelope (the
    # per-item isolation surface) only engages at num_shards == 1
    node.request("PUT", "/m1", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "msg": {"type": "text"}, "level": {"type": "keyword"},
            "code": {"type": "integer"}}}})
    for i in range(N_DOCS):
        lines.append(json.dumps({"index": {"_index": "m1",
                                           "_id": f"d{i}"}}))
        lines.append(json.dumps({
            "msg": f"error in module {i}" if i % 2 else f"ok module {i}",
            "level": "error" if i % 2 else "info", "code": i}))
    node.request("PUT", "/hyb", {
        "settings": {"number_of_shards": 2},
        "mappings": {"properties": {
            "title": {"type": "text"},
            "vec": {"type": "knn_vector", "dimension": 4,
                    "method": {"space_type": "l2"}}}}})
    for i in range(12):
        lines.append(json.dumps({"index": {"_index": "hyb",
                                           "_id": f"h{i}"}}))
        lines.append(json.dumps({
            "title": "red dog" if i % 2 else "blue cat",
            "vec": [0.1 * i, 0.2, 0.3, 0.4]}))
    r = node.request("POST", "/_bulk", "\n".join(lines) + "\n",
                     refresh="true")
    assert r["_status"] == 200 and not r["errors"], r
    return node


def _shard_ids(node, index):
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


def _clear_request_cache():
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    REQUEST_CACHE.clear()


def _msearch(node, bodies, index="logs", **params):
    lines = []
    for b in bodies:
        lines.append(json.dumps({"index": index}))
        lines.append(json.dumps(b))
    resp = node.handle("POST", "/_msearch",
                       params={k: str(v) for k, v in params.items()},
                       body="\n".join(lines) + "\n")
    return resp.status, resp.body


def _check_page_integrity(resp, clean_hits, violations, row):
    """The corrupt-page check: every hit that DID render must carry the
    clean run's exact score for that id — a partial page may be smaller,
    never wrong."""
    for h in resp.get("hits", {}).get("hits", []):
        if h["_id"] not in clean_hits:
            violations.append(f"{row}: hit {h['_id']} not in clean run")
        elif clean_hits[h["_id"]] != h["_score"]:
            violations.append(
                f"{row}: hit {h['_id']} score {h['_score']} != clean "
                f"{clean_hits[h['_id']]} (corrupt page)")


def _classify(resp, expect, clean, surviving_oracle, row, violations):
    """Validate one response against its expectation class; returns the
    outcome cell for the table."""
    status = resp["_status"]
    failed = resp.get("_shards", {}).get("failed", 0)
    if status >= 500:
        etype = (resp.get("error") or {}).get("type")
        if not etype:
            violations.append(f"{row}: raw untyped {status}")
            return f"RAW-{status}"
        if expect != "typed_error":
            violations.append(
                f"{row}: expected {expect}, got {status} [{etype}] "
                f"(5xx-when-partial-expected)")
        return f"typed-{status} [{etype}]"
    if expect == "typed_error":
        violations.append(f"{row}: expected typed_error, got {status}")
        return f"{status} (expected error)"
    clean_hits = _hit_map(clean)
    _check_page_integrity(resp, clean_hits, violations, row)
    if expect == "full":
        if failed != 0:
            violations.append(f"{row}: expected full, failed={failed}")
        elif _hit_map(resp) != clean_hits:
            violations.append(f"{row}: full response != clean run")
        return f"full-200 failed=0"
    # expect == "partial"
    failures = resp.get("_shards", {}).get("failures", [])
    if failed < 1 or len(failures) != failed:
        violations.append(
            f"{row}: expected partial, failed={failed} "
            f"failures={len(failures)}")
        return f"200 failed={failed} (expected partial)"
    for f in failures:
        if not f.get("reason", {}).get("type"):
            violations.append(f"{row}: failures[] entry missing reason")
    # the differential oracle: hits == clean restricted to shards that
    # did NOT report a failure
    surviving = set()
    for si, ids in enumerate(surviving_oracle):
        if si not in {f["shard"] for f in failures}:
            surviving.update(ids)
    want = {d: s for d, s in clean_hits.items() if d in surviving}
    if _hit_map(resp) != want:
        violations.append(
            f"{row}: surviving-shard differential failed "
            f"({len(_hit_map(resp))} hits vs oracle {len(want)})")
    return f"partial-200 failed={failed}"


def _check_permits(node, row, violations):
    """The permit-leak invariant (ISSUE 11, extended to scheduler-queued
    requests in ISSUE 12): after a row quiesces, the backpressure gate
    must be back at baseline — current == 0 and the admitted/released
    counters equal — and the wave scheduler's queue must be EMPTY. A
    request stranded in the coalesce queue holds its permit forever
    (its thread blocks inside the acquire/release bracket), so a
    non-drained queue IS a permit leak in the making; checking both
    makes the invariant cover the window."""
    bp = node.search_backpressure
    if bp.current != 0 or bp.admitted_total != bp.released_total:
        violations.append(
            f"{row}: permit leak (current={bp.current}, "
            f"admitted={bp.admitted_total}, "
            f"released={bp.released_total})")
    sched = getattr(node, "wave_scheduler", None)
    if sched is not None and sched.queue_depth() != 0:
        violations.append(
            f"{row}: wave scheduler queue not drained "
            f"(depth={sched.queue_depth()})")


def _rule(site, kind):
    spec = {"site": site, "kind": kind, "seed": 0}
    if kind == "exception":
        spec["max_fires"] = 1       # one shard's slice, not the request
    elif kind == "delay":
        spec.update(delay_ms=5, max_fires=3)
    # transient at p=1 defaults to max_fires=1 (fail-once-then-succeed)
    return spec


def run_sweep(fast: bool = False):
    """Returns (table rows, violations). Each row is
    (site, kind, workload, outcome)."""
    from opensearch_tpu.common import faults

    faults.clear()
    node = build_corpus()
    kinds = ("exception", "transient") if fast \
        else ("exception", "transient", "delay")

    # clean baselines (also warm every executable so fault runs measure
    # fault handling, not compiles)
    clean_search = node.request("POST", "/logs/_search", SEARCH_BODY)
    clean_aggs = node.request("POST", "/logs/_search", AGGS_BODY)
    assert clean_search["_status"] == 200 and clean_aggs["_status"] == 200
    logs_shards = _shard_ids(node, "logs")
    hyb_shards = _shard_ids(node, "hyb")

    rows = []
    violations: list = []
    for site in sorted(faults.SITES):
        workload = SITE_WORKLOAD[site]
        for kind in kinds:
            row = f"{site}×{kind}"
            expect = "full" if kind == "delay" \
                else EXPECT[(site, kind)]
            faults.clear()
            _clear_request_cache()
            faults.install(_rule(site, kind))
            try:
                if workload == "warmup":
                    outcome = _run_warmup_combo(node, expect, row,
                                                violations)
                elif workload == "aggs":
                    resp = node.request("POST", "/logs/_search",
                                        AGGS_BODY)
                    outcome = _classify(resp, expect, clean_aggs,
                                        logs_shards, row, violations)
                    if (expect == "full" and resp["_status"] == 200 and
                            resp.get("aggregations")
                            != clean_aggs.get("aggregations")):
                        violations.append(f"{row}: agg tree != clean")
                else:
                    resp = node.request("POST", "/logs/_search",
                                        SEARCH_BODY)
                    outcome = _classify(resp, expect, clean_search,
                                        logs_shards, row, violations)
            finally:
                faults.clear()
            _check_permits(node, row, violations)
            rows.append((site, kind, workload, outcome))

    rows.extend(_scenario_rows(node, clean_search, logs_shards,
                               hyb_shards, violations, fast))
    _check_permits(node, "scenario-rows", violations)
    faults.clear()
    return rows, violations


def _run_warmup_combo(node, expect, row, violations):
    """warmup.replay: a faulted entry costs errors += 1 (exception) or a
    retried success (transient); warm_executor never raises."""
    from opensearch_tpu.search.warmup import WarmupRegistry
    executor = node.indices.get("logs").shards[0].executor
    reg = WarmupRegistry()
    reg.record("logs", {"query": {"match": {"msg": "module"}},
                        "size": 3}, 1, ("chaos-sig", "logs", 3))
    try:
        out = reg.warm_executor(executor)
    except Exception as e:
        violations.append(f"{row}: warm_executor raised "
                          f"{type(e).__name__}: {e}")
        return "RAISED"
    n = len(reg.entries())
    if expect == "isolated":
        if out["errors"] != n or out["warmed"] != 0:
            violations.append(f"{row}: expected all-entries-errored, "
                              f"got {out}")
        return f"isolated errors={out['errors']}"
    if out["warmed"] != n or out["errors"] != 0:
        violations.append(f"{row}: expected warmed={n}, got {out}")
    return f"warmed={out['warmed']}"


def _scenario_rows(node, clean_search, logs_shards, hyb_shards,
                   violations, fast):
    """The contract rows beyond the plain site×kind matrix: timeout,
    per-item msearch isolation, hybrid partial."""
    from opensearch_tpu.common import faults
    rows = []

    # ---- timeout: a delayed shard + timeout=10ms → timed_out partial
    faults.clear()
    _clear_request_cache()
    faults.install({"site": "query.shard", "kind": "delay",
                    "delay_ms": 60, "max_fires": 1})
    r = node.request("POST", "/logs/_search",
                     {**SEARCH_BODY, "timeout": "10ms"})
    faults.clear()
    if r["_status"] != 200 or r.get("timed_out") is not True:
        violations.append(
            f"timeout-scenario: status={r['_status']} "
            f"timed_out={r.get('timed_out')}")
    _check_page_integrity(r, _hit_map(clean_search), violations,
                          "timeout-scenario")
    rows.append(("query.shard", "delay+timeout=10ms", "search",
                 f"timed_out={r.get('timed_out')} "
                 f"hits={len(r['hits']['hits'])}"))

    # ---- msearch: a device fault downgrades ONE wave group's items to
    # per-item error objects; siblings match the clean run
    bodies = [{"query": {"match": {"msg": "module"}},
               "size": 5 if i % 2 else 20} for i in range(8)]
    faults.clear()
    _clear_request_cache()
    status, clean = _msearch(node, bodies, index="m1")
    assert status == 200
    _clear_request_cache()
    faults.install({"site": "query.dispatch", "kind": "exception",
                    "max_fires": 1})
    status, body = _msearch(node, bodies, index="m1")
    faults.clear()
    if status != 200:
        violations.append(f"msearch-scenario: envelope died ({status})")
    err_items = [it for it in body.get("responses", [])
                 if "error" in it]
    ok_items = [(i, it) for i, it in enumerate(body.get("responses", []))
                if "error" not in it]
    if not err_items or not ok_items:
        violations.append(
            f"msearch-scenario: expected one group failed + siblings "
            f"alive, got {len(err_items)} errors / {len(ok_items)} ok")
    for it in err_items:
        if not it.get("error", {}).get("type"):
            violations.append("msearch-scenario: untyped item error")
    for i, it in ok_items:
        if it["hits"] != clean["responses"][i]["hits"]:
            violations.append(
                f"msearch-scenario: surviving item {i} != clean")
    rows.append(("query.dispatch", "exception", "msearch B=8",
                 f"per-item errors={len(err_items)} "
                 f"ok={len(ok_items)}"))

    # ---- hybrid: one shard's fault costs one failures[] entry; the id
    # set equals clean ∩ surviving shards (scores shift with the
    # normalization bounds, membership must not)
    hyb_body = {"query": {"hybrid": {"queries": [
        {"match": {"title": "red dog"}},
        {"knn": {"vec": {"vector": [0.5, 0.2, 0.3, 0.4], "k": 4}}}]}},
        "size": 12, "_source": False}
    faults.clear()
    _clear_request_cache()
    clean_h = node.request("POST", "/hyb/_search", hyb_body)
    _clear_request_cache()
    faults.install({"site": "query.shard", "kind": "exception",
                    "max_fires": 1})
    r = node.request("POST", "/hyb/_search", hyb_body)
    faults.clear()
    if r["_status"] != 200 or r["_shards"]["failed"] != 1:
        violations.append(
            f"hybrid-scenario: status={r['_status']} "
            f"shards={r.get('_shards')}")
    else:
        failed_shard = r["_shards"]["failures"][0]["shard"]
        surviving = set()
        for si, ids in enumerate(hyb_shards):
            if si != failed_shard:
                surviving.update(ids)
        clean_ids = {h["_id"] for h in clean_h["hits"]["hits"]}
        got_ids = {h["_id"] for h in r["hits"]["hits"]}
        if got_ids != clean_ids & surviving:
            violations.append(
                "hybrid-scenario: surviving-shard membership "
                "differential failed")
    rows.append(("query.shard", "exception", "hybrid",
                 f"partial-200 failed="
                 f"{r.get('_shards', {}).get('failed')}"))
    return rows


def run_chaos_concurrent(clients: int = 4, n_requests: int = 96,
                         rate: float = 150.0, seed: int = 3,
                         node=None, scheduler: bool = False):
    """Chaos UNDER concurrency (ISSUE 11): seeded faults fire at
    `query.dispatch` (permanent, per-shard) and `fetch.gather`
    (transient, retry-absorbed) WHILE `clients` open-loop workers drive
    the REST search path on a Poisson schedule — the sequential sweep
    above proves per-row fault handling, this proves it while the
    permit gate, the wave engine and the retry helper are all
    contended.

    The contract checked (returns (summary, violations)):
      - zero 5xx: every completed request is a 200 (partial or full)
        or an admission 429 — a fault under concurrency must never
        escape as a raw error;
      - zero serve exceptions (the in-process path never raises);
      - zero permit leaks: the backpressure gate is back at baseline
        after the run (counter invariant, `_check_permits`);
      - goodput floor: >= 90% of requests complete as 200s (faults
        cost shard slices, not requests; admission sheds only under
        genuine pressure).

    Fault schedule: STAGGERED single-fire rules (skip + max_fires=1)
    instead of per-invocation probability draws. Same-site fire points
    sit further apart than any one request's invocation span, so no
    request can ever absorb more than one fire per site — at most 2 of
    its 3 shards fail, which the partial-failure contract renders as a
    200, NEVER the all-shards-failed 503. That makes "zero 5xx" a
    deterministic property of the schedule under ANY thread
    interleaving, not a probabilistic hope (a p=0.15 draw per
    invocation measurably lands 3 fires in one request and 503s)."""
    import json as _json

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import openloop

    from opensearch_tpu.common import faults

    faults.clear()
    owns_node = node is None
    if owns_node:
        node = build_corpus()
    violations: list = []
    if scheduler:
        # ISSUE 12: the same chaos contract with the wave scheduler
        # COALESCING while the faults fire — per-wave fault isolation
        # must downgrade only the owning wave's items even when those
        # items belong to different coalesced requests, and the permit
        # invariant must hold across the window (checked below with
        # the queue-drained extension)
        node.wave_scheduler.set_enabled(True)
    # the scheduler variant drives the SINGLE-SHARD index so requests
    # actually coalesce (the scheduler only engages there); a
    # one-shard index has no partial-failure escape — one shard failed
    # IS all shards failed, a legitimate 503 — so its fault schedule
    # is transient-only: the bounded retry helper must absorb every
    # fire inside the shared waves
    path = "/m1/_search" if scheduler else "/logs/_search"
    # warm the executables so the measured window exercises fault
    # handling, not compiles
    clean = node.request("POST", path, SEARCH_BODY)
    assert clean["_status"] == 200, clean
    bodies = [{**SEARCH_BODY, "size": 4 + (i % 3) * 8}
              for i in range(n_requests)]
    for b in bodies[:6]:
        node.request("POST", path, b)
    base_admitted = node.search_backpressure.admitted_total
    base_released = node.search_backpressure.released_total

    statuses_5xx = []

    def serve(body):
        resp = node.handle("POST", path, body=_json.dumps(body))
        if resp.status >= 500:
            statuses_5xx.append((resp.status, resp.body))
        return resp.status

    # staggered deterministic fires (see docstring): a request spends 3
    # query.dispatch invocations (one per shard) and well under 100
    # fetch.gather invocations (page hits), so same-site gaps of 90 /
    # 400 guarantee one fire per site per request at most
    for skip in (10, 100, 190):
        faults.install({"site": "query.dispatch",
                        "kind": "transient" if scheduler
                        else "exception",
                        "skip": skip, "max_fires": 1})
    for skip in (50, 450, 850):
        faults.install({"site": "fetch.gather", "kind": "transient",
                        "skip": skip, "max_fires": 1})
    try:
        res = openloop.run_open_loop(serve, bodies, clients=clients,
                                     arrival_rate=rate, seed=seed)
    finally:
        faults.clear()
        if scheduler:
            # disable drains: every queued request completes before
            # the thread exits, so the depth check below sees 0 or a
            # real leak
            node.wave_scheduler.set_enabled(False)
    if scheduler and node.wave_scheduler.queue_depth() != 0:
        violations.append(
            f"concurrent-chaos: scheduler queue not drained "
            f"(depth={node.wave_scheduler.queue_depth()})")
    if statuses_5xx:
        violations.append(
            f"concurrent-chaos: {len(statuses_5xx)} 5xx response(s), "
            f"first: {str(statuses_5xx[0])[:200]}")
    if res["errors"]:
        violations.append(
            f"concurrent-chaos: {res['errors']} serve exception(s)")
    bp = node.search_backpressure
    if bp.current != 0 or \
            (bp.admitted_total - base_admitted) \
            != (bp.released_total - base_released):
        violations.append(
            f"concurrent-chaos: permit leak (current={bp.current}, "
            f"admitted+{bp.admitted_total - base_admitted}, "
            f"released+{bp.released_total - base_released})")
    if res["ok"] < 0.9 * n_requests:
        violations.append(
            f"concurrent-chaos: goodput floor broken "
            f"({res['ok']}/{n_requests} 200s)")
    summary = {"clients": clients, "n_requests": n_requests,
               "ok": res["ok"], "rejected": res["rejected"],
               "failed": res["failed"], "errors": res["errors"],
               "goodput_qps": res["goodput_qps"],
               "p99_ms": res["p99_ms"]}
    if scheduler:
        s = node.wave_scheduler.stats()
        summary["scheduler"] = {
            "dispatched_waves": s["dispatched_waves"],
            "coalesced": s["coalesced"],
            "co_batched_max": s["co_batched"]["max"],
            "shed_deadline": s["shed_deadline"]}
    return summary, violations


def main():
    # a correctness sweep: pinned to the CPU backend, before jax loads
    os.environ["JAX_PLATFORMS"] = "cpu"
    fast = "--fast" in sys.argv
    if "--concurrency" in sys.argv:
        summary, violations = run_chaos_concurrent()
        print("chaos-under-concurrency:", json.dumps(summary))
        if violations:
            print(f"\n{len(violations)} contract violation(s):")
            for v in violations:
                print(" ", v)
            sys.exit(1)
        print("chaos-under-concurrency clean: zero 5xx, zero permit "
              "leaks, goodput floor held")
        return
    rows, violations = run_sweep(fast=fast)
    w_site = max(len(r[0]) for r in rows)
    w_kind = max(len(r[1]) for r in rows)
    w_load = max(len(r[2]) for r in rows)
    print(f"{'SITE':<{w_site}}  {'KIND':<{w_kind}}  "
          f"{'WORKLOAD':<{w_load}}  OUTCOME")
    for site, kind, workload, outcome in rows:
        print(f"{site:<{w_site}}  {kind:<{w_kind}}  "
              f"{workload:<{w_load}}  {outcome}")
    if violations:
        print(f"\n{len(violations)} contract violation(s):")
        for v in violations:
            print(" ", v)
        sys.exit(1)
    print(f"\nchaos sweep clean: {len(rows)} combos, every outcome a "
          "correct partial or a clean typed error")


if __name__ == "__main__":
    main()
