"""The REST route table: every handler the node serves.

Re-design of the reference's rest/action/* handlers + the TransportActions
behind them (action/ActionModule.java:733 registrations). Handlers are thin:
they parse request params and delegate to IndicesService / IndexService,
which own the actual behavior. NDJSON endpoints (_bulk, _msearch) parse the
raw body. _cat handlers render fixed-width text tables like the reference's
AbstractCatAction.
"""

from __future__ import annotations

import json
import logging
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from opensearch_tpu.search import dsl

from opensearch_tpu.common.errors import (
    IllegalArgumentError, IndexNotFoundError, OpenSearchTpuError)
from opensearch_tpu.rest.controller import RestRequest, RestResponse
from opensearch_tpu.telemetry import TELEMETRY


# --------------------------------------------------------------------- utils

def _ndjson_lines(request: RestRequest) -> List[Any]:
    raw = request.raw_body
    if raw is None:
        raise IllegalArgumentError("request body is required")
    text = raw.decode("utf-8") if isinstance(raw, bytes) else raw
    out = []
    for line in text.split("\n"):
        line = line.strip()
        if line:
            out.append(json.loads(line))
    return out


def _search_targets(node, index_expr: Optional[str]):
    """Resolve an index expression to (executors, alias_filters) pairs for
    a cross-index search, honoring alias filters per concrete index."""
    index_expr = _expand_data_streams(node, index_expr)
    names = node.indices.resolve(index_expr, ignore_unavailable=False,
                                 allow_no_indices=True)
    executors, filters = [], []
    for name in names:
        svc = node.indices.get(name)
        svc.check_open()    # explicitly-named closed index: 400
        alias_filter = node.indices.alias_filter(index_expr or "", name)
        for shard in svc.shards:
            executors.append(shard.executor)
            filters.append(alias_filter)
    return executors, filters


def _check_require_alias(node, req) -> None:
    """?require_alias=true: the write target must be an alias
    (IndexRequest#requireAlias / DocWriteRequest)."""
    if req.bool_param("require_alias") and \
            req.param("index") not in node.indices.aliases:
        from opensearch_tpu.common.errors import IndexNotFoundError
        raise IndexNotFoundError(
            f"[{req.param('index')}] is not an alias and [require_alias] "
            f"request flag is [true]")


def _validate_doc_id(doc_id: Optional[str]) -> None:
    """IndexRequest.validate: ids are capped at 512 UTF-8 bytes."""
    if doc_id is not None and len(doc_id.encode("utf-8")) > 512:
        raise IllegalArgumentError(
            f"id [{doc_id[:64]}...] is too long, must be no longer than "
            f"512 bytes but was: {len(doc_id.encode('utf-8'))}")


def _write_index(node, name: str) -> str:
    """Write-target resolution incl. data streams (stream → newest backing
    index, reference: IndexAbstraction.DataStream.getWriteIndex) and
    auto-creation of missing indices on document writes
    (action.auto_create_index, default true — AutoCreateIndex.java)."""
    ds = node.data_streams.resolve_write_index(name)
    if ds is not None:
        return ds
    from opensearch_tpu.common.errors import IndexNotFoundError
    try:
        return node.indices.write_index(name)
    except IndexNotFoundError:
        if str(node.settings.get("action.auto_create_index",
                                 True)).lower() == "false":
            raise
        from opensearch_tpu.common.errors import ResourceAlreadyExistsError
        try:
            node.indices.create_index(name, {})
        except ResourceAlreadyExistsError:
            pass    # concurrent writer won the auto-create race
        node.persist_metadata()
        return name


def _expand_data_streams(node, index_expr: Optional[str]) -> Optional[str]:
    if not index_expr:
        return index_expr
    parts = []
    for part in index_expr.split(","):
        backing = node.data_streams.resolve_search(part.strip())
        parts.extend(backing if backing is not None else [part])
    return ",".join(parts)


def _search_services(node, index_expr: Optional[str]):
    names = node.indices.resolve(_expand_data_streams(node, index_expr),
                                 ignore_unavailable=True,
                                 allow_no_indices=True)
    return [node.indices.get(n) for n in names]


def _cluster_allow_partial(node) -> Optional[bool]:
    """Cluster-level default for allow_partial_search_results
    (`search.default_allow_partial_results`, dynamic; transient beats
    persistent like every cluster setting). None = not set (the
    controller then applies the reference default of true)."""
    for scope in ("transient", "persistent"):
        v = node.cluster_settings.get(scope, {}).get(
            "search.default_allow_partial_results")
        if v is not None:
            return str(v).strip().lower() != "false"
    return None


def _run_search(node, index_expr: Optional[str], body: Optional[dict],
                search_pipeline=None, tenant: Optional[str] = None) -> dict:
    """Search with the full pipeline wrap: resolve the search pipeline
    (request param > inline body definition > the single target index's
    `index.search.default_pipeline` setting), apply request processors,
    execute (the pipeline's normalization-processor spec rides along for
    hybrid queries), then apply response processors.
    `search_pipeline="_none"` disables resolution entirely (internal
    callers like _count that the reference serves without pipelines).

    Telemetry: every request opens a root span (rest.search) that closes
    on EVERY exit — success, error, and backpressure rejection (status
    "rejected") — with child spans from the pipeline processors and the
    search phases; per-phase times feed the slow log's query/fetch
    thresholds."""
    from opensearch_tpu.search import dsl
    from opensearch_tpu.search.controller import (
        _parse_deadline, execute_search)
    tracer = TELEMETRY.tracer
    metrics = TELEMETRY.metrics
    # the always-on span ring (telemetry/tracer.py): `rest.search` is
    # the interval `rest.search_ms` times, from the same two clock
    # reads, under the HTTP request's span where there is one
    ring = tracer.spans
    ring_trace, ring_id, ring_parent = ring.enter()
    root = tracer.start_trace("rest.search", index=index_expr or "_all")
    metrics.counter("rest.search_requests").inc()
    # request lifecycle (telemetry/lifecycle.py): arrive is implicit at
    # timeline construction; admit/reject bracket the backpressure gate
    # below. None (one attribute load + branch) unless the flight
    # recorder is enabled.
    flight = TELEMETRY.flight
    tl = flight.timeline()
    tl_prev = flight.bind(tl) if tl is not None else None
    phase_times: Dict[str, float] = {}
    t0 = time.monotonic_ns()
    try:
        executors, filters = _search_targets(node, index_expr)
        body = dict(body or {})
        inline = body.pop("search_pipeline", None)
        services = _search_services(node, index_expr)
        pipeline = node.search_pipelines.resolve(
            search_pipeline if search_pipeline is not None else inline,
            services)
        ctx: Dict[str, Any] = {}
        phase_spec = None
        if pipeline is not None:
            body = pipeline.process_request(body, ctx, trace=root)
            phase_spec = pipeline.phase_spec()
        parsed = dsl.parse_query(body.get("query"))
        if isinstance(parsed, dsl.PercolateQuery):
            from opensearch_tpu.search.percolator import execute_percolate
            k = int(body.get("size", 10)) + int(body.get("from", 0))
            with root.child("query", path="percolate"):
                return execute_percolate(executors, parsed, max(k, 10),
                                         body)
        # admission (common/admission.py: quota -> breaker -> deadline
        # shed -> permits). The deadline parses BEFORE admission so the
        # shed stage can price it — and so a malformed timeout 400s
        # without consuming a permit; the task registers before too.
        # NOTHING runs between a successful acquire() and the try whose
        # finally releases — the permit-leak invariant
        # tools/chaos_sweep.py re-checks after every fault row.
        deadline = _parse_deadline(body)
        # shape-aware shed pricing (ISSUE 15): resolve the query's
        # shape id BEFORE admission — only while the shape-pricing gate
        # is on, so the default shed path never pays the intern walk.
        # The same id feeds the release-side per-shape service estimator.
        shed_shape = None
        if node.search_backpressure.shedder.shape_gate() is not None:
            from opensearch_tpu.telemetry.insights import query_shape
            shed_shape = query_shape(body.get("query"))[0]
        task = node.task_manager.register(
            "indices:data/read/search",
            description=f"indices[{index_expr or '_all'}]", cancellable=True)
        t_admit = time.monotonic() if tl is not None else 0.0
        try:
            node.search_backpressure.acquire(tenant=tenant,
                                             deadline=deadline,
                                             shape=shed_shape)
        except OpenSearchTpuError as rej:
            # the span for a rejected request still closes, with its own
            # status — rejections must be visible in traces, not lost
            node.task_manager.unregister(task)
            root.set_attribute("backpressure", "rejected")
            root.end(status="rejected")
            if tl is not None:
                # structured reject reason + tenant: what
                # tools/tail_report.py groups rejection captures by
                tl.event("reject",
                         reason=getattr(rej, "reject_reason",
                                        "backpressure"),
                         tenant=tenant or "_default")
                flight.complete(tl, status="rejected", span=root)
            raise
        t_exec0 = time.monotonic()
        # insights tenant binding (ISSUE 15): the executor/controller
        # note reads the request's tenant back thread-locally for the
        # per-shape tenant breakdown (disabled = one attribute load)
        ins = TELEMETRY.insights.gate()
        ins_prev = ins.bind_tenant(tenant) if ins is not None else None
        try:
            if tl is not None:
                # the admission gate's own wait (~0; the scheduler's
                # coalesce window adds its REAL queue delay below)
                tl.queue_wait((t_exec0 - t_admit) * 1000)
                tl.event("admit")
            # wave scheduler (search/scheduler.py): an eligible plain
            # single-index request enqueues into the coalescing queue
            # instead of executing inline — the permit + quota token
            # stay HELD by this blocked thread across the window (the
            # finally below releases the permit, preserving the PR 11
            # counter invariant), and a request the scheduler shed at
            # deadline or rejected queue-full refunds its quota token:
            # it never executed. Disabled: one attribute load + branch.
            sched = node.wave_scheduler.gate()
            if sched is not None and pipeline is None \
                    and len(executors) == 1 \
                    and not (filters and filters[0]) \
                    and sched.eligible(body):
                from opensearch_tpu.common.errors import \
                    AdmissionRejectedError
                try:
                    res, _shed = sched.execute(
                        executors[0], body, deadline=deadline,
                        timeline=tl, tenant=tenant, task=task)
                except AdmissionRejectedError:
                    node.search_backpressure.refund_unserved(tenant)
                    raise
                if _shed:
                    node.search_backpressure.refund_unserved(tenant)
            else:
                res = execute_search(
                    executors, body, extra_filters=filters,
                    task=task, allow_envelope=True,
                    phase_processors=phase_spec,
                    trace=root, phase_times=phase_times,
                    allow_partial=_cluster_allow_partial(node))
        finally:
            if ins is not None:
                ins.unbind_tenant(ins_prev)
            node.task_manager.unregister(task)
            # the measured service wall feeds the deadline-shed
            # predictor's rolling estimator (common/admission.py) —
            # per-shape too when shape pricing resolved one
            node.search_backpressure.release(
                service_ms=(time.monotonic() - t_exec0) * 1000.0,
                shape=shed_shape)
        res.pop("_page_cursor", None)
        if pipeline is not None:
            res = pipeline.process_response(res, ctx, targets=services,
                                            trace=root)
        root.set_attribute("took_ms", res.get("took"))
        _maybe_slow_log(node, index_expr, body, res, phase_times)
        return res
    except BaseException as e:  # except-ok: span lifecycle -- closes the root span with error status, then always re-raises
        if getattr(root, "status", "ok") == "ok":
            root.end(error=e)
        raise
    finally:
        t1 = time.monotonic_ns()
        metrics.histogram("rest.search_ms").observe((t1 - t0) / 1e6)
        ring_trace.spans.append(
            (ring_id, ring_parent, "rest.search", t0, t1, None))
        ring.leave(ring_trace, ring_parent)
        if tl is not None:
            flight.unbind(tl_prev)
            if tl.took_ms is None:      # the reject path completed above
                tl.event("respond")
                flight.complete(
                    tl, status="error" if sys.exc_info()[0] is not None
                    else "ok", span=root)
        tracer.finish(root)


# query/fetch phase slow-log loggers, children of the original logger
# name so existing capture configuration keeps working
_SLOW_LOGGERS: Dict[str, Any] = {}  # shared-state-ok: getLogger is idempotent + thread-safe; dict slot write is GIL-atomic

# level check order mirrors SearchSlowLog.java: most severe first, the
# first threshold the phase time clears wins
_SLOW_LOG_LEVELS = (("warn", logging.WARNING), ("info", logging.INFO),
                    ("debug", logging.DEBUG), ("trace", 5))


def _slow_logger(phase: str):
    logger = _SLOW_LOGGERS.get(phase)
    if logger is None:
        logger = logging.getLogger(
            f"opensearch_tpu.index.search.slowlog.{phase}")
        _SLOW_LOGGERS[phase] = logger
    return logger


def _maybe_slow_log(node, index_expr, body, res, phase_times=None):
    """Per-index search slow log (index/SearchSlowLog.java:61) with full
    reference parity: independent `query` and `fetch` phase thresholds at
    all four levels (`search.slowlog.threshold.{query,fetch}.{warn,info,
    debug,trace}`), each logging at the matching logger level on its own
    phase logger. `-1` (or any negative) disables a threshold. Phase
    times come from the request's telemetry phase breakdown; without one
    (envelope-served requests) the query phase falls back to `took`."""
    from opensearch_tpu.common.settings import parse_time_value
    took_ms = res.get("took", 0)
    phase_times = phase_times or {}
    phase_ms = {"query": phase_times.get("query", took_ms),
                "fetch": phase_times.get("fetch", 0.0)}
    total_hits = (res.get("hits", {}).get("total") or {}).get("value")
    # transfer attribution (telemetry/ledger.py via the request's
    # LedgerScope): a slow query whose wall is transfer volume says so in
    # its own log line. 0 when the ledger is off — the fields stay so
    # line-parsers see a fixed shape.
    bytes_fetched = int(phase_times.get("bytes_fetched", 0) or 0)
    device_get_ms = float(phase_times.get("device_get", 0.0) or 0.0)
    # the query's shape id (ISSUE 15): the interned template signature
    # (fallback structural hash) telemetry/insights.py groups costs by —
    # a slow-log line joins its insights shape row without re-parsing
    # the body. Resolved lazily: only a line that actually fires pays
    # the intern walk.
    shape_id = None
    for name in node.indices.resolve(index_expr, ignore_unavailable=True):
        settings = node.indices.get(name).settings
        for phase, t_ms in phase_ms.items():
            for level, py_level in _SLOW_LOG_LEVELS:
                threshold = settings.get(
                    f"search.slowlog.threshold.{phase}.{level}")
                if threshold is None:
                    continue
                from opensearch_tpu.common.errors import SettingsError
                try:
                    threshold_s = parse_time_value(threshold, "slowlog")
                except (SettingsError, TypeError, ValueError):
                    continue        # unparseable threshold never logs
                if threshold_s < 0 or t_ms < threshold_s * 1000:
                    continue
                if shape_id is None:
                    from opensearch_tpu.telemetry.insights import \
                        query_shape
                    shape_id = query_shape((body or {}).get("query"))[0]
                _slow_logger(phase).log(
                    py_level,
                    "[%s] took[%sms], took[%s][%.1fms], total_hits[%s], "
                    "bytes_fetched[%s], device_get_ms[%.1f], shape[%s], "
                    "source[%s]",
                    name, took_ms, phase, t_ms, total_hits,
                    bytes_fetched, device_get_ms, shape_id, body)
                break               # most severe matching level only


# ---------------------------------------------------------------- documents

def register_document_actions(node, c):
    def _run_ingest_op(req, fn):
        """Run a single-doc write handler under an ingest lifecycle
        timeline (telemetry/lifecycle.py IngestRecorder, ISSUE 13):
        arrive at construction, engine phases (parse/version_plan/
        translog_append) accumulate via the thread binding,
        refresh_wait lands from maybe_refresh, respond on exit. The
        disabled path costs the timeline() gate — one attribute load
        and a branch."""
        ing = TELEMETRY.ingest
        tl = ing.timeline()
        if tl is None:
            return fn(req)
        try:
            with ing.bound(tl):
                out = fn(req)
        except BaseException:  # except-ok: timeline lifecycle -- completes the ingest timeline with error status, then always re-raises
            tl.event("respond")
            ing.complete(tl, status="error", kind="op")
            raise
        tl.event("respond")
        ing.complete(tl, status="ok", kind="op")
        return out

    def write_params(req):
        kw = {}
        if req.param("if_seq_no") is not None:
            kw["if_seq_no"] = req.int_param("if_seq_no")
        if req.param("if_primary_term") is not None:
            kw["if_primary_term"] = req.int_param("if_primary_term")
        if req.param("version") is not None and \
                req.param("version_type") == "external":
            kw["external_version"] = req.int_param("version")
        return kw

    def maybe_refresh(req, svc):
        mode = req.param("refresh")
        if mode in ("true", "", "wait_for"):
            tl = TELEMETRY.ingest.current()
            if tl is None:
                svc.refresh()
                return
            # refresh_wait: how long THIS request blocked on making its
            # write searchable (seal + device upload + reader sync) —
            # `wait_for` semantics collapse to a forced refresh on the
            # single-node build, but the wait is measured either way
            t0 = time.monotonic()
            svc.refresh()
            tl.event("refresh_wait",
                     ms=round((time.monotonic() - t0) * 1000, 3),
                     mode="wait_for" if mode == "wait_for" else "forced")

    def run_pipelines(svc, idx, doc_id, source, pipeline_param):
        """default_pipeline / request pipeline / final_pipeline chain
        (reference: TransportBulkAction ingest reroute + IngestService).
        Returns None when a drop processor dropped the doc."""
        pipeline = pipeline_param or svc.settings.get("default_pipeline")
        meta = {"_index": idx, "_id": doc_id}
        if pipeline and pipeline != "_none":
            source = node.ingest.execute(pipeline, source, meta)
            if source is None:
                return None
        final = svc.settings.get("final_pipeline")
        if final and final != "_none":
            source = node.ingest.execute(final, source, meta)
        return source

    def do_index(req):
        return _run_ingest_op(req, _do_index_inner)

    def _do_index_inner(req):
        # validation precedes auto-create: a rejected request must not
        # leave an empty index behind
        _check_require_alias(node, req)
        doc_id = req.param("id")
        _validate_doc_id(doc_id)
        idx = _write_index(node, req.param("index"))
        svc = node.indices.get(idx)
        op_type = req.param("op_type", "index")
        source = run_pipelines(svc, idx, doc_id, req.body or {},
                               req.param("pipeline"))
        if source is None:
            return 200, {"_index": idx, "_id": doc_id, "result": "noop",
                         "_shards": {"total": 0, "successful": 0,
                                     "failed": 0}}
        res = svc.index_doc(doc_id, source,
                            routing=req.param("routing"),
                            op_type=op_type, **write_params(req))
        maybe_refresh(req, svc)
        status = 201 if res.get("result") == "created" else 200
        return status, res

    def do_create(req):
        req.params["op_type"] = "create"
        return do_index(req)

    def do_get(req):
        svc = node.indices.get(
            node.indices.write_index(req.param("index")))
        res = svc.get_doc(req.param("id"), routing=req.param("routing"),
                          realtime=req.bool_param("realtime", True))
        return (200 if res.get("found") else 404), res

    def do_get_source(req):
        svc = node.indices.get(node.indices.write_index(req.param("index")))
        res = svc.get_doc(req.param("id"), routing=req.param("routing"))
        if not res.get("found"):
            return 404, {"error": f"document [{req.param('id')}] missing"}
        return 200, res.get("_source")

    def do_delete(req):
        return _run_ingest_op(req, _do_delete_inner)

    def _do_delete_inner(req):
        idx = node.indices.write_index(req.param("index"))
        svc = node.indices.get(idx)
        res = svc.delete_doc(req.param("id"), routing=req.param("routing"),
                             **write_params(req))
        maybe_refresh(req, svc)
        return (200 if res.get("result") == "deleted" else 404), res

    def do_update(req):
        return _run_ingest_op(req, _do_update_inner)

    def _do_update_inner(req):
        # update auto-creates like any document write (the reference's
        # AutoCreateIndex covers TransportUpdateAction too — an upsert
        # against a fresh index must not 404)
        _check_require_alias(node, req)
        _validate_doc_id(req.param("id"))
        idx = _write_index(node, req.param("index"))
        svc = node.indices.get(idx)
        res = svc.update_doc(req.param("id"), req.body or {},
                             routing=req.param("routing"), **write_params(req))
        maybe_refresh(req, svc)
        return res

    def do_mget(req):
        body = req.body or {}
        default_index = req.param("index")
        docs_spec = body.get("docs")
        if docs_spec is None and "ids" in body:
            docs_spec = [{"_id": i} for i in body["ids"]]
        if docs_spec is None:
            raise IllegalArgumentError("unexpected content, expected [docs] or [ids]")
        docs = []
        for spec in docs_spec:
            idx = spec.get("_index", default_index)
            if idx is None:
                raise IllegalArgumentError("index is missing for doc")
            try:
                svc = node.indices.get(node.indices.write_index(idx))
                docs.append(svc.get_doc(str(spec["_id"]),
                                        routing=spec.get("routing")))
            except IndexNotFoundError:
                docs.append({"_index": idx, "_id": spec.get("_id"),
                             "error": {"type": "index_not_found_exception",
                                       "reason": f"no such index [{idx}]"}})
        return {"docs": docs}

    def do_bulk(req):
        ing = TELEMETRY.ingest
        tl = ing.timeline(detail=False)   # bulk: phases only, no per-op
        payload_bytes = len(req.raw_body or b"")
        node.indexing_pressure.acquire(payload_bytes)
        if tl is not None:
            tl.event("admit", bytes=payload_bytes)
        ops = [0]
        try:
            if tl is None:
                return _do_bulk_inner(req)
            with ing.bound(tl):
                out = _do_bulk_inner(req)
            ops[0] = len(out.get("items") or [])
            tl.event("respond")
            ing.complete(tl, status="error" if out.get("errors")
                         else "ok", kind="bulk", ops=ops[0])
            return out
        except BaseException:  # except-ok: timeline lifecycle -- completes the bulk ingest timeline with error status, then always re-raises
            if tl is not None:
                tl.event("respond")
                ing.complete(tl, status="error", kind="bulk", ops=ops[0])
            raise
        finally:
            node.indexing_pressure.release(payload_bytes)

    def _do_bulk_inner(req):
        ops = _ndjson_lines(req)
        default_index = req.param("index")
        # regroup NDJSON action/source pairs into the ops shape the
        # index-service bulk API takes, resolving per-item indices
        items: List[dict] = []
        i = 0
        while i < len(ops):
            action_line = ops[i]
            i += 1
            if len(action_line) != 1:
                raise IllegalArgumentError(
                    "Malformed action/metadata line, expected one action")
            op, meta = next(iter(action_line.items()))
            if op not in ("index", "create", "update", "delete"):
                raise IllegalArgumentError(
                    f"Unknown action [{op}], expected one of "
                    f"[create, delete, index, update]")
            entry = {"action": op,
                     **{k.lstrip("_"): v for k, v in meta.items()
                        if k in ("_index", "_id", "routing", "_routing",
                                 "if_seq_no", "if_primary_term")}}
            if entry.get("id") is not None:
                # JSON metadata may carry numeric ids; ids are strings
                # everywhere downstream (routing hash, doc tables)
                entry["id"] = str(entry["id"])
            entry.setdefault("index", default_index)
            if entry.get("index") is None:
                raise IllegalArgumentError("bulk item missing _index")
            if op != "delete":
                if i >= len(ops):
                    raise IllegalArgumentError(
                        f"bulk [{op}] action missing source line")
                entry["source"] = ops[i]
                i += 1
            items.append(entry)

        # group by concrete index, preserving order within each index;
        # responses keep the original item order (reference: BulkResponse)
        by_index: Dict[str, List[int]] = {}
        for pos, item in enumerate(items):
            concrete = _write_index(node, item["index"])
            item["index"] = concrete
            by_index.setdefault(concrete, []).append(pos)
        responses: List[Optional[dict]] = [None] * len(items)
        errors = False
        took = 0
        for concrete, positions in by_index.items():
            svc = node.indices.get(concrete)
            sub_ops = []
            for p in positions:
                item = items[p]
                if item["action"] in ("index", "create"):
                    source = run_pipelines(svc, concrete, item.get("id"),
                                           item["source"],
                                           req.param("pipeline"))
                    if source is None:  # dropped by a pipeline
                        responses[p] = {item["action"]: {
                            "_index": concrete, "_id": item.get("id"),
                            "result": "noop", "status": 200}}
                        continue
                    item = {**item, "source": source}
                sub_ops.append((p, item))
            if not sub_ops:
                continue
            res = svc.bulk([it for _, it in sub_ops])
            positions = [p for p, _ in sub_ops]
            took = max(took, res.get("took", 0))
            errors = errors or res.get("errors", False)
            for p, item_res in zip(positions, res["items"]):
                responses[p] = item_res
        if req.param("refresh") in ("true", "", "wait_for"):
            _tl = TELEMETRY.ingest.current()
            _t0 = time.monotonic() if _tl is not None else 0.0
            for concrete in by_index:
                node.indices.get(concrete).refresh()
            if _tl is not None:
                _tl.event(
                    "refresh_wait",
                    ms=round((time.monotonic() - _t0) * 1000, 3),
                    mode="wait_for" if req.param("refresh") == "wait_for"
                    else "forced")
            # BulkItemResponse reports forced_refresh per successful item
            # when the request forced one (DocWriteResponse#forcedRefresh)
            for item_res in responses:
                if item_res:
                    body = next(iter(item_res.values()))
                    if isinstance(body, dict) and "error" not in body:
                        body["forced_refresh"] = True
        return {"took": took, "errors": errors, "items": responses}

    c.register("PUT", "/{index}/_doc/{id}", do_index)
    c.register("POST", "/{index}/_doc/{id}", do_index)
    c.register("POST", "/{index}/_doc", do_index)
    c.register("PUT", "/{index}/_create/{id}", do_create)
    c.register("POST", "/{index}/_create/{id}", do_create)
    c.register("GET", "/{index}/_doc/{id}", do_get)
    c.register("GET", "/{index}/_source/{id}", do_get_source)
    c.register("DELETE", "/{index}/_doc/{id}", do_delete)
    c.register("POST", "/{index}/_update/{id}", do_update)
    c.register("GET", "/_mget", do_mget)
    c.register("POST", "/_mget", do_mget)
    c.register("GET", "/{index}/_mget", do_mget)
    c.register("POST", "/{index}/_mget", do_mget)
    c.register("POST", "/_bulk", do_bulk)
    c.register("PUT", "/_bulk", do_bulk)
    c.register("POST", "/{index}/_bulk", do_bulk)
    c.register("PUT", "/{index}/_bulk", do_bulk)


# ------------------------------------------------------------------- search

def register_search_actions(node, c):
    from opensearch_tpu.search.scroll import (
        continue_scroll, create_pit, delete_pits, delete_scrolls,
        search_with_pit, start_scroll)

    def _total_as_int(resp):
        """rest_total_hits_as_int=true renders hits.total as the bare
        number (the pre-7.x shape the YAML suites request)."""
        if isinstance(resp, dict):
            hits = resp.get("hits")
            if isinstance(hits, dict) and isinstance(hits.get("total"),
                                                     dict):
                hits["total"] = hits["total"].get("value", 0)
            for sub in resp.get("responses", []):
                _total_as_int(sub)
        return resp

    def do_search(req):
        body = req.body if isinstance(req.body, dict) else {}
        body = dict(body)
        # URI-search params override/augment the body
        if req.param("q") is not None:
            body["query"] = {"query_string": {"query": req.param("q")}}
        if req.param("search_type"):
            body["search_type"] = req.param("search_type")
        if req.param("timeout") is not None:
            # the long-ignored timeout param: enforced at phase
            # boundaries by the controller (deadline checkpoints)
            body["timeout"] = req.param("timeout")
        if req.param("allow_partial_search_results") is not None:
            body["allow_partial_search_results"] = req.bool_param(
                "allow_partial_search_results", True)
        for p in ("from", "size"):
            if req.param(p) is not None:
                body[p] = req.int_param(p)
        if req.param("sort") is not None:
            body["sort"] = [
                ({s.split(":")[0]: s.split(":")[1]} if ":" in s else s)
                for s in req.param("sort").split(",")]
        if req.param("_source") is not None:
            v = req.param("_source")
            body["_source"] = (v.split(",") if "," in v
                               else (v if v not in ("true", "false")
                                     else v == "true"))
        includes = req.param("_source_includes")
        excludes = req.param("_source_excludes")
        if includes or excludes:
            body["_source"] = {
                **({"includes": includes.split(",")} if includes else {}),
                **({"excludes": excludes.split(",")} if excludes else {})}
        as_int = req.param("rest_total_hits_as_int") == "true"
        if req.param("request_cache") is not None \
                and not req.param("scroll"):
            # the request's word beats index.requests.cache.enable
            # (indices/request_cache.py `admits`)
            from opensearch_tpu.indices.request_cache import REQUEST_KEY
            body[REQUEST_KEY] = req.bool_param("request_cache", True)
        if req.param("scroll"):
            if int(body.get("size", 10)) == 0:
                raise IllegalArgumentError(
                    "[size] cannot be [0] in a scroll context")
            if req.param("request_cache"):
                raise IllegalArgumentError(
                    "[request_cache] cannot be used in a scroll context")
            out = start_scroll(node, req.param("index"), body,
                               req.param("scroll"))
        elif isinstance(body.get("pit"), dict):
            out = search_with_pit(node, body)
        else:
            out = _run_search(node, req.param("index"), body,
                              search_pipeline=req.param("search_pipeline"),
                              tenant=req.tenant())
        return _total_as_int(out) if as_int else out

    def do_field_caps(req):
        """_field_caps: per-field search/aggregation capabilities across
        indices (reference: action/fieldcaps/TransportFieldCapabilities
        Action — merges per-index mapper views)."""
        expr = req.param("index")
        names = node.indices.resolve(expr) if expr \
            else list(node.indices.indices)
        patterns = (req.param("fields")
                    or (req.body or {}).get("fields") or "*")
        if isinstance(patterns, str):
            patterns = patterns.split(",")
        import fnmatch as _fn
        fields: Dict[str, dict] = {}
        for n in names:
            mapper = node.indices.get(n).mapper
            for fname, ft in mapper.field_types.items():
                if "#" in fname:
                    continue    # hidden columns (join parent id)
                if not any(_fn.fnmatchcase(fname, p) for p in patterns):
                    continue
                searchable = bool(ft.index)
                aggregatable = bool(ft.doc_values) and not ft.is_text
                caps = fields.setdefault(fname, {}).setdefault(
                    ft.type, {"type": ft.type,
                              "searchable": searchable,
                              "aggregatable": aggregatable})
                caps["searchable"] = caps["searchable"] or searchable
                caps["aggregatable"] = caps["aggregatable"] or aggregatable
        return {"indices": sorted(names), "fields": fields}

    def do_termvectors(req):
        """_termvectors: per-field term statistics for one document
        (reference: action/termvectors/TransportTermVectorsAction). Terms,
        freqs and positions come from the live segment postings."""
        index = req.param("index")
        doc_id = req.param("id")
        names = node.indices.resolve(index, allow_aliases=True)
        if not names:
            from opensearch_tpu.common.errors import IndexNotFoundError
            raise IndexNotFoundError(index)
        svc = node.indices.get(names[0])
        shard = svc.shard_for(doc_id, routing=req.param("routing"))
        shard.refresh()
        wanted = req.param("fields")
        wanted = wanted.split(",") if wanted else None
        found = False
        term_vectors: Dict[str, dict] = {}
        for seg in shard.engine.segments:
            ord_ = seg.ord_of(doc_id)
            if ord_ is None:
                continue
            found = True
            for (field, term), tm in seg.term_dict.items():
                if "#" in field or (wanted and field not in wanted):
                    continue
                ft = svc.mapper.get_field(field)
                if ft is None or not ft.is_text:
                    continue
                blocks = seg.post_docs[
                    tm.start_block:tm.start_block + tm.num_blocks].ravel()
                hits = np.nonzero(blocks == ord_)[0]
                if not len(hits):
                    continue
                # postings pad only the tail with -1, so the entry index
                # is also the index into the parallel positions lists
                entry_i = int(hits[0])
                tf = int(seg.post_tf[
                    tm.start_block:tm.start_block
                    + tm.num_blocks].ravel()[entry_i])
                tinfo = {"term_freq": tf, "doc_freq": tm.doc_freq,
                         "ttf": tm.total_term_freq}
                pos_lists = seg.positions.get((field, term))
                if pos_lists is not None and entry_i < len(pos_lists):
                    tinfo["tokens"] = [
                        {"position": int(p)}
                        for p in pos_lists[entry_i]]
                fld = term_vectors.setdefault(field, {
                    "field_statistics": {
                        "doc_count":
                            seg.field_stats[field].doc_count,
                        "sum_doc_freq":
                            seg.field_stats[field].sum_doc_freq,
                        "sum_ttf":
                            seg.field_stats[field].sum_total_term_freq},
                    "terms": {}})
                fld["terms"][term] = tinfo
            break
        return {"_index": names[0], "_id": doc_id, "found": found,
                "term_vectors": term_vectors}

    def do_validate_query(req):
        """_validate/query: parse + compile the query without running it
        (reference: action/admin/indices/validate/query)."""
        body = req.body or {}
        q = body.get("query", {"match_all": {}})
        explain = req.param("explain") == "true"
        expr = req.param("index")
        # a missing index is a 404, not an invalid query
        names = node.indices.resolve(expr, allow_no_indices=False) \
            if expr else []
        try:
            query_node = dsl.parse_query(q)
            for n in names:
                svc = node.indices.get(n)
                shard = svc.shards[0]
                shard.refresh()
                from opensearch_tpu.search.compile import Compiler
                reader = shard.executor.reader
                compiler = Compiler(reader.mapper, reader.stats())
                for seg, (arrays, meta) in zip(reader.segments,
                                               reader.device):
                    compiler.compile(query_node, seg, meta)
        except (OpenSearchTpuError, ValueError, TypeError, KeyError) as e:
            # the endpoint's contract is to REPORT invalid queries, so bad
            # parameter types (e.g. a non-numeric boost raising ValueError
            # inside the parser) are valid:false, never a 500
            out = {"valid": False,
                   "_shards": {"total": 1, "successful": 1, "failed": 0}}
            if explain:
                out["explanations"] = [{"index": expr, "valid": False,
                                        "error": str(e)}]
            return out
        out = {"valid": True,
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if explain:
            out["explanations"] = [{"index": n, "valid": True,
                                    "explanation": str(body.get("query"))}
                                   for n in (names or [expr])]
        return out

    def do_explain(req):
        """_explain/{id}: score explanation for one document (reference:
        action/explain/TransportExplainAction — a single-shard query
        constrained to the doc)."""
        expr = req.param("index")
        doc_id = req.param("id")
        body = req.body or {}
        if req.param("q") is not None:
            query = {"query_string": {"query": req.param("q")}}
        else:
            if "query" not in body:
                raise IllegalArgumentError(
                    "[explain] request body must contain [query]")
            query = body["query"]
        names = node.indices.resolve(expr, allow_aliases=True)
        if not names:
            from opensearch_tpu.common.errors import IndexNotFoundError
            raise IndexNotFoundError(expr)
        if len(names) > 1:
            # the reference rejects multi-index _explain up front
            raise IllegalArgumentError(
                f"Alias [{expr}] has more than one indices associated "
                f"with it [{sorted(names)}], can't execute a single index "
                f"op")
        index = names[0]
        out = _run_search(node, expr, {
            "query": {"bool": {"must": [query],
                               "filter": [{"ids": {"values": [doc_id]}}]}},
            "size": 1, "explain": True}, search_pipeline="_none")
        hits = out["hits"]["hits"]
        if hits:
            return {"_index": index, "_id": doc_id, "matched": True,
                    "explanation": hits[0].get("_explanation")}
        exists = node.indices.get(index).shard_for(doc_id).get_doc(doc_id)
        if exists is None:
            return 404, {"_index": index, "_id": doc_id, "matched": False}
        return {"_index": index, "_id": doc_id, "matched": False}

    def do_scroll(req):
        body = req.body or {}
        scroll_id = body.get("scroll_id", req.param("scroll_id"))
        if not scroll_id:
            raise IllegalArgumentError("scroll_id is missing")
        out = continue_scroll(node, scroll_id, body.get("scroll",
                                                        req.param("scroll")))
        if req.param("rest_total_hits_as_int") == "true":
            out = _total_as_int(out)
        return out

    def do_delete_scroll(req):
        body = req.body or {}
        ids = body.get("scroll_id", req.param("scroll_id"))
        if ids == "_all" or req.path.endswith("/_all"):
            ids = None
        elif isinstance(ids, str):
            ids = [ids]
        return delete_scrolls(node, ids)

    def do_create_pit(req):
        keep_alive = req.param("keep_alive")
        if not keep_alive:
            raise IllegalArgumentError("[keep_alive] is required")
        return create_pit(node, req.param("index"), keep_alive)

    def do_delete_pit(req):
        body = req.body or {}
        ids = body.get("pit_id")
        if isinstance(ids, str):
            ids = [ids]
        return delete_pits(node, ids)

    def do_delete_all_pits(req):
        return delete_pits(node, None)

    def do_count(req):
        body = dict(req.body or {})
        if req.param("q") is not None:
            body["query"] = {"query_string": {"query": req.param("q")}}
        body["size"] = 0
        body.pop("from", None)
        body.pop("aggs", None)
        body.pop("aggregations", None)
        res = _run_search(node, req.param("index"), body,
                          search_pipeline="_none")
        return {"count": res["hits"]["total"]["value"],
                "_shards": res["_shards"]}

    def do_msearch(req):
        # `rest.msearch` in the always-on span ring: the whole handler,
        # whichever path serves the batch, on every exit
        ring = TELEMETRY.tracer.spans
        trace, sid, parent = ring.enter()
        t0 = time.monotonic_ns()
        try:
            return _msearch(req)
        finally:
            trace.spans.append((sid, parent, "rest.msearch", t0,
                                time.monotonic_ns(), None))
            ring.leave(trace, parent)

    def _msearch(req):
        lines = _ndjson_lines(req)
        if len(lines) % 2 != 0:
            raise IllegalArgumentError(
                "msearch request must have an even number of lines "
                "(header, body pairs)")
        pairs = []
        for i in range(0, len(lines), 2):
            header, body = lines[i], lines[i + 1]
            index_expr = header.get("index", req.param("index"))
            if isinstance(index_expr, list):
                index_expr = ",".join(index_expr)
            pairs.append((index_expr, body))

        # fast path: every search hits the same single unfiltered index →
        # IndexService.multi_search vmaps same-shaped queries into one
        # batched device program (capability from the SPMD _msearch work)
        exprs = {e for e, _ in pairs}
        if len(exprs) == 1 and not any(
                isinstance(b, dict) and b.get("search_pipeline")
                for _, b in pairs):
            expr = next(iter(exprs))
            try:
                names = node.indices.resolve(expr)
            except OpenSearchTpuError:
                names = []
            default_pipe = (node.indices.get(names[0]).settings.get(
                "search.default_pipeline") if len(names) == 1 else None)
            if len(names) == 1 and \
                    node.indices.alias_filter(expr, names[0]) is None and \
                    default_pipe in (None, "_none"):
                # one ROOT SPAN PER SUB-REQUEST even though the envelope
                # executes the whole batch as fused device programs — the
                # per-request accounting contract survives batching
                bodies = [b for _, b in pairs]
                # deadline parsing can 400 — do it BEFORE admission so a
                # malformed timeout can't leak backpressure permits (and
                # reuse the controller's parser so /_search and /_msearch
                # reject the same value with the same error shape)
                from opensearch_tpu.search.controller import \
                    _parse_deadline
                deadline = _parse_deadline(
                    {"timeout": req.param("timeout")})
                spans = [TELEMETRY.tracer.start_trace(
                    "rest.search", index=expr, msearch=True, batched=True,
                    batch_size=len(pairs)) for _ in pairs]
                task = node.task_manager.register(
                    "indices:data/read/msearch",
                    description=f"indices[{expr}][{len(bodies)}]",
                    cancellable=True)
                # envelope lifecycle (telemetry/lifecycle.py): one
                # timeline for the whole envelope — its coalesce/
                # dispatch/collect events come from the wave engine; the
                # admit event records the batch admission split
                flight = TELEMETRY.flight
                tl = flight.timeline()
                tenant = req.tenant()
                t_admit = time.monotonic() if tl is not None else 0.0
                # batch-aware admission (quota -> breaker -> deadline
                # shed -> permits): each stage admits what fits; the
                # OVERFLOW items reject with per-item 429 error objects
                # carrying the FIRST clipping stage's structured reason
                # instead of 429ing the whole envelope. NOTHING runs
                # between acquire and the try — release_batch lives in
                # finally (the permit-leak invariant chaos_sweep
                # re-checks).
                admitted, reject = \
                    node.search_backpressure.acquire_batch_ex(
                        len(bodies), tenant=tenant, deadline=deadline)
                tl_prev = None
                # insights tenant binding (ISSUE 15): the envelope's
                # per-item notes read it back thread-locally
                ins = TELEMETRY.insights.gate()
                ins_prev = ins.bind_tenant(tenant) \
                    if ins is not None else None
                t_exec0 = time.monotonic()
                try:
                    if tl is not None:
                        tl.queue_wait((t_exec0 - t_admit) * 1000)
                        tl.event("admit", admitted=admitted,
                                 rejected=len(bodies) - admitted)
                        if reject is not None:
                            tl.event(
                                "reject",
                                reason=getattr(reject, "reject_reason",
                                               "backpressure"),
                                tenant=tenant or "_default",
                                items=len(bodies) - admitted)
                        tl_prev = flight.bind(tl)
                    svc = node.indices.get(names[0])
                    sched = node.wave_scheduler.gate()
                    if sched is not None and admitted \
                            and svc.num_shards == 1 \
                            and len(bodies) <= \
                            sched.msearch_coalesce_max \
                            and all(sched.eligible(b)
                                    for b in bodies[:admitted]):
                        # wave scheduler: the envelope's admitted items
                        # enqueue as one unit and coalesce with
                        # whatever OTHER requests the window collects
                        # (cross-envelope shared waves). Permits stay
                        # held by this thread (release_batch in the
                        # finally); quota tokens of items the scheduler
                        # shed at deadline — or queue-full-rejected,
                        # rendered per-item through the PR 6 machinery
                        # — refund: they never executed.
                        from opensearch_tpu.common.errors import \
                            AdmissionRejectedError
                        from opensearch_tpu.search.executor import \
                            _item_error
                        svc.check_open()
                        try:
                            sub, shed_n = sched.execute_many(
                                svc.shards[0].executor,
                                bodies[:admitted], deadline=deadline,
                                timeline=tl, tenant=tenant, task=task)
                        except AdmissionRejectedError as qfull:
                            shed_n = admitted
                            item = _item_error(qfull)
                            sub = [dict(item) for _ in range(admitted)]
                        for _ in range(shed_n):
                            node.search_backpressure.refund_unserved(
                                tenant)
                        res = {"took": int((time.monotonic() - t_exec0)
                                           * 1000),
                               "responses": sub}
                    elif admitted == len(bodies):
                        res = svc.multi_search(
                            bodies, task=task, deadline=deadline)
                    else:
                        res = svc.multi_search(
                            bodies[:admitted], task=task,
                            deadline=deadline) if admitted else \
                            {"took": 0, "responses": []}
                    if admitted < len(bodies):
                        from opensearch_tpu.search.executor import \
                            _item_error
                        rejected = _item_error(
                            reject if reject is not None else
                            node.search_backpressure.rejection_error(
                                tenant=tenant))
                        res["responses"].extend(
                            dict(rejected)
                            for _ in range(len(bodies) - admitted))
                except BaseException as e:  # except-ok: span lifecycle -- closes every sub-request span, then always re-raises
                    for s in spans:
                        s.end(error=e)
                    raise
                finally:
                    if ins is not None:
                        ins.unbind_tenant(ins_prev)
                    node.task_manager.unregister(task)
                    node.search_backpressure.release_batch(
                        admitted,
                        service_ms=(time.monotonic() - t_exec0) * 1000.0)
                    if tl is not None:
                        flight.unbind(tl_prev)
                        tl.event("respond")
                        # the envelope's ONE timeline attaches to the
                        # FIRST sub-request's span: the per-wave
                        # coalesce/dispatch/collect/overlap events must
                        # reach a trace (tools/trace_report.py's wave
                        # pipeline table) on the real msearch path, and
                        # duplicating the dict onto all B spans would
                        # bloat the ring B-fold
                        flight.complete(
                            tl, status="error"
                            if sys.exc_info()[0] is not None else "ok",
                            span=spans[0] if spans else None)
                    for s in spans:
                        TELEMETRY.tracer.finish(s)
                for r in res["responses"]:
                    r.setdefault("status", 200)
                return res

        responses = []
        took = 0
        for index_expr, body in pairs:
            try:
                res = _run_search(node, index_expr, body,
                                  tenant=req.tenant())
                res["status"] = 200
                took = max(took, res.get("took", 0))
                responses.append(res)
            except OpenSearchTpuError as e:
                responses.append({"error": e.to_xcontent(),
                                  "status": e.status})
        return {"took": took, "responses": responses}

    c.register("GET", "/_search", do_search)
    c.register("POST", "/_search", do_search)
    c.register("GET", "/{index}/_search", do_search)
    c.register("POST", "/{index}/_search", do_search)
    c.register("GET", "/_count", do_count)
    c.register("POST", "/_count", do_count)
    c.register("GET", "/{index}/_count", do_count)
    c.register("POST", "/{index}/_count", do_count)
    c.register("GET", "/_msearch", do_msearch)
    c.register("POST", "/_msearch", do_msearch)
    c.register("GET", "/{index}/_msearch", do_msearch)
    c.register("POST", "/{index}/_msearch", do_msearch)
    c.register("GET", "/{index}/_explain/{id}", do_explain)
    c.register("POST", "/{index}/_explain/{id}", do_explain)
    c.register("GET", "/_field_caps", do_field_caps)
    c.register("POST", "/_field_caps", do_field_caps)
    c.register("GET", "/{index}/_field_caps", do_field_caps)
    c.register("POST", "/{index}/_field_caps", do_field_caps)
    c.register("GET", "/{index}/_termvectors/{id}", do_termvectors)
    c.register("POST", "/{index}/_termvectors/{id}", do_termvectors)
    c.register("GET", "/_validate/query", do_validate_query)
    c.register("POST", "/_validate/query", do_validate_query)
    c.register("GET", "/{index}/_validate/query", do_validate_query)
    c.register("POST", "/{index}/_validate/query", do_validate_query)
    c.register("GET", "/_search/scroll", do_scroll)
    c.register("POST", "/_search/scroll", do_scroll)
    c.register("POST", "/_search/scroll/{scroll_id}", do_scroll)
    c.register("DELETE", "/_search/scroll", do_delete_scroll)
    c.register("DELETE", "/_search/scroll/{scroll_id}", do_delete_scroll)
    c.register("DELETE", "/_search/scroll/_all", do_delete_scroll)
    c.register("POST", "/{index}/_search/point_in_time", do_create_pit)
    c.register("DELETE", "/_search/point_in_time", do_delete_pit)
    c.register("DELETE", "/_search/point_in_time/_all", do_delete_all_pits)


# --------------------------------------------------------- search pipelines

def register_search_pipeline_actions(node, c):
    """PUT/GET/DELETE /_search/pipeline/{id} — search-pipeline CRUD
    persisted in cluster state (reference: rest/action/search/
    RestPutSearchPipelineAction + SearchPipelineService cluster-state
    updates)."""

    def do_put_pipeline(req):
        node.search_pipelines.put(req.param("id"), req.body or {})
        node.persist_metadata()
        return {"acknowledged": True}

    def do_get_pipeline(req):
        pid = req.param("id")
        if pid is None or pid in ("*", "_all"):
            return {pid_: p.body
                    for pid_, p in node.search_pipelines.pipelines.items()}
        import fnmatch as _fn
        matched = {pid_: p.body
                   for pid_, p in node.search_pipelines.pipelines.items()
                   if _fn.fnmatchcase(pid_, pid)}
        if not matched:
            return 404, {}
        return matched

    def do_delete_pipeline(req):
        node.search_pipelines.delete(req.param("id"))     # 404 if missing
        node.persist_metadata()
        return {"acknowledged": True}

    c.register("PUT", "/_search/pipeline/{id}", do_put_pipeline)
    c.register("GET", "/_search/pipeline", do_get_pipeline)
    c.register("GET", "/_search/pipeline/{id}", do_get_pipeline)
    c.register("DELETE", "/_search/pipeline/{id}", do_delete_pipeline)


# ------------------------------------------------------------ index admin

def register_indices_actions(node, c):
    def do_create_index(req):
        name = req.param("index")
        node.indices.create_index(name, req.body)
        node.persist_metadata()
        return {"acknowledged": True, "shards_acknowledged": True,
                "index": name}

    def do_delete_index(req):
        expr = req.param("index")
        ignore_unavailable = req.param("ignore_unavailable") == "true"
        # aliases may not be deleted via DELETE /{index}
        # (IndexNameExpressionResolver forbids write ops on aliases);
        # exclusions and wildcards delegate to the shared resolver
        parts = [p.strip() for p in expr.split(",") if p.strip()]
        filtered = []
        for i, part in enumerate(parts):
            concrete = part[1:] if part.startswith("-") and i > 0 else part
            if concrete in node.indices.aliases:
                if ignore_unavailable:
                    continue
                raise IllegalArgumentError(
                    f"The provided expression [{concrete}] matches an "
                    f"alias, specify the corresponding concrete indices "
                    f"instead.")
            filtered.append(part)
        if not filtered:
            return {"acknowledged": True}
        names = node.indices.resolve(
            ",".join(filtered), allow_aliases=False,
            ignore_unavailable=ignore_unavailable)
        for n in dict.fromkeys(names):
            node.indices.delete_index(n)
        node.persist_metadata()
        return {"acknowledged": True}

    def index_info(name):
        svc = node.indices.get(name)
        return {
            "aliases": {a: m.to_dict() for a, m in
                        node.indices.alias_metadata(name).items()},
            "mappings": svc.mapping_dict(),
            "settings": {"index": {
                "number_of_shards": str(svc.num_shards),
                "number_of_replicas": str(svc.num_replicas),
                "creation_date": str(svc.creation_date),
                "uuid": name,
                "provided_name": name,
                **{k: v for k, v in svc.settings.items()
                   if k not in ("number_of_shards", "number_of_replicas")},
            }},
        }

    def do_get_index(req):
        names = node.indices.resolve(req.param("index"),
                                     allow_no_indices=False)
        return {n: index_info(n) for n in names}

    def do_index_exists(req):
        try:
            names = node.indices.resolve(req.param("index"),
                                         allow_no_indices=False)
        except IndexNotFoundError:
            return 404, ""
        return (200 if names else 404), ""

    def do_get_mapping(req):
        names = node.indices.resolve(req.param("index"))
        return {n: {"mappings": node.indices.get(n).mapping_dict()}
                for n in names}

    def do_put_mapping(req):
        for n in node.indices.resolve(req.param("index"),
                                      allow_no_indices=False):
            node.indices.get(n).put_mapping(req.body or {})
        node.persist_metadata()
        return {"acknowledged": True}

    def do_get_settings(req):
        names = node.indices.resolve(req.param("index"))
        out = {n: {"settings": index_info(n)["settings"]} for n in names}
        name_filter = req.param("name")
        if name_filter and name_filter not in ("_all", "*"):
            import fnmatch as _fn
            patterns = [p[len("index."):] if p.startswith("index.") else p
                        for p in name_filter.split(",")]
            out = {n: {"settings": {"index": {
                k: v for k, v in e["settings"]["index"].items()
                if any(_fn.fnmatchcase(f"index.{k}", f"index.{p}")
                       or _fn.fnmatchcase(k, p) for p in patterns)}}}
                for n, e in out.items()}
        return out

    def do_put_settings(req):
        from opensearch_tpu.indices.service import (_normalize_settings,
                                                    validate_dynamic_updates)
        updates = _normalize_settings(req.body or {})
        validate_dynamic_updates(updates)
        for n in node.indices.resolve(req.param("index"),
                                      allow_no_indices=False):
            svc = node.indices.get(n)
            svc.settings.update(updates)
            if "number_of_replicas" in updates:
                svc.num_replicas = int(updates["number_of_replicas"])
            if "max_result_window" in updates:
                for shard in svc.shards:
                    shard.executor.max_result_window = \
                        int(updates["max_result_window"])
            if "requests.cache.enable" in updates:
                from opensearch_tpu.indices.request_cache import enabled_by
                for shard in svc.shards:
                    shard.executor.request_cache_enabled = \
                        enabled_by(svc.settings)
        return {"acknowledged": True}

    def do_refresh(req):
        names = node.indices.resolve(req.param("index"))
        for n in names:
            node.indices.get(n).refresh()
        return {"_shards": _shards_header(node, names)}

    def do_flush(req):
        names = node.indices.resolve(req.param("index"))
        for n in names:
            node.indices.get(n).flush()
        return {"_shards": _shards_header(node, names)}

    def do_forcemerge(req):
        names = node.indices.resolve(req.param("index"))
        for n in names:
            node.indices.get(n).force_merge()
        return {"_shards": _shards_header(node, names)}

    def do_close_index(req):
        names = node.indices.close_index(req.param("index"))
        return {"acknowledged": True, "shards_acknowledged": True,
                "indices": {n: {"closed": True} for n in names}}

    def do_open_index(req):
        node.indices.open_index(req.param("index"))
        return {"acknowledged": True, "shards_acknowledged": True}

    def do_stats(req):
        names = node.indices.resolve(req.param("index"))
        out_indices = {}
        total_docs = total_del = 0
        for n in names:
            st = node.indices.get(n).stats()
            total_docs += st["docs"]["count"]
            total_del += st["docs"]["deleted"]
            out_indices[n] = {
                "primaries": {"docs": st["docs"],
                              "segments": st["segments"]},
                "total": {"docs": st["docs"], "segments": st["segments"]},
            }
        return {
            "_shards": _shards_header(node, names),
            "_all": {"primaries": {"docs": {"count": total_docs,
                                            "deleted": total_del}},
                     "total": {"docs": {"count": total_docs,
                                        "deleted": total_del}}},
            "indices": out_indices,
        }

    def do_analyze(req):
        from opensearch_tpu.analysis.registry import get_default_registry
        body = req.body or {}
        text = body.get("text")
        if text is None:
            raise IllegalArgumentError("text is missing")
        texts = text if isinstance(text, list) else [text]
        analyzer = get_default_registry().get(body.get("analyzer", "standard"))
        tokens = []
        pos_offset = 0
        for t in texts:
            last_pos = 0
            for term, pos in analyzer.analyze(t):
                tokens.append({"token": term, "type": "<ALPHANUM>",
                               "position": pos + pos_offset})
                last_pos = pos
            pos_offset += last_pos + 100  # position gap between array items
        return {"tokens": tokens}

    c.register("PUT", "/{index}", do_create_index)
    c.register("DELETE", "/{index}", do_delete_index)
    c.register("GET", "/{index}", do_get_index)
    c.register("HEAD", "/{index}", do_index_exists)
    c.register("GET", "/_mapping", do_get_mapping)
    c.register("GET", "/{index}/_mapping", do_get_mapping)
    c.register("PUT", "/{index}/_mapping", do_put_mapping)
    c.register("POST", "/{index}/_mapping", do_put_mapping)
    c.register("GET", "/_settings", do_get_settings)
    c.register("GET", "/_settings/{name}", do_get_settings)
    c.register("GET", "/{index}/_settings", do_get_settings)
    c.register("GET", "/{index}/_settings/{name}", do_get_settings)
    c.register("PUT", "/{index}/_settings", do_put_settings)
    c.register("PUT", "/_settings", do_put_settings)
    c.register("POST", "/_refresh", do_refresh)
    c.register("GET", "/_refresh", do_refresh)
    c.register("POST", "/{index}/_refresh", do_refresh)
    c.register("POST", "/_flush", do_flush)
    c.register("POST", "/{index}/_flush", do_flush)
    c.register("POST", "/_forcemerge", do_forcemerge)
    c.register("POST", "/{index}/_forcemerge", do_forcemerge)
    c.register("POST", "/{index}/_close", do_close_index)
    c.register("POST", "/{index}/_open", do_open_index)
    c.register("GET", "/_stats", do_stats)
    c.register("GET", "/{index}/_stats", do_stats)
    c.register("GET", "/_analyze", do_analyze)
    c.register("POST", "/_analyze", do_analyze)
    c.register("GET", "/{index}/_analyze", do_analyze)
    c.register("POST", "/{index}/_analyze", do_analyze)


def _shards_header(node, names):
    total = sum(node.indices.get(n).num_shards for n in names)
    return {"total": total, "successful": total, "failed": 0}


# ------------------------------------------------------- aliases/templates

def register_alias_template_actions(node, c):
    def do_update_aliases(req):
        body = req.body or {}
        actions = body.get("actions")
        if not actions:
            raise IllegalArgumentError("No action specified")
        node.indices.update_aliases(actions)
        node.persist_metadata()
        return {"acknowledged": True}

    def do_put_alias(req):
        for n in node.indices.resolve(req.param("index"),
                                      allow_aliases=False,
                                      allow_no_indices=False):
            node.indices.put_alias(n, req.param("name"), req.body)
        node.persist_metadata()
        return {"acknowledged": True}

    def do_delete_alias(req):
        node.indices.remove_alias(req.param("index"), req.param("name"))
        node.persist_metadata()
        return {"acknowledged": True}

    def do_get_alias(req):
        name_filter = req.param("name")
        if name_filter in ("_all", "*"):
            name_filter = None
        index_filter = req.param("index")
        names = node.indices.resolve(index_filter, allow_aliases=True) \
            if index_filter else list(node.indices.indices)
        out: Dict[str, dict] = {}
        import fnmatch as _fn
        requested = name_filter.split(",") if name_filter else []
        found_patterns: set = set()
        for n in names:
            aliases = {}
            for alias, meta in node.indices.alias_metadata(n).items():
                if requested:
                    hit = [p for p in requested
                           if _fn.fnmatchcase(alias, p)]
                    if not hit:
                        continue
                    found_patterns.update(hit)
                aliases[alias] = meta.to_dict()
            if aliases or not requested:
                out[n] = {"aliases": aliases}
        # concrete requested names with no match → 404, but the body still
        # carries whatever WAS found (reference GetAliasesResponse shape)
        missing = sorted(p for p in requested
                         if p not in found_patterns and "*" not in p)
        if requested and missing:
            label = (f"alias [{missing[0]}]" if len(missing) == 1
                     else "aliases [" + ",".join(missing) + "]")
            return 404, {"error": f"{label} missing",
                         "status": 404, **out}
        return out

    def do_alias_exists(req):
        resp = do_get_alias(req)
        if isinstance(resp, tuple):
            return 404, ""
        return 200, ""

    def do_put_template(req, legacy):
        node.indices.put_template(req.param("name"), req.body or {},
                                  legacy=legacy)
        node.persist_metadata()
        return {"acknowledged": True}

    def do_get_template(req, legacy):
        store = (node.indices.legacy_templates if legacy
                 else node.indices.templates)
        name = req.param("name")
        if name:
            import fnmatch as _fn
            matched = {k: v for k, v in store.items()
                       if _fn.fnmatchcase(k, name)}
            if not matched:
                raise IndexNotFoundError(f"index template [{name}]")
        else:
            matched = store
        if legacy:
            return {k: v.to_dict() for k, v in matched.items()}
        return {"index_templates": [{"name": k, "index_template": v.to_dict()}
                                    for k, v in matched.items()]}

    def do_delete_template(req, legacy):
        node.indices.delete_template(req.param("name"), legacy=legacy)
        return {"acknowledged": True}

    def do_put_component(req):
        node.indices.put_component_template(req.param("name"), req.body or {})
        return {"acknowledged": True}

    def do_get_component(req):
        name = req.param("name")
        store = node.indices.component_templates
        matched = ({name: store[name]} if name and name in store
                   else {} if name else store)
        if name and not matched:
            raise IndexNotFoundError(f"component template [{name}]")
        return {"component_templates": [
            {"name": k, "component_template": v} for k, v in matched.items()]}

    c.register("POST", "/_aliases", do_update_aliases)
    c.register("PUT", "/{index}/_alias/{name}", do_put_alias)
    c.register("POST", "/{index}/_alias/{name}", do_put_alias)
    c.register("PUT", "/{index}/_aliases/{name}", do_put_alias)
    c.register("DELETE", "/{index}/_alias/{name}", do_delete_alias)
    c.register("DELETE", "/{index}/_aliases/{name}", do_delete_alias)
    c.register("GET", "/_alias", do_get_alias)
    c.register("GET", "/_alias/{name}", do_get_alias)
    c.register("GET", "/{index}/_alias", do_get_alias)
    c.register("GET", "/{index}/_alias/{name}", do_get_alias)
    c.register("HEAD", "/_alias/{name}", do_alias_exists)
    c.register("PUT", "/_template/{name}",
               lambda r: do_put_template(r, True))
    c.register("POST", "/_template/{name}",
               lambda r: do_put_template(r, True))
    c.register("GET", "/_template",
               lambda r: do_get_template(r, True))
    c.register("GET", "/_template/{name}",
               lambda r: do_get_template(r, True))
    c.register("DELETE", "/_template/{name}",
               lambda r: do_delete_template(r, True))
    c.register("PUT", "/_index_template/{name}",
               lambda r: do_put_template(r, False))
    c.register("POST", "/_index_template/{name}",
               lambda r: do_put_template(r, False))
    c.register("GET", "/_index_template",
               lambda r: do_get_template(r, False))
    c.register("GET", "/_index_template/{name}",
               lambda r: do_get_template(r, False))
    c.register("DELETE", "/_index_template/{name}",
               lambda r: do_delete_template(r, False))
    c.register("PUT", "/_component_template/{name}", do_put_component)
    c.register("GET", "/_component_template", do_get_component)
    c.register("GET", "/_component_template/{name}", do_get_component)


# ------------------------------------------------------------------ cluster

def register_cluster_actions(node, c):
    def do_root(req):
        return node.root_info()

    def do_health(req):
        return node.cluster_health(req.param("index"))

    def do_cluster_settings_get(req):
        out = dict(node.cluster_settings)
        if req.bool_param("include_defaults"):
            out["defaults"] = dict(node.settings)
        return out

    def do_cluster_settings_put(req):
        body = req.body or {}
        # validate-then-commit: a malformed admission value must 400
        # WITHOUT touching the store — a persisted bad key would 500
        # every later settings update (the apply re-runs over the full
        # merged map) and fail node restart from the gateway
        from opensearch_tpu.common.admission import AdmissionController
        from opensearch_tpu.common.settings import Settings
        candidate = {scope: dict(node.cluster_settings[scope])
                     for scope in ("persistent", "transient")}
        for scope in ("persistent", "transient"):
            for k, v in (body.get(scope) or {}).items():
                if v is None:
                    candidate[scope].pop(k, None)
                else:
                    candidate[scope][k] = v
        merged = Settings(node.settings).as_dict()
        merged.update(Settings(candidate["persistent"]).as_dict())
        merged.update(Settings(candidate["transient"]).as_dict())
        AdmissionController.parse_settings(merged)  # raises -> 400
        from opensearch_tpu.search.scheduler import WaveScheduler
        WaveScheduler.parse_settings(merged)        # raises -> 400
        node.cluster_settings["persistent"] = candidate["persistent"]
        node.cluster_settings["transient"] = candidate["transient"]
        # dynamic admission/quota/breaker settings take effect on the
        # controller immediately (common/admission.py apply_settings)
        node.apply_admission_settings()
        return {"acknowledged": True,
                "persistent": node.cluster_settings["persistent"],
                "transient": node.cluster_settings["transient"]}

    def _device_info() -> dict:
        """The accelerator as jax reports it — the chip smoke and the
        start-up log read the same three facts."""
        import jax
        dev = jax.devices()[0]
        return {"platform": dev.platform, "device_kind": dev.device_kind,
                "count": jax.device_count()}

    def do_cluster_stats(req):
        total_docs = sum(svc.stats()["docs"]["count"]
                         for svc in node.indices.indices.values())
        total_shards = sum(svc.num_shards
                           for svc in node.indices.indices.values())
        return {
            "cluster_name": node.cluster_name,
            "status": "green",
            "indices": {
                "count": len(node.indices.indices),
                "shards": {"total": total_shards},
                "docs": {"count": total_docs},
            },
            "nodes": {
                "count": {"total": 1, "data": 1, "cluster_manager": 1},
                "versions": [node.root_info()["version"]["number"]],
                "devices": _device_info(),
            },
        }

    def do_cluster_state(req):
        return {
            "cluster_name": node.cluster_name,
            "cluster_uuid": node.node_id,
            "metadata": {
                "indices": {n: {
                    "state": "open",
                    "settings": {"index": {
                        "number_of_shards": str(svc.num_shards),
                        "number_of_replicas": str(svc.num_replicas)}},
                    "mappings": svc.mapping_dict(),
                    "aliases": list(node.indices.alias_metadata(n)),
                } for n, svc in node.indices.indices.items()},
                "templates": {k: v.to_dict()
                              for k, v in node.indices.legacy_templates.items()},
            },
        }

    def do_nodes_info(req):
        return {
            "_nodes": {"total": 1, "successful": 1, "failed": 0},
            "cluster_name": node.cluster_name,
            "nodes": {node.node_id: {
                "name": node.node_name,
                "version": node.root_info()["version"]["number"],
                "roles": ["cluster_manager", "data", "ingest"],
                "tpu": _device_info(),
            }},
        }

    def do_nodes_stats(req):
        from opensearch_tpu.indices.query_cache import QUERY_CACHE
        from opensearch_tpu.indices.request_cache import REQUEST_CACHE
        from opensearch_tpu.monitor import (os_probe as _os_probe,
                                            process_probe as _process_probe)
        from opensearch_tpu.analysis.native import native_available
        from opensearch_tpu.search.warmup import WARMUP
        idx_stats = {n: svc.stats()
                     for n, svc in node.indices.indices.items()}
        import resource
        max_rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return {
            "_nodes": {"total": 1, "successful": 1, "failed": 0},
            "cluster_name": node.cluster_name,
            "nodes": {node.node_id: {
                "name": node.node_name,
                "indices": {
                    "docs": {"count": sum(s["docs"]["count"]
                                          for s in idx_stats.values()),
                             "deleted": sum(s["docs"]["deleted"]
                                            for s in idx_stats.values())},
                    "segments": {"count": sum(s["segments"]["count"]
                                              for s in idx_stats.values())},
                    "request_cache": REQUEST_CACHE.stats(),
                    "query_cache": QUERY_CACHE.stats(),
                },
                "search_warmup": WARMUP.stats(),
                "analysis": {"native_tokenizer": native_available()},
                "telemetry": TELEMETRY.stats(),
                "breakers": node.breaker_service.stats(),
                "indexing_pressure": node.indexing_pressure.stats(),
                "search_backpressure": node.search_backpressure.stats(),
                "scheduler": node.wave_scheduler.stats(),
                "thread_pool": node.threadpool.stats(),
                "os": _os_probe(),
                "process": {**_process_probe(),
                            "mem": {"resident_in_bytes": max_rss_kb * 1024}},
            }},
        }

    def do_cat_thread_pool(req):
        rows = [[node.node_name, name, st["active"], st["queue"],
                 st["rejected"], st["completed"], st["threads"]]
                for name, st in sorted(node.threadpool.stats().items())]
        return _cat_table(req, ["node_name", "name", "active", "queue",
                                "rejected", "completed", "size"], rows)

    c.register("GET", "/", do_root)
    c.register("GET", "/_cluster/health", do_health)
    c.register("GET", "/_cluster/health/{index}", do_health)
    c.register("GET", "/_cluster/settings", do_cluster_settings_get)
    c.register("PUT", "/_cluster/settings", do_cluster_settings_put)
    c.register("GET", "/_cluster/stats", do_cluster_stats)
    c.register("GET", "/_cluster/state", do_cluster_state)
    def do_hot_threads(req):
        """_nodes/hot_threads analog (monitor/jvm/HotThreads.java): sample
        every live Python thread's stack N times and report the hottest
        frames by sample count — same contract, interpreter threads
        instead of JVM threads."""
        import sys
        import threading
        import time as _time
        import traceback as _tb
        from collections import Counter

        try:
            samples = max(1, min(int(req.param("snapshots", "3")), 10))
            top_n = int(req.param("threads", "3"))
        except (TypeError, ValueError):
            raise IllegalArgumentError(
                "snapshots/threads must be integers")
        interval_s = 0.02
        per_thread: Dict[int, Counter] = {}
        names = {t.ident: t.name for t in threading.enumerate()}
        self_tid = threading.get_ident()
        for i in range(samples):
            for tid, frame in sys._current_frames().items():
                if tid == self_tid:
                    continue    # the sampler is always on-CPU (ref
                    # HotThreads excludes itself the same way)
                stack = "".join(_tb.format_stack(frame, limit=8))
                per_thread.setdefault(tid, Counter())[stack] += 1
            if i + 1 < samples:
                _time.sleep(interval_s)
        lines = [f"::: {{{node.node_name}}}{{{node.node_id}}}", ""]
        ranked = sorted(per_thread.items(),
                        key=lambda kv: -sum(kv[1].values()))
        for tid, stacks in ranked[:top_n]:
            top_stack, hits = stacks.most_common(1)[0]
            lines.append(
                f"   {hits}/{samples} snapshots sharing following "
                f"fragment of thread [{names.get(tid, tid)}]:")
            lines.append(top_stack.rstrip())
            lines.append("")
        return RestResponse(200, "\n".join(lines) + "\n",
                            content_type="text/plain")

    def do_nodes_filtered(req):
        # node-filter paths (_nodes/data:true, _nodes/master:true, ids,
        # names) — the single in-process node carries every role, so any
        # role filter resolves to it; unknown ids resolve to none
        flt = req.param("node_id") or ""
        out = do_nodes_info(req)
        if ":" in flt or flt in ("_all", "_local", "", node.node_id,
                                 node.node_name):
            return out
        return {**out, "_nodes": {"total": 0, "successful": 0, "failed": 0},
                "nodes": {}}

    c.register("GET", "/_nodes", do_nodes_info)
    c.register("GET", "/_nodes/stats", do_nodes_stats)
    c.register("GET", "/_nodes/{node_id}", do_nodes_filtered)
    c.register("GET", "/_cat/thread_pool", do_cat_thread_pool)
    c.register("GET", "/_nodes/hot_threads", do_hot_threads)
    c.register("GET", "/_nodes/{node_id}/hot_threads", do_hot_threads)


# --------------------------------------------------------------------- _cat

def _cat_table(req: RestRequest, headers: List[str],
               rows: List[List[Any]]) -> RestResponse:
    """Fixed-width text table like the reference's _cat output; ?v adds the
    header row, ?h=a,b selects columns, format=json renders JSON."""
    selected = req.param("h")
    if selected:
        names = [n.strip() for n in selected.split(",")]
        idxs = [headers.index(n) for n in names if n in headers]
        headers = [headers[i] for i in idxs]
        rows = [[r[i] for i in idxs] for r in rows]
    if req.param("format") == "json":
        return RestResponse(200, [dict(zip(headers, map(str, r)))
                                  for r in rows])
    str_rows = [[("" if v is None else str(v)) for v in r] for r in rows]
    display = ([headers] if req.bool_param("v") else []) + str_rows
    if not display:
        return RestResponse(200, "", content_type="text/plain")
    widths = [max(len(r[i]) for r in display)
              for i in range(len(display[0]))]
    lines = [" ".join(v.ljust(w) for v, w in zip(r, widths)).rstrip()
             for r in display]
    return RestResponse(200, "\n".join(lines) + "\n",
                        content_type="text/plain")


def register_cat_actions(node, c):
    def cat_indices(req):
        rows = []
        names = (node.indices.resolve(req.param("index"))
                 if req.param("index") else list(node.indices.indices))
        for n in names:
            svc = node.indices.get(n)
            st = svc.stats()
            rows.append(["green", "open", n, n, svc.num_shards,
                         svc.num_replicas, st["docs"]["count"],
                         st["docs"]["deleted"]])
        return _cat_table(req, ["health", "status", "index", "uuid", "pri",
                                "rep", "docs.count", "docs.deleted"], rows)

    def cat_health(req):
        h = node.cluster_health()
        return _cat_table(req, ["cluster", "status", "node.total",
                                "node.data", "shards", "pri", "relo", "init",
                                "unassign"],
                          [[node.cluster_name, h["status"],
                            h["number_of_nodes"], h["number_of_data_nodes"],
                            h["active_shards"], h["active_primary_shards"],
                            0, 0, 0]])

    def cat_count(req):
        expr = req.param("index")
        total = sum(node.indices.get(n).count()
                    for n in node.indices.resolve(expr))
        import time as _t
        now = int(_t.time())
        return _cat_table(req, ["epoch", "timestamp", "count"],
                          [[now, _t.strftime("%H:%M:%S", _t.gmtime(now)),
                            total]])

    def cat_shards(req):
        rows = []
        names = (node.indices.resolve(req.param("index"))
                 if req.param("index") else list(node.indices.indices))
        for n in names:
            svc = node.indices.get(n)
            for shard in svc.shards:
                st = shard.stats()
                rows.append([n, shard.shard_id, "p", "STARTED",
                             st["docs"]["count"], node.node_name])
        return _cat_table(req, ["index", "shard", "prirep", "state", "docs",
                                "node"], rows)

    def cat_aliases(req):
        rows = []
        for alias, members in node.indices.aliases.items():
            for idx, meta in members.items():
                rows.append([alias, idx,
                             "*" if meta.filter is not None else "-",
                             meta.index_routing or "-",
                             meta.search_routing or "-",
                             str(meta.is_write_index).lower()])
        return _cat_table(req, ["alias", "index", "filter", "routing.index",
                                "routing.search", "is_write_index"], rows)

    def cat_templates(req):
        rows = []
        for name, t in node.indices.legacy_templates.items():
            rows.append([name, str(t.index_patterns), t.priority,
                         t.version or "", ""])
        for name, t in node.indices.templates.items():
            rows.append([name, str(t.index_patterns), t.priority,
                         t.version or "", ""])
        return _cat_table(req, ["name", "index_patterns", "order", "version",
                                "composed_of"], rows)

    def cat_nodes(req):
        return _cat_table(req, ["ip", "node.role", "cluster_manager", "name"],
                          [["127.0.0.1", "dim", "*", node.node_name]])

    def cat_segments(req):
        rows = []
        names = (node.indices.resolve(req.param("index"))
                 if req.param("index") else list(node.indices.indices))
        for n in names:
            svc = node.indices.get(n)
            for shard in svc.shards:
                for seg in shard.executor.reader.segments:
                    rows.append([n, shard.shard_id, seg.seg_id,
                                 seg.live_doc_count,
                                 seg.num_docs - seg.live_doc_count,
                                 seg.memory_bytes(), "true",
                                 node.node_name])
        return _cat_table(req, ["index", "shard", "segment", "docs.count",
                                "docs.deleted", "size", "searchable",
                                "node"], rows)

    def cat_allocation(req):
        shards = sum(svc.num_shards
                     for svc in node.indices.indices.values())
        from opensearch_tpu.monitor import fs_probe
        disk = fs_probe(getattr(node.indices, "data_path", None))
        rows = [[shards, disk["used_in_bytes"], disk["available_in_bytes"],
                 disk["total_in_bytes"], "127.0.0.1", node.node_name]]
        return _cat_table(req, ["shards", "disk.used", "disk.avail",
                                "disk.total", "ip", "node"], rows)

    def cat_nodeattrs(req):
        rows = [[node.node_name, "127.0.0.1",
                 k[len("node.attr."):], str(v)]
                for k, v in sorted(node.settings.items())
                if k.startswith("node.attr.")]
        return _cat_table(req, ["node", "host", "attr", "value"], rows)

    def cat_repositories(req):
        rows = [[name, getattr(repo, "repo_type", "fs")]
                for name, repo in sorted(
                    node.repositories.repositories.items())]
        return _cat_table(req, ["id", "type"], rows)

    def cat_cluster_manager(req):
        return _cat_table(req, ["id", "host", "ip", "node"],
                          [[node.node_id, "127.0.0.1", "127.0.0.1",
                            node.node_name]])

    def cat_master_deprecated(req):
        from opensearch_tpu.common.logging import DEPRECATION
        DEPRECATION.deprecate(
            "cat_master",
            "[GET /_cat/master] is deprecated! Use [GET "
            "/_cat/cluster_manager] instead.")
        return cat_cluster_manager(req)

    def cat_pending_tasks(req):
        return _cat_table(req, ["insertOrder", "timeInQueue", "priority",
                                "source"], [])

    def cat_recovery(req):
        rows = []
        names = (node.indices.resolve(req.param("index"))
                 if req.param("index") else list(node.indices.indices))
        for n in names:
            svc = node.indices.get(n)
            for shard in svc.shards:
                rows.append([n, shard.shard_id, "0ms", "existing_store",
                             "done", node.node_name, node.node_name])
        return _cat_table(req, ["index", "shard", "time", "type", "stage",
                                "source_node", "target_node"], rows)

    def cat_root(req):
        paths = ["/_cat/indices", "/_cat/health", "/_cat/count",
                 "/_cat/shards", "/_cat/aliases", "/_cat/templates",
                 "/_cat/nodes", "/_cat/plugins", "/_cat/thread_pool",
                 "/_cat/segments", "/_cat/allocation", "/_cat/nodeattrs",
                 "/_cat/repositories", "/_cat/cluster_manager",
                 "/_cat/pending_tasks", "/_cat/recovery",
                 "/_cat/snapshots", "/_cat/tasks"]
        return RestResponse(200, "=^.^=\n" + "\n".join(paths) + "\n",
                            content_type="text/plain")

    def cat_plugins(req):
        from opensearch_tpu.plugins import installed_info
        lines = [f"{node.node_name} {p['name']} {p['component']}"
                 for p in installed_info()]
        return RestResponse(200, "\n".join(lines) + ("\n" if lines else ""),
                            content_type="text/plain")

    c.register("GET", "/_cat", cat_root)
    c.register("GET", "/_cat/plugins", cat_plugins)
    c.register("GET", "/_cat/indices", cat_indices)
    c.register("GET", "/_cat/indices/{index}", cat_indices)
    c.register("GET", "/_cat/health", cat_health)
    c.register("GET", "/_cat/count", cat_count)
    c.register("GET", "/_cat/count/{index}", cat_count)
    c.register("GET", "/_cat/shards", cat_shards)
    c.register("GET", "/_cat/shards/{index}", cat_shards)
    c.register("GET", "/_cat/aliases", cat_aliases)
    c.register("GET", "/_cat/templates", cat_templates)
    c.register("GET", "/_cat/nodes", cat_nodes)
    c.register("GET", "/_cat/segments", cat_segments)
    c.register("GET", "/_cat/segments/{index}", cat_segments)
    c.register("GET", "/_cat/allocation", cat_allocation)
    c.register("GET", "/_cat/nodeattrs", cat_nodeattrs)
    c.register("GET", "/_cat/repositories", cat_repositories)
    c.register("GET", "/_cat/cluster_manager", cat_cluster_manager)
    c.register("GET", "/_cat/master", cat_master_deprecated)
    c.register("GET", "/_cat/pending_tasks", cat_pending_tasks)
    c.register("GET", "/_cat/recovery", cat_recovery)
    c.register("GET", "/_cat/recovery/{index}", cat_recovery)


# ------------------------------------------------------- scripts & ingest

def register_script_ingest_actions(node, c):
    def _resolve_template(body):
        """{source | id} + params → rendered search body (search
        templates: modules/lang-mustache RestSearchTemplateAction)."""
        from opensearch_tpu.script.mustache import render_search_template
        body = body or {}
        source = body.get("source")
        if source is None and body.get("id"):
            ss = node.script_service.get_stored(body["id"])
            if ss is None or ss.lang != "mustache":
                # a stored painless script is NOT a template — treating it
                # as one produces a misleading render error
                from opensearch_tpu.common.errors import (
                    ResourceNotFoundError)
                raise ResourceNotFoundError(
                    f"unable to find search template [{body['id']}]")
            source = ss.source
        if source is None:
            raise IllegalArgumentError(
                "template is missing [source] or [id] of a stored script")
        return render_search_template(source, body.get("params"))

    def do_search_template(req):
        rendered = _resolve_template(req.body)
        sub = RestRequest(method="POST",
                          path=(f"/{req.param('index')}/_search"
                                if req.param("index") else "/_search"),
                          params={k: v for k, v in req.params.items()
                                  if k not in ("index",)},
                          body=rendered)
        return node.controller.dispatch(sub)

    def do_render_template(req):
        body = dict(req.body or {})
        if req.param("id") and "id" not in body:
            body["id"] = req.param("id")
        return {"template_output": _resolve_template(body)}

    def do_msearch_template(req):
        lines = _ndjson_lines(req)
        if len(lines) % 2:
            raise IllegalArgumentError(
                "_msearch/template expects header/body line pairs")
        # render each item independently: one bad template yields a
        # per-item error entry, never a whole-request failure (matching
        # do_msearch's per-item semantics)
        entries = []          # (header, rendered) | (None, error_dict)
        for i in range(0, len(lines), 2):
            try:
                entries.append((lines[i],
                                _resolve_template(lines[i + 1])))
            except OpenSearchTpuError as e:
                entries.append((None, {
                    "error": {"type": e.error_type, "reason": str(e)},
                    "status": e.status}))
        ndjson = []
        for header, rendered in entries:
            if header is not None:
                ndjson.append(json.dumps(header))
                ndjson.append(json.dumps(rendered))
        responses: List[Any] = []
        if ndjson:
            sub = RestRequest(
                method="POST",
                path=(f"/{req.param('index')}/_msearch"
                      if req.param("index") else "/_msearch"),
                params={}, body=None,
                raw_body=("\n".join(ndjson) + "\n").encode())
            inner = node.controller.dispatch(sub)
            if inner.status != 200:
                return inner
            responses = list(inner.body.get("responses", []))
        out = []
        for header, rendered in entries:
            out.append(responses.pop(0) if header is not None else rendered)
        return {"responses": out}

    c.register("GET", "/_search/template", do_search_template)
    c.register("POST", "/_search/template", do_search_template)
    c.register("GET", "/{index}/_search/template", do_search_template)
    c.register("POST", "/{index}/_search/template", do_search_template)
    c.register("POST", "/_render/template", do_render_template)
    c.register("GET", "/_render/template", do_render_template)
    c.register("POST", "/_render/template/{id}", do_render_template)
    c.register("GET", "/_render/template/{id}", do_render_template)
    c.register("POST", "/_msearch/template", do_msearch_template)
    c.register("POST", "/{index}/_msearch/template", do_msearch_template)

    def do_put_script(req):
        node.script_service.put_stored(req.param("id"), req.body or {})
        return {"acknowledged": True}

    def do_get_script(req):
        ss = node.script_service.get_stored(req.param("id"))
        if ss is None:
            return 404, {"_id": req.param("id"), "found": False}
        return {"_id": req.param("id"), "found": True,
                "script": ss.to_dict()}

    def do_delete_script(req):
        if not node.script_service.delete_stored(req.param("id")):
            return 404, {"acknowledged": False}
        return {"acknowledged": True}

    def do_put_pipeline(req):
        node.ingest.put_pipeline(req.param("id"), req.body or {})
        return {"acknowledged": True}

    def do_get_pipeline(req):
        pid = req.param("id")
        if pid:
            p = node.ingest.get_pipeline(pid)
            if p is None:
                return 404, {}
            return {pid: p.body}
        return {pid: p.body for pid, p in node.ingest.pipelines.items()}

    def do_delete_pipeline(req):
        from opensearch_tpu.common.errors import IndexNotFoundError as _INF
        if not node.ingest.delete_pipeline(req.param("id")):
            raise IllegalArgumentError(
                f"pipeline [{req.param('id')}] is missing")
        return {"acknowledged": True}

    def do_simulate(req):
        return node.ingest.simulate(req.body or {}, req.param("id"))

    c.register("PUT", "/_scripts/{id}", do_put_script)
    c.register("POST", "/_scripts/{id}", do_put_script)
    c.register("GET", "/_scripts/{id}", do_get_script)
    c.register("DELETE", "/_scripts/{id}", do_delete_script)
    c.register("PUT", "/_ingest/pipeline/{id}", do_put_pipeline)
    c.register("GET", "/_ingest/pipeline", do_get_pipeline)
    c.register("GET", "/_ingest/pipeline/{id}", do_get_pipeline)
    c.register("DELETE", "/_ingest/pipeline/{id}", do_delete_pipeline)
    c.register("POST", "/_ingest/pipeline/_simulate", do_simulate)
    c.register("GET", "/_ingest/pipeline/_simulate", do_simulate)
    c.register("POST", "/_ingest/pipeline/{id}/_simulate", do_simulate)
    c.register("GET", "/_ingest/pipeline/{id}/_simulate", do_simulate)


# ----------------------------------------------------------------- snapshots

def register_snapshot_actions(node, c):
    def do_put_repo(req):
        node.repositories.put_repository(req.param("repository"),
                                         req.body or {})
        return {"acknowledged": True}

    def do_get_repo(req):
        name = req.param("repository")
        if name and name != "_all":
            repo = node.repositories.get(name)
            return {name: {"type": "fs",
                           "settings": {"location": repo.location}}}
        return {n: {"type": "fs", "settings": {"location": r.location}}
                for n, r in node.repositories.repositories.items()}

    def do_delete_repo(req):
        from opensearch_tpu.repositories.blobstore import SnapshotMissingError
        if not node.repositories.delete_repository(req.param("repository")):
            raise SnapshotMissingError(f"[{req.param('repository')}] missing")
        return {"acknowledged": True}

    def do_create_snapshot(req):
        repo = node.repositories.get(req.param("repository"))
        body = req.body or {}
        indices_expr = body.get("indices", "_all")
        if isinstance(indices_expr, list):
            indices_expr = ",".join(indices_expr)
        names = node.indices.resolve(indices_expr)
        manifest = repo.create_snapshot(req.param("snapshot"), node.indices,
                                        names)
        if req.bool_param("wait_for_completion", False):
            return 200, {"snapshot": repo.snapshot_info(
                req.param("snapshot"))}
        return 202, {"accepted": True}

    def do_get_snapshot(req):
        repo = node.repositories.get(req.param("repository"))
        name = req.param("snapshot")
        if name in ("_all", "*", None):
            return {"snapshots": [repo.snapshot_info(s)
                                  for s in repo.snapshot_names()]}
        return {"snapshots": [repo.snapshot_info(name)]}

    def do_delete_snapshot(req):
        repo = node.repositories.get(req.param("repository"))
        repo.delete_snapshot(req.param("snapshot"))
        return {"acknowledged": True}

    def do_restore(req):
        repo = node.repositories.get(req.param("repository"))
        body = req.body or {}
        indices_expr = body.get("indices")
        if isinstance(indices_expr, str):
            indices_expr = indices_expr.split(",")
        res = repo.restore_snapshot(
            req.param("snapshot"), node.indices,
            index_names=indices_expr,
            rename_pattern=body.get("rename_pattern"),
            rename_replacement=body.get("rename_replacement"))
        node.persist_metadata()
        return res

    def do_status(req):
        repo = node.repositories.get(req.param("repository"))
        return {"snapshots": [repo.status(req.param("snapshot"))]}

    def cat_snapshots(req):
        repo = node.repositories.get(req.param("repository"))
        rows = []
        for name in repo.snapshot_names():
            info = repo.snapshot_info(name)
            rows.append([name, info["state"],
                         info["start_time_in_millis"],
                         info["end_time_in_millis"],
                         len(info["indices"])])
        return _cat_table(req, ["id", "status", "start_epoch", "end_epoch",
                                "indices"], rows)

    def do_dangling(req):
        if node.gateway is None:
            return {"dangling_indices": []}
        return {"dangling_indices": [
            {"index_name": n}
            for n in node.gateway.dangling_indices(node.indices)]}

    def do_import_dangling(req):
        if node.gateway is None:
            raise IllegalArgumentError("node has no data path")
        node.gateway.import_dangling(node.indices, req.param("index"))
        return {"acknowledged": True}

    c.register("PUT", "/_snapshot/{repository}", do_put_repo)
    c.register("POST", "/_snapshot/{repository}", do_put_repo)
    c.register("GET", "/_snapshot", do_get_repo)
    c.register("GET", "/_snapshot/{repository}", do_get_repo)
    c.register("DELETE", "/_snapshot/{repository}", do_delete_repo)
    c.register("PUT", "/_snapshot/{repository}/{snapshot}",
               do_create_snapshot)
    c.register("POST", "/_snapshot/{repository}/{snapshot}",
               do_create_snapshot)
    c.register("GET", "/_snapshot/{repository}/{snapshot}", do_get_snapshot)
    c.register("DELETE", "/_snapshot/{repository}/{snapshot}",
               do_delete_snapshot)
    c.register("POST", "/_snapshot/{repository}/{snapshot}/_restore",
               do_restore)
    c.register("GET", "/_snapshot/{repository}/{snapshot}/_status", do_status)
    c.register("GET", "/_cat/snapshots/{repository}", cat_snapshots)
    c.register("GET", "/_dangling", do_dangling)
    c.register("POST", "/_dangling/{index}", do_import_dangling)


# -------------------------------------- reindex family / rank-eval / resize

def register_module_actions(node, c):
    from opensearch_tpu.datastreams import resize_index, rollover_alias
    from opensearch_tpu.rankeval import rank_eval
    from opensearch_tpu.reindex import (
        delete_by_query, reindex, update_by_query)

    def do_reindex(req):
        return reindex(node, req.body or {})

    def do_update_by_query(req):
        res = update_by_query(node, req.param("index"), req.body,
                              refresh=req.bool_param("refresh"))
        return res

    def do_delete_by_query(req):
        return delete_by_query(node, req.param("index"), req.body,
                               refresh=req.bool_param("refresh"))

    def do_rank_eval(req):
        return rank_eval(node, req.param("index"), req.body or {})

    def do_create_data_stream(req):
        node.data_streams.create(req.param("name"))
        return {"acknowledged": True}

    def do_get_data_stream(req):
        name = req.param("name")
        if name:
            return {"data_streams": [node.data_streams.get(name).to_dict()]}
        return {"data_streams": [s.to_dict() for s in
                                 node.data_streams.streams.values()]}

    def do_delete_data_stream(req):
        node.data_streams.delete(req.param("name"))
        return {"acknowledged": True}

    def do_rollover(req):
        # the path trie binds the first-registered param name at this
        # level ({index}); accept either spelling
        target = req.param("alias") or req.param("index")
        return rollover_alias(node, target, req.body)

    def make_resize(kind):
        def handler(req):
            return resize_index(node, req.param("index"),
                                req.param("target"), req.body, kind)
        return handler

    c.register("POST", "/_reindex", do_reindex)
    c.register("POST", "/{index}/_update_by_query", do_update_by_query)
    c.register("POST", "/{index}/_delete_by_query", do_delete_by_query)
    c.register("GET", "/_rank_eval", do_rank_eval)
    c.register("POST", "/_rank_eval", do_rank_eval)
    c.register("GET", "/{index}/_rank_eval", do_rank_eval)
    c.register("POST", "/{index}/_rank_eval", do_rank_eval)
    c.register("PUT", "/_data_stream/{name}", do_create_data_stream)
    c.register("GET", "/_data_stream", do_get_data_stream)
    c.register("GET", "/_data_stream/{name}", do_get_data_stream)
    c.register("DELETE", "/_data_stream/{name}", do_delete_data_stream)
    c.register("POST", "/{alias}/_rollover", do_rollover)
    c.register("POST", "/{alias}/_rollover/{new_index}", do_rollover)
    c.register("POST", "/{index}/_shrink/{target}", make_resize("shrink"))
    c.register("PUT", "/{index}/_shrink/{target}", make_resize("shrink"))
    c.register("POST", "/{index}/_split/{target}", make_resize("split"))
    c.register("PUT", "/{index}/_split/{target}", make_resize("split"))
    c.register("POST", "/{index}/_clone/{target}", make_resize("clone"))
    c.register("PUT", "/{index}/_clone/{target}", make_resize("clone"))


# ---------------------------------------------------------- fault injection

def register_fault_actions(node, c):
    """REST control for the deterministic fault-injection subsystem
    (common/faults.py): POST installs seeded rules at named hot-path
    sites, GET enumerates them with invocation/fire counts (the chaos
    sweep's reproducibility surface), DELETE clears all rules or one
    site's. Injection is strictly OFF (module-level flag, zero hot-path
    overhead) unless at least one rule is installed."""
    from opensearch_tpu.common import faults

    def do_get_faults(req):
        return {"enabled": faults.ENABLED, "sites": sorted(faults.SITES),
                "rules": faults.snapshot()}

    def do_install_fault(req):
        body = req.body or {}
        specs = body.get("rules") if isinstance(body.get("rules"), list) \
            else [body]
        if not specs:
            raise IllegalArgumentError(
                "fault injection requires a rule body "
                "({site, kind, ...} or {rules: [...]})")
        installed = [faults.install(spec) for spec in specs]
        return {"acknowledged": True, "installed": installed,
                "enabled": faults.ENABLED}

    def do_clear_faults(req):
        removed = faults.clear(req.param("site"))
        return {"acknowledged": True, "removed": removed,
                "enabled": faults.ENABLED}

    c.register("GET", "/_fault_injection", do_get_faults)
    c.register("POST", "/_fault_injection", do_install_fault)
    c.register("DELETE", "/_fault_injection", do_clear_faults)
    c.register("DELETE", "/_fault_injection/{site}", do_clear_faults)


# ---------------------------------------------------------------- telemetry

def register_telemetry_actions(node, c):
    """The node's observability surface (the REST face of
    opensearch_tpu/telemetry): dump/clear the completed-trace ring buffer
    and toggle tracing at runtime. Tracing is OFF by default
    (`telemetry.tracing.enabled` node setting turns it on at start)."""

    def do_get_traces(req):
        size = req.int_param("size", 0)
        return {"enabled": TELEMETRY.tracer.enabled,
                "stats": TELEMETRY.tracer.stats(),
                "traces": TELEMETRY.tracer.traces(size or None)}

    def do_get_spans(req):
        # the always-on flat span ring (ISSUE 25): every kept span that
        # overlaps [since_ns, until_ns] on the monotonic clock
        def _ns(name):
            v = req.param(name)
            if v in (None, ""):
                return None
            try:
                return int(v)
            except ValueError:
                raise IllegalArgumentError(
                    f"[{name}] must be an integer of nanoseconds, "
                    f"got [{v}]")
        return TELEMETRY.tracer.spans.export(_ns("since_ns"),
                                             _ns("until_ns"))

    def do_clear_spans(req):
        TELEMETRY.tracer.spans.clear()
        return {"acknowledged": True}

    def do_clear_traces(req):
        TELEMETRY.tracer.clear()
        return {"acknowledged": True}

    def do_enable(req):
        TELEMETRY.enable()
        return {"acknowledged": True, "enabled": True}

    def do_disable(req):
        TELEMETRY.disable()
        return {"acknowledged": True, "enabled": False}

    def do_metrics(req):
        return {"metrics": TELEMETRY.metrics.to_dict()}

    def do_get_transfers(req):
        # the transfer ledger's aggregate face (telemetry/ledger.py):
        # per-channel host↔device bytes/round-trips + the live rolling
        # bytes-per-wave / device_get-wall percentiles, next to the
        # device-memory gauges (the HBM analog of JVM mem stats)
        return {"transfers": TELEMETRY.ledger.snapshot(),
                "device_memory": TELEMETRY.device_memory.stats()}

    def do_transfers_enable(req):
        TELEMETRY.ledger.enabled = True
        return {"acknowledged": True, "enabled": True}

    def do_transfers_disable(req):
        TELEMETRY.ledger.enabled = False
        return {"acknowledged": True, "enabled": False}

    def do_transfers_clear(req):
        TELEMETRY.ledger.reset()
        return {"acknowledged": True}

    def do_get_tail(req):
        # the flight recorder's capture ring (telemetry/lifecycle.py):
        # complete lifecycle timelines of requests that breached the SLO
        # threshold or the live rolling p99 — tools/tail_report.py input
        size = req.int_param("size", 0)
        return {"enabled": TELEMETRY.flight.enabled,
                "stats": TELEMETRY.flight.stats(),
                "captured": TELEMETRY.flight.captured(size or None)}

    def do_tail_enable(req):
        thr = req.param("threshold_ms")
        if thr is not None:
            try:
                TELEMETRY.flight.threshold_ms = float(thr)
            except (TypeError, ValueError):
                raise IllegalArgumentError(
                    f"failed to parse [threshold_ms] with value [{thr!r}]")
        TELEMETRY.flight.enabled = True
        return {"acknowledged": True, "enabled": True,
                "threshold_ms": TELEMETRY.flight.threshold_ms}

    def do_tail_disable(req):
        TELEMETRY.flight.enabled = False
        return {"acknowledged": True, "enabled": False}

    def do_tail_clear(req):
        TELEMETRY.flight.clear()
        return {"acknowledged": True}

    def do_get_ingest(req):
        # the write path's observability face (ISSUE 13): ingest
        # lifecycle timelines + the always-on engine event log + the
        # segment-churn ledger's per-event device-cost attribution,
        # plus the off-path precompiler's counters (ISSUE 16) — the
        # warm_hit/precompiled/recompile-on-serve verdict mix is read
        # straight off this endpoint
        from opensearch_tpu.search.warmup import PRECOMPILE
        from opensearch_tpu.telemetry.lifecycle import INGEST_EVENTS
        size = req.int_param("size", 0)
        return {"enabled": TELEMETRY.ingest.enabled,
                "stats": TELEMETRY.ingest.stats(),
                "recent": TELEMETRY.ingest.captured(size or None),
                "events": INGEST_EVENTS.recent(size or None),
                "churn": {**TELEMETRY.churn.snapshot(),
                          "records": TELEMETRY.churn.records(
                              size or None)},
                "precompile": PRECOMPILE.stats()}

    def do_ingest_enable(req):
        # one switch for the write-path instrumentation pair: per-op
        # timelines AND churn attribution (they are read together)
        TELEMETRY.ingest.enabled = True
        TELEMETRY.churn.enabled = True
        return {"acknowledged": True, "enabled": True}

    def do_ingest_disable(req):
        TELEMETRY.ingest.enabled = False
        TELEMETRY.churn.enabled = False
        return {"acknowledged": True, "enabled": False}

    def do_ingest_clear(req):
        from opensearch_tpu.telemetry.lifecycle import INGEST_EVENTS
        TELEMETRY.ingest.clear()
        TELEMETRY.churn.reset()
        INGEST_EVENTS.clear()
        return {"acknowledged": True}

    def do_precompile(req):
        # ISSUE 16 off-path precompilation trigger: drain anything the
        # background worker has queued, then replay the warmup registry
        # on this thread with the compiles attributed off-path. Works
        # with the background gate off — an explicit POST is operator
        # opt-in by construction.
        from opensearch_tpu.search.warmup import PRECOMPILE
        index = req.param("index")
        raw_budget = req.param("budget_ms")
        budget_s = None
        if raw_budget is not None:
            try:
                budget_s = float(raw_budget) / 1000.0
            except (TypeError, ValueError):
                raise IllegalArgumentError(
                    f"failed to parse [budget_ms] with value "
                    f"[{raw_budget!r}]")
        drained = PRECOMPILE.run_pending()
        r = PRECOMPILE.sweep(node.indices, index, budget_s)
        return {"acknowledged": True, **r, "drained": drained,
                "precompile": PRECOMPILE.stats()}

    def do_get_insights(req):
        # query insights (ISSUE 15): per-shape cost attribution rows +
        # the three heavy-query top-N registries — the reference Query
        # Insights analog over the interned-template shape vocabulary
        return {"insights": TELEMETRY.insights.snapshot(top=True)}

    def do_top_queries(req):
        from opensearch_tpu.telemetry.insights import TOP_METRICS
        metric = req.param("metric", "latency")
        if metric not in TOP_METRICS:
            raise IllegalArgumentError(
                f"unknown insights metric [{metric}] (one of "
                f"{', '.join(TOP_METRICS)})")
        size = req.int_param("size", 0)
        return {"enabled": TELEMETRY.insights.enabled,
                "metric": metric,
                "top_queries": TELEMETRY.insights.top_queries(
                    metric, size or None)}

    def do_insights_enable(req):
        TELEMETRY.insights.enabled = True
        return {"acknowledged": True, "enabled": True}

    def do_insights_disable(req):
        TELEMETRY.insights.enabled = False
        return {"acknowledged": True, "enabled": False}

    def do_insights_clear(req):
        TELEMETRY.insights.clear()
        return {"acknowledged": True}

    def do_get_kernels(req):
        # the executable census (ISSUE 19; always on, compile time
        # only) and the roofline table from XLA's flop and byte counts;
        # ?scopes=true adds each executable's {HLO instruction ->
        # stage} map, built on first demand (ISSUE 25)
        return {"kernels": TELEMETRY.kernels.snapshot(
            scopes=req.bool_param("scopes"))}

    def do_kernels_clear(req):
        TELEMETRY.kernels.clear()
        return {"acknowledged": True}

    def do_telemetry_index(req):
        # the gate index (ISSUE 19 satellite): every gated subsystem's
        # enabled state + its REST face in one response — operators see
        # which of the nine gates are on without probing each endpoint
        # (the kernel census has no gate: it is always on)
        from opensearch_tpu.common import faults
        subsystems = {
            "tracer": (TELEMETRY.tracer.enabled, "/_telemetry/traces"),
            "transfers": (TELEMETRY.ledger.enabled,
                          "/_telemetry/transfers"),
            "devices": (TELEMETRY.device_ledger.enabled,
                        "/_telemetry/devices"),
            "tail": (TELEMETRY.flight.enabled, "/_telemetry/tail"),
            "ingest": (TELEMETRY.ingest.enabled, "/_telemetry/ingest"),
            "churn": (TELEMETRY.churn.enabled, "/_telemetry/ingest"),
            "insights": (TELEMETRY.insights.enabled, "/_insights"),
            "scheduler": (getattr(getattr(node, "wave_scheduler", None),
                                  "enabled", False), "/_scheduler"),
            "faults": (faults.ENABLED, "/_fault_injection"),
        }
        return {"subsystems": {
            name: {"enabled": bool(enabled), "endpoint": ep}
            for name, (enabled, ep) in subsystems.items()}}

    def do_get_devices(req):
        # sharded-serving observability (ISSUE 14): per-device
        # transfer/phase aggregates + straggler skew, next to the
        # always-on scanned-bytes heat map (the block-max trigger
        # metric — live regardless of any gate)
        return {"devices": TELEMETRY.device_ledger.snapshot(),
                "scan": TELEMETRY.scan.stats()}

    def do_devices_enable(req):
        # one switch for the sharded-serving instrumentation pair:
        # per-device attribution AND the SPMD collective-phase
        # timeline (they are read together in the tail reports)
        TELEMETRY.device_ledger.enabled = True
        TELEMETRY.spmd_timeline.enabled = True
        return {"acknowledged": True, "enabled": True}

    def do_devices_disable(req):
        TELEMETRY.device_ledger.enabled = False
        TELEMETRY.spmd_timeline.enabled = False
        return {"acknowledged": True, "enabled": False}

    def do_devices_clear(req):
        TELEMETRY.device_ledger.reset()
        TELEMETRY.scan.reset()
        return {"acknowledged": True}

    c.register("GET", "/_telemetry/traces", do_get_traces)
    c.register("POST", "/_telemetry/traces/_clear", do_clear_traces)
    c.register("GET", "/_telemetry/spans", do_get_spans)
    c.register("POST", "/_telemetry/spans/_clear", do_clear_spans)
    c.register("POST", "/_telemetry/_enable", do_enable)
    c.register("POST", "/_telemetry/_disable", do_disable)
    c.register("GET", "/_telemetry/metrics", do_metrics)
    c.register("GET", "/_telemetry/transfers", do_get_transfers)
    c.register("POST", "/_telemetry/transfers/_enable",
               do_transfers_enable)
    c.register("POST", "/_telemetry/transfers/_disable",
               do_transfers_disable)
    c.register("POST", "/_telemetry/transfers/_clear", do_transfers_clear)
    c.register("GET", "/_telemetry/tail", do_get_tail)
    c.register("POST", "/_telemetry/tail/_enable", do_tail_enable)
    c.register("POST", "/_telemetry/tail/_disable", do_tail_disable)
    c.register("POST", "/_telemetry/tail/_clear", do_tail_clear)
    c.register("GET", "/_telemetry/ingest", do_get_ingest)
    c.register("POST", "/_telemetry/ingest/_enable", do_ingest_enable)
    c.register("POST", "/_telemetry/ingest/_disable", do_ingest_disable)
    c.register("POST", "/_telemetry/ingest/_clear", do_ingest_clear)
    c.register("POST", "/_warmup/_precompile", do_precompile)
    c.register("POST", "/{index}/_warmup/_precompile", do_precompile)
    c.register("GET", "/_telemetry/devices", do_get_devices)
    c.register("POST", "/_telemetry/devices/_enable", do_devices_enable)
    c.register("POST", "/_telemetry/devices/_disable",
               do_devices_disable)
    c.register("POST", "/_telemetry/devices/_clear", do_devices_clear)
    c.register("GET", "/_telemetry", do_telemetry_index)
    c.register("GET", "/_telemetry/kernels", do_get_kernels)
    c.register("POST", "/_telemetry/kernels/_clear", do_kernels_clear)
    c.register("GET", "/_insights", do_get_insights)
    c.register("GET", "/_insights/top_queries", do_top_queries)
    c.register("POST", "/_insights/_enable", do_insights_enable)
    c.register("POST", "/_insights/_disable", do_insights_disable)
    c.register("POST", "/_insights/_clear", do_insights_clear)


# -------------------------------------------------------------------- tasks

def register_task_actions(node, c):
    def do_list_tasks(req):
        tasks = node.task_manager.list_tasks(req.param("actions"))
        return {"tasks": {f"_local:{t.task_id}": t.to_dict(node.node_id)
                          for t in tasks}}

    def do_get_task(req):
        task_id = req.param("task_id")
        tid = int(task_id.split(":")[-1])
        task = node.task_manager.tasks.get(tid)
        if task is None:
            from opensearch_tpu.common.errors import IndexNotFoundError
            return 404, {"error": {
                "type": "resource_not_found_exception",
                "reason": f"task [{task_id}] isn't running and hasn't "
                          f"stored its results"}, "status": 404}
        return {"completed": False, "task": task.to_dict(node.node_id)}

    def do_cancel_task(req):
        task_id = req.param("task_id")
        tid = int(task_id.split(":")[-1])
        ok = node.task_manager.cancel(tid)
        tasks = {} if not ok else {
            f"_local:{tid}":
                node.task_manager.tasks[tid].to_dict(node.node_id)}
        return {"nodes": {node.node_id: {"tasks": tasks}}
                if ok else {}, "node_failures": []}

    def do_cancel_matching(req):
        cancelled = []
        for t in node.task_manager.list_tasks(req.param("actions")):
            if node.task_manager.cancel(t.task_id):
                cancelled.append(t)
        return {"nodes": {node.node_id: {
            "tasks": {f"_local:{t.task_id}": t.to_dict(node.node_id)
                      for t in cancelled}}}}

    def cat_tasks(req):
        rows = [[t.action, f"_local:{t.task_id}", "transport",
                 t.start_time_ms,
                 f"{t.running_time_in_nanos() // 1000000}ms"]
                for t in node.task_manager.list_tasks()]
        return _cat_table(req, ["action", "task_id", "type", "start_time",
                                "running_time"], rows)

    c.register("GET", "/_tasks", do_list_tasks)
    c.register("GET", "/_tasks/{task_id}", do_get_task)
    c.register("POST", "/_tasks/{task_id}/_cancel", do_cancel_task)
    c.register("POST", "/_tasks/_cancel", do_cancel_matching)
    c.register("GET", "/_cat/tasks", cat_tasks)


# ---------------------------------------------------------- wave scheduler

def register_scheduler_actions(node, c):
    """The async wave scheduler's REST face (search/scheduler.py):
    runtime enable/disable (the dynamic-cluster-setting analog for
    operators without settings access) + the stats block. Disabling
    drains the queue — every queued request completes first."""

    def do_stats(req):
        return {"scheduler": node.wave_scheduler.stats()}

    def do_enable(req):
        s = node.wave_scheduler
        w = req.param("window_ms")
        if w is not None:
            # same validation as the cluster-settings path
            # (parse_settings' >= 0 rule): a negative cap would clamp
            # every window to 0 and silently disable coalescing while
            # reporting enabled
            try:
                w_val = float(w)
            except (TypeError, ValueError):
                raise IllegalArgumentError(
                    f"failed to parse [window_ms] with value [{w!r}]")
            if w_val < 0:
                raise IllegalArgumentError(
                    f"[window_ms] must be >= 0, got [{w!r}]")
            s.window_max_ms = w_val
        s.set_enabled(True)
        return {"acknowledged": True, "enabled": True,
                "window_max_ms": s.window_max_ms}

    def do_disable(req):
        node.wave_scheduler.set_enabled(False)
        return {"acknowledged": True, "enabled": False}

    c.register("GET", "/_scheduler", do_stats)
    c.register("POST", "/_scheduler/_enable", do_enable)
    c.register("POST", "/_scheduler/_disable", do_disable)


def register_all(node):
    c = node.controller
    register_cluster_actions(node, c)
    register_document_actions(node, c)
    register_search_actions(node, c)
    register_search_pipeline_actions(node, c)
    register_indices_actions(node, c)
    register_alias_template_actions(node, c)
    register_cat_actions(node, c)
    register_script_ingest_actions(node, c)
    register_snapshot_actions(node, c)
    register_module_actions(node, c)
    register_task_actions(node, c)
    register_telemetry_actions(node, c)
    register_fault_actions(node, c)
    register_scheduler_actions(node, c)
