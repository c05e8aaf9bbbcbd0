"""IndexService: one index = N shards + mapper + settings; document-level API.

Re-design of the reference IndexService (index/IndexService.java:133) plus the
document-action layer that sits above it: murmur3 doc→shard routing
(cluster/routing/OperationRouting.java:412), the update API's
get-merge-reindex loop (action/update/UpdateHelper.java), _bulk grouping by
shard (action/bulk/TransportBulkAction.java:484), and multi-shard search via
the coordinator reduce (search/controller.py).
"""

from __future__ import annotations

import json
import logging
import secrets
import time
import uuid
from typing import Any, Dict, List, Optional

from opensearch_tpu.cluster.routing import generate_shard_id
from opensearch_tpu.common.errors import (
    DocumentMissingError, IllegalArgumentError, OpenSearchTpuError,
    VersionConflictError)
from opensearch_tpu.analysis import AnalysisRegistry
from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.shard import IndexShard


def _auto_id() -> str:
    """Auto-generated doc id (reference: time-based UUID, 20 url-safe chars)."""
    return secrets.token_urlsafe(15)


# ------------------------------------------------------- indexing slow log

# child logger under the reference's name shape (IndexingSlowLog.java:
# "index.indexing.slowlog.index") so existing capture config keeps
# working — the search slow log's sibling (rest/actions.py)
_INDEXING_SLOW_LOGGER = logging.getLogger(
    "opensearch_tpu.index.indexing.slowlog.index")

# most severe first: the first threshold the op time clears wins
_INDEXING_SLOW_LEVELS = (("warn", logging.WARNING),
                         ("info", logging.INFO),
                         ("debug", logging.DEBUG), ("trace", 5))

_SLOWLOG_THRESHOLD_KEYS = tuple(
    f"indexing.slowlog.threshold.index.{level}"
    for level, _ in _INDEXING_SLOW_LEVELS)


def _slow_log_source(settings: dict, source: dict) -> str:
    """Render the source line per reference semantics
    (IndexingSlowLogMessage): `index.indexing.slowlog.source` is the max
    characters to include (default 1000), `false`/`0` omits the source
    entirely, `true` logs it whole."""
    raw = settings.get("indexing.slowlog.source", 1000)
    if isinstance(raw, str):
        low = raw.strip().lower()
        if low == "true":
            limit = -1
        elif low == "false":
            limit = 0
        else:
            try:
                limit = int(low)
            except ValueError:
                limit = 1000      # unparseable: reference default
    elif raw is True:
        limit = -1
    elif raw is False:
        limit = 0
    else:
        try:
            limit = int(raw)
        except (TypeError, ValueError):
            limit = 1000          # null/odd types: a bad SOURCE
            # setting must degrade like a bad threshold does, never
            # 500 the write that tripped the slow log
    if limit == 0:
        return ""
    try:
        text = json.dumps(source, default=str)
    except (TypeError, ValueError):
        text = str(source)
    if limit > 0 and len(text) > limit:
        # reference Strings.cleanTruncate semantics: hard cut at the
        # character budget (surrogate safety is a non-issue here)
        text = text[:limit]
    return text


def _maybe_indexing_slow_log(settings: dict, index_name: str,
                             doc_id: Optional[str], source: dict,
                             took_ms: float) -> None:
    """Per-index indexing slow log (reference IndexingSlowLog.java):
    `index.indexing.slowlog.threshold.index.{warn,info,debug,trace}`
    each log at the matching level on the shared child logger; `-1` (any
    negative) disables a threshold; the most severe matching level wins.
    Covers index/create ops (the reference hook, IndexingOperationListener
    postIndex) — the paths IndexService.index_doc serves."""
    from opensearch_tpu.common.errors import SettingsError
    from opensearch_tpu.common.settings import parse_time_value
    for level, py_level in _INDEXING_SLOW_LEVELS:
        threshold = settings.get(
            f"indexing.slowlog.threshold.index.{level}")
        if threshold is None:
            continue
        try:
            threshold_s = parse_time_value(threshold, "slowlog")
        except (SettingsError, TypeError, ValueError):
            continue              # unparseable threshold never logs
        if threshold_s < 0 or took_ms < threshold_s * 1000:
            continue
        _INDEXING_SLOW_LOGGER.log(
            py_level,
            "[%s] took[%.1fms], took_millis[%d], id[%s], source[%s]",
            index_name, took_ms, int(took_ms), doc_id,
            _slow_log_source(settings, source))
        break                     # most severe matching level only


def deep_merge(base: dict, patch: dict) -> dict:
    """Recursive map merge used by partial-doc updates (UpdateHelper)."""
    out = dict(base)
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = v
    return out


class IndexService:
    def __init__(self, index_name: str, mapping: Optional[dict] = None,
                 settings: Optional[dict] = None,
                 data_path: Optional[str] = None,
                 script_service=None):
        settings = settings or {}
        self.index_name = index_name
        self.settings = settings
        # index UUID (IndexMetadata.SETTING_INDEX_UUID): identifies this
        # *incarnation* of the index — snapshot blob paths key on it so a
        # delete+recreate under the same name can never alias stale blobs
        self.uuid = settings.get("uuid") or uuid.uuid4().hex[:22]
        settings.setdefault("uuid", self.uuid)
        self._script_service = script_service
        # index open/close lifecycle (MetadataIndexStateService analog):
        # a closed index keeps its data and metadata but rejects every
        # data-plane operation until reopened
        self.closed = bool(settings.get("closed", False))
        self.num_shards = int(settings.get("number_of_shards", 1))
        self.num_replicas = int(settings.get("number_of_replicas", 0))
        self.routing_partition_size = int(
            settings.get("routing_partition_size", 1))
        self.routing_num_shards = int(
            settings.get("number_of_routing_shards", self.num_shards))
        if self.num_shards < 1:
            raise IllegalArgumentError("number_of_shards must be >= 1")
        # reference (IndexMetadata.java:784): routingNumShards must be a
        # positive multiple of numberOfShards or routing goes out of range
        if (self.routing_num_shards < self.num_shards
                or self.routing_num_shards % self.num_shards != 0):
            raise IllegalArgumentError(
                f"number_of_routing_shards [{self.routing_num_shards}] must "
                f"be a multiple of number_of_shards [{self.num_shards}]")
        if self.routing_partition_size < 1 or (
                self.routing_partition_size > 1
                and self.routing_partition_size >= self.num_shards):
            raise IllegalArgumentError(
                f"routing_partition_size [{self.routing_partition_size}] "
                f"should be a positive number less than number_of_shards "
                f"[{self.num_shards}]")
        # un-flatten index.analysis.* settings back into the nested config
        # AnalysisRegistry consumes (custom analyzers/tokenizers/filters,
        # incl. plugin-registered ones — AnalysisModule analog)
        analysis_cfg: dict = {}
        for k, v in settings.items():
            if k.startswith("analysis."):
                parts = k.split(".")[1:]
                d = analysis_cfg
                for p in parts[:-1]:
                    d = d.setdefault(p, {})
                d[parts[-1]] = v
        registry = AnalysisRegistry(analysis_cfg) if analysis_cfg else None
        self.mapper = MapperService(mapping, analysis_registry=registry)
        durability = settings.get("translog.durability", "request")
        self.shards: List[IndexShard] = [
            IndexShard(i, self.mapper, index_name=index_name,
                       data_path=data_path, durability=durability)
            for i in range(self.num_shards)
        ]
        self.creation_date = int(time.time() * 1000)
        window = int(self.settings.get("max_result_window", 10000))
        from opensearch_tpu.indices.request_cache import enabled_by
        for shard in self.shards:
            shard.executor.max_result_window = window
            shard.executor.request_cache_enabled = enabled_by(self.settings)
        # ingest-concurrent serving knobs (ISSUE 16), all OFF by
        # default: bounded merge windows ("index.merge.windowed" +
        # "index.merge.window_budget_ms") and segment-keyed memo carry
        # ("index.search.memo_carry"). Strict boolean parse — a typo'd
        # value fails index creation, never silently stays off.
        from opensearch_tpu.common.settings import _parse_bool
        raw_windowed = settings.get("merge.windowed")
        raw_budget = settings.get("merge.window_budget_ms")
        raw_carry = settings.get("search.memo_carry")
        for shard in self.shards:
            if raw_windowed is not None:
                shard.engine.merge_windowed = _parse_bool(
                    raw_windowed, "index.merge.windowed")
            if raw_budget is not None:
                shard.engine.merge_window_budget_ms = float(raw_budget)
            if raw_carry is not None:
                shard.reader.memo_carry = _parse_bool(
                    raw_carry, "index.search.memo_carry")

    # --------------------------------------------------------------- routing

    def check_open(self):
        """Data-plane gate for closed indices (IndexClosedException)."""
        if self.closed:
            from opensearch_tpu.common.errors import IndexClosedError
            raise IndexClosedError(self.index_name)

    def shard_for(self, doc_id: str, routing: Optional[str] = None) -> IndexShard:
        sid = generate_shard_id(
            doc_id, self.num_shards, routing=routing,
            routing_num_shards=self.routing_num_shards,
            routing_partition_size=self.routing_partition_size)
        return self.shards[sid]

    # ------------------------------------------------------------- doc CRUD

    def _indexing_slowlog_armed(self) -> bool:
        """One threshold configured = time every op; none = zero-cost
        fast path (no clock reads on the write path)."""
        s = self.settings
        return any(s.get(k) is not None for k in _SLOWLOG_THRESHOLD_KEYS)

    def index_doc(self, doc_id: Optional[str], source: dict,
                  routing: Optional[str] = None, op_type: str = "index",
                  **kw) -> dict:
        self.check_open()
        if doc_id is None:
            doc_id = _auto_id()
            op_type = "create"
        shard = self.shard_for(doc_id, routing)
        if not self._indexing_slowlog_armed():
            res = shard.index_doc(doc_id, source, op_type=op_type, **kw)
        else:
            t0 = time.monotonic()
            res = shard.index_doc(doc_id, source, op_type=op_type, **kw)
            _maybe_indexing_slow_log(
                self.settings, self.index_name, doc_id, source,
                (time.monotonic() - t0) * 1000)
        return self._write_response(res, shard,
                                    "created" if res.created else "updated")

    def get_doc(self, doc_id: str, routing: Optional[str] = None,
                realtime: bool = True) -> dict:
        self.check_open()
        shard = self.shard_for(doc_id, routing)
        res = shard.get_doc(doc_id, realtime=realtime)
        if res is None:
            return {"_index": self.index_name, "_id": doc_id, "found": False}
        return {"_index": self.index_name, "_id": doc_id, "found": True,
                "_version": res.version, "_seq_no": res.seq_no,
                "_primary_term": res.primary_term, "_source": res.source}

    def delete_doc(self, doc_id: str, routing: Optional[str] = None,
                   **kw) -> dict:
        self.check_open()
        shard = self.shard_for(doc_id, routing)
        res = shard.delete_doc(doc_id, **kw)
        return self._write_response(res, shard,
                                    "deleted" if res.found else "not_found")

    def update_doc(self, doc_id: str, body: dict,
                   routing: Optional[str] = None,
                   if_seq_no: Optional[int] = None,
                   if_primary_term: Optional[int] = None,
                   external_version: Optional[int] = None) -> dict:
        """Partial update: realtime GET → merge → reindex with seq-no CAS
        (UpdateHelper semantics: detect_noop default true, upsert,
        doc_as_upsert, retry left to the caller). A caller-supplied
        if_seq_no/if_primary_term CAS is checked against the current doc."""
        self.check_open()
        if external_version is not None:
            # reference: UpdateRequest.validate rejects external versioning
            raise IllegalArgumentError(
                "internal versioning can not be used for optimistic "
                "concurrency control. Please use `if_seq_no` and "
                "`if_primary_term` instead")
        _KNOWN = {"doc", "doc_as_upsert", "script", "upsert",
                  "scripted_upsert", "detect_noop", "_source", "lang",
                  "if_seq_no", "if_primary_term", "fields"}
        for key in body:
            if key not in _KNOWN:
                import difflib
                guess = difflib.get_close_matches(key, sorted(_KNOWN), n=1)
                hint = f" did you mean [{guess[0]}]?" if guess else ""
                raise IllegalArgumentError(
                    f"[UpdateRequest] unknown field [{key}]{hint}")
        # CAS values may arrive in the body instead of URL params
        # (UpdateRequest.fromXContent parses both)
        if if_seq_no is None and body.get("if_seq_no") is not None:
            if_seq_no = int(body["if_seq_no"])
        if if_primary_term is None and body.get("if_primary_term") is not None:
            if_primary_term = int(body["if_primary_term"])
        shard = self.shard_for(doc_id, routing)
        cur = shard.get_doc(doc_id)
        # CAS applies to scripted updates too — check BEFORE dispatching
        # to the script path or a stale writer wins a lost update
        if if_seq_no is not None or if_primary_term is not None:
            if cur is None:
                # a CAS against a missing doc is a 404, not a conflict
                # (UpdateHelper prepare: DocumentMissingException wins)
                raise DocumentMissingError(
                    f"[{doc_id}]: document missing")
            if ((if_seq_no is not None and cur.seq_no != if_seq_no)
                    or (if_primary_term is not None
                        and cur.primary_term != if_primary_term)):
                raise VersionConflictError(
                    f"[{doc_id}]: version conflict, required seqNo "
                    f"[{if_seq_no}], primary term [{if_primary_term}]. "
                    f"current document has seqNo [{cur.seq_no}] and primary "
                    f"term [{cur.primary_term}]")
        if "script" in body:
            return self._update_with_script(shard, doc_id, body, cur)
        doc_patch = body.get("doc")
        if cur is None:
            if body.get("doc_as_upsert") and doc_patch is not None:
                new_source = doc_patch
            elif "upsert" in body:
                new_source = body["upsert"]
            else:
                raise DocumentMissingError(
                    f"[{doc_id}]: document missing")
            res = shard.index_doc(doc_id, new_source, op_type="create")
            return self._write_response(res, shard, "created")
        if doc_patch is None:
            raise IllegalArgumentError("update requires [doc] or [upsert]")
        merged = deep_merge(cur.source, doc_patch)
        if body.get("detect_noop", True) and merged == cur.source:
            return {"_index": self.index_name, "_id": doc_id,
                    "_version": cur.version, "result": "noop",
                    "_seq_no": cur.seq_no, "_primary_term": cur.primary_term,
                    "_shards": {"total": 0, "successful": 0, "failed": 0}}
        res = shard.index_doc(doc_id, merged, if_seq_no=cur.seq_no,
                              if_primary_term=cur.primary_term)
        return self._write_response(res, shard, "updated")

    def _update_with_script(self, shard, doc_id: str, body: dict, cur) -> dict:
        """Scripted update (reference: UpdateHelper.prepareUpdateScriptRequest
        — ctx._source mutation, ctx.op = index|delete|none)."""
        if self._script_service is None:
            from opensearch_tpu.script.service import ScriptService
            self._script_service = ScriptService()
        script = self._script_service.compile(body["script"], "update")
        if cur is None:
            if "upsert" in body:
                if body.get("scripted_upsert", False):
                    ctx = {"_source": dict(body["upsert"]), "op": "create",
                           "_index": self.index_name, "_id": doc_id}
                    script.execute(ctx)
                    if ctx.get("op") in ("none", "noop"):
                        return {"_index": self.index_name, "_id": doc_id,
                                "result": "noop",
                                "_shards": {"total": 0, "successful": 0,
                                            "failed": 0}}
                    new_source = ctx["_source"]
                else:
                    new_source = body["upsert"]
                res = shard.index_doc(doc_id, new_source, op_type="create")
                return self._write_response(res, shard, "created")
            raise DocumentMissingError(f"[{doc_id}]: document missing")
        ctx = {"_source": dict(cur.source), "op": "index",
               "_index": self.index_name, "_id": doc_id,
               "_version": cur.version, "_now": int(time.time() * 1000)}
        script.execute(ctx)
        op = ctx.get("op", "index")
        if op in ("none", "noop"):
            return {"_index": self.index_name, "_id": doc_id,
                    "_version": cur.version, "result": "noop",
                    "_seq_no": cur.seq_no, "_primary_term": cur.primary_term,
                    "_shards": {"total": 0, "successful": 0, "failed": 0}}
        if op == "delete":
            res = shard.delete_doc(doc_id)
            return self._write_response(res, shard, "deleted")
        if op != "index" and op != "create":
            raise IllegalArgumentError(
                f"Operation type [{op}] not allowed, only [noop, index, "
                f"delete] are allowed")
        res = shard.index_doc(doc_id, ctx["_source"], if_seq_no=cur.seq_no,
                              if_primary_term=cur.primary_term)
        return self._write_response(res, shard, "updated")

    def mget(self, ids: List[Any]) -> dict:
        self.check_open()
        docs = []
        for item in ids:
            if isinstance(item, dict):
                docs.append(self.get_doc(item["_id"],
                                         routing=item.get("routing")))
            else:
                docs.append(self.get_doc(item))
        return {"docs": docs}

    def _write_response(self, res, shard: IndexShard, result: str) -> dict:
        return {
            "_index": self.index_name,
            "_id": res.doc_id,
            "_version": res.version,
            "result": result,
            "_shards": {"total": 1 + self.num_replicas,
                        "successful": 1, "failed": 0},
            "_seq_no": res.seq_no,
            "_primary_term": res.primary_term,
        }

    # ------------------------------------------------------------------ bulk

    def bulk(self, operations: List[dict]) -> dict:
        """Execute parsed bulk items: [{action, id, source, routing, ...}].
        Items are routed per doc and executed in order per shard
        (TransportShardBulkAction.performOnPrimary runs items serially)."""
        self.check_open()
        start = time.monotonic()
        items = []
        errors = False
        for op in operations:
            action = op["action"]
            cas = {k: op[k] for k in ("if_seq_no", "if_primary_term")
                   if op.get(k) is not None}
            try:
                if action in ("index", "create"):
                    resp = self.index_doc(op.get("id"), op["source"],
                                          routing=op.get("routing"),
                                          op_type=("create"
                                                   if action == "create"
                                                   else "index"), **cas)
                    status = 201 if resp["result"] == "created" else 200
                elif action == "delete":
                    resp = self.delete_doc(op["id"], routing=op.get("routing"),
                                           **cas)
                    status = 200 if resp["result"] == "deleted" else 404
                elif action == "update":
                    resp = self.update_doc(op["id"], op["source"],
                                           routing=op.get("routing"), **cas)
                    status = 200
                else:
                    raise IllegalArgumentError(
                        f"unknown bulk action [{action}]")
                resp["status"] = status
                items.append({action: resp})
            except OpenSearchTpuError as e:
                errors = True
                items.append({action: {
                    "_index": self.index_name, "_id": op.get("id"),
                    "status": e.status,
                    "error": e.to_xcontent(),
                }})
        return {"took": int((time.monotonic() - start) * 1000),
                "errors": errors, "items": items}

    # ---------------------------------------------------------------- search

    def search(self, body: Optional[dict] = None) -> dict:
        self.check_open()
        from opensearch_tpu.search.controller import execute_search
        return execute_search([s.executor for s in self.shards], body,
                              allow_envelope=True)

    def multi_search(self, bodies: List[dict], task=None,
                     deadline=None) -> dict:
        self.check_open()
        if self.num_shards == 1:
            return self.shards[0].executor.multi_search(
                bodies, task=task, deadline=deadline)
        # multi-shard fallback keeps the same per-item failure contract
        # as the batched envelope: one malformed body renders an error
        # item, siblings execute (TransportMultiSearchAction semantics).
        # Cancellation kills the envelope at item boundaries; a passed
        # deadline renders the unlaunched tail as timed-out partials.
        import time as _time
        from opensearch_tpu.search.executor import (
            _item_error, _item_error_untyped, _timed_out_item)
        start = _time.monotonic()
        responses = []
        for b in bodies:
            if task is not None:
                task.check_cancelled()
            if deadline is not None and _time.monotonic() > deadline:
                responses.append(_timed_out_item(start))
                continue
            try:
                responses.append(self.search(b))
            except OpenSearchTpuError as e:
                responses.append(_item_error(e))
            except Exception as e:
                responses.append(_item_error_untyped(e))
        return {"took": 0, "responses": responses}

    def count(self, body: Optional[dict] = None) -> int:
        self.check_open()
        body = dict(body or {})
        body["size"] = 0
        body.pop("from", None)
        return self.search(body)["hits"]["total"]["value"]

    # ------------------------------------------------------------- lifecycle

    def refresh(self):
        self.check_open()
        for s in self.shards:
            s.refresh()

    def flush(self):
        self.check_open()
        for s in self.shards:
            s.flush()

    def force_merge(self):
        self.check_open()
        for s in self.shards:
            s.force_merge()

    def close(self):
        for s in self.shards:
            s.close()

    def stats(self) -> dict:
        shard_stats = [s.stats() for s in self.shards]
        return {
            "index": self.index_name,
            "docs": {"count": sum(s["docs"]["count"] for s in shard_stats),
                     "deleted": sum(s["docs"]["deleted"]
                                    for s in shard_stats)},
            "segments": {"count": sum(s["segments"]["count"]
                                      for s in shard_stats)},
            "shards": shard_stats,
        }

    def mapping_dict(self) -> dict:
        return self.mapper.mapping_dict()

    def put_mapping(self, mapping: dict):
        self.mapper.merge(mapping)
