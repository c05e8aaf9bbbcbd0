"""Shard request cache: memoize shard-level query-phase results.

Re-design of the reference's IndicesRequestCache (indices/
IndicesRequestCache.java:82): the reference caches the serialized shard
query result keyed by (reader identity, request bytes) and serves repeated
size=0/aggregation requests without re-executing; entries die with the
reader (refresh/merge). Here the key is (segment uids + live doc counts,
canonical request JSON, k) — segment uids are process-unique and the live
count changes on delete, so a refresh or delete naturally misses and old
entries age out of the LRU instead of needing explicit invalidation hooks.
"""

from __future__ import annotations

import json
import re
import threading
from collections import OrderedDict
from typing import Any, Optional, Tuple

from opensearch_tpu.telemetry import TELEMETRY

# telemetry mirror of the hit/miss counters (the `telemetry` section of
# _nodes/stats); module-level handles keep the hot path to one int add
_CACHE_HITS = TELEMETRY.metrics.counter("request_cache.hits")
_CACHE_MISSES = TELEMETRY.metrics.counter("request_cache.misses")
# cacheable bodies that were not looked up because the index
# (`index.requests.cache.enable: false`) or the request
# (`?request_cache=false`) said so: once a lookup skipped, as hits and
# misses count once a lookup made (a shard's on the host loop, an item's
# on the envelope, a request's on the SPMD route)
_CACHE_BYPASSED = TELEMETRY.metrics.counter("search.request_cache.bypassed")

# the body key `?request_cache=true|false` travels under from the REST
# layer to whichever route serves the request (internal, like `_dfs`)
REQUEST_KEY = "_request_cache"


class RequestCache:
    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._store: "OrderedDict[Any, Any]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    _MISS = object()

    def get(self, key):
        """Cached value or RequestCache._MISS; counts a hit on success."""
        with self._lock:
            if key in self._store:
                self.hits += 1
                self._store.move_to_end(key)
                _CACHE_HITS.inc()
                return self._store[key]
        return self._MISS

    def put(self, key, value):
        with self._lock:
            self.misses += 1
            _CACHE_MISSES.inc()
            self._store[key] = value
            self._store.move_to_end(key)
            while len(self._store) > self.max_entries:
                self._store.popitem(last=False)

    def clear(self):
        with self._lock:
            self._store.clear()

    def stats(self) -> dict:
        with self._lock:
            return {"hit_count": self.hits, "miss_count": self.misses,
                    "entries": len(self._store)}


# node-wide shared cache (the reference's is also a single node-level
# cache shared by all shards, indices/IndicesRequestCache.java:82)
REQUEST_CACHE = RequestCache()


def cache_key(segments, body: dict, k: int,
              extra_filter: Optional[dict],
              query_key: Optional[Tuple] = None) -> Optional[Tuple]:
    """None = not cacheable (unserializable body).

    `query_key` — the interned template key for body["query"]
    (dsl.intern_query's (sig, literals)) — stands in for the query's
    share of the canonical-JSON dump, so the msearch envelope's cacheable
    bodies skip most of the per-query json.dumps host cost. Template keys
    and dumped keys live in disjoint key spaces (the "tpl" tag), so the
    two paths can't alias each other."""
    try:
        if query_key is not None:
            rest = {k2: v for k2, v in body.items() if k2 != "query"}
            req: Any = ("tpl", query_key,
                        json.dumps(rest, sort_keys=True,
                                   separators=(",", ":")))
        else:
            req = json.dumps(body, sort_keys=True, separators=(",", ":"))
        extra = json.dumps(extra_filter, sort_keys=True) \
            if extra_filter is not None else None
    except (TypeError, ValueError):
        return None
    # the block-max gate is node state, not request state, yet it changes
    # the cached payload (pruned totals are lower bounds, relation "gte")
    # — a gate flip must miss, not serve the other regime's entry
    from opensearch_tpu.ops import bm25 as _bm25
    return (tuple((s.uid, s.live_doc_count) for s in segments), req, k,
            extra, _bm25.BLOCKMAX)


# date-math expression relative to evaluation time: "now", "now-1d",
# "now+2h/d", ... — same family indices.query_cache.cacheable_node
# rejects at the compiled-filter level (RangeQuery bounds containing
# "now"). Anchored so plain values like "nowhere" don't match.
_NOW_MATH = re.compile(r"^now([+\-/].*)?$")


def _has_now_date_math(obj) -> bool:
    """True if any string value anywhere under the query/agg tree is a
    now-relative date-math expression. Walking every value (not just
    range bounds) deliberately over-rejects: date math appears in range
    filters, date_range agg specs, extended_bounds, distance_feature
    origins — and a skipped cache entry only costs a recompute, where a
    cached now-relative result is silently stale until LRU eviction."""
    if isinstance(obj, str):
        return bool(_NOW_MATH.match(obj))
    if isinstance(obj, dict):
        return any(_has_now_date_math(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return any(_has_now_date_math(v) for v in obj)
    return False


def enabled_by(settings: dict) -> bool:
    """`index.requests.cache.enable` of an index's (normalised)
    settings; true where it is not set, as upstream's default."""
    raw = settings.get("requests.cache.enable")
    if raw is None:
        return True
    from opensearch_tpu.common.settings import _parse_bool
    return _parse_bool(raw, "index.requests.cache.enable")


def admits(body: dict, index_enabled: bool,
           query_now_safe: bool = False) -> bool:
    """Whether this request is looked up in (and stored to) the cache:
    the ONE decision every route asks (the host loop's shard query
    phase, the msearch envelope, the SPMD route). A body `cacheable`
    refuses is never cached; one it admits is cached where the request
    says so (`?request_cache=`, IndicesService.canCache: the request's
    word beats the index's) and else where the index's
    `index.requests.cache.enable` does. Unlike upstream an explicit
    `request_cache=true` does not extend to bodies with hits: the cached
    value holds totals and aggregation partials, no page."""
    if not cacheable(body, query_now_safe):
        return False
    wanted = body.get(REQUEST_KEY)
    if wanted is None:
        wanted = index_enabled
    if not wanted:
        _CACHE_BYPASSED.inc()
    return bool(wanted)


def cacheable(body: dict, query_now_safe: bool = False) -> bool:
    """Default policy mirrors the reference: only size=0 requests (aggs,
    counts) are cached; profile runs always execute. Bodies whose query or
    agg tree contains now-relative date math never cache — "now" resolves
    per evaluation, so a cached result would keep serving the resolution
    instant of the first request (IndicesService.canCache's
    Rewriteable.isCacheable gate in the reference).

    query_now_safe=True skips the query-tree walk: the caller already
    interned the query (dsl.intern_query), which rejects now-relative
    range bounds — the one place date math is time-dependent in the
    shapes it admits — so re-walking the tree per query is pure host
    cost on the warm msearch path."""
    return (body.get("size", 10) == 0
            and not body.get("profile")
            and body.get("search_after") is None
            and (query_now_safe
                 or not _has_now_date_math(body.get("query")))
            and not _has_now_date_math(body.get("aggs")
                                       or body.get("aggregations")))
