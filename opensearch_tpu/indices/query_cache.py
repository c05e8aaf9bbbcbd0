"""Segment-level filter (query) cache.

Re-design of indices/IndicesQueryCache.java:70 + Lucene's
LRUQueryCache/UsageTrackingQueryCachingPolicy: filter-context sub-queries
that recur cache their per-segment match MASK, so later queries splice a
precomputed bitset into the compiled plan instead of re-deriving the
filter on device. Policy follows the reference: a filter becomes
cache-worthy only after repeated use (min_uses), and the cache is a
node-wide LRU bounded by entry count (masks are dense bool[d_pad] — a
131K-lane segment's mask is 128KiB, so the default cap bounds memory to
~32MiB, the reference's indices.queries.cache.size spirit).

Keys are (segment uid, filter fingerprint): segment uids are
process-unique and never reused, so stale entries from merged-away
segments simply age out of the LRU. Cached masks deliberately exclude
liveness — deletes mutate a segment's live bitmap in place, and the
query phase applies `live` after plan evaluation, so a cached mask stays
correct across deletes.

Time-relative filters (date math containing "now") and script/knn/
percolate queries never cache.

Scope: the cache splices into the HOST per-segment loop only. The SPMD
batch path requires structure-uniform plans across its (shard, segment)
rows — a spliced precomputed mask would change one row's plan signature
and break the single-program batching — so the executor installs the
FilterCacheContext only on the host path (field sorts, collapse/rescore,
and other batch-ineligible requests).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import fields as dc_fields
from typing import Dict, Optional, Tuple

import numpy as np

from opensearch_tpu.search import dsl

_CACHEABLE_LEAVES = (
    dsl.TermQuery, dsl.TermsQuery, dsl.RangeQuery, dsl.ExistsQuery,
    dsl.IdsQuery, dsl.PrefixQuery, dsl.WildcardQuery, dsl.RegexpQuery,
    dsl.FuzzyQuery, dsl.MatchQuery, dsl.MatchPhraseQuery,
    dsl.MatchAllQuery, dsl.MatchNoneQuery,
)
_CACHEABLE_COMPOUND = (dsl.BoolQuery, dsl.ConstantScoreQuery,
                       dsl.NestedQuery)


def cacheable_node(node) -> bool:
    """UsageTrackingQueryCachingPolicy#shouldCache's safety half: only
    deterministic, segment-pure filters may cache."""
    if isinstance(node, dsl.RangeQuery):
        for bound in (node.gte, node.gt, node.lte, node.lt):
            if isinstance(bound, str) and "now" in bound:
                return False            # time-relative: changes per query
        return True
    if isinstance(node, _CACHEABLE_LEAVES):
        return True
    if isinstance(node, _CACHEABLE_COMPOUND):
        for f in dc_fields(node):
            sub = getattr(node, f.name, None)
            if isinstance(sub, dsl.QueryNode) and not cacheable_node(sub):
                return False
            if isinstance(sub, (list, tuple)) and any(
                    isinstance(s, dsl.QueryNode) and not cacheable_node(s)
                    for s in sub):
                return False
        return True
    return False


def fingerprint(node) -> str:
    """Dataclass repr is deterministic and covers every field — the
    normalized-query-bytes key of the reference."""
    return repr(node)


class QueryCache:
    def __init__(self, max_entries: int = 256, min_uses: int = 2,
                 max_bytes: int = 64 << 20):
        self.max_entries = max_entries
        self.min_uses = min_uses
        self.max_bytes = max_bytes
        self._bytes = 0
        self._masks: "OrderedDict[Tuple[int, str], np.ndarray]" \
            = OrderedDict()
        self._uses: "OrderedDict[Tuple[int, str], int]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def lookup(self, seg_uid: int, fp: str) -> Optional[np.ndarray]:
        key = (seg_uid, fp)
        with self._lock:
            mask = self._masks.get(key)
            if mask is not None:
                self._masks.move_to_end(key)
                self.hits += 1
                return mask
            self.misses += 1
            return None

    def record_use(self, seg_uid: int, fp: str) -> bool:
        """Count a use; True once the filter crosses the caching threshold
        (fill now). The usage ledger is itself LRU-bounded."""
        key = (seg_uid, fp)
        with self._lock:
            count = self._uses.get(key, 0) + 1
            self._uses[key] = count
            self._uses.move_to_end(key)
            while len(self._uses) > self.max_entries * 4:
                self._uses.popitem(last=False)
            return count >= self.min_uses and key not in self._masks

    def put(self, seg_uid: int, fp: str, mask: np.ndarray):
        key = (seg_uid, fp)
        with self._lock:
            old = self._masks.get(key)
            if old is not None:
                self._bytes -= old.nbytes
            self._masks[key] = mask
            self._bytes += mask.nbytes
            self._masks.move_to_end(key)
            # entry-count AND byte budget (indices.queries.cache.size):
            # large segments have proportionally large masks
            while self._masks and (len(self._masks) > self.max_entries
                                   or self._bytes > self.max_bytes):
                _, dropped = self._masks.popitem(last=False)
                self._bytes -= dropped.nbytes
                self.evictions += 1

    def clear(self):
        with self._lock:
            self._masks.clear()
            self._uses.clear()
            self._bytes = 0
            self.hits = self.misses = self.evictions = 0

    def stats(self) -> Dict:
        with self._lock:
            return {
                "hit_count": self.hits,
                "miss_count": self.misses,
                "cache_count": len(self._masks),
                "evictions": self.evictions,
                "memory_size_in_bytes": self._bytes,
            }


QUERY_CACHE = QueryCache()


class FilterCacheContext:
    """Per-segment splice point installed on the Compiler by the executor:
    cached filters compile to a precomputed-mask plan; uncached ones
    compile normally and, once used min_uses times, are evaluated
    standalone on device (one extra launch, amortized) and cached."""

    def __init__(self, seg, arrays):
        self.seg = seg
        self.arrays = arrays

    def compile_filter(self, compiler, node, seg, meta):
        from opensearch_tpu.search.compile import Plan
        if seg is not self.seg or not cacheable_node(node):
            return compiler.compile(node, seg, meta)
        fp = fingerprint(node)
        mask = QUERY_CACHE.lookup(seg.uid, fp)
        if mask is not None:
            d_pad = self.arrays["live"].shape[0]
            return Plan("precomputed", inputs={
                "scores": np.zeros(d_pad, dtype=np.float32),
                "matches": mask})
        plan = compiler.compile(node, seg, meta)
        if QUERY_CACHE.record_use(seg.uid, fp):
            QUERY_CACHE.put(seg.uid, fp,
                            _eval_filter_mask(plan, self.arrays))
        return plan


_MASK_JIT: Dict = {}


def _eval_filter_mask(plan, arrays) -> np.ndarray:
    """Run ONLY the filter sub-plan on device and pull its match mask to
    host. Jitted per plan signature, like the executor's query runners.
    The mask pull is a real query-path transfer (a cache fill riding the
    triggering request), so it is ledger-attributed on its own channel —
    before this it was an invisible sync no profile's decomposition
    could explain."""
    import time

    import jax
    import jax.numpy as jnp

    from opensearch_tpu.search.plan_eval import _eval_plan
    from opensearch_tpu.telemetry import TELEMETRY

    sig = ("filter_mask", plan.sig())
    fn = _MASK_JIT.get(sig)
    if fn is None:
        def filter_mask(seg, flat_inputs, _plan=plan):  # jit_filter_mask
            cursor = [0]
            _, matches = _eval_plan(_plan, seg, flat_inputs, cursor)
            return matches
        fn = _MASK_JIT[sig] = jax.jit(filter_mask)  # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
    flat = jax.tree_util.tree_map(jnp.asarray, plan.flatten_inputs([]))
    ledger = TELEMETRY.ledger
    scope = ledger.current()
    accounting = ledger.enabled or scope is not None
    with ledger.attributed():
        # dispatch before the clock: a first-seen filter signature
        # compiles synchronously inside fn(), and compile wall must not
        # report as device_get/transfer wall
        out = fn(arrays, flat)
        t0 = time.monotonic() if accounting else 0.0
        mask = np.asarray(jax.device_get(out))
    if accounting:
        ledger.record("filter_mask", "d2h", mask.nbytes,
                      wave=ledger.new_wave(), scope=scope)
        ledger.note_device_get((time.monotonic() - t0) * 1000,
                               nbytes=mask.nbytes, scope=scope)
    return mask
