"""Coordinator-side hybrid search: normalization + weighted combination.

The reduce half of the neural-search plugin's NormalizationProcessor
(normalization/ScoreNormalizationTechnique + combination/
ScoreCombinationTechnique, driven by NormalizationProcessorWorkflow):
every shard's fused hybrid query phase (search/executor.py
build_hybrid_query_phase) returns per-sub-query top-k candidates PLUS
per-sub-query (min, max, sum-of-squares, count) bounds computed on
device over that shard's candidate window. The bounds ride the shard
merge (search/spmd.py merge_hybrid_bounds — min/max/psum reduction, the
host analog of the collective merge), so normalization at reduce uses
GLOBAL per-sub-query statistics, exactly like the reference normalizing
over the union of all shards' TopDocs.

Semantics (tests/reference_impl.ref_hybrid_scores is the independent
oracle):
  min_max: (s - min) / (max - min); all-equal scores → 1.0; an exact-0
           result is floored to 0.001 (MinMaxScoreNormalizationTechnique
           MIN_SCORE).
  l2:      s / sqrt(Σ s²) over every collected candidate of the
           sub-query; zero norm → 0.
  arithmetic_mean: Σ wᵢsᵢ / Σ wᵢ over ALL sub-queries (a doc missing
           from a sub-query's candidates contributes 0 with its weight
           still in the denominator — ArithmeticMeanScoreCombination).
  geometric_mean / harmonic_mean: only sub-queries with sᵢ > 0
           participate (numerator AND denominator); no positive scores
           → 0.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Optional, Tuple

from opensearch_tpu.common.errors import IllegalArgumentError
from opensearch_tpu.search import dsl
from opensearch_tpu.telemetry import TELEMETRY

# hybrid requests rendered from the fused program's rows (B=1 `_search`
# and each item of `_msearch`'s hybrid waves), once a request; and the
# candidates their normalization read, every sub-query's window summed
_HYBRID_QUERIES = TELEMETRY.metrics.counter("search.hybrid.queries")
_HYBRID_CANDIDATES = TELEMETRY.metrics.counter("search.hybrid.candidates")

# neural-search MinMaxScoreNormalizationTechnique constants
MIN_SCORE = 0.001
SINGLE_RESULT_SCORE = 1.0

DEFAULT_SPEC = {"normalization": "min_max",
                "combination": "arithmetic_mean", "weights": None}

# body keys the hybrid flow serves; anything else is an explicit 400 —
# never a silently-wrong page (the reference's HybridQueryPhaseSearcher
# rejects most of these shapes too)
_HYBRID_UNSUPPORTED = ("aggs", "aggregations", "collapse", "rescore",
                       "search_after", "slice", "suggest", "highlight",
                       "script_fields", "docvalue_fields", "scroll", "pit")


def normalize_scores(values: List[float], bounds: Tuple[float, float,
                                                        float, int],
                     technique: str) -> List[float]:
    """Normalize one sub-query's candidate scores with its GLOBAL bounds."""
    mn, mx, ssq, count = bounds
    if technique == "l2":
        norm = math.sqrt(ssq)
        return [v / norm if norm > 0 else 0.0 for v in values]
    if technique != "min_max":
        raise IllegalArgumentError(
            f"unknown normalization technique [{technique}]")
    out = []
    for v in values:
        if count == 0:
            out.append(0.0)
        elif mx == mn:
            out.append(SINGLE_RESULT_SCORE)
        else:
            normalized = (v - mn) / (mx - mn)
            out.append(MIN_SCORE if normalized == 0.0 else normalized)
    return out


def combine_scores(scores: List[Optional[float]],
                   weights: Optional[List[float]],
                   technique: str) -> float:
    """Weighted combination of one doc's per-sub-query normalized scores
    (None = the doc was not in that sub-query's candidates)."""
    n = len(scores)
    ws = weights if weights is not None else [1.0] * n
    if technique == "arithmetic_mean":
        total = sum(ws[i] * (scores[i] or 0.0) for i in range(n))
        denom = sum(ws)
        return total / denom if denom > 0 else 0.0
    if technique == "geometric_mean":
        log_sum = 0.0
        denom = 0.0
        for i in range(n):
            s = scores[i]
            if s is not None and s > 0:
                log_sum += ws[i] * math.log(s)
                denom += ws[i]
        return math.exp(log_sum / denom) if denom > 0 else 0.0
    if technique == "harmonic_mean":
        num = 0.0
        denom = 0.0
        for i in range(n):
            s = scores[i]
            if s is not None and s > 0:
                num += ws[i]
                denom += ws[i] / s
        return num / denom if denom > 0 else 0.0
    raise IllegalArgumentError(
        f"unknown combination technique [{technique}]")


def _validate_body(body: dict, n_sub: int, spec: dict) -> None:
    for key in _HYBRID_UNSUPPORTED:
        if body.get(key):
            raise IllegalArgumentError(
                f"[{key}] is not supported with a [hybrid] query")
    sort = body.get("sort")
    if sort not in (None, "_score", ["_score"]):
        raise IllegalArgumentError(
            "[sort] is not supported with a [hybrid] query (hybrid "
            "results are ranked by the combined normalized score)")
    weights = spec.get("weights")
    if weights is not None and len(weights) != n_sub:
        raise IllegalArgumentError(
            f"number of weights [{len(weights)}] must match number of "
            f"sub-queries [{n_sub}] in hybrid query")


def resolve_spec(phase_spec: Optional[dict]) -> dict:
    spec = dict(DEFAULT_SPEC)
    if phase_spec:
        spec.update({k: v for k, v in phase_spec.items()
                     if v is not None})
    return spec


def validate_hybrid_request(body: dict, n_sub: int, spec: dict,
                            executors: List) -> Tuple[int, int, int]:
    """Shared request validation for the per-query and the batched
    msearch hybrid paths. Returns (size, from_, k)."""
    _validate_body(body, n_sub, spec)
    size = int(body.get("size", 10))
    from_ = int(body.get("from", 0))
    if size < 0 or from_ < 0:
        raise IllegalArgumentError(
            "[from] parameter cannot be negative" if from_ < 0
            else "[size] parameter cannot be negative")
    window = min((getattr(ex, "max_result_window", 10000)
                  for ex in executors), default=10000)
    if from_ + size > window:
        raise IllegalArgumentError(
            f"Result window is too large, from + size must be less than "
            f"or equal to: [{window}] but was [{from_ + size}]. See the "
            f"scroll api for a more efficient way to request large data "
            f"sets. This limit can be set by changing the "
            f"[index.max_result_window] index level setting.")
    return size, from_, max(from_ + size, 10)


def merge_hybrid(shard_results: List, spec: dict, n_sub: int
                 ) -> Tuple[List[Tuple[float, Tuple[int, int, int]]], int]:
    """The hybrid reduce: global bounds (the collective-merge analog) →
    normalize every candidate → weighted combine → the combined order,
    (score, (shard, seg, ord)) best first, and the candidates it read.
    Counts the request and its candidates. Shared by
    execute_hybrid_search and the batched _msearch hybrid envelope,
    each of which then calls render_hybrid."""
    from opensearch_tpu.search import spmd

    global_bounds = spmd.merge_hybrid_bounds(
        [r.bounds for r in shard_results], n_sub)

    # doc key = (shard, seg, ord); values = per-sub normalized scores
    docs: Dict[Tuple[int, int, int], List[Optional[float]]] = {}
    candidates = 0
    for i in range(n_sub):
        raw: List[float] = []
        keys: List[Tuple[int, int, int]] = []
        for shard_i, r in enumerate(shard_results):
            for score, seg_i, ord_ in r.per_sub[i]:
                raw.append(score)
                keys.append((shard_i, seg_i, ord_))
        candidates += len(raw)
        for key, ns in zip(keys, normalize_scores(
                raw, global_bounds[i], spec["normalization"])):
            docs.setdefault(key, [None] * n_sub)[i] = ns

    combined = [(combine_scores(subs, spec.get("weights"),
                                spec["combination"]), key)
                for key, subs in docs.items()]
    # combined-score desc; (shard, seg, doc) asc tie-break — the same
    # final order mergeTopDocs uses for equal scores
    combined.sort(key=lambda e: (-e[0], e[1]))
    _HYBRID_QUERIES.inc()
    _HYBRID_CANDIDATES.inc(candidates)
    return combined, candidates


def render_hybrid(executors: List, body: dict, shard_results: List,
                  combined: List, start: float,
                  total_shards: Optional[int] = None,
                  failed_shards: int = 0,
                  failures: Optional[List[dict]] = None) -> dict:
    """The page of `merge_hybrid`'s combined order: fetch, hits.total
    and the `_shards` block."""
    size = int(body.get("size", 10))
    from_ = int(body.get("from", 0))
    total = sum(r.total for r in shard_results)
    page = combined[from_:from_ + size]
    max_score = combined[0][0] if combined else None

    hits = []
    for score, (shard_i, seg_i, ord_) in page:
        ex = executors[shard_i]
        hits.append(ex._hit_dict(seg_i, ord_, float(score), body))

    n_shards = total_shards if total_shards is not None else len(executors)
    track_total = body.get("track_total_hits", True)
    hits_block: Dict[str, Any] = {"max_score": max_score, "hits": hits}
    if track_total is False:
        pass
    elif track_total is True:
        hits_block = {"total": {"value": total, "relation": "eq"},
                      **hits_block}
    else:
        threshold = int(track_total)
        if total > threshold:
            hits_block = {"total": {"value": threshold,
                                    "relation": "gte"}, **hits_block}
        else:
            hits_block = {"total": {"value": total, "relation": "eq"},
                          **hits_block}

    n_failed = failed_shards + len(failures or [])
    shards_block: Dict[str, Any] = {
        "total": n_shards, "successful": max(n_shards - n_failed, 0),
        "skipped": 0, "failed": n_failed}
    if failures:
        shards_block["failures"] = list(failures)
    return {
        "took": int((time.monotonic() - start) * 1000),
        "timed_out": False,
        "_shards": shards_block,
        "hits": hits_block,
    }


def execute_hybrid_search(executors: List, body: dict,
                          phase_spec: Optional[dict] = None,
                          extra_filters: Optional[List[Optional[dict]]]
                          = None,
                          total_shards: Optional[int] = None,
                          failed_shards: int = 0, task=None,
                          allow_partial: bool = True,
                          ledger_scope=None) -> dict:
    """Full hybrid query-then-fetch over shard executors.

    Per shard the FUSED program returns per-sub-query candidates + score
    bounds; the merge reduces bounds globally (spmd.merge_hybrid_bounds),
    normalizes every candidate with the global statistics, combines into
    one score per doc, and renders the page with the standard fetch.
    A failed shard contributes an empty result + a `_shards.failures[]`
    entry (same partial contract as the plain controller path).
    `ledger_scope` (telemetry/ledger.py) accumulates every shard's
    transfer attribution for the caller's span / slow log — the hybrid
    path used to report bytes_to_device = 0."""
    from opensearch_tpu.common import faults
    from opensearch_tpu.common.errors import (
        SearchPhaseExecutionError, TaskCancelledError,
        shard_failure_entry)
    from opensearch_tpu.search.executor import _empty_hybrid_result
    start = time.monotonic()
    spec = resolve_spec(phase_spec)
    node = dsl.parse_query(body.get("query"))
    if not isinstance(node, dsl.HybridQuery):
        raise IllegalArgumentError("hybrid search requires a top-level "
                                   "[hybrid] query")
    n_sub = len(node.queries)
    _size, _from, k = validate_hybrid_request(body, n_sub, spec, executors)

    shard_results = []
    failures: List[dict] = []
    for shard_i, ex in enumerate(executors):
        if task is not None:
            task.check_cancelled()
        extra = extra_filters[shard_i] if extra_filters else None
        try:
            if faults.ENABLED:
                faults.fire("query.shard")
            shard_results.append(
                ex.execute_hybrid_query_phase(body, k, extra_filter=extra,
                                              ledger_scope=ledger_scope))
        except TaskCancelledError:
            raise
        except Exception as e:  # except-ok: per-shard isolation -- 5xx-class faults land in _shards.failures[], 4xx re-raises below
            from opensearch_tpu.common.errors import OpenSearchTpuError
            if isinstance(e, OpenSearchTpuError) and e.status < 500:
                # deterministic request defect (parse/validation): every
                # shard would fail identically — keep the 4xx contract
                raise
            failures.append(shard_failure_entry(
                shard_i, ex.reader.index_name, e))
            shard_results.append(_empty_hybrid_result(n_sub))

    if failures and len(failures) >= len(executors):
        raise SearchPhaseExecutionError(
            "all shards failed", phase="query", grouped=True,
            failed_shards=failures)
    if failures and not allow_partial:
        raise SearchPhaseExecutionError(
            "Partial shards failure", phase="query", grouped=True,
            failed_shards=failures)
    # the route's last two spans in the always-on ring, beside each
    # shard's `hybrid.compile`, `dispatch` and `device_wait`:
    # `hybrid.merge` (bounds merge, normalization, combination, order)
    # and `respond` (the page render)
    t_merge = time.monotonic()
    combined, candidates = merge_hybrid(shard_results, spec, n_sub)
    t_render = time.monotonic()
    res = render_hybrid(executors, body, shard_results, combined, start,
                        total_shards=total_shards,
                        failed_shards=failed_shards, failures=failures)
    ring = TELEMETRY.tracer.spans
    ring.child("hybrid.merge", t_merge, t_render,
               {"candidates": candidates})
    ring.child("respond", t_render, time.monotonic())
    return res
