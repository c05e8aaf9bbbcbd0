"""Pure-numpy reference implementation of Lucene/OpenSearch BM25 semantics.

Used as the parity oracle for the device kernels: idf and length-norm math
follow LegacyBM25Similarity (the reference's default similarity,
index/similarity/SimilarityService.java:85) including SmallFloat norm
quantization of doc length.
"""

from __future__ import annotations

import datetime as _dt
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from opensearch_tpu.index.segment import (
    smallfloat_byte4_to_int, smallfloat_int_to_byte4)

K1 = 1.2
B = 0.75


# --------------------------------------------------- date_histogram oracle

_CAL_MONTHS = {"month": 1, "M": 1, "1M": 1,
               "quarter": 3, "q": 3, "1q": 3,
               "year": 12, "y": 12, "1y": 12}


def ref_date_histogram(values_ms: Sequence[int],
                       fixed_ms: Optional[int] = None,
                       calendar: Optional[str] = None,
                       offset_ms: int = 0, tz_ms: int = 0,
                       min_doc_count: int = 0,
                       extended_bounds: Optional[Dict[str, int]] = None,
                       ) -> Dict[int, int]:
    """Independent date_histogram oracle: per-value key computed the
    straightforward way (shift into offset-adjusted local time, round
    down, shift back to UTC), gap-filled / bounds-extended the slow way.
    Returns {utc_key_ms: doc_count} in key order."""
    shift = tz_ms - offset_ms

    def key_of(v: float) -> int:
        if fixed_ms is not None:
            return int(math.floor((v + shift) / fixed_ms)) * fixed_ms - shift
        t = _dt.datetime.fromtimestamp((v + shift) / 1000.0,
                                       tz=_dt.timezone.utc)
        step = _CAL_MONTHS[calendar]
        month0 = ((t.month - 1) // step) * step
        t = t.replace(month=month0 + 1, day=1, hour=0, minute=0, second=0,
                      microsecond=0)
        return int(t.timestamp() * 1000) - shift

    counts: Dict[int, int] = {}
    for v in values_ms:
        k = key_of(float(v))
        counts[k] = counts.get(k, 0) + 1

    keys = sorted(counts)
    if min_doc_count == 0 and keys:
        lo, hi = keys[0], keys[-1]
        if extended_bounds:
            if extended_bounds.get("min") is not None:
                lo = min(lo, key_of(float(extended_bounds["min"])))
            if extended_bounds.get("max") is not None:
                hi = max(hi, key_of(float(extended_bounds["max"])))
        if fixed_ms is not None:
            k = lo
            while k <= hi:
                counts.setdefault(k, 0)
                k += fixed_ms
        else:
            # walk calendar buckets one by one from lo
            k = lo
            while k < hi:
                nxt = key_of(k + _next_bucket_step(calendar))
                counts.setdefault(nxt, 0)
                k = nxt
    out = {k: counts[k] for k in sorted(counts)
           if counts[k] >= min_doc_count}
    return out


def _next_bucket_step(calendar: str) -> int:
    """A duration guaranteed to land in the NEXT calendar bucket but not
    skip one (calendar buckets are 28-92 days for month/quarter, 365/366
    for year)."""
    days = {"month": 32, "M": 32, "1M": 32,
            "quarter": 93, "q": 93, "1q": 93,
            "year": 367, "y": 367, "1y": 367}[calendar]
    return days * 86400_000


# ------------------------------------------------------ hybrid-score oracle

def ref_hybrid_scores(shard_candidates: Sequence[Sequence[Dict]],
                      normalization: str = "min_max",
                      combination: str = "arithmetic_mean",
                      weights: Optional[Sequence[float]] = None,
                      ) -> Dict:
    """Independent oracle for the hybrid normalization + combination merge
    (neural-search ScoreNormalization/ScoreCombination semantics, computed
    the straightforward way — no bounds carrying, no device math).

    shard_candidates: per SHARD, a list over SUB-QUERIES of {doc_key:
    score} — each shard's already-selected candidate window for that
    sub-query (the union of shard windows is what the reference
    normalizes over). Returns {doc_key: combined_score}.
    """
    n_sub = max(len(subs) for subs in shard_candidates) \
        if shard_candidates else 0
    ws = list(weights) if weights is not None else [1.0] * n_sub

    # global per-sub-query candidate pools
    pools: List[Dict] = [{} for _ in range(n_sub)]
    for subs in shard_candidates:
        for i, cands in enumerate(subs):
            pools[i].update(cands)

    normalized: List[Dict] = []
    for i in range(n_sub):
        pool = pools[i]
        if normalization == "l2":
            norm = math.sqrt(sum(s * s for s in pool.values()))
            normalized.append({k: (s / norm if norm > 0 else 0.0)
                               for k, s in pool.items()})
        elif normalization == "min_max":
            if not pool:
                normalized.append({})
                continue
            mn, mx = min(pool.values()), max(pool.values())
            out = {}
            for k, s in pool.items():
                if mx == mn:
                    out[k] = 1.0          # single-value case
                else:
                    v = (s - mn) / (mx - mn)
                    out[k] = 0.001 if v == 0.0 else v
            normalized.append(out)
        else:
            raise ValueError(normalization)

    docs = sorted({k for pool in normalized for k in pool})
    result = {}
    for key in docs:
        scores = [normalized[i].get(key) for i in range(n_sub)]
        if combination == "arithmetic_mean":
            denom = sum(ws)
            combined = (sum(ws[i] * (scores[i] or 0.0)
                            for i in range(n_sub)) / denom
                        if denom > 0 else 0.0)
        elif combination == "geometric_mean":
            num = denom = 0.0
            for i in range(n_sub):
                if scores[i] is not None and scores[i] > 0:
                    num += ws[i] * math.log(scores[i])
                    denom += ws[i]
            combined = math.exp(num / denom) if denom > 0 else 0.0
        elif combination == "harmonic_mean":
            num = denom = 0.0
            for i in range(n_sub):
                if scores[i] is not None and scores[i] > 0:
                    num += ws[i]
                    denom += ws[i] / scores[i]
            combined = num / denom if denom > 0 else 0.0
        else:
            raise ValueError(combination)
        result[key] = combined
    return result


def ref_knn_l2_score(doc_vec: Sequence[float],
                     query_vec: Sequence[float]) -> float:
    """k-NN plugin l2 space score: 1 / (1 + squared distance)."""
    d2 = sum((float(a) - float(b)) ** 2
             for a, b in zip(doc_vec, query_vec))
    return 1.0 / (1.0 + d2)


def ref_knn_score(doc_vec: Sequence[float], query_vec: Sequence[float],
                  space: str) -> float:
    """k-NN plugin score of one document in `space`, float64, by the
    plugin's rules: l2 1 / (1 + squared distance); cosinesimil
    (1 + cos) / 2; innerproduct ip + 1 where ip >= 0, else
    1 / (1 - ip)."""
    x = [float(a) for a in doc_vec]
    q = [float(b) for b in query_vec]
    if space == "l2":
        return ref_knn_l2_score(x, q)
    ip = math.fsum(a * b for a, b in zip(x, q))
    if space == "cosinesimil":
        norms = math.sqrt(math.fsum(a * a for a in x)
                          * math.fsum(b * b for b in q))
        cos = ip / norms if norms > 0 else 0.0
        return (1.0 + max(-1.0, min(1.0, cos))) / 2.0
    if space == "innerproduct":
        return ip + 1.0 if ip >= 0 else 1.0 / (1.0 - ip)
    raise ValueError(f"unknown knn space [{space}]")


def ref_maxsim_scores(segment_docs: Sequence[Sequence[Optional[Sequence[Sequence[float]]]]],
                      query_vectors: Sequence[Sequence[float]],
                      k: int) -> List[Dict[Tuple[int, int], float]]:
    """Pure-Python late-interaction MaxSim oracle (ISSUE 18).

    `segment_docs`: per segment, per doc ord, the doc's token vectors
    (list of [dims] lists) or None when the doc has no rank_vectors
    value (such docs never match — the exists mask). Empty token lists
    behave like None. `query_vectors`: [Tq][dims].

    Returns one {(seg_idx, doc_ord): score} dict per segment holding
    that segment's top-k matches, scored with numpy float32 arithmetic
    in the same reduction order as ops/maxsim.exact_maxsim_scores
    (token dots -> max over doc tokens -> sum over query tokens), so
    the executor's responses agree to f32 precision. Cross-segment
    merge is the caller's concern — exactly like the executor, where
    ops/topk.value_merge_key handles it."""
    import numpy as np
    q = np.asarray(query_vectors, dtype=np.float32)
    out: List[Dict[Tuple[int, int], float]] = []
    for seg_idx, docs in enumerate(segment_docs):
        scored = []
        for ord_, toks in enumerate(docs):
            if toks is None or len(toks) == 0:
                continue
            mat = np.asarray(toks, dtype=np.float32)
            dots = mat @ q.T                       # [T, Tq], f32
            score = np.float32(0.0)
            for t in range(q.shape[0]):            # sum over query tokens
                score = np.float32(score + dots[:, t].max())
            scored.append((ord_, float(score)))
        scored.sort(key=lambda e: (-e[1], e[0]))   # stable: ties by ord
        out.append({(seg_idx, ord_): s for ord_, s in scored[:k]})
    return out


class RefField:
    """One text field over a corpus of already-analyzed docs."""

    def __init__(self, docs_terms: Sequence[Sequence[str]]):
        # docs with no value for the field are represented by None
        self.docs = [list(d) if d is not None else None for d in docs_terms]
        self.doc_count = sum(1 for d in self.docs if d is not None)
        self.sum_ttf = sum(len(d) for d in self.docs if d is not None)
        self.avgdl = self.sum_ttf / self.doc_count if self.doc_count else 1.0
        self.df: Dict[str, int] = {}
        for d in self.docs:
            if d is None:
                continue
            for t in set(d):
                self.df[t] = self.df.get(t, 0) + 1

    def idf(self, term: str) -> float:
        df = self.df.get(term, 0)
        if df == 0:
            return 0.0
        return math.log(1.0 + (self.doc_count - df + 0.5) / (df + 0.5))

    def norm_dl(self, doc_i: int) -> float:
        d = self.docs[doc_i]
        if d is None:
            return 0.0
        return float(smallfloat_byte4_to_int(smallfloat_int_to_byte4(len(d))))

    def bm25(self, doc_i: int, term: str, boost: float = 1.0) -> float:
        d = self.docs[doc_i]
        if d is None:
            return 0.0
        tf = d.count(term)
        if tf == 0:
            return 0.0
        dl = self.norm_dl(doc_i)
        denom = tf + K1 * (1 - B + B * dl / self.avgdl)
        return boost * self.idf(term) * tf * (K1 + 1) / denom

    def match_scores(self, terms: Sequence[str], operator: str = "or",
                     boost: float = 1.0) -> np.ndarray:
        """Per-doc scores of a match query; 0 where the doc doesn't match."""
        n = len(self.docs)
        out = np.zeros(n, dtype=np.float64)
        for i in range(n):
            if self.docs[i] is None:
                continue
            hit_terms = [t for t in set(terms) if t in self.docs[i]]
            if operator == "and" and len(hit_terms) != len(set(terms)):
                continue
            if not hit_terms:
                continue
            # duplicate query terms score multiple times (Lucene sums clauses)
            score = sum(self.bm25(i, t, boost) for t in terms if t in self.docs[i])
            out[i] = score
        return out


# ------------------------------------------------------------- admission

def ref_predict_queue_ms(service_ms, queue_depth):
    """Oracle for common/admission.predict_queue_ms: the serial-queue
    model `(depth + 1) * service`, None when no estimate exists."""
    if service_ms is None or service_ms <= 0.0:
        return None
    return service_ms * (max(queue_depth, 0) + 1)


def ref_deadline_shed(service_ms, queue_depth, budget_ms):
    """Oracle for the shed verdict (DeadlineShedder.check, ignoring the
    warmup/probe escapes): shed iff an estimate exists, a budget exists,
    and the predicted queue time exceeds it."""
    if budget_ms is None:
        return False
    predicted = ref_predict_queue_ms(service_ms, queue_depth)
    return predicted is not None and predicted > budget_ms


def ref_token_bucket(rate, burst, events):
    """Oracle for TokenBucket.take_up_to: `events` is a sequence of
    (at_seconds, want) pairs in nondecreasing time order; returns the
    admitted count per event."""
    tokens = float(burst)
    last = 0.0
    out = []
    for at, want in events:
        tokens = min(float(burst), tokens + (at - last) * rate)
        last = at
        got = min(int(tokens), int(want))
        tokens -= got
        out.append(got)
    return out


def ref_window_ms(budgets_ms, service_ms, queue_depth, arrival_gap_ms,
                  window_max_ms):
    """Oracle for search/scheduler.plan_window_ms: the adaptive
    micro-batch delay window. Budget cap = the minimum over queued
    budgets of (budget - predicted queue time) with the serial-queue
    model (ref_predict_queue_ms), clamped to [0, window_max]; the
    pressure term zeroes the window when the live arrival-gap estimate
    says no companion is likely to arrive within the cap."""
    cap = float(window_max_ms)
    predicted = ref_predict_queue_ms(service_ms, queue_depth)
    if predicted is None:
        predicted = 0.0
    for budget in budgets_ms:
        if budget is None:
            continue
        cap = min(cap, budget - predicted)
    cap = max(0.0, min(cap, float(window_max_ms)))
    if cap <= 0.0:
        return 0.0
    if arrival_gap_ms is None or arrival_gap_ms > cap:
        return 0.0
    return cap


# ------------------------------------------- nyc_taxis aggregation oracle

def ref_histogram_stats(docs: Sequence[Dict], bucket_field: str,
                        interval: float, lo: float, hi: float,
                        metric_field: str) -> Tuple[int, List[Tuple]]:
    """`range bucket_field {gte lo, lt hi}` > `histogram(bucket_field,
    interval)` > `stats(metric_field)` over plain documents, in float64:
    (hits, [(key, doc_count, (count, min, max, avg, sum) or None)]) for
    every bucket from the first non-empty one to the last, the empty
    ones between them included (`min_doc_count` 0). Values are taken as
    a `scaled_float` of factor 100 stores them: round(v * 100) / 100."""
    def stored(v):
        return round(v * 100) / 100
    sel = [d for d in docs if lo <= stored(d[bucket_field]) < hi]
    by: Dict[int, List[float]] = {}
    for d in sel:
        by.setdefault(math.floor(stored(d[bucket_field]) / interval),
                      []).append(stored(d[metric_field]))
    out = []
    for b in range(min(by), max(by) + 1) if by else ():
        vs = by.get(b)
        out.append((b * interval, len(vs or ()),
                    None if not vs else
                    (len(vs), min(vs), max(vs), math.fsum(vs) / len(vs),
                     math.fsum(vs))))
    return len(sel), out


def ref_day_counts(times_ms: Sequence[int], lo_ms: int,
                   hi_ms: int) -> Tuple[int, List[Tuple[int, int]]]:
    """`range {gte lo_ms, lte hi_ms}` > `date_histogram(day)` over epoch
    milliseconds: (hits, [(UTC day key in ms, doc_count)]) from the
    first non-empty day to the last."""
    day = 86_400_000
    sel = [t for t in times_ms if lo_ms <= t <= hi_ms]
    by: Dict[int, int] = {}
    for t in sel:
        by[t // day] = by.get(t // day, 0) + 1
    return len(sel), [(d * day, by.get(d, 0))
                      for d in (range(min(by), max(by) + 1) if by else ())]


def ref_terms_avg(docs: Sequence[Dict], keep, term_field: str,
                  metric_field: str) -> Tuple[int, List[Tuple]]:
    """`terms(term_field)` > `avg(metric_field)` over the documents
    `keep` admits: (hits, [(key, doc_count, avg)]) by doc_count
    descending, then key ascending."""
    by: Dict[str, List[float]] = {}
    sel = [d for d in docs if keep(d)]
    for d in sel:
        by.setdefault(d[term_field], []).append(
            round(d[metric_field] * 100) / 100)
    return len(sel), [(k, len(v), math.fsum(v) / len(v))
                      for k, v in sorted(by.items(),
                                         key=lambda kv: (-len(kv[1]),
                                                         kv[0]))]
