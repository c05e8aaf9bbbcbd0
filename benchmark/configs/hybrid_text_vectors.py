"""Builder `hybrid_text_vectors`: one shard of a passage collection that
carries both a BM25 `text` field and a 768-d `embedding`, in the shape
of OpenSearch's hybrid search over MS MARCO passage, and its plain
reference for the `hybrid` query under a `normalization-processor`
(`min_max`, `arithmetic_mean`, weights).

The corpus is the two corpus models the benchmark already has, over the
same doc ordinals, imported and not copied: `zipf_text` makes the
postings, tfs and norm bytes of every passage, `dense_vectors` the
Gaussian-mixture vectors, each from its own streams of the seed. One
sealed segment holds both (`text` postings and norms beside the
`embedding` column); ids are a `PrefixedIds` ("p<ordinal>"), nothing is
stored. Text and vector are independent draws, so a query's BM25 window
and its k-NN window barely overlap and the normalization pool is nearly
the two windows whole.

Traffic. Every query is a `hybrid` of a `match` (terms drawn as
`zipf_text` draws them, in (distinct terms, QB bucket) classes) and a
`knn` (a fresh mixture draw, k `k`), `size` `size`, `_source` false,
with the configuration's normalization pipeline sent inline in the body
as a temporary search pipeline (`search_pipeline`).

Reference (`reference_responses`, `judge`): numpy in float64, nothing
of `opensearch_tpu`. Per sub-query a window of the best `max(from +
size, 10)`: the BM25 scores of every matching passage (`zipf_text`'s
`match`), and of the k-NN clause's exact k winners (`dense_vectors`'
blocked product, scored again from the exact sum of products, the k-NN
plugin's `innerproduct` score). `min_max` over the window (all equal:
1.0; an exact 0 floored to 0.001), `arithmetic_mean` with the weights,
a document missing from a window counting 0 with its weight; order by
the combined score descending, then doc ordinal ascending; `hits.total`
the size of (BM25 matches | the k-NN winners), exact.

Tolerance (`Window.allowed`). The program's sub-scores are held to
`oracle.RTOL` (BM25) and `oracle.KNN_RTOL` (k-NN) relative. A sub-score
off by at most t = rtol * max, in a window of spread R = max - min
whose own min and max are each off by at most t too, normalizes to
within 2t / (R - 2t) of the reference's: (s - min) and (max - min) each
move by 2t at most, and the quotient is at most 1. A hit's combined
score may then lie anywhere in the weighted sum of its sub-scores'
intervals, each clamped to [0, 1]; the interval reaches down to 0 and
up to 0.001 where the sub-score may be its window's minimum (the
floor). A document whose sub-score ties its window's edge within 2t
may be in that window or out of it: its interval for that sub-query
runs from 0 (out) to its normalized score (in). The page is correct
when every hit is a candidate inside its interval, the page is in the
program's own order (combined descending, ties by ordinal), and no
candidate left off the page is surely above the page's last hit.

What `compared` reads (`score_rel_err_max`): for each judged hit, its
distance from the middle of its interval as the relative sub-score
error that distance implies (the interval's half-width is the hit's
own weighted tolerance), against the limit `oracle.KNN_RTOL`, the
looser of the two; a hit is held to its own weighted tolerance, at
most that, and a hit outside it counts in `pages_differing`.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os

import numpy as np

from benchmark import oracle

HERE = os.path.dirname(os.path.abspath(__file__))
# a sub-score window's edge: how many tolerances apart two scores may be
# and still be ordered either way by the program
EDGE = 2.0


def _sibling(name: str):
    """Another builder of this directory, loaded as the harness loads it."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_builder_{name}", os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


zipf_text = _sibling("zipf_text")
dense_vectors = _sibling("dense_vectors")


class Query:
    __slots__ = ("text", "vector", "klass", "work")

    def __init__(self, text: str, vector: np.ndarray, klass, work: dict):
        self.text = text
        self.vector = vector
        self.klass = klass          # (distinct terms, QB bucket)
        self.work = work            # {"lanes": posting lanes of the terms}


class Window:
    """One sub-query's reference window: ordinals and float64 scores by
    (score desc, ordinal asc), the documents sure to be in the program's
    window and those at its edge, and the tolerance t of a score."""

    def __init__(self, ords, scores, pool_ords, pool_scores, rtol: float):
        self.ords, self.scores = ords, scores
        self.n = len(ords)
        if self.n == 0:
            self.mn = self.mx = 0.0
            self.t = 0.0
            self.sure, self.edge = set(), set()
            return
        self.mn, self.mx = float(scores[-1]), float(scores[0])
        self.t = rtol * max(abs(self.mx), abs(self.mn))
        lo = self.mn - EDGE * self.t
        hi = self.mn + EDGE * self.t
        self.sure = {int(o) for o, s in zip(ords, scores) if s > hi}
        near = (pool_scores >= lo) & (pool_scores <= hi)
        self.edge = {int(o) for o in pool_ords[near]} - self.sure
        if len(self.sure) + len(self.edge) == self.n:
            # every document at the edge is in the window whichever way
            # the program orders them
            self.sure |= self.edge
            self.edge = set()
        self.score = dict(zip(ords.tolist(), scores.tolist()))
        for o in self.edge:
            if o not in self.score:
                i = int(np.flatnonzero(pool_ords == o)[0])
                self.score[o] = float(pool_scores[i])

    def normalized(self, o: int) -> float:
        """min_max of the reference's own score (searchpipeline
        semantics: all equal 1.0, an exact 0 floored to 0.001)."""
        if self.mx == self.mn:
            return 1.0
        v = (self.score[o] - self.mn) / (self.mx - self.mn)
        return 0.001 if v == 0.0 else v

    def allowed(self, o: int):
        """(low, high, values) of the normalized sub-score the program
        may give document `o`: the interval its tolerance allows, and
        the exact values it can take, which differ where the document
        may be out of the window (0) or its minimum (0.001)."""
        if o not in self.sure and o not in self.edge:
            return 0.0, 0.0, [0.0]
        ref = self.normalized(o)
        span = self.mx - self.mn
        # s off by t at most, max by t, the program's window minimum by
        # t + EDGE * t (another document at the edge): (s - min) and
        # (max - min) move by 4t at most, and their quotient is <= 1
        slack = (EDGE + 2.0) * self.t
        tau = 1.0 if span <= slack else 2.0 * slack / (span - slack)
        lo, hi = ref - tau, ref + tau
        values = [ref]      # the reference's own value first
        if o in self.edge:
            lo = 0.0
            values.append(0.0)
        if lo <= 0.0:
            # may be its window's minimum: an exact 0, floored
            lo, hi = 0.0, max(hi, 0.001)
            values += [0.001, max(ref, 0.0)]
        return max(lo, 0.0), min(hi, 1.0), values

    def sensitivity(self, o: int) -> float:
        """How far a relative sub-score error of 1 moves the normalized
        score of `o` (0: it is in no window)."""
        if o not in self.sure and o not in self.edge:
            return 0.0
        span = self.mx - self.mn
        return 1.0 if span <= 0.0 \
            else 2.0 * max(abs(self.mx), abs(self.mn)) / span


class Corpus:
    def __init__(self, config: dict, seed: int, dry_run: bool):
        from opensearch_tpu.index.segment import (PrefixedIds, Segment,
                                                  pad_bucket)
        size = {**config, **(config["dry_run"] if dry_run else {})}
        n = int(size["documents"])
        self.n = n
        self.k, self.size = int(config["k"]), int(config["size"])
        self.index = config["index"]
        self.text_field = config["text_field"]
        self.vector_field = config["vector_field"]
        p = config["pipeline"]
        self.weights = [float(w) for w in p["weights"]]
        self.pipeline = {"phase_results_processors": [{
            "normalization-processor": {
                "normalization": {"technique": p["normalization"]},
                "combination": {"technique": p["combination"],
                                "parameters": {"weights": self.weights}}}}]}
        if (p["normalization"], p["combination"]) \
                != ("min_max", "arithmetic_mean"):
            raise ValueError("this builder's reference normalizes min_max "
                             "and combines arithmetic_mean")
        text_cfg = {**config, "passages": n, "dry_run": {"passages": n}}
        vec_cfg = {**config, "vectors": n, "field": self.vector_field,
                   "dry_run": {"vectors": n}}
        self.text = zipf_text.Corpus(text_cfg, seed, dry_run)
        self.vectors = dense_vectors.Corpus(vec_cfg, seed, dry_run)
        ts = self.text.segments[0]
        vs = self.vectors.segments[0]
        src = zipf_text.FIELD
        term_dict = {(self.text_field, term): meta
                     for (_f, term), meta in ts.term_dict.items()}
        self.segments = [Segment(
            "h0", n, PrefixedIds("p", n), [None] * n, term_dict,
            ts.post_docs, ts.post_tf, {self.text_field: ts.norms[src]},
            {self.text_field: ts.field_stats[src]}, {}, {},
            {self.vector_field: vs.vector_dv[self.vector_field]})]
        # the two builders' own segments (and zipf_text's n id strings)
        # are not served
        self.text.segments = self.vectors.segments = None
        self.text.doc_ids = None
        self.index_settings = {"number_of_shards": 1,
                               "number_of_replicas": 0}
        self.mapping = {"properties": {
            self.text_field: {"type": "text"},
            self.vector_field: {
                "type": "knn_vector", "dimension": int(config["dimension"]),
                "method": {"space_type": config["space_type"]}}}}
        self.sizes = {"d_pad": pad_bucket(n), "num_docs": n,
                      "dimension": int(config["dimension"]),
                      "posting_blocks": int(ts.post_docs.shape[0])}

    # ------------------------------------------------------------ queries

    def draw(self, spec: dict, classes: list, seed: int) -> list:
        """One query for each entry of `classes`: the terms of
        `zipf_text`'s draw for the class, a fresh vector of
        `dense_vectors`' mixture. No query repeats."""
        texts = zipf_text.draw_queries(self.text.table, spec, classes, seed)
        vecs = self.vectors.draw(spec, classes, seed)
        return [Query(t.text, v.vector, t.klass, t.work)
                for t, v in zip(texts, vecs)]

    def body(self, query: Query) -> dict:
        return {"query": {"hybrid": {"queries": [
                    {"match": {self.text_field: query.text}},
                    {"knn": {self.vector_field: {
                        "vector": query.vector.tolist(), "k": self.k}}}]}},
                "size": self.size, "_source": False,
                "search_pipeline": self.pipeline}

    def payload(self, query: Query) -> bytes:
        return json.dumps(self.body(query), separators=(",", ":")).encode()

    # ------------------------------------------------------------- oracle

    def _windows(self, queries: list, scores_as=lambda s: s) -> list:
        """[(BM25 window, k-NN window, BM25 match ordinals, k-NN winner
        ordinals)] a query, its sub-scores in the precision `scores_as`
        leaves them in."""
        window = max(self.size, 10)
        q = np.stack([query.vector for query in queries]).astype(np.float64)
        ip = dense_vectors.reference_scores(self.vectors.x, q)
        out = []
        for j, query in enumerate(queries):
            m_ords, m_scores = self.text.match(query.text)
            m_scores = scores_as(m_scores)
            w_ords, w_scores = oracle.top_ords(m_scores, m_ords, window)
            bm25 = Window(w_ords, w_scores, m_ords, m_scores, oracle.RTOL)
            cand, c_scores = self.vectors._candidates(ip[:, j], q[j])
            c_scores = scores_as(c_scores)
            k_ords, k_scores = oracle.top_ords(c_scores, cand, self.k)
            # the clause's k winners (a winner at the clause's own edge
            # may be swapped for the next candidate), and the window of
            # them: the winners themselves where it holds them all
            winners = Window(k_ords, k_scores, cand, c_scores,
                             oracle.KNN_RTOL)
            knn = winners if window >= len(k_ords) else Window(
                k_ords[:window], k_scores[:window], k_ords, k_scores,
                oracle.KNN_RTOL)
            out.append((bm25, knn, m_ords, winners))
        return out

    def _combined(self, wins, o: int):
        """(low, high, values, sensitivity) of the combined score of
        document `o`: the interval the sub-scores' tolerances allow, the
        exact values it can take, and how far a relative error of 1 in
        every sub-score moves it."""
        tot = sum(self.weights)
        lo = hi = sens = 0.0
        values = [0.0]
        for w, win in zip(self.weights, wins):
            a, b, vs = win.allowed(o)
            lo += w * a / tot
            hi += w * b / tot
            values = [c + w * v / tot for c in values for v in vs]
            sens += w * win.sensitivity(o) / tot
        return lo, hi, values, sens

    def reference_responses(self, queries: list,
                            scores_as=lambda s: s) -> list:
        """The responses the plain reference itself would serve, its
        sub-scores in the precision `scores_as` leaves them in (the
        control of `correct`: `oracle.lower_precision`)."""
        out = []
        tot = sum(self.weights)
        for bm25, knn, m_ords, winners in self._windows(queries, scores_as):
            docs = {}
            for i, win in enumerate((bm25, knn)):
                for o in win.ords.tolist():
                    docs.setdefault(o, [0.0, 0.0])[i] = win.normalized(o)
            ranked = sorted(
                ((sum(w * s for w, s in zip(self.weights, subs)) / tot, o)
                 for o, subs in docs.items()), key=lambda e: (-e[0], e[1]))
            total = len(np.union1d(m_ords, winners.ords))
            out.append({
                "timed_out": False, "_shards": {"failed": 0},
                "hits": {"total": {"value": int(total), "relation": "eq"},
                         "hits": [{"_id": f"p{o}", "_score": float(s)}
                                  for s, o in ranked[:self.size]]}})
        return out

    def judge(self, pairs: list, seen: dict = None) -> list:
        """[(query, response)] -> one message a page that differs;
        `seen` keeps what was compared (`score_rel_err_max`)."""
        if not pairs:
            return []
        bad = []
        wins = self._windows([q for q, _ in pairs])
        for (query, resp), (bm25, knn, m_ords, winners) in zip(pairs, wins):
            try:
                self._judge_one(query, resp, (bm25, knn), m_ords, winners,
                                seen)
            except oracle.Mismatch as e:
                bad.append(str(e))
            except (KeyError, TypeError, IndexError, ValueError) as e:
                bad.append(f"hybrid: malformed response "
                           f"({type(e).__name__}: {e})")
        return bad

    def _judge_one(self, query, resp, wins, m_ords, winners,
                   seen=None) -> None:
        what = f"hybrid [{query.text}]"
        oracle.check_clean(resp, what)
        bm25, knn = wins
        # hits.total: every BM25 match and the k-NN clause's k winners;
        # a winner at the clause's edge may be swapped for another there
        matches = set(m_ords.tolist())
        sure = winners.sure
        base = len(matches | sure)
        need = winners.n - len(sure)
        outside = len(winners.edge - matches)
        lo_t = base + max(0, need - len(winners.edge & matches))
        hi_t = base + min(need, outside)
        t = resp["hits"]["total"]
        oracle.require(t["relation"] == "eq" and lo_t <= t["value"] <= hi_t,
                       f"{what}: total {t}, reference {lo_t}..{hi_t}")
        cands = bm25.sure | bm25.edge | knn.sure | knn.edge
        hits = resp["hits"]["hits"]
        n_lo = max(bm25.n, knn.n, len(bm25.sure | knn.sure))
        n_hi = len(bm25.sure | knn.sure) + (bm25.n - len(bm25.sure)) \
            + (knn.n - len(knn.sure))
        oracle.require(
            min(self.size, n_lo) <= len(hits) <= min(self.size, n_hi),
            f"{what}: {len(hits)} hits, reference {min(self.size, n_lo)}"
            f"..{min(self.size, n_hi)}")
        got = [int(h["_id"][1:]) for h in hits]
        oracle.require(len(set(got)) == len(got), f"{what}: duplicate hits")
        if seen is not None:
            seen["score_rel_err_limit"] = oracle.KNN_RTOL
            seen.setdefault("score_rel_err_max", 0.0)
            seen.setdefault("hits_compared", 0)
        prev = None
        for i, (h, o) in enumerate(zip(hits, got)):
            g = h["_score"]
            oracle.require(g is not None and math.isfinite(g),
                           f"{what}: hit {i} score {g}")
            oracle.require(o in cands, f"{what}: hit {i} is doc {o}, in "
                           f"neither reference window")
            lo, hi, values, sens = self._combined(wins, o)
            if seen is not None:
                # the relative sub-score error the gap implies
                seen["hits_compared"] += 1
                seen["score_rel_err_max"] = max(
                    seen["score_rel_err_max"],
                    min(abs(g - v) for v in values) / sens)
            oracle.require(lo - 1e-12 <= g <= hi + 1e-12,
                           f"{what}: hit {i} doc {o} score {g!r}, reference "
                           f"{values[0]!r} (allowed {lo!r}..{hi!r})")
            if prev is not None:
                oracle.require(g < prev[0] or (g == prev[0] and o > prev[1]),
                               f"{what}: hit {i} out of order")
            prev = (g, o)
        if prev is None:
            return
        served = set(got)
        for o in cands - served:
            lo, _hi, values, _s = self._combined(wins, o)
            oracle.require(
                lo <= prev[0] + 1e-12,
                f"{what}: doc {o} (reference {values[0]!r}, at least "
                f"{lo!r}) is left off a page that ends at {prev[0]!r}")


def build(config: dict, seed: int, dry_run: bool) -> Corpus:
    return Corpus(config, seed, dry_run)
