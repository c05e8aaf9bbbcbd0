"""Builder `dense_vectors`: one shard of a dense-vector corpus in the
shape of OpenSearch Benchmark's `vectorsearch` workload (corpus
`cohere-10m`: 768-d float32, space `innerproduct`, `query_k` 100), and
its plain reference.

The source's embeddings (Cohere's wikipedia-22-12) are not in the tree,
so the vectors are a Gaussian mixture made from the seed: `clusters`
centres of `centre_scale` a component, one drawn for every vector, plus
unit noise a component (the configuration's `assumed`). An
exact scan does the same work on any data; what the mixture keeps is
that a query has near neighbours (the vectors of its own centre) whose
scores lie close together, so the page's order is decided by the sixth
digit and not the first. Vectors are made in blocks of `GEN_BLOCK` rows,
each from a generator of its own (`[seed, tag, block]`) so that threads
fill them side by side; ids are a `PrefixedIds`, not a list of strings.
One sealed segment, one `knn_vector` field, nothing stored.

Traffic. One query class: `knn` with k and `size` the configuration's
`k`, `_source` false. Every query vector is a fresh draw from the same
mixture (a centre plus noise), warm-up and window from one sequence, so
no request repeats another; the source's 10,000-query set is its size,
not a cap.

Reference (`reference_scores`, `exact_inner_products`, `plugin_score`):
numpy, float64, nothing of `opensearch_tpu`. The inner product of every
vector with every judged query in blocks of `REF_BLOCK` rows (one
float64 matmul a block), then, for the candidates that can reach the
page, the float64 sum of the elementwise products; score by the k-NN
plugin's `innerproduct` rule: `ip + 1` where `ip >= 0`, else
`1 / (1 - ip)`. Exact k-NN answers k documents a shard, so `hits.total`
is min(k, vectors). `Corpus.judge` holds a served page to that through
`oracle.check_page` (ids in order, ties by lowest doc, every score
within `oracle.KNN_RTOL`).
"""

from __future__ import annotations

import concurrent.futures
import json
import os

import numpy as np

from benchmark import oracle

GEN_BLOCK = 32768       # rows a generator fills (one thread's piece)
REF_BLOCK = 131072      # rows a float64 matmul of the reference reads
VEC_TAG, QUERY_TAG = 0x76656373, 0x6b6e6e


# ---------------------------------------------------------------- reference

def plugin_score(ip: np.ndarray) -> np.ndarray:
    """The k-NN plugin's score of an inner product, both branches."""
    ip = np.asarray(ip, dtype=np.float64)
    return np.where(ip >= 0, ip + 1.0, 1.0 / (1.0 - np.minimum(ip, 0.0)))


def reference_scores(x: np.ndarray, q: np.ndarray,
                     block: int = REF_BLOCK) -> np.ndarray:
    """Inner products [vectors, queries] in float64, `block` rows of `x`
    (float32 [n, dims]) at a time against `q` (float64 [m, dims])."""
    out = np.empty((len(x), len(q)), dtype=np.float64)
    qt = np.ascontiguousarray(q.T)
    for lo in range(0, len(x), block):
        xb = x[lo:lo + block].astype(np.float64)
        out[lo:lo + len(xb)] = xb @ qt
    return out


def exact_inner_products(x: np.ndarray, ords: np.ndarray,
                         qv: np.ndarray) -> np.ndarray:
    """The float64 sum of products of the rows `ords` with one query."""
    return (x[ords].astype(np.float64) * qv).sum(axis=1)


# ------------------------------------------------------------------- corpus

class Query:
    __slots__ = ("vector", "klass", "work")

    def __init__(self, vector: np.ndarray):
        self.vector = vector
        self.klass = ("knn", 1)
        self.work = {}


def mixture_rows(seed: int, block: int, centers: np.ndarray,
                 out: np.ndarray) -> None:
    """Fill `out` ([rows, dims] float32) with block `block` of the
    seed's vectors: a centre each, plus unit noise."""
    rng = np.random.default_rng([seed, VEC_TAG, block])
    rng.standard_normal(out.shape, dtype=np.float32, out=out)
    out += centers[rng.integers(0, len(centers), size=len(out))]


class Corpus:
    def __init__(self, config: dict, seed: int, dry_run: bool):
        from opensearch_tpu.index.segment import (PrefixedIds, Segment,
                                                  VectorColumn, pad_bucket)
        size = {**config, **(config["dry_run"] if dry_run else {})}
        n, dims = int(size["vectors"]), int(config["dimension"])
        if config["space_type"] != "innerproduct":
            raise ValueError("this builder's reference scores innerproduct")
        self.k = int(config["k"])
        self.index, self.field = config["index"], config["field"]
        mix = config["mixture"]
        self.centers = np.random.default_rng([seed, VEC_TAG]) \
            .standard_normal((int(mix["clusters"]), dims), dtype=np.float32)
        self.centers *= np.float32(mix["centre_scale"])
        x = np.empty((n, dims), dtype=np.float32)

        def fill(b: int) -> None:
            mixture_rows(seed, b, self.centers,
                         x[b * GEN_BLOCK:(b + 1) * GEN_BLOCK])
        blocks = range(-(-n // GEN_BLOCK))
        with concurrent.futures.ThreadPoolExecutor(
                min(len(blocks), os.cpu_count() or 1)) as pool:
            list(pool.map(fill, blocks))
        self.x = x
        self.index_settings = {"number_of_shards": 1,
                               "number_of_replicas": 0}
        self.mapping = {"properties": {self.field: {
            "type": "knn_vector", "dimension": dims,
            "method": {"space_type": config["space_type"]}}}}
        self.segments = [Segment(
            "v0", n, PrefixedIds("v", n), [None] * n, {},
            np.full((1, 128), -1, dtype=np.int32),
            np.zeros((1, 128), dtype=np.float32), {}, {}, {}, {},
            {self.field: VectorColumn(x, np.ones(n, dtype=bool))})]
        self.sizes = {"d_pad": pad_bucket(n), "dimension": dims,
                      "num_docs": n}

    # ------------------------------------------------------------ queries

    def draw(self, spec: dict, classes: list, seed: int) -> list:
        """One query a class entry, each a fresh draw; every class is
        the one k-NN shape."""
        rng = np.random.default_rng([seed, QUERY_TAG])
        n = len(classes)
        q = rng.standard_normal((n, self.x.shape[1]), dtype=np.float32)
        q += self.centers[rng.integers(0, len(self.centers), size=n)]
        return [Query(v) for v in q]

    def payload(self, query: Query) -> bytes:
        return json.dumps({
            "size": self.k,
            "query": {"knn": {self.field: {"vector": query.vector.tolist(),
                                           "k": self.k}}},
            "_source": False}, separators=(",", ":")).encode()

    # ------------------------------------------------------------- oracle

    def _candidates(self, ip_col: np.ndarray, qv: np.ndarray):
        """(ordinals, float64 scores) of every vector that can reach the
        page: the 4k best of the blocked product, scored again from the
        exact sum of products."""
        m = min(4 * self.k, len(ip_col))
        cand = np.argpartition(ip_col, len(ip_col) - m)[len(ip_col) - m:]
        return cand, plugin_score(exact_inner_products(self.x, cand, qv))

    def reference_responses(self, queries: list,
                            scores_as=lambda s: s) -> list:
        """The responses the plain reference itself would serve, its
        scores in the precision `scores_as` leaves them in (the control
        of `correct`: `oracle.lower_precision`); one blocked product
        for all of them."""
        q = np.stack([query.vector for query in queries]).astype(np.float64)
        ip = reference_scores(self.x, q)
        out = []
        for j in range(len(queries)):
            cand, scores = self._candidates(ip[:, j], q[j])
            top, top_scores = oracle.top_ords(scores_as(scores), cand,
                                              self.k)
            out.append({
                "timed_out": False, "_shards": {"failed": 0},
                "hits": {"total": {"value": min(self.k, len(self.x)),
                                   "relation": "eq"},
                         "hits": [{"_id": f"v{int(o)}", "_score": float(s)}
                                  for o, s in zip(top, top_scores)]}})
        return out

    def judge(self, pairs: list, seen: dict = None) -> list:
        """[(query, response)] -> one message a page that differs;
        `seen` keeps what was compared (oracle.check_page)."""
        if not pairs:
            return []
        q = np.stack([p[0].vector for p in pairs]).astype(np.float64)
        ip = reference_scores(self.x, q)
        bad = []
        for j, (query, resp) in enumerate(pairs):
            try:
                self._judge_one(resp, ip[:, j], q[j], seen)
            except oracle.Mismatch as e:
                bad.append(str(e))
            except (KeyError, TypeError, IndexError, ValueError) as e:
                bad.append(f"knn: malformed response "
                           f"({type(e).__name__}: {e})")
        return bad

    def _judge_one(self, resp, ip_col, qv, seen=None) -> None:
        what = "knn"
        oracle.check_clean(resp, what)
        cand, scores = self._candidates(ip_col, qv)
        # exact k-NN answers k docs a shard; the total is that count
        oracle.check_total(what, resp, min(self.k, len(ip_col)))
        oracle.check_page(
            what, resp["hits"]["hits"], cand, scores,
            lambda o: float(plugin_score(exact_inner_products(
                self.x, np.array([o]), qv))[0]),
            lambda _id: int(_id[1:]), self.k, rtol=oracle.KNN_RTOL,
            seen=seen)


def build(config: dict, seed: int, dry_run: bool) -> Corpus:
    return Corpus(config, seed, dry_run)
