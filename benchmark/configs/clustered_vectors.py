"""Builder `clustered_vectors`: a dense-vector corpus in SIFT-1M's shape
and its plain reference.

The vectors are chip_smoke.py's clustered generator (256 Gaussian
centres scaled by 4, unit noise): the source's descriptors are not in
the tree, and an exact scan does the same work on any data. One sealed
segment, `knn_vector` l2, f32. Queries come from the same mixture.

Reference: float64 brute force. ||x - q||^2 for every vector through the
float64 expansion (one blocked matmul for all judged queries), then the
exact float64 sum of squared differences for the candidates that can
reach the page; score 1 / (1 + d^2), the k-NN plugin's l2 score.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark import oracle


class Query:
    __slots__ = ("vector", "klass", "work")

    def __init__(self, vector: np.ndarray):
        self.vector = vector
        self.klass = ("knn", 1)
        self.work = {}


class Corpus:
    def __init__(self, config: dict, seed: int, dry_run: bool):
        from opensearch_tpu.index.segment import (Segment, VectorColumn,
                                                  pad_bucket)
        size = config["dry_run"] if dry_run else config
        n, dims = int(size["vectors"]), int(config["dimension"])
        self.k = int(config["k"])
        self.index = config["index"]
        rng = np.random.default_rng(seed)
        self.centers = (rng.standard_normal(
            (int(config["clusters"]), dims), dtype=np.float32)
            * np.float32(config["cluster_scale"]))
        x = rng.standard_normal((n, dims), dtype=np.float32)
        x += self.centers[rng.integers(0, len(self.centers), size=n)]
        self.x = x
        self.doc_ids = [f"v{i}" for i in range(n)]
        self.index_settings = {"number_of_shards": 1}
        self.mapping = {"properties": {"vec": {
            "type": "knn_vector", "dimension": dims,
            "method": {"space_type": config["space_type"]}}}}
        self.segments = [Segment(
            "v0", n, self.doc_ids, [None] * n, {},
            np.full((1, 128), -1, dtype=np.int32),
            np.zeros((1, 128), dtype=np.float32), {}, {}, {}, {},
            {"vec": VectorColumn(x, np.ones(n, dtype=bool))})]
        self.sizes = {"d_pad": pad_bucket(n), "dimension": dims,
                      "num_docs": n}

    # ------------------------------------------------------------ queries

    def draw(self, spec: dict, classes: list, seed: int) -> list:
        """One query a class entry; every class is the one kNN shape."""
        rng = np.random.default_rng([seed, 0x6b6e6e])
        n = len(classes)
        q = rng.standard_normal((n, self.x.shape[1]), dtype=np.float32)
        q += self.centers[rng.integers(0, len(self.centers), size=n)]
        return [Query(v) for v in q]

    def payload(self, query: Query) -> bytes:
        return json.dumps({
            "query": {"knn": {"vec": {"vector": query.vector.tolist(),
                                      "k": self.k}}},
            "size": self.k, "_source": False},
            separators=(",", ":")).encode()

    # ------------------------------------------------------------- oracle

    def judge(self, pairs: list, seen: dict = None) -> list:
        """[(query, response)] -> one message a page that differs;
        `seen` keeps what was compared (oracle.check_page)."""
        if not pairs:
            return []
        q = np.stack([p[0].vector for p in pairs]).astype(np.float64)
        n = len(self.x)
        d2 = np.empty((n, len(pairs)), dtype=np.float64)
        qq = (q * q).sum(axis=1)
        for lo in range(0, n, 131072):
            xb = self.x[lo:lo + 131072].astype(np.float64)
            d2[lo:lo + len(xb)] = ((xb * xb).sum(axis=1)[:, None]
                                   - 2.0 * (xb @ q.T) + qq[None, :])
        bad = []
        for j, (query, resp) in enumerate(pairs):
            try:
                self._judge_one(query, resp, d2[:, j], seen)
            except oracle.Mismatch as e:
                bad.append(str(e))
        return bad

    def _exact_d2(self, ords, qv):
        diff = self.x[ords].astype(np.float64) - qv
        return (diff * diff).sum(axis=1)

    def _judge_one(self, query, resp, d2_col, seen=None) -> None:
        what = "knn"
        oracle.check_clean(resp, what)
        qv = query.vector.astype(np.float64)
        m = min(4 * self.k, len(d2_col))
        cand = np.argpartition(d2_col, m - 1)[:m]
        scores = 1.0 / (1.0 + self._exact_d2(cand, qv))
        # exact kNN answers k docs a shard; the total is that count
        oracle.check_total(what, resp, min(self.k, len(d2_col)))
        oracle.check_page(
            what, resp["hits"]["hits"], cand, scores,
            lambda o: float(1.0 / (1.0 + self._exact_d2(
                np.array([o]), qv)[0])),
            lambda _id: int(_id[1:]), self.k, rtol=oracle.KNN_RTOL,
            seen=seen)


def build(config: dict, seed: int, dry_run: bool) -> Corpus:
    return Corpus(config, seed, dry_run)
