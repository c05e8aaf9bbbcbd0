"""Builder `zipf_text`: a text collection in MS MARCO passage's shape,
generated from the seed, and its plain BM25 reference.

The passages are not in the tree, so the corpus is synthetic in the
collection's shape: the full passage count, lognormal passage lengths
about the source's mean, and a Zipf vocabulary. Written after
utils/demo.build_shards_fast (same sealed layout: sorted (field, term)
keys, 128-lane blocked postings padded -1/0, SmallFloat norms, per-field
stats), with three differences that the benchmark needs:

- the materialised terms run from the head (df in the millions) down to
  terms of a few dozen postings: ranks 1..`head_ranks` whole, then two
  geometric grids of ranks (`mid_terms`, `rare_terms`) down to the end
  of the natural vocabulary. A grid term stands for the ranks between it
  and the next: it carries their unigram mass in the query model;
- each term's df is FIXED BY ITS RANK, not sampled:
  df(r) = round(N * (1 - exp(-mean_len * p(r)))), p(r) = r^-s / H(V, s).
  So the number of 128-lane blocks a term — and with it every shape
  bucket the executor compiles for — is the same for every seed. The seed
  decides which passages hold the term (one posting drawn uniformly in
  each of df equal strata of the doc-id space: sorted, distinct, O(df))
  and each posting's tf (zero-truncated Poisson at the term's rate);
- queries are drawn in CLASSES (distinct terms x the executor's QB
  bucket = pad_bucket(sum of the terms' blocks, 8)); the traffic file says
  how many of each, the seed which terms.

Reference: TextShard.match / check_page of chip_smoke.py (BM25 k1 1.2,
b 0.75, Lucene idf, SmallFloat-decoded lengths) on the host arrays the
corpus was GENERATED as, in float64; a head-term query (millions of
matches) is accumulated into a dense [N] vector.
"""

from __future__ import annotations

import json
import math
from collections import Counter

import numpy as np

from benchmark import oracle

FIELD = "body"
BLOCK = 128


def zipf_table(cfg: dict, n_docs: int) -> dict:
    """The materialised vocabulary, by arithmetic alone (no postings):
    ranks, df, blocks and unigram mass of every materialised term."""
    v = cfg["vocabulary"]
    big_v, s = int(v["natural_size"]), float(v["zipf_exponent"])
    all_ranks = np.arange(1, big_v + 1, dtype=np.float64)
    p_all = all_ranks ** -s
    p_all /= p_all.sum()
    head = int(v["head_ranks"])
    rate = float(cfg["mean_passage_len"]) * p_all
    df_all = np.maximum(np.rint(n_docs * -np.expm1(-rate)), 1)
    # where the rare band starts: the first rank whose df fits it
    rare_from = int(np.searchsorted(-df_all, -float(v["rare_max_df"]),
                                    side="left")) + 1
    mid = np.geomspace(head + 1, rare_from - 1, int(v["mid_terms"]))
    rare = np.geomspace(rare_from, big_v, int(v["rare_terms"]))
    ranks = np.unique(np.concatenate([
        np.arange(1, head + 1), np.rint(mid), np.rint(rare)])
        .astype(np.int64))
    ranks = ranks[ranks <= big_v]
    # a term carries the unigram mass of the ranks up to the next one
    cum = np.concatenate([[0.0], np.cumsum(p_all)])
    nxt = np.append(ranks[1:], big_v + 1)
    mass = cum[nxt - 1] - cum[ranks - 1]
    df = df_all[ranks - 1].astype(np.int64)
    return {"ranks": ranks, "df": df, "blocks": -(-df // BLOCK),
            "mass": mass / mass.sum(), "rate": rate[ranks - 1],
            "terms": [f"t{r:07d}" for r in ranks],
            "rare": df <= int(v["rare_max_df"])}


def qb_bucket(blocks: int) -> int:
    """The executor's lane bucket for a text clause
    (compile.py `_text_clause`: pad_bucket(len(ids), minimum=8))."""
    size = 8
    while size < blocks:
        size *= 2
    return size


def _tf_table(rate: float) -> np.ndarray:
    """256 quantiles of the zero-truncated Poisson(rate): tf by one byte."""
    k = np.arange(1, 64)
    logp = k * math.log(max(rate, 1e-12)) - np.cumsum(np.log(k))
    p = np.exp(logp - logp.max())
    cdf = np.cumsum(p / p.sum())
    return (1 + np.searchsorted(cdf, (np.arange(256) + 0.5) / 256.0)
            ).astype(np.float32)


class Query:
    __slots__ = ("text", "klass", "work")

    def __init__(self, text: str, klass, lanes: int):
        self.text = text
        self.klass = klass          # (distinct terms, QB bucket)
        self.work = {"lanes": lanes}


class Corpus:
    def __init__(self, config: dict, seed: int, dry_run: bool):
        from opensearch_tpu.index.segment import (LENGTH_TABLE, FieldStats,
                                                  Segment, TermMeta,
                                                  pad_bucket,
                                                  smallfloat_int_to_byte4)
        self.config = config
        n = int((config["dry_run"] if dry_run else config)["passages"])
        self.n = n
        self.k = int(config["k"])
        self.index = config["index"]
        self.table = tab = zipf_table(config, n)
        rng = np.random.default_rng(seed)

        # passage lengths: lognormal about the source's mean
        cv = float(config["passage_len_cv"])
        sigma = math.sqrt(math.log(1.0 + cv * cv))
        lo, hi = config["passage_len_clip"]
        lengths = np.clip(np.rint(
            float(config["mean_passage_len"])
            * rng.lognormal(-sigma * sigma / 2.0, sigma, n)), lo, hi
        ).astype(np.int64)
        sf = np.array([smallfloat_int_to_byte4(i) for i in range(hi + 1)],
                      dtype=np.uint8)
        norms = sf[lengths]

        # postings, straight into the blocked layout. One posting in each
        # of df equal strata of the doc-id space: sorted and distinct
        # without a sort. Scratch buffers are reused: at 131M postings
        # fresh arrays cost more than the arithmetic.
        starts = np.concatenate([[0], np.cumsum(tab["blocks"])])
        nb = int(starts[-1])
        post_docs = np.empty(nb * BLOCK, dtype=np.int32)
        post_tf = np.zeros(nb * BLOCK, dtype=np.float32)
        max_df = int(tab["df"].max())
        base = np.arange(max_df + 1, dtype=np.float64)
        f_buf = np.empty(max_df + 1, dtype=np.float64)
        e_buf = np.empty(max_df + 1, dtype=np.int32)
        u_buf = np.empty(max_df, dtype=np.float32)
        w_buf = np.empty(max_df, dtype=np.float32)
        # keeps u * width under width after f32 rounding
        below_one = np.float32(0.9999995)
        term_dict = {}
        sum_df = 0
        for i, term in enumerate(tab["terms"]):
            df = int(tab["df"][i])
            edges, u, w = e_buf[:df + 1], u_buf[:df], w_buf[:df]
            np.multiply(base[:df + 1], n / df, out=f_buf[:df + 1])
            edges[:] = f_buf[:df + 1]       # truncation: floor, all >= 0
            edges[df] = n
            rng.random(out=u, dtype=np.float32)
            np.subtract(edges[1:], edges[:-1], out=w)
            np.multiply(w, below_one, out=w)
            np.multiply(w, u, out=w)
            at = int(starts[i]) * BLOCK
            ords = post_docs[at:at + df]
            ords[:] = w
            ords += edges[:-1]
            post_docs[at + df:int(starts[i + 1]) * BLOCK] = -1
            tf = _tf_table(float(tab["rate"][i]))[
                rng.integers(0, 256, size=df, dtype=np.uint8)]
            post_tf[at:at + df] = tf
            term_dict[(FIELD, term)] = TermMeta(
                doc_freq=df, total_term_freq=int(tf.sum()),
                start_block=int(starts[i]), num_blocks=int(tab["blocks"][i]))
            sum_df += df
        self.post_docs = post_docs.reshape(nb, BLOCK)
        self.post_tf = post_tf.reshape(nb, BLOCK)
        self.starts = starts
        self.term_index = {t: i for i, t in enumerate(tab["terms"])}
        sum_ttf = int(lengths.sum())
        self.doc_ids = [f"d{i}" for i in range(n)]
        self.segments = [Segment(
            "s0", n, self.doc_ids, [None] * n, term_dict, self.post_docs,
            self.post_tf, {FIELD: norms},
            {FIELD: FieldStats(doc_count=n, sum_total_term_freq=sum_ttf,
                               sum_doc_freq=sum_df)}, {}, {}, {})]
        self.index_settings = {"number_of_shards": 1}
        self.mapping = {"properties": {FIELD: {"type": "text"}}}
        self.sizes = {"d_pad": pad_bucket(n), "num_docs": n,
                      "posting_blocks": nb}
        # the reference's view of the same arrays
        self.k1 = float(config["bm25"]["k1"])
        b = float(config["bm25"]["b"])
        # k1 * (1 - b + b * dl / avgdl) of every passage, once
        self.doc_norm = self.k1 * (1.0 - b + b * LENGTH_TABLE[norms].astype(
            np.float64) / (sum_ttf / n))

    # ------------------------------------------------------------ queries

    def draw(self, spec: dict, classes: list, seed: int) -> list:
        """One query for each entry of `classes`, in order. A class is
        {"terms": n, "qb": bucket}; `spec["model"]` says how terms are
        drawn: "unigram" (by corpus mass, over every materialised term)
        or "rare" (uniform over the rare band). No query repeats."""
        return draw_queries(self.table, spec, classes, seed)

    def payload(self, query: Query) -> bytes:
        return json.dumps({"query": {"match": {FIELD: query.text}},
                           "size": self.k, "_source": False},
                          separators=(",", ":")).encode()

    # ------------------------------------------------------------- oracle

    def postings(self, term: str):
        i = self.term_index[term]
        at, df = int(self.starts[i]), int(self.table["df"][i])
        return (self.post_docs.reshape(-1)[at * BLOCK:at * BLOCK + df],
                self.post_tf.reshape(-1)[at * BLOCK:at * BLOCK + df])

    def match(self, text: str):
        """`match` (operator OR) -> (ords, f64 scores) of every matching
        passage, ords ascending."""
        docs_l, s_l = [], []
        for term, mult in Counter(text.split()).items():
            docs, tf = self.postings(term)
            tf = tf.astype(np.float64)
            df = len(docs)
            idf = math.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
            docs_l.append(docs)
            s_l.append(mult * idf * tf * (self.k1 + 1.0)
                       / (tf + self.doc_norm[docs]))
        if sum(len(d) for d in docs_l) < self.n // 8:
            uniq, inv = np.unique(np.concatenate(docs_l),
                                  return_inverse=True)
            return uniq, np.bincount(inv, weights=np.concatenate(s_l))
        # head terms: millions of postings, summed term by term into a
        # dense vector; every BM25 partial is > 0, so the matches are the
        # non-zero lanes
        dense = np.zeros(self.n, dtype=np.float64)
        for docs, part in zip(docs_l, s_l):
            dense += np.bincount(docs, weights=part, minlength=self.n)
        ords = np.flatnonzero(dense)
        return ords, dense[ords]

    def judge(self, pairs: list, seen: dict = None) -> list:
        bad = []
        for query, resp in pairs:
            what = f"match [{query.text}]"
            try:
                oracle.check_clean(resp, what)
                ords, scores = self.match(query.text)

                def score_of(o, ords=ords, scores=scores):
                    i = int(np.searchsorted(ords, o))
                    return float(scores[i]) \
                        if i < len(ords) and ords[i] == o else None
                oracle.check_total(what, resp, len(ords))
                oracle.check_page(what, resp["hits"]["hits"], ords, scores,
                                  score_of, lambda _id: int(_id[1:]),
                                  self.k, seen=seen)
            except oracle.Mismatch as e:
                bad.append(str(e))
        return bad


def draw_queries(tab: dict, spec: dict, classes: list, seed: int) -> list:
    """See Corpus.draw. Host arithmetic on the block table only, so the
    tests can run it at full size without building a posting."""
    rng = np.random.default_rng([seed, 0x71756572])
    blocks = tab["blocks"]
    if spec["model"] == "rare":
        pool = np.flatnonzero(tab["rare"])
        weights = None
    else:
        pool = np.arange(len(blocks))
        weights = tab["mass"]
    want = Counter((int(c["terms"]), int(c["qb"])) for c in classes)
    got = {k: [] for k in want}
    seen = set()
    for (n_terms, qb), count in sorted(want.items()):
        tries = 0
        while len(got[(n_terms, qb)]) < count:
            tries += 1
            if tries > 200:
                raise RuntimeError(
                    f"class terms={n_terms} qb={qb} is too rare under "
                    f"model [{spec['model']}]: the traffic file is wrong")
            m = max(4 * count, 4096)
            rows = pool[rng.choice(len(pool), size=(m, n_terms), p=weights)]
            srt = np.sort(rows, axis=1)
            ok = np.all(srt[:, 1:] != srt[:, :-1], axis=1)
            total = blocks[rows].sum(axis=1)
            lo = qb // 2 if qb > 8 else 0
            ok &= (total > lo) & (total <= qb)
            for row, tot in zip(rows[ok].tolist(), total[ok].tolist()):
                key = tuple(sorted(row))
                if key in seen:
                    continue
                seen.add(key)
                got[(n_terms, qb)].append((row, tot))
                if len(got[(n_terms, qb)]) >= count:
                    break
    out, used = [], Counter()
    for c in classes:
        k = (int(c["terms"]), int(c["qb"]))
        row, tot = got[k][used[k]]
        used[k] += 1
        out.append(Query(" ".join(tab["terms"][i] for i in row), k,
                         int(tot) * BLOCK))
    return out


def build(config: dict, seed: int, dry_run: bool) -> Corpus:
    return Corpus(config, seed, dry_run)
