"""Deterministic synthetic corpora for the graft entry and tests.

Generates an msmarco-passage-shaped workload (zipfian vocabulary, ~60-token
passages) without shipping data: the reference's macro benchmarks point at
external corpora (client/benchmark/README.md:25) that are unavailable here,
so the bench harness synthesizes an equivalent distribution with a fixed
seed — same shape, reproducible numbers.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import Segment, SegmentBuilder

DEMO_MAPPING = {
    "properties": {
        "body": {"type": "text"},
        "tag": {"type": "keyword"},
        "views": {"type": "integer"},
        "ts": {"type": "date"},
    }
}


def _vocab(size: int) -> List[str]:
    return [f"w{i:05d}" for i in range(size)]


def synth_docs(n_docs: int, vocab_size: int = 5000, avg_len: int = 60,
               seed: int = 42) -> List[dict]:
    """Zipf-distributed token stream chunked into passages + structured fields."""
    rng = np.random.default_rng(seed)
    vocab = np.array(_vocab(vocab_size))
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    lens = np.maximum(8, rng.poisson(avg_len, n_docs))
    tags = [f"cat{i}" for i in range(16)]
    docs = []
    base_ts = 1700000000000  # 2023-11-14T22:13:20Z
    for i in range(n_docs):
        toks = rng.choice(vocab, size=int(lens[i]), p=probs)
        docs.append({
            "body": " ".join(toks.tolist()),
            "tag": tags[int(rng.integers(0, len(tags)))],
            "views": int(rng.integers(0, 10000)),
            "ts": int(base_ts + rng.integers(0, 90 * 86400_000)),
        })
    return docs


def build_shards(n_docs: int, n_shards: int = 1, vocab_size: int = 5000,
                 avg_len: int = 60, seed: int = 42,
                 mapper: Optional[MapperService] = None,
                 ) -> Tuple[MapperService, List[Segment]]:
    """Route synthetic docs round-robin into n_shards sealed segments."""
    mapper = mapper or MapperService(DEMO_MAPPING)
    docs = synth_docs(n_docs, vocab_size, avg_len, seed)
    builders = [SegmentBuilder(mapper, f"s{i}") for i in range(n_shards)]
    for i, d in enumerate(docs):
        b = builders[i % n_shards]
        b.add(mapper.parse_document(f"d{i}", d))
    return mapper, [b.seal() for b in builders]


def query_terms(n_queries: int, vocab_size: int = 5000, seed: int = 7,
                terms_per_query: int = 2) -> List[str]:
    """Query strings drawn from the mid-frequency band of the zipf vocab
    (head terms match ~everything, tail terms match ~nothing)."""
    rng = np.random.default_rng(seed)
    lo, hi = vocab_size // 50, vocab_size // 2
    out = []
    for _ in range(n_queries):
        ids = rng.integers(lo, hi, size=terms_per_query)
        out.append(" ".join(f"w{i:05d}" for i in ids))
    return out


# --------------------------------------------- vectorized scale builder ----

# SmallFloat encode table for vectorized norm quantization (lengths are
# bounded by the builder's clip below, so a fixed-size table suffices)
_SF_MAX_LEN = 1 << 16


def _sf_table() -> np.ndarray:
    global _SF_ENC
    try:
        return _SF_ENC
    except NameError:
        from opensearch_tpu.index.segment import smallfloat_int_to_byte4
        _SF_ENC = np.array([smallfloat_int_to_byte4(i)
                            for i in range(_SF_MAX_LEN)], dtype=np.uint8)
        return _SF_ENC


def build_shards_fast(n_docs: int, n_shards: int = 1,
                      vocab_size: int = 20000, avg_len: int = 60,
                      seed: int = 42, materialize_terms: int = 128,
                      burst_tf: float = 0.0,
                      burst_window: int = 0,
                      burst_regions: int = 1,
                      doc_len_cv: float = 0.0,
                      columns: bool = False,
                      mapper: Optional[MapperService] = None,
                      ) -> Tuple[MapperService, List["Segment"], List[str]]:
    """Sealed segments at 10M-doc scale without the per-doc parse loop.

    `build_shards` routes every token through the mapper/SegmentBuilder
    path — minutes at 1M docs, hours at 10M. This builder emits the SAME
    sealed layout (sorted (field, term) keys, 128-lane blocked CSR padded
    -1/0, SmallFloat norms, per-field stats) directly from vectorized
    per-term sampling, materializing postings only for `materialize_terms`
    mid-band zipf terms (the band `query_terms` draws from); every other
    term exists only virtually, through the doc-length norms and avgdl.
    Queries against a fast corpus must draw from the returned term list
    (`fast_query_terms`).

    Burstiness knobs (the block-max bench's prunable arm): each
    materialized term gets one CONTIGUOUS doc-ord window per shard of
    `burst_window` docs whose tf is raised by ~`burst_tf`, placed in one
    of `burst_regions` shared region anchors (term rank mod regions).
    The window must stay SMALL next to the terms' natural df — it is the
    hot cluster (2-3 posting blocks); if it dominates df, every block is
    a burst block and the bound distribution goes flat. Clustering in
    doc-id space is the point — bursty postings spread uniformly over
    doc ids put a high-tf lane in every 128-lane block, and nothing
    prunes. SHARED regions matter just as much: a
    multi-term query only develops a competitive threshold above the
    common-block bounds when some docs score high on ALL its terms, which
    is what co-located bursts (topically dense long docs — the shape real
    corpora cluster by crawl/time locality) produce. `doc_len_cv` adds
    lognormal doc-length variance on top of the Poisson baseline.

    `columns=True` also gives every doc `synth_docs`' structured fields —
    `tag` (keyword: postings + ordinals), `views` and `ts` (numeric doc
    values) — drawn after the postings, so the text side of a corpus is
    the same with and without them. Without it the corpus has no
    doc-values columns at all.

    Returns (mapper, segments, terms) with docs round-robined over shards
    (global _id "d{ord}" matches build_shards' layout).
    """
    from opensearch_tpu.index.segment import (DocValuesColumn, FieldStats,
                                              OrdinalsColumn, Segment,
                                              TermMeta, _hash64, _pad_to)
    mapper = mapper or MapperService(DEMO_MAPPING)
    ranks_all = np.arange(1, vocab_size + 1, dtype=np.float64)
    h_v = float(np.sum(1.0 / ranks_all))
    lo, hi = max(vocab_size // 50, 1), max(vocab_size // 2, 2)
    m = min(materialize_terms, hi - lo)
    term_ranks = np.unique(np.linspace(lo, hi - 1, m).astype(np.int64))
    terms = [f"w{r:05d}" for r in term_ranks]
    sf = _sf_table()

    segments: List[Segment] = []
    for s in range(n_shards):
        rng = np.random.default_rng(seed + 1000 * s)
        n = n_docs // n_shards + (1 if s < n_docs % n_shards else 0)
        lengths = np.maximum(8, rng.poisson(avg_len, n)).astype(np.int64)
        if doc_len_cv > 0:
            sigma = float(np.sqrt(np.log(1.0 + doc_len_cv ** 2)))
            mult = rng.lognormal(-sigma * sigma / 2.0, sigma, n)
            lengths = np.maximum(8, (lengths * mult).astype(np.int64))
        wlen = min(int(burst_window), n) if burst_tf > 0 else 0

        term_dict = {}
        rows_docs: List[np.ndarray] = []
        rows_tf: List[np.ndarray] = []
        next_block = 0
        sum_df = 0

        def emit(field, term, ords, tf):
            """Append one term's postings as 128-lane blocks (-1/0
            padded), in seal()'s layout."""
            nonlocal next_block
            padded = _pad_to(ords.size, 128)
            docs_p = np.full(padded, -1, dtype=np.int32)
            tfs_p = np.zeros(padded, dtype=np.float32)
            docs_p[:ords.size] = ords
            tfs_p[:ords.size] = tf
            nb = padded // 128
            rows_docs.append(docs_p.reshape(nb, 128))
            rows_tf.append(tfs_p.reshape(nb, 128))
            term_dict[(field, term)] = TermMeta(
                doc_freq=int(ords.size), total_term_freq=int(tf.sum()),
                start_block=next_block, num_blocks=nb)
            next_block += nb

        # seal() sorts (field, term); zero-padded w-terms sort by rank
        for rank, term in zip(term_ranks, terms):
            p = (1.0 / float(rank)) / h_v
            lam = avg_len * p
            keep = rng.random(n) < (1.0 - np.exp(-lam))
            if wlen:
                region = int(rank) % max(burst_regions, 1)
                w0 = int((region * 2654435761) % max(n - wlen, 1))
                keep[w0:w0 + wlen] = True
            ords = np.nonzero(keep)[0].astype(np.int32)
            tf = (1.0 + rng.poisson(lam, ords.size)).astype(np.float32)
            if wlen:
                in_w = (ords >= w0) & (ords < w0 + wlen)
                # high-IMPACT postings: tf raised while the doc keeps its
                # baseline length (tag/title-style term repetition). If
                # the burst tokens also lengthened the doc, BM25's length
                # normalization would cancel the burst (g = tf/(tf+k1·c)
                # with c growing ∝ tf) and the block bounds would stay
                # flat — no impact skew, nothing for phase A to separate
                tf = np.where(
                    in_w, tf + rng.poisson(burst_tf, ords.size), tf)
            if ords.size == 0:
                continue
            emit("body", term, ords, tf)
            sum_df += int(ords.size)

        lengths = np.minimum(lengths, _SF_MAX_LEN - 1)
        norms = {"body": sf[lengths]}
        stats = {"body": FieldStats(
            doc_count=n, sum_total_term_freq=int(lengths.sum()),
            sum_doc_freq=sum_df)}
        numeric_dv, ordinal_dv = {}, {}
        if columns:
            tags = sorted(f"cat{i}" for i in range(16))
            tag_ord = rng.integers(0, len(tags), n).astype(np.int32)
            every = np.arange(n, dtype=np.int32)
            for t_i, tag in enumerate(tags):    # sorted: ("tag", t) keys
                ords = np.nonzero(tag_ord == t_i)[0].astype(np.int32)
                if ords.size:
                    emit("tag", tag, ords, np.ones(ords.size, np.float32))
            stats["tag"] = FieldStats(doc_count=n, sum_total_term_freq=n,
                                      sum_doc_freq=n)
            ordinal_dv["tag"] = OrdinalsColumn(
                every, tag_ord, np.ones(n, dtype=bool), tags,
                np.array([_hash64(t) for t in tags], dtype=np.uint64))
            for field, values in (
                    ("views", rng.integers(0, 10000, n)),
                    ("ts", 1700000000000
                     + rng.integers(0, 90 * 86400_000, n))):
                unique, value_ords = np.unique(
                    values.astype(np.float64), return_inverse=True)
                numeric_dv[field] = DocValuesColumn(
                    every, values.astype(np.float64),
                    np.ones(n, dtype=bool), np.ones(n, dtype=np.int32),
                    value_ords.astype(np.int32), unique)
        post_docs = np.concatenate(rows_docs, axis=0) if rows_docs \
            else np.full((1, 128), -1, dtype=np.int32)
        post_tf = np.concatenate(rows_tf, axis=0) if rows_tf \
            else np.zeros((1, 128), dtype=np.float32)
        doc_ids = [f"d{s + i * n_shards}" for i in range(n)]
        segments.append(Segment(
            f"s{s}", n, doc_ids, [None] * n, term_dict,
            post_docs, post_tf, norms, stats, numeric_dv, ordinal_dv, {}))
    return mapper, segments, terms


def fast_query_terms(n_queries: int, terms: List[str], seed: int = 7,
                     terms_per_query: int = 2) -> List[str]:
    """Query strings over a fast corpus's MATERIALIZED terms only."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_queries):
        ids = rng.integers(0, len(terms), size=terms_per_query)
        out.append(" ".join(terms[i] for i in ids))
    return out
