"""BM25 scoring kernels — the TPU replacement for Lucene's BulkScorer hot loop.

Reference hot loop: search/internal/ContextIndexSearcher.java:260 →
Lucene Weight.bulkScorer → BM25 per posting, one doc at a time. Here the same
math runs data-parallel: a query clause gathers its terms' 128-wide postings
blocks from the resident `[NB, 128]` matrices, computes BM25 partials for all
lanes at once on the VPU, and scatter-adds into a dense per-doc score vector.
Conjunction semantics (`operator: and`, `minimum_should_match` >= 2) fall out
of a second, parallel hit-count scatter (each (term, doc) pair appears exactly
once in postings, so the hit count per doc equals the number of distinct
clause terms that matched). That scatter costs what the score scatter costs,
so it is built only where the count is read: a clause whose matches are "any
term touched the doc" (the default `match`, `min_hits` 1) and whose every
partial is provably a positive NORMAL float32 takes its matches from the
score vector (`scores > 0`) and scatters once. The planner decides that
statically (search/compile.py `text_clause_score_only`); see
`score_text_clause` for the bound.

Score parity: idf = ln(1 + (docCount - df + 0.5)/(df + 0.5)) per
LegacyBM25Similarity (reference: index/similarity/SimilarityService.java:85 —
OpenSearch's default keeps the (k1+1) numerator factor), doc length decoded
from the SmallFloat norm byte that rides beside each posting's tf
(`posting_lengths`: the values of Lucene's 256-entry LENGTH_TABLE, computed,
not looked up), and avgdl = sumTotalTermFreq / docCount, all matching Lucene
to float precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from opensearch_tpu.telemetry.kernels import stage

# Block-max pruning (ISSUE 20, ROADMAP item 4): skip posting blocks whose
# seal-time score upper bound cannot reach the query's competitive top-k
# threshold — the BMW/BM25S family of impact-bounded skipping, rank-exact
# by construction. OFF by default; flipped by the dynamic node setting
# `search.blockmax.enabled` (see node.py), never flip inline in library code.
BLOCKMAX = False

# Phase A derives the competitive threshold from an exactly-scored slice of
# the highest-bound blocks: top SLICE_BLOCKS blocks by upper bound are fully
# scored (gather + sort + windowed run-sum), and the k-th best eligible doc
# score in that slice lower-bounds the true k-th best — every block whose
# upper bound falls below it is provably beaten.
BLOCKMAX_SLICE_BLOCKS = 8
# Clauses touching fewer blocks than this skip phase A entirely (static,
# host-side admission): the slice would cover most of the postings anyway.
BLOCKMAX_MIN_BLOCKS = 16

_NEG_INF = jnp.float32(-jnp.inf)
# min_score above this sentinel means the caller set a real floor (or this is
# an SPMD padding row with +inf) — pruning is disabled for those rows.
_MIN_SCORE_OFF = -1e30


def idf(doc_count: int, doc_freq: int) -> float:
    """Lucene BM25Similarity.idfExplain."""
    return math.log(1.0 + (doc_count - doc_freq + 0.5) / (doc_freq + 0.5))


def posting_lengths(seg, block_ids):
    """Doc length of every posting in the gathered blocks, f32 [..., 128].

    seg["post_norm"] holds each posting's SmallFloat norm byte lane for lane
    with post_docs/post_tf, so this is one more row gather with the ids the
    caller already gathers docs and tfs with — no per-lane gather from a
    [d_pad] norms row, no table lookup. The decode is
    index/segment.py `smallfloat_byte4_to_int` exactly: with bits = n & 7 and
    e = n >> 3, the length is bits where e == 0, else (bits | 8) << (e - 1)
    = 1.bbb × 2^(e+2). The top bytes overflow int32 and exp2 is not promised
    exact, so the f32 is assembled from its fields: exponent e + 129,
    mantissa bits << 20 — a normal number for every e >= 1.
    """
    n = seg["post_norm"][block_ids].astype(jnp.int32)
    bits = n & 7
    e = n >> 3
    scaled = jax.lax.bitcast_convert_type(
        ((e + 129) << 23) | (bits << 20), jnp.float32)
    return jnp.where(e == 0, bits.astype(jnp.float32), scaled)


def blockmax_keep_mask(seg, blk, k1, n_terms, k, min_score=None):
    """Phase A of the two-phase block-max kernel: per-block keep mask
    (the `blockmax_mask` stage of a device trace).

    seg must carry the seal-time `post_bound` leaf (f32 [NBp]: per-block
    max(tf/(tf+k1_seal*norm))). blk carries, beyond score_text_clause's
    inputs, `tid` (int32 [QB] query-term index per lane) and `bscale`
    (f32 scalar: host-computed ceiling on g_query/g_seal over the doc
    lengths occurring in the segment, so sealed bounds stay upper bounds
    under the query's own k1/b/avgdl).

    n_terms, k are STATIC python ints (clause term count, top-k depth);
    callers must statically skip phase A when k > SLICE_BLOCKS*128 or the
    clause has fewer than BLOCKMAX_MIN_BLOCKS lanes.

    Rank-exactness: ub(block X of term t) = self_ub(X) + sum_{t'!=t} tmax(t')
    where self_ub = max(w,0)*(k1+1)*bscale*bound upper-bounds the term's
    partial for any doc in X and tmax(t') that of any other term, so any
    doc's full score is <= the ub of EVERY block holding one of its
    postings. theta is the k-th best exact score of an eligible-doc subset,
    hence <= the true k-th best; `keep = ub >= theta` therefore never drops
    a block containing a top-k doc, and boundary ties survive strictness.

    Returns (keep bool [QB], pruned int32 scalar — real lanes masked off).
    """
    with stage("blockmax_mask"):
        return _blockmax_keep_mask(seg, blk, k1, n_terms, k, min_score)


def _blockmax_keep_mask(seg, blk, k1, n_terms, k, min_score):
    lane_real = blk["ids"] >= 0                            # [QB]
    safe_ids = jnp.where(lane_real, blk["ids"], 0)
    safe_tid = jnp.where(lane_real, blk["tid"], 0)
    w_pos = jnp.maximum(blk["w"], 0.0)
    self_ub = (w_pos * (k1 + 1.0) * blk["bscale"]
               * seg["post_bound"][safe_ids])
    self_ub = jnp.where(lane_real, self_ub, 0.0)           # [QB]
    # per-term best bound (static loop: n_terms is a compile-time fact)
    tmax = jnp.stack([
        jnp.max(jnp.where(lane_real & (blk["tid"] == t), self_ub, 0.0))
        for t in range(n_terms)])                          # [T]
    ub = self_ub + (jnp.sum(tmax) - tmax[safe_tid])        # [QB]

    # --- exact-score the top-bound slice to derive theta ---
    n_slice = min(BLOCKMAX_SLICE_BLOCKS, ub.shape[0])
    _, sidx = jax.lax.top_k(jnp.where(lane_real, ub, _NEG_INF), n_slice)
    s_real = lane_real[sidx]                               # [S]
    docs = seg["post_docs"][safe_ids[sidx]]                # [S, 128]
    tfs = seg["post_tf"][safe_ids[sidx]]
    dl = posting_lengths(seg, safe_ids[sidx])
    valid = (docs >= 0) & s_real[:, None]
    safe_docs = jnp.where(valid, docs, 0)
    b = blk["b"]
    denom = tfs + k1 * (1.0 - b + b * dl / blk["avgdl"])
    partial = blk["w"][sidx][:, None] * tfs * (k1 + 1.0) / denom
    # theta must come from truly-eligible docs only: deleted/nested docs
    # could otherwise inflate it past the real k-th best (unsafe)
    elig0 = valid & seg["live"][safe_docs] & seg["root"][safe_docs]
    sentinel = jnp.int32(2 ** 31 - 1)
    flat_docs = jnp.where(elig0, docs, sentinel).ravel()   # [S*128]
    flat_p = jnp.where(elig0, partial, 0.0).ravel()
    flat_h = jnp.where(elig0, 1, 0).astype(jnp.int32).ravel()
    sdocs, sp, sh = jax.lax.sort((flat_docs, flat_p, flat_h), num_keys=1)
    # per-doc windowed run-sum: a doc appears at most once per term
    tot, hits = sp, sh
    for j in range(1, n_terms):
        same = jnp.concatenate(
            [sdocs[j:] == sdocs[:-j], jnp.zeros(j, jnp.bool_)])
        tot = tot + jnp.where(
            same, jnp.concatenate([sp[j:], jnp.zeros(j, jnp.float32)]), 0.0)
        hits = hits + jnp.where(
            same, jnp.concatenate([sh[j:], jnp.zeros(j, jnp.int32)]), 0)
    head = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), sdocs[1:] != sdocs[:-1]])
    elig = head & (sdocs < sentinel) & (hits >= blk["min_hits"])
    cand = jnp.where(elig, tot, _NEG_INF)
    theta = jax.lax.top_k(cand, min(k, cand.shape[0]))[0][-1]
    # fewer than k eligible slice docs -> -inf padding -> no pruning; rows
    # with a caller-set score floor (incl. SPMD +inf padding rows) never prune
    if min_score is not None:
        theta = jnp.where(min_score > _MIN_SCORE_OFF, _NEG_INF, theta)
    keep = ub >= theta
    pruned = jnp.sum((lane_real & ~keep).astype(jnp.int32))
    return keep, pruned


def score_text_clause(seg, blk, k1, block_keep=None, score_only=False):
    """Score one text clause (match / term / terms over one field family).

    seg: device segment dict (post_docs, post_tf, post_norm, live).
    blk: per-block gathered inputs:
      - ids:    int32 [QB] block row indices into post_docs/post_tf/post_norm
                (power-of-two bucketed; -1 = padding lane)
      - w:      float32 [QB] idf * boost * multiplicity for the block's term
      - avgdl:  float32 scalar average field length for the clause's field
      - b:      float32 scalar BM25 b (0 for norm-less keyword fields,
                matching Lucene's omit-norms denominator tf + k1)
    k1: BM25 k1 (traced scalar).

    Clause constants are SCALARS (one field per clause): per-lane data is
    only (ids, w), which halves the msearch envelope bytes per query.

    block_keep: optional bool [QB] phase-A mask (blockmax_keep_mask): pruned
    lanes gather the shared row 0 instead of streaming their posting block
    and contribute nothing downstream. Rank-exact for top-k pages; the hit
    count (hence `total`) becomes a lower bound, mirroring Lucene BMW under
    track_total_hits.

    score_only: STATIC (Plan.static[2], decided by the planner's
    `text_clause_score_only`). False: a second scatter counts the distinct
    matched clause terms per doc and `matches = hits >= blk["min_hits"]`,
    which is what operator=and / minimum_should_match >= 2 need. True: the
    count scatter is not built and `matches = scores > 0`. That is the same
    set only when the count is not needed (`min_hits` <= 1, not
    constant-score) and every partial of a real posting is > 0 AS THE CHIP
    COMPUTES IT: w > 0, tf >= 1 and a finite positive denominator give
    partial >= w_min * (k1 + 1) / (1 + k1 * c_max), c_max = 1 - b + b *
    dl_max / avgdl with dl_max the largest length a norm byte decodes to.
    The TPU flushes denormals to zero, so "positive" has to mean a normal
    float32: the planner sets the flag only where that lower bound clears
    the smallest normal with room to spare (a zero, negative or tiny boost
    keeps the count). The score scatter is the same either way, so served
    scores are bit-identical.

    Returns (scores f32 [Dp], matches bool [Dp]); under `block_keep` a
    pruned lane adds to neither vector, so `matches` keeps its lower-bound
    meaning either way.
    """
    d_pad = seg["live"].shape[0]
    with stage("postings_gather"):
        lane_real = blk["ids"] >= 0                  # [QB]
        if block_keep is not None:
            lane_real = lane_real & block_keep
        safe_ids = jnp.where(lane_real, blk["ids"], 0)
        docs = seg["post_docs"][safe_ids]            # [QB, 128]
        tfs = seg["post_tf"][safe_ids]               # [QB, 128]
        dl = posting_lengths(seg, safe_ids)          # [QB, 128]
        valid = docs >= 0
    with stage("bm25_score"):
        b = blk["b"]
        denom = tfs + k1 * (1.0 - b + b * dl / blk["avgdl"])
        partial = blk["w"][:, None] * tfs * (k1 + 1.0) / denom
        real = valid & lane_real[:, None]
        partial = jnp.where(real, partial, 0.0)
    with stage("scatter"):
        # padding lanes scatter to index d_pad which is dropped (out of
        # bounds)
        scatter_idx = jnp.where(real, docs, d_pad).ravel()
        scores = jnp.zeros(d_pad, jnp.float32).at[scatter_idx].add(
            partial.ravel(), mode="drop")
        if score_only:
            return scores, scores > 0.0
        ones = jnp.where(real, 1, 0).astype(jnp.int32)
        hits = jnp.zeros(d_pad, jnp.int32).at[scatter_idx].add(
            ones.ravel(), mode="drop")
    return scores, hits >= blk["min_hits"]


def _pairs_to_docs(hit, doc_ids, d_pad, ident: bool):
    """Per-pair hit flags → per-doc bool [d_pad]. Identity pair layouts
    (single-valued dense columns, doc k ↔ lane k) skip the scatter-max —
    XLA scatters lower to a serial per-element loop on CPU and a slow
    path on TPU, and this op sits on every range/terms query."""
    if ident:
        n = hit.shape[-1]
        if n == d_pad:
            return hit
        if n < d_pad:
            pad = jnp.zeros(d_pad - n, jnp.bool_)
            return jnp.concatenate([hit, jnp.broadcast_to(
                pad, hit.shape[:-1] + pad.shape)], axis=-1)
        return hit[..., :d_pad]
    pair_valid = doc_ids >= 0
    scatter_idx = jnp.where(pair_valid, doc_ids, d_pad)
    return jnp.zeros(d_pad, jnp.bool_).at[scatter_idx].max(hit, mode="drop")


def range_match_on_ranks(doc_ids, ords, lo_rank, hi_rank, d_pad,
                         ident: bool = False):
    """Doc matches if ANY of its values has rank in [lo_rank, hi_rank).

    (doc_ids, ords) are a value-pair column (doc_id -1 = padding). Rank bounds
    come from the host's searchsorted over the column's sorted unique values —
    integer compares on device, exact for dates/longs/doubles alike.
    """
    pair_valid = doc_ids >= 0
    in_range = (ords >= lo_rank) & (ords < hi_rank) & pair_valid
    return _pairs_to_docs(in_range, doc_ids, d_pad, ident)


def ordinal_terms_match(doc_ids, ords, ord_mask, d_pad, ident: bool = False):
    """Doc matches if ANY of its ordinals is in the query's ordinal set.

    ord_mask: bool [card_pad] — query-side mask over the field's dictionary
    (keyword ordinals or numeric value ranks alike).
    """
    pair_valid = doc_ids >= 0
    hit = ord_mask[ords] & pair_valid
    return _pairs_to_docs(hit, doc_ids, d_pad, ident)
