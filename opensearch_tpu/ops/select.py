"""Exact selection of the k best eligible lanes of a dense score vector.

One route for every caller that selects k winners of `[d_pad]` lanes
where a plain `jax.lax.top_k` would: a k-NN or maxsim clause's own k
(ops/knn.py `knn_match_topk`, search/plan_eval.py `eval_knn_winners`) and
a hybrid sub-query's window over its dense pair (search/executor.py
`build_hybrid_query_phase`). From 2^15 lanes up, where the k winning
blocks are at most a quarter of the lanes, it reads block maxima
(`blocked_select_width`, `_blocked_top_k`); below, one `top_k`. Either
way the answer is `top_k`'s: the same values and ordinals in the same
order, ties by lowest lane.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from opensearch_tpu.telemetry.kernels import stage

SELECT_BLOCK = 128          # lanes a block of the blocked selection
SELECT_MIN_LANES = 1 << 15  # below it XLA's TopK is one cheap pass


def blocked_select_width(d_pad: int, k: int) -> int:
    """The block width `select_top_k` selects through for a `[d_pad]`
    score vector and k winners, 0 where it runs one plain `top_k`. Plain,
    XLA's `TopK` costs ~1 ns a lane on a v5e from 2^15 lanes up and
    ~10 us below; blocked, it takes one max-reduce, a `top_k` over the
    `d_pad / w` block maxima and a sort of the `k * w` lanes of the k
    winning blocks, whose cost does not grow with `d_pad` (PERF.md §6).
    Blocks are one vreg row (128 f32 lanes) wide, and the route wants
    the candidates to be at most a quarter of the lanes."""
    w = SELECT_BLOCK
    if d_pad < SELECT_MIN_LANES or 4 * k * w > d_pad:
        return 0
    return w


def _blocked_top_k(masked: jnp.ndarray, k: int, w: int):
    """`jax.lax.top_k(masked, k)`, the same values and the same indices,
    from the lanes of the k contiguous `w`-lane blocks with the largest
    maxima (ties to the lower block). Exact, ties included: with T the
    k-th of those maxima, every lane above T is in a chosen block. Each
    chosen block whose maximum is above T holds a lane above T, so no
    more lanes at T win than there are chosen blocks whose maximum is T;
    each of those holds a lane at T and lies below every passed-over
    block whose maximum is T, so the lowest lanes at T are all chosen.
    The candidates are sorted by score descending, then doc ascending:
    `top_k`'s lowest-index rule. The docs are distinct, so the sort need
    not be stable (a stable one takes half again as long); and it is a sort,
    not a second `top_k`, because the sorts XLA rewrites a `top_k` into
    carry no stage of their own."""
    blocks = masked.reshape(masked.shape[0] // w, w)
    _, blk = jax.lax.top_k(jnp.max(blocks, axis=1), k)
    docs = blk[:, None] * w + jnp.arange(w, dtype=blk.dtype)
    neg, docs = jax.lax.sort((-blocks[blk].reshape(k * w),
                              docs.reshape(k * w)), num_keys=2,
                             is_stable=False)
    return -neg[:k], docs[:k]


def select_top_k(scores: jnp.ndarray, eligible: jnp.ndarray, k: int):
    """The k best eligible docs of a dense score vector, as (values, doc
    ordinals, valid), score-desc with ties by lowest doc (`top_k`'s
    lowest-index rule). Fewer than k eligible docs leave the tail slots
    `-inf` and not `valid`. Blocked where `blocked_select_width` says
    so, else one `top_k`."""
    with stage("top_k"):
        masked = jnp.where(eligible, scores, -jnp.inf)
        d = int(scores.shape[0])
        k = min(int(k), d)
        w = blocked_select_width(d, k)
        top_vals, top_idx = _blocked_top_k(masked, k, w) if w \
            else jax.lax.top_k(masked, k)
        return top_vals, top_idx, top_vals > -jnp.inf
