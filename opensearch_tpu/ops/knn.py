"""k-NN kernels: exact brute-force distances and IVF approximate search.

The reference ships dense_vector storage only (modules/mapper-extras
DenseVectorFieldMapper) with brute-force painless `script_score`; the k-NN
plugin (opensearch-project/k-NN, out-of-repo — SURVEY.md §2.3 note) adds
HNSW/IVF via native faiss/nmslib. Here both are TPU-native:

- **Exact**: one [D, dims] × [dims] product per (segment, query), f32 at
  `F32_MATMUL`; an `_msearch` batch makes it [D, dims] × [dims, Q]. L2
  uses the ||x||² - 2x·q + ||q||² expansion (the norms are summed again
  by every query, a second pass over the column that innerproduct does
  not make). Served on a v5e at 768-d, k=100, 2,000,000 rows a shard
  (`d_pad` 2,097,152, 6.44 GB resident; `vectorsearch-knn-closed-8`,
  PERF.md §5): XLA lowers the one-column product to a VPU
  multiply-reduce that reads the column once, 8.5 ms a query (758 GB/s),
  and to no MXU op; the clause's k=100 selection (ops/select.py
  `select_top_k`) takes the maxima of 16,384 blocks of 128 lanes, the
  100 best of them, and one sort of those blocks' 12,800 lanes: 0.17 ms
  more, where one `TopK` over the 2,097,152 lanes took 1.29. A clause
  that is the whole query, or the whole of a hybrid sub-query, takes
  its page or window from those k winners (`knn_page`), not from a
  second selection over `d_pad` lanes (another 1.2 to 1.4 ms).
- **IVF**: k-means centroids (built at seal time, Lloyd's on device),
  inverted lists as a padded [nlist, max_len] int32 matrix. A query scores
  centroids, takes the top-nprobe lists, gathers their candidates, and
  scores only those — graph walks (HNSW) are TPU-hostile; IVF reaches the
  recall targets with dense, statically-shaped compute (BASELINE.md config 5).

Score conventions follow the k-NN plugin's spaces:
  l2: 1/(1+d²), cosinesimil: (1+cos)/2, innerproduct: ip≥0 → ip+1 else 1/(1-ip).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from opensearch_tpu.ops import F32_MATMUL
from opensearch_tpu.ops.select import select_top_k
from opensearch_tpu.telemetry.kernels import stage

SPACES = ("l2", "cosinesimil", "innerproduct")


def _check_space(space: str):
    if space not in SPACES:
        raise ValueError(f"unknown knn space [{space}]")


def raw_similarity(vectors: jnp.ndarray, query: jnp.ndarray,
                   space: str) -> jnp.ndarray:
    """Higher-is-closer raw similarity per doc ([D, dims] × [dims] → [D])."""
    dots = jnp.matmul(vectors, query, precision=F32_MATMUL)  # MXU matvec
    if space == "l2":
        dn = jnp.sum(vectors * vectors, axis=1)
        qn = jnp.sum(query * query)
        return -(dn - 2.0 * dots + qn)           # negative squared distance
    if space == "cosinesimil":
        dn = jnp.sqrt(jnp.sum(vectors * vectors, axis=1))
        qn = jnp.sqrt(jnp.sum(query * query))
        return dots / jnp.maximum(dn * qn, 1e-30)
    return dots                                  # innerproduct


def space_score(raw: jnp.ndarray, space: str) -> jnp.ndarray:
    """Raw similarity → k-NN plugin score (rank-monotone per space)."""
    if space == "l2":
        return 1.0 / (1.0 + jnp.maximum(-raw, 0.0))
    if space == "cosinesimil":
        return (1.0 + jnp.clip(raw, -1.0, 1.0)) / 2.0
    return jnp.where(raw >= 0, raw + 1.0, 1.0 / (1.0 - raw))


def exact_knn_scores(vectors: jnp.ndarray, query: jnp.ndarray,
                     space: str) -> jnp.ndarray:
    _check_space(space)
    with stage("distance"):
        return space_score(raw_similarity(vectors, query, space), space)


def knn_match_topk(scores: jnp.ndarray, eligible: jnp.ndarray,
                   k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Restrict a dense score vector to its top-k eligible docs.

    Returns (scores, matches): matches true only for the k best eligible
    docs (score-desc, doc-asc tie-break via top_k's lowest-index rule).
    `select_top_k` densified again, for every caller that reads `[d_pad]`
    vectors: a clause under a `bool`/`function_score`/`nested` parent,
    the aggregating and sorting query phases, the SPMD program. A plan
    whose ROOT is the clause, served score-sorted and aggregation-free
    (search/executor.py build_batched_query_phase), or a hybrid
    sub-query that is the clause (build_hybrid_query_phase), takes its
    page or window from the k winners instead (`knn_page`) and never
    builds this pair."""
    d = scores.shape[0]
    top_vals, top_idx, valid = select_top_k(scores, eligible, k)
    with stage("top_k"):
        # invalid slots scatter out of bounds and are dropped — routing
        # them to index 0 would clobber a real winner at doc ord 0
        matches = jnp.zeros(d, jnp.bool_).at[
            jnp.where(valid, top_idx, d)].set(True, mode="drop")
        matches = matches & eligible
        return jnp.where(matches, scores, 0.0), matches


def knn_page(scores: jnp.ndarray, idx: jnp.ndarray,
             returnable: jnp.ndarray, size: int):
    """The `size`-slot page of a clause's k winners: the returnable ones
    by score descending, ties by lowest doc ordinal (two raw scores can
    meet once boosted, and the clause's own order is by raw score), the
    rest `-inf`. What a `top_k` over the densified `[d_pad]` vector
    would select, from a sort of k pairs."""
    with stage("top_k"):
        neg, ords = jax.lax.sort(
            (jnp.where(returnable, -scores, jnp.inf), idx), num_keys=2)
        n = min(int(size), int(scores.shape[0]))
        pad = int(size) - n
        return (jnp.pad(-neg[:n], (0, pad), constant_values=-jnp.inf),
                jnp.pad(ords[:n], (0, pad)))


# ------------------------------------------------------------------- IVF ----

# fixed block width for inverted-list storage: probing slices whole
# blocks, so the per-query candidate count is budget · IVF_BLOCK
# regardless of how imbalanced the clusters are (a worst-case list no
# longer inflates every probe — the round-4 layout padded ALL lists to
# the longest list's length, making nprobe·max_len ≈ the whole corpus)
IVF_BLOCK = 256


@dataclass
class IVFIndex:
    """Host-side IVF structure attached to a VectorColumn at seal time.

    Lists are stored as fixed-width BLOCKS: `lists[i]` is one block of
    IVF_BLOCK doc ords (-1 padded) owned by centroid
    `block_centroid[i]`; a cluster with many members spans several
    consecutive blocks."""
    centroids: np.ndarray        # [nlist, dims] float32
    lists: np.ndarray            # [n_blocks, IVF_BLOCK] int32, -1 padded
    block_centroid: np.ndarray   # int32 [n_blocks] owning centroid
    nlist: int
    nprobe: int              # default probe count from the mapping


def _kmeans(vectors: np.ndarray, nlist: int, iters: int = 10,
            seed: int = 17) -> np.ndarray:
    """Lloyd's k-means on device (jit per (shape, nlist)); returns centroids."""
    n = vectors.shape[0]
    rng = np.random.RandomState(seed)
    init = vectors[rng.choice(n, size=nlist, replace=False)]

    @jax.jit
    def step(data, centroids):
        # assign: [n, nlist] distances via the same matmul expansion
        dots = jnp.matmul(data, centroids.T, precision=F32_MATMUL)
        dn = jnp.sum(data * data, axis=1, keepdims=True)
        cn = jnp.sum(centroids * centroids, axis=1)
        assign = jnp.argmin(dn - 2 * dots + cn, axis=1)
        # update: segment mean
        one_hot = jax.nn.one_hot(assign, nlist, dtype=jnp.float32)
        sums = jnp.matmul(one_hot.T, data, precision=F32_MATMUL)
        counts = one_hot.sum(axis=0)[:, None]
        return jnp.where(counts > 0, sums / jnp.maximum(counts, 1), centroids)

    data = jnp.asarray(vectors, dtype=jnp.float32)
    centroids = jnp.asarray(init, dtype=jnp.float32)
    # the first step call pays the XLA compile for this (shape, nlist)
    # — routed through the shared first-call timer (ISSUE 19) so the
    # compile reaches `search.xla_compile_ms` and the executable census
    # like every executor jit site; the remaining iters call the raw fn
    from opensearch_tpu.telemetry.kernels import timed_first_call
    first = timed_first_call(
        step, family="knn",
        shape=f"n{data.shape[0]}/d{data.shape[1]}/c{nlist}",
        key=("kmeans", data.shape, nlist))
    for it in range(iters):
        centroids = first(data, centroids) if it == 0 \
            else step(data, centroids)
    return np.asarray(centroids)


def build_ivf(vectors: np.ndarray, exists: np.ndarray, nlist: int,
              nprobe: int = 0, iters: int = 10, seed: int = 17) -> IVFIndex:
    """Cluster present vectors; inverted lists hold doc ords per centroid."""
    present = np.nonzero(exists)[0].astype(np.int32)
    nlist = max(1, min(nlist, len(present)))
    data = vectors[present].astype(np.float32)
    centroids = _kmeans(data, nlist, iters=iters, seed=seed)
    dots = data @ centroids.T
    dn = (data ** 2).sum(axis=1, keepdims=True)
    cn = (centroids ** 2).sum(axis=1)
    assign = np.argmin(dn - 2 * dots + cn, axis=1)
    blocks = []
    block_centroid = []
    for c in range(nlist):
        members = present[assign == c]
        # empty clusters emit NO block: an all-padding block would still
        # win probe-budget slots whenever its centroid lands near the
        # query, displacing blocks with real candidates
        for off in range(0, len(members), IVF_BLOCK):
            chunk = members[off:off + IVF_BLOCK]
            row = np.full(IVF_BLOCK, -1, dtype=np.int32)
            row[:len(chunk)] = chunk
            blocks.append(row)
            block_centroid.append(c)
    if not blocks:          # no vectors at all: one padding block keeps
        blocks.append(np.full(IVF_BLOCK, -1, dtype=np.int32))
        block_centroid.append(0)        # shapes valid for the scan
    lists = np.stack(blocks)
    if nprobe <= 0:
        nprobe = max(1, nlist // 8)
    return IVFIndex(centroids=centroids, lists=lists,
                    block_centroid=np.asarray(block_centroid, np.int32),
                    nlist=nlist, nprobe=nprobe)


def pack_ivf_lists(vectors: np.ndarray, lists: np.ndarray):
    """List-contiguous copies of the vector rows + their doc ords.

    IVF probing gathers ~nprobe·max_len arbitrary vector rows per query;
    XLA lowers that gather to a scalar loop on CPU and a serial path on
    TPU, and it dominated the IVF scan. With the rows laid out list-major
    at build time, each probed list is ONE contiguous dynamic_slice —
    pure copies + matmul. Costs a second copy of the vector matrix
    (inflated by list padding) in exchange."""
    flat = lists.reshape(-1)
    safe = np.where(flat >= 0, flat, 0)
    packed = np.ascontiguousarray(vectors[safe].astype(np.float32))
    packed[flat < 0] = 0.0
    return packed, np.ascontiguousarray(flat.astype(np.int32))


def ivf_knn_scores(packed_vecs: jnp.ndarray, packed_ids: jnp.ndarray,
                   centroids: jnp.ndarray, block_centroid: jnp.ndarray,
                   d: int, query: jnp.ndarray, space: str,
                   nprobe: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """IVF probe: returns (dense scores [D], candidate mask [D]).

    Scores are exact for candidate docs; non-candidates are masked out —
    the standard IVF recall/compute trade. Blocks are ranked by their
    owning centroid's distance and the best `budget` blocks are sliced
    CONTIGUOUSLY from the packed copy (see pack_ivf_lists) — no row
    gather, and the probe budget is independent of cluster imbalance
    (budget ≈ nprobe · avg-blocks-per-list; a skewed list legitimately
    consumes more of the budget because it holds more of the mass)."""
    _check_space(space)
    # centroid ranking always by L2 (clusters were built in L2 space); for
    # innerproduct/cosine the probe order still correlates (faiss does the
    # same for IVF+IP via L2-clustered coarse quantizers)
    cd = jnp.sum(centroids * centroids, axis=1) \
        - 2.0 * jnp.matmul(centroids, query, precision=F32_MATMUL)
    nlist = int(centroids.shape[0])
    n_blocks = int(block_centroid.shape[0])
    nprobe_eff = min(int(nprobe), nlist)
    budget = min(n_blocks,
                 -(-nprobe_eff * n_blocks // nlist) + 1)
    key = cd[block_centroid]                         # [n_blocks] tiny
    _, blk_ids = jax.lax.top_k(-key, budget)
    dims = packed_vecs.shape[1]
    # BLOCK-level gather: each gathered element is a contiguous
    # [IVF_BLOCK, dims] chunk (a memcpy, not the per-row scalar gather
    # this layout exists to avoid), and the graph stays O(1) in budget
    cand_vecs = jnp.take(packed_vecs.reshape(n_blocks, IVF_BLOCK, dims),
                         blk_ids, axis=0).reshape(budget * IVF_BLOCK,
                                                  dims)
    cand = jnp.take(packed_ids.reshape(n_blocks, IVF_BLOCK),
                    blk_ids, axis=0).reshape(budget * IVF_BLOCK)
    with stage("distance"):
        raw = raw_similarity(cand_vecs, query, space)
        scores01 = space_score(raw, space)
    valid = cand >= 0
    # padding slots scatter out of bounds (dropped) — using index 0 would
    # overwrite doc ord 0's entries
    cand_scatter = jnp.where(valid, cand, d)
    dense = jnp.zeros(d, jnp.float32).at[cand_scatter].max(
        scores01, mode="drop")
    mask = jnp.zeros(d, jnp.bool_).at[cand_scatter].set(True, mode="drop")
    return dense, mask
