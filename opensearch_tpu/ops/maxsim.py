"""Late-interaction MaxSim kernels: tiled exact scoring and fused-PQ ADC.

ColBERT-style scoring (arxiv 1707.08275): a doc stores one vector per
token, a query brings one vector per query token, and the doc score is

    score(doc) = sum_t  max_s  q_t . d_s

over query tokens t and doc tokens s. The reference ecosystem serves
this from CPU/GPU ANN libraries; here both storage layouts are
TPU-native, shaped by FLASH-MAXSIM (arxiv 2605.29517) and TileMaxSim
(arxiv 2606.26439):

- **Exact**: per-doc token matrices live as one padded [D, T, dims] f32
  block. The kernel walks the dims axis in MXU-friendly tiles
  (DIM_TILE lanes at a time) accumulating partial dot products, so the
  working set per step is the [D*T, tile] slab — the dimension-tiling
  loop TileMaxSim shows is what keeps HBM traffic linear in dims.
  Padded token lanes (s >= token_count) are masked to -inf BEFORE the
  max so they can never win; zero-token docs score 0 and stay
  ineligible via the exists mask.
- **PQ (fused decode)**: token vectors are product-quantized at seal
  time (index/segment.py) into [D, T, M] uint8 codes against a
  [M, 256, dsub] codebook. The kernel builds the per-query ADC lookup
  table lut[Tq, M, 256] = codebook . q_subvectors once per (query,
  segment) and scores codes by table gather inside the loop — the
  compressed vectors are decoded in-register, never materialized
  (FLASH-MAXSIM's fusion contract).

Both variants end in the same top-k epilogue as k-NN
(ops/knn.knn_match_topk): a dense masked score vector restricted to
the k best eligible docs, so cross-segment merge, the value-keyed
result page (ops/topk.py), and the msearch envelope all work
unchanged.

Query token matrices are padded to power-of-two token buckets by the
compiler (search/compile.py) with a qmask zeroing padded query lanes —
executables are keyed on the bucket, not the raw token count.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np
import jax.numpy as jnp

from opensearch_tpu.ops import F32_MATMUL

# dims-axis tile width for the exact kernel: one VPU/MXU lane group
# (the last-axis native lane width); dims smaller than a tile take one
# partial step
DIM_TILE = 128

# PQ geometry: 8-bit codes -> 256 centroids per subspace
PQ_CODES = 256


def token_mask(token_count: jnp.ndarray, t_bucket: int) -> jnp.ndarray:
    """[D, T] bool: True for real token lanes (s < token_count[d])."""
    lanes = jnp.arange(t_bucket, dtype=jnp.int32)
    return lanes[None, :] < token_count[:, None]


def _tiled_token_dots(tokens2d: jnp.ndarray, query: jnp.ndarray) -> jnp.ndarray:
    """[N, dims] x [Tq, dims] -> [N, Tq] dot products, accumulated over
    DIM_TILE-wide dims slices (the TileMaxSim loop). Tile count is
    static per (shape bucket), so the loop unrolls into a fixed chain
    of MXU matmuls."""
    dims = tokens2d.shape[1]
    acc = None
    for lo in range(0, dims, DIM_TILE):
        hi = min(lo + DIM_TILE, dims)
        part = jnp.matmul(tokens2d[:, lo:hi], query[:, lo:hi].T,
                          precision=F32_MATMUL)
        acc = part if acc is None else acc + part
    return acc


def exact_maxsim_scores(tokens: jnp.ndarray, token_count: jnp.ndarray,
                        query: jnp.ndarray, qmask: jnp.ndarray) -> jnp.ndarray:
    """Fused exact MaxSim over a padded token block.

    tokens: [D, T, dims] f32 (padded lanes zero), token_count: [D] i32,
    query: [Tq, dims] f32 (padded query lanes zero), qmask: [Tq] f32
    (1.0 real / 0.0 padding). Returns [D] f32 scores; zero-token docs
    score 0.
    """
    d, t_bucket, dims = tokens.shape
    tq = query.shape[0]
    tmask = token_mask(token_count, t_bucket)            # [D, T]
    # [D*T, Tq] partial-dot accumulation over dims tiles, then the
    # masked max over doc-token lanes per query token
    dots = _tiled_token_dots(tokens.reshape(d * t_bucket, dims), query)
    dots = dots.reshape(d, t_bucket, tq)
    dots = jnp.where(tmask[:, :, None], dots, -jnp.inf)
    best = jnp.max(dots, axis=1)                         # [D, Tq]
    # empty docs have every lane at -inf: clamp to 0 before the sum so
    # they contribute nothing (they are masked ineligible anyway)
    best = jnp.where(jnp.isfinite(best), best, 0.0)
    return jnp.sum(best * qmask[None, :], axis=1)


def pq_lut(codebook: jnp.ndarray, query: jnp.ndarray) -> jnp.ndarray:
    """ADC lookup table lut[Tq, M, 256]: each query token's dot product
    against every subspace centroid. codebook: [M, 256, dsub] f32,
    query: [Tq, dims] with dims == M * dsub."""
    m, codes, dsub = codebook.shape
    tq = query.shape[0]
    qsub = query.reshape(tq, m, dsub)
    return jnp.einsum("mcd,tmd->tmc", codebook, qsub,
                      precision=F32_MATMUL)


def pq_maxsim_scores(codes: jnp.ndarray, codebook: jnp.ndarray,
                     token_count: jnp.ndarray, query: jnp.ndarray,
                     qmask: jnp.ndarray) -> jnp.ndarray:
    """Fused-PQ MaxSim: codes are scored against the per-query ADC
    table inside the loop — decoded vectors are never materialized.

    codes: [D, T, M] uint8, codebook: [M, 256, dsub] f32,
    token_count: [D] i32, query: [Tq, dims] f32, qmask: [Tq] f32.
    Returns [D] f32 approximate MaxSim scores.
    """
    d, t_bucket, m = codes.shape
    tq = query.shape[0]
    lut = pq_lut(codebook, query)                        # [Tq, M, 256]
    tmask = token_mask(token_count, t_bucket)            # [D, T]
    idx = codes.astype(jnp.int32)
    sub = jnp.arange(m, dtype=jnp.int32)[None, None, :]
    out = []
    # per-query-token gather keeps the live slab at [D, T, M] — the
    # [D, T, Tq] cross product never materializes (Tq is a static
    # bucket, so this unrolls like the exact kernel's tile chain)
    for t in range(tq):
        dots = jnp.sum(lut[t][sub, idx], axis=-1)        # [D, T]
        dots = jnp.where(tmask, dots, -jnp.inf)
        best = jnp.max(dots, axis=1)                     # [D]
        out.append(jnp.where(jnp.isfinite(best), best, 0.0))
    return jnp.sum(jnp.stack(out, axis=1) * qmask[None, :], axis=1)


# ------------------------------------------------------- seal-time PQ ----

def train_pq(vectors: np.ndarray, m: int, iters: int = 8,
             seed: int = 29) -> np.ndarray:
    """Per-subspace k-means codebook [m, 256, dsub] over the segment's
    token vectors (host/seal path). Fewer distinct tokens than 256
    leaves the tail centroids zero — codes never reference them."""
    n, dims = vectors.shape
    dsub = dims // m
    codebook = np.zeros((m, PQ_CODES, dsub), dtype=np.float32)
    if n == 0:
        return codebook
    rng = np.random.RandomState(seed)
    data = vectors.astype(np.float32).reshape(n, m, dsub)
    for sub in range(m):
        x = data[:, sub, :]
        ncent = min(PQ_CODES, n)
        cent = x[rng.choice(n, size=ncent, replace=False)].copy()
        for _ in range(iters):
            d2 = ((x[:, None, :] - cent[None, :, :]) ** 2).sum(axis=2)
            assign = np.argmin(d2, axis=1)
            for c in range(ncent):
                members = x[assign == c]
                if len(members):
                    cent[c] = members.mean(axis=0)
        codebook[sub, :ncent] = cent
    return codebook


def encode_pq(vectors: np.ndarray, codebook: np.ndarray) -> np.ndarray:
    """[N, dims] -> [N, M] uint8 nearest-centroid codes (host/seal)."""
    n = vectors.shape[0]
    m, _, dsub = codebook.shape
    if n == 0:
        return np.zeros((0, m), dtype=np.uint8)
    data = vectors.astype(np.float32).reshape(n, m, dsub)
    codes = np.zeros((n, m), dtype=np.uint8)
    for sub in range(m):
        x = data[:, sub, :]
        cent = codebook[sub]
        d2 = ((x[:, None, :] - cent[None, :, :]) ** 2).sum(axis=2)
        codes[:, sub] = np.argmin(d2, axis=1).astype(np.uint8)
    return codes


def decode_pq(codes: np.ndarray, codebook: np.ndarray) -> np.ndarray:
    """[N, M] codes -> [N, dims] reconstructed vectors (host-side
    differential/debug only — the device kernel never calls this)."""
    n, m = codes.shape
    dsub = codebook.shape[2]
    out = np.zeros((n, m * dsub), dtype=np.float32)
    for sub in range(m):
        out[:, sub * dsub:(sub + 1) * dsub] = codebook[sub][codes[:, sub]]
    return out


def maxsim_match_topk(scores: jnp.ndarray, eligible: jnp.ndarray,
                      k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k epilogue — identical contract to ops/knn.knn_match_topk so
    cross-segment merge and the result page treat maxsim matches like
    any other dense score vector."""
    from opensearch_tpu.ops.knn import knn_match_topk
    return knn_match_topk(scores, eligible, k)
