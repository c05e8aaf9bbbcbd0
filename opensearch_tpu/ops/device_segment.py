"""Device-resident segment: the HBM image of a sealed columnar segment.

This is the TPU analog of Lucene's on-heap/off-heap segment readers
(reference: the SegmentReader/LeafReaderContext machinery consumed by
search/internal/ContextIndexSearcher.java). All arrays are padded to
power-of-two buckets so differently-sized segments reuse the same compiled
executable (XLA recompiles per shape — bucketing bounds the compile count).

Layout:
- `post_docs`/`post_tf`/`post_norm`: the global blocked postings matrices
  `[NBp, 128]` — doc id (int32, -1 padded), term frequency (f32) and the
  SmallFloat norm byte (uint8) of that doc in the block's field, lane for
  lane, so BM25 reads a posting's length where it reads its tf.
- `norms`: stacked `[F, Dp]` int32 SmallFloat norm bytes, one row per indexed
  text field (row index assigned in `DeviceSegmentMeta.norm_rows`); read by
  `exists` on a text field only — scoring reads `post_norm`.
- numeric doc values per field: `(doc_ids, val_ords, values_f32)` value-pair
  arrays (pad doc_id = -1) + dense `exists`, `min_rank`/`max_rank` per doc for
  sorting and can-match pruning.
- ordinal (keyword) doc values per field: `(doc_ids, ords)` pairs + `exists`.
- vectors per field: dense `[Dp, dims]` float32.
- `live`: deletion bitmap, AND-ed into every match mask.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field as dc_field
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from opensearch_tpu.index.segment import (Segment, block_score_bounds,
                                          pad_bucket, posting_norms)
from opensearch_tpu.telemetry import TELEMETRY

INT32_MAX = np.int32(2 ** 31 - 1)
_F32_MAX = float(np.finfo(np.float32).max)

# ISSUE 16 delta publish: when ON, publish_segment() ships only the
# populated prefix of every padded column to the device and expands it
# to the padded bucket on-chip (jnp.full + .at[].set under jit) — the
# resident image is byte-identical to a full upload_segment(), but the
# host→device transfer (and the churn ledger's upload.corpus bytes) is
# proportional to real data, not the power-of-two bucket. OFF by
# default: the default write path is exactly upload_segment().
DELTA_PUBLISH = False


def _to_f32_finite(values: np.ndarray) -> np.ndarray:
    """float64 → float32 with saturation instead of overflow-to-inf: range
    fields store an unbounded-side sentinel (mapper.RANGE_UNBOUNDED = 1e308)
    that must stay finite on device so metric kernels over the decode tables
    never see inf."""
    # cast first, then saturate in place: one float32 pass instead of a
    # float64 temporary (8 columns x 8.4M values a row at log scale)
    with np.errstate(over="ignore"):
        out = np.asarray(values).astype(np.float32)
    np.clip(out, -_F32_MAX, _F32_MAX, out=out)
    return out


@dataclass(frozen=True)
class DeviceSegmentMeta:
    """Static (hashable) shape/layout info — safe to close over in jit."""
    seg_id: str
    num_docs: int
    d_pad: int
    nb_pad: int
    norm_rows: Tuple[Tuple[str, int], ...]   # field → row in norms stack
    numeric_fields: Tuple[str, ...]
    ordinal_fields: Tuple[str, ...]
    vector_fields: Tuple[str, ...]
    # (field, token_bucket, compression) per rank_vectors field — the
    # token bucket and storage variant are executable-shaping facts, so
    # they live in the compile key, not just the runtime array shapes
    rank_vector_fields: Tuple[Tuple[str, int, str], ...] = ()
    # seal-time per-block score bounds leaf (ISSUE 20 block-max pruning):
    # always present in the image ([nb_pad] f32 rides next to the block
    # metadata, ~0.4% of the postings bytes) so flipping the query-time
    # gate never forces a re-upload; part of the compile key because the
    # leaf's existence shapes every traced program's input tree
    block_bounds: bool = True

    def norm_row(self, field: str) -> Optional[int]:
        for f, r in self.norm_rows:
            if f == field:
                return r
        return None

    def compile_key(self) -> tuple:
        """Everything a compiled program closes over, seg_id EXCLUDED —
        seg_id is pure identity metadata, never read in traced code, so
        two segments equal on this key (plus equal runtime arg shapes)
        share every compiled executable. Keying the executor's JIT
        cache on this instead of the whole meta is what lets a freshly
        refreshed segment land in an already-compiled (plan-struct,
        shape-bucket) family instead of paying a per-segment XLA
        recompile (ISSUE 13 / ROADMAP item 5: incremental segment
        publish without cold recompiles)."""
        return (self.num_docs, self.d_pad, self.nb_pad, self.norm_rows,
                self.numeric_fields, self.ordinal_fields,
                self.vector_fields, self.rank_vector_fields,
                self.block_bounds)


def upload_segment(seg: Segment, to_device: bool = True):
    """Build the device pytree (dict of jnp arrays) + static meta for a
    segment. An upload is an `install.upload_segment` of the span ring's
    process track (`_note_install`); the host image alone (`to_device`
    False: a row of an SPMD shard set, which records its own
    `install.shard_set`) is none."""
    t0 = time.monotonic()
    arrays, meta = _host_image(seg)
    if to_device:
        t1 = time.monotonic()
        arrays = _tree_to_jnp(arrays)
        _note_install(meta, tree_nbytes(arrays), t0, t1, time.monotonic())
    return arrays, meta


def _note_install(meta: "DeviceSegmentMeta", nbytes: int, t0: float,
                  t1: float, t2: float) -> None:
    """One segment's image put on the device, on the process track of
    the always-on span ring (telemetry/tracer.py; what the benchmark's
    `install_*_s` read): `install.upload_segment` (`segment`, `d_pad`,
    `nbytes`) from `t0` to `t2`, and below it `install.host_pad` (to
    `t1`: the padded host copy of every column, with the passes that
    derive `post_norm`, the block bounds and the rank extremes) and
    `install.device_put` (from `t1`: the calls that hand the arrays to
    the device; what the runtime still has in flight when they return
    is in neither)."""
    ring = TELEMETRY.tracer.spans
    upload_id = ring.process(
        "install.upload_segment", t0, t2,
        {"segment": meta.seg_id, "d_pad": meta.d_pad, "nbytes": nbytes})
    ring.process("install.host_pad", t0, t1, None, upload_id)
    ring.process("install.device_put", t1, t2, None, upload_id)


def _host_image(seg: Segment):
    """The padded host arrays (numpy) of a segment's device pytree, and
    its static meta."""
    d_pad = pad_bucket(max(seg.num_docs, 1))
    nb = seg.post_docs.shape[0]
    nb_pad = pad_bucket(nb, minimum=8)

    post_docs = np.full((nb_pad, seg.post_docs.shape[1]), -1, dtype=np.int32)
    post_docs[:nb] = seg.post_docs
    post_tf = np.zeros((nb_pad, seg.post_tf.shape[1]), dtype=np.float32)
    post_tf[:nb] = seg.post_tf
    # each posting's norm byte beside its tf; padding blocks and lanes 0
    post_norm = np.zeros(post_docs.shape, dtype=np.uint8)
    post_norm[:nb] = posting_norms(seg)
    # seal-time per-block score upper bounds (block-max pruning, ISSUE 20):
    # [nb_pad] f32 next to the block matrices; padding blocks bound 0
    post_bound = np.zeros(nb_pad, dtype=np.float32)
    post_bound[:nb] = block_score_bounds(seg)

    norm_fields = sorted(seg.norms.keys())
    norms = np.zeros((max(len(norm_fields), 1), d_pad), dtype=np.int32)
    for row, fname in enumerate(norm_fields):
        norms[row, :seg.num_docs] = seg.norms[fname]

    live = np.zeros(d_pad, dtype=bool)
    live[:seg.num_docs] = seg.live

    # doc-block structure for nested queries: root mask (top-level rows —
    # the only rows a search may return), parent row pointer, nested-path
    # ordinal (segment.py block-join layout). Root-only segments carry the
    # trivial encoding so all segments share one array layout.
    root = np.zeros(d_pad, dtype=bool)
    root[:seg.num_docs] = getattr(seg, "root",
                                  np.ones(seg.num_docs, bool))
    parent_ptr = np.full(d_pad, -1, dtype=np.int32)
    parent_ptr[:seg.num_docs] = getattr(
        seg, "parent_ptr", np.full(seg.num_docs, -1, np.int32))
    nested_path = np.full(d_pad, -1, dtype=np.int32)
    nested_path[:seg.num_docs] = getattr(
        seg, "path_ords", np.full(seg.num_docs, -1, np.int32))

    arrays: Dict = {
        "post_docs": post_docs,
        "post_tf": post_tf,
        "post_norm": post_norm,
        "post_bound": post_bound,
        "norms": norms,
        "live": live,
        "root": root,
        "parent_ptr": parent_ptr,
        "nested_path": nested_path,
        "numeric": {},
        "ordinal": {},
        "vector": {},
        "rank_vectors": {},
    }

    for fname, col in seg.numeric_dv.items():
        nv_pad = pad_bucket(max(len(col.doc_ids), 1))
        doc_ids = np.full(nv_pad, -1, dtype=np.int32)
        doc_ids[:len(col.doc_ids)] = col.doc_ids
        val_ords = np.zeros(nv_pad, dtype=np.int32)
        val_ords[:len(col.doc_ids)] = col.value_ords
        values_f32 = np.zeros(nv_pad, dtype=np.float32)
        values_f32[:len(col.doc_ids)] = _to_f32_finite(col.values)
        exists = np.zeros(d_pad, dtype=bool)
        exists[:seg.num_docs] = col.exists
        min_rank = np.full(d_pad, INT32_MAX, dtype=np.int32)
        max_rank = np.full(d_pad, -1, dtype=np.int32)
        if len(col.doc_ids):
            np.minimum.at(min_rank, col.doc_ids, col.value_ords)
            np.maximum.at(max_rank, col.doc_ids, col.value_ords)
        # rank → value decode table (f32) for device-side metric aggregations
        u_pad = pad_bucket(max(len(col.unique), 1), minimum=8)
        unique_f32 = np.zeros(u_pad, dtype=np.float32)
        unique_f32[:len(col.unique)] = _to_f32_finite(col.unique)
        arrays["numeric"][fname] = {
            "doc_ids": doc_ids, "val_ords": val_ords, "values_f32": values_f32,
            "exists": exists, "min_rank": min_rank, "max_rank": max_rank,
            "unique_f32": unique_f32,
        }

    for fname, col in seg.ordinal_dv.items():
        nv_pad = pad_bucket(max(len(col.doc_ids), 1))
        doc_ids = np.full(nv_pad, -1, dtype=np.int32)
        doc_ids[:len(col.doc_ids)] = col.doc_ids
        ords = np.zeros(nv_pad, dtype=np.int32)
        ords[:len(col.doc_ids)] = col.ords
        exists = np.zeros(d_pad, dtype=bool)
        exists[:seg.num_docs] = col.exists
        arrays["ordinal"][fname] = {
            "doc_ids": doc_ids, "ords": ords, "exists": exists,
        }

    for fname, col in seg.vector_dv.items():
        vecs = np.zeros((d_pad, col.vectors.shape[1]), dtype=np.float32)
        vecs[:seg.num_docs] = col.vectors
        exists = np.zeros(d_pad, dtype=bool)
        exists[:seg.num_docs] = col.exists
        entry = {"vectors": vecs, "exists": exists}
        if col.ivf is not None:
            from opensearch_tpu.ops.knn import pack_ivf_lists
            packed, flat_ids = pack_ivf_lists(col.vectors, col.ivf.lists)
            entry["ivf_centroids"] = col.ivf.centroids
            entry["ivf_block_centroid"] = col.ivf.block_centroid
            entry["ivf_packed_vecs"] = packed
            entry["ivf_packed_ids"] = flat_ids
        arrays["vector"][fname] = entry

    # rank_vectors (late-interaction token matrices): docs axis padded to
    # d_pad like every dense column; the token axis keeps the segment's
    # power-of-two bucket from seal. PQ mappings ship codes + codebook
    # instead of the raw f32 matrices (the kernel decodes in-register).
    rank_vector_fields = []
    for fname, col in sorted(getattr(seg, "rank_vectors_dv", {}).items()):
        token_count = np.zeros(d_pad, dtype=np.int32)
        token_count[:seg.num_docs] = col.token_count
        exists = np.zeros(d_pad, dtype=bool)
        exists[:seg.num_docs] = col.exists
        entry = {"token_count": token_count, "exists": exists}
        if col.codes is not None:
            codes = np.zeros((d_pad,) + col.codes.shape[1:], dtype=np.uint8)
            codes[:seg.num_docs] = col.codes
            entry["codes"] = codes
            entry["codebook"] = col.codebook
            compression = "pq"
        else:
            tokens = np.zeros((d_pad,) + col.tokens.shape[1:], dtype=np.float32)
            tokens[:seg.num_docs] = col.tokens
            entry["tokens"] = tokens
            compression = "none"
        arrays["rank_vectors"][fname] = entry
        rank_vector_fields.append((fname, col.t_bucket, compression))

    meta = DeviceSegmentMeta(
        seg_id=seg.seg_id,
        num_docs=seg.num_docs,
        d_pad=d_pad,
        nb_pad=nb_pad,
        norm_rows=tuple((f, i) for i, f in enumerate(norm_fields)),
        numeric_fields=tuple(sorted(seg.numeric_dv.keys())),
        ordinal_fields=tuple(sorted(seg.ordinal_dv.keys())),
        vector_fields=tuple(sorted(seg.vector_dv.keys())),
        rank_vector_fields=tuple(rank_vector_fields),
    )
    return arrays, meta


def _tree_to_jnp(tree):
    if isinstance(tree, dict):
        return {k: _tree_to_jnp(v) for k, v in tree.items()}
    return jnp.asarray(tree)


def tree_nbytes(tree) -> int:
    """Total array bytes in a device pytree (dict-of-dicts-of-arrays) —
    `nbytes` is shape·itemsize metadata on both numpy and jax arrays, so
    this never forces a device sync. Feeds the transfer ledger's
    `upload.corpus` channel and the corpus-columns memory gauge."""
    if isinstance(tree, dict):
        return sum(tree_nbytes(v) for v in tree.values())
    return int(getattr(tree, "nbytes", 0))


def _compact_spec(seg: Segment, meta: DeviceSegmentMeta) -> Dict[tuple, tuple]:
    """Tree-path → ((compact extents, None = full axis), pad fill) for
    every leaf whose padded tail is a constant fill. Leaves absent from
    the spec (ivf_* packings, PQ codebooks) transfer in full."""
    nd = seg.num_docs
    nb = seg.post_docs.shape[0]
    # postings width is sized to the DOC pad bucket by the builder, but
    # a term's doc list can never exceed num_docs — on a small segment
    # (the refresh-churn case) the width axis is almost all fill, and
    # it is the dominant share of the padded image
    spec: Dict[tuple, tuple] = {
        ("post_docs",): ((nb, nd), -1),
        ("post_tf",): ((nb, nd), 0.0),
        ("post_norm",): ((nb, nd), 0),
        ("post_bound",): ((nb,), 0.0),
        ("norms",): ((None, nd), 0),
        ("live",): ((nd,), False),
        ("root",): ((nd,), False),
        ("parent_ptr",): ((nd,), -1),
        ("nested_path",): ((nd,), -1),
    }
    for fname, col in seg.numeric_dv.items():
        nv = len(col.doc_ids)
        spec[("numeric", fname, "doc_ids")] = ((nv,), -1)
        spec[("numeric", fname, "val_ords")] = ((nv,), 0)
        spec[("numeric", fname, "values_f32")] = ((nv,), 0.0)
        spec[("numeric", fname, "exists")] = ((nd,), False)
        # minimum.at/maximum.at only touch rows < num_docs, so the
        # padded tail keeps the initial fill
        spec[("numeric", fname, "min_rank")] = ((nd,), int(INT32_MAX))
        spec[("numeric", fname, "max_rank")] = ((nd,), -1)
        spec[("numeric", fname, "unique_f32")] = ((len(col.unique),), 0.0)
    for fname, col in seg.ordinal_dv.items():
        nv = len(col.doc_ids)
        spec[("ordinal", fname, "doc_ids")] = ((nv,), -1)
        spec[("ordinal", fname, "ords")] = ((nv,), 0)
        spec[("ordinal", fname, "exists")] = ((nd,), False)
    for fname in seg.vector_dv:
        spec[("vector", fname, "vectors")] = ((nd, None), 0.0)
        spec[("vector", fname, "exists")] = ((nd,), False)
    for fname, col in getattr(seg, "rank_vectors_dv", {}).items():
        spec[("rank_vectors", fname, "token_count")] = ((nd,), 0)
        spec[("rank_vectors", fname, "exists")] = ((nd,), False)
        if col.codes is not None:
            spec[("rank_vectors", fname, "codes")] = ((nd, None, None), 0)
            # codebook is query-shaped, not doc-shaped: full transfer
        else:
            spec[("rank_vectors", fname, "tokens")] = ((nd, None, None), 0.0)
    return spec


_EXPAND_CACHE: Dict[tuple, object] = {}


def _expand_fn(compact_shape: tuple, full_shape: tuple, fill, dtype_str: str):
    """Compiled on-device expansion: fill-pad a compact prefix block out
    to the padded bucket shape. Cached per (shapes, fill, dtype) family —
    compact extents are power-of-two bucketed by the caller so this stays
    a bounded set of executables, not one per document count.

    The explicit miss/hit split (vs the old lru_cache) exists for the
    compile-event discipline (ISSUE 19): the MISS returns the shared
    first-call timer — so the expander's XLA compile reaches
    `search.xla_compile_ms` / `xla_cache_miss` and the executable
    census like every executor jit site — while hits return the raw
    executable, paying nothing."""
    key = (compact_shape, full_shape, fill, dtype_str)
    fn = _EXPAND_CACHE.get(key)
    if fn is not None:
        return fn

    def expand(x):
        out = jnp.full(full_shape, fill, dtype=dtype_str)
        return out.at[tuple(slice(0, s) for s in compact_shape)].set(x)

    from opensearch_tpu.telemetry.kernels import (jit_family,
                                                  timed_first_call)
    fn = jit_family(expand, "expand")
    _EXPAND_CACHE[key] = fn  # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
    nbytes = float(np.prod(full_shape)) * np.dtype(dtype_str).itemsize \
        if full_shape else float(np.dtype(dtype_str).itemsize)
    return timed_first_call(
        fn, family="expand",
        shape="x".join(str(s) for s in full_shape) or "scalar", key=key,
        cost=(float(np.prod(full_shape) if full_shape else 1), nbytes))


def _delta_tree(host, spec: Dict[tuple, tuple], transferred: list,
                path: tuple = ()):
    """Walk the host pytree; ship each specced leaf as its compact prefix
    + on-device expansion, everything else in full. `transferred[0]`
    accumulates actual host→device bytes."""
    if isinstance(host, dict):
        return {k: _delta_tree(v, spec, transferred, path + (k,))
                for k, v in host.items()}
    full = tuple(int(s) for s in host.shape)
    entry = spec.get(path)
    if entry is not None:
        raw, fill = entry
        # bucket the compact extents so the expansion executables form a
        # bounded power-of-two family (same trick as pad_bucket itself)
        cshape = tuple(
            f if c is None else min(pad_bucket(max(int(c), 1), minimum=8), f)
            for c, f in zip(raw, full))
        if cshape != full:
            compact = np.ascontiguousarray(
                host[tuple(slice(0, s) for s in cshape)])
            transferred[0] += int(compact.nbytes)
            return _expand_fn(cshape, full, fill,
                              str(host.dtype))(jnp.asarray(compact))
    transferred[0] += int(host.nbytes)
    return jnp.asarray(host)


def publish_segment(seg: Segment, to_device: bool = True):
    """upload_segment + transfer accounting: returns (arrays, meta,
    transfer_nbytes). With DELTA_PUBLISH off (the default) this is
    exactly upload_segment and the transfer equals the resident image;
    with it on, only the populated prefixes cross the host→device link
    and transfer_nbytes is the byte-exact compact total."""
    if not DELTA_PUBLISH or not to_device:
        arrays, meta = upload_segment(seg, to_device=to_device)
        return arrays, meta, tree_nbytes(arrays)
    t0 = time.monotonic()
    host, meta = _host_image(seg)
    spec = _compact_spec(seg, meta)
    transferred = [0]
    t1 = time.monotonic()
    arrays = _delta_tree(host, spec, transferred)
    _note_install(meta, transferred[0], t0, t1, time.monotonic())
    return arrays, meta, transferred[0]


def refresh_live(arrays: Dict, seg: Segment):
    """Re-upload just the liveness bitmap after deletes."""
    d_pad = arrays["live"].shape[0]
    live = np.zeros(d_pad, dtype=bool)
    live[:seg.num_docs] = seg.live
    arrays["live"] = jnp.asarray(live) if isinstance(arrays["post_docs"], jnp.ndarray) \
        else live
    return arrays
