"""Immutable columnar segment format — the TPU replacement for Lucene's file formats.

Reference behaviors re-designed here:
- Lucene postings lists (reference hot loop: search/internal/ContextIndexSearcher.java:260
  driving BulkScorer over per-term postings) become **blocked CSR** arrays: one
  global `[num_blocks, 128]` int32 doc-id matrix plus a parallel float32
  term-frequency matrix, padded with -1/0. A (field, term) entry in the term
  dictionary points at a contiguous run of blocks. A query gathers just its
  terms' block rows on device and scatter-adds BM25 partials into a dense
  per-doc score vector — turning Lucene's pointer-chasing skip lists into a
  dense, MXU/VPU-friendly batch computation.
- Lucene norms (SmallFloat-encoded doc lengths used by BM25Similarity) are kept
  bit-identical: `smallfloat_int_to_byte4` mirrors Lucene's
  `SmallFloat.intToByte4`, and scoring decodes each byte to the value of
  Lucene's 256-entry length table (on the device arithmetically, from the
  byte carried beside each posting: `posting_norms`), so BM25 scores match
  Lucene's to float precision.
- Doc values (reference: index/fielddata/) become value-pair columns
  `(doc_ids[int32], values[float64])` per field — the scatter/segment-sum
  friendly layout for aggregations — plus a dense `exists` bitmap per field.
- Keyword fields get sorted ordinal dictionaries (reference:
  index/fielddata/ordinals/GlobalOrdinalsBuilder.java builds the same thing
  lazily; here ordinals are a seal-time artifact).

Segments are append-only and immutable after `seal()`, exactly like Lucene
segments; deletes are a liveness bitmap applied in the scoring kernels.
"""

from __future__ import annotations

import hashlib
import itertools
from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from opensearch_tpu.index.mapper import MapperService, ParsedDocument

BLOCK = 128  # postings block width == TPU lane width

# ------------------------------------------------------------- SmallFloat ----

def smallfloat_int_to_byte4(i: int) -> int:
    """Lucene SmallFloat.intToByte4: lossy 8-bit encoding of a non-negative int.

    Values < 16 are exact; larger values keep 3 mantissa bits + implicit leading
    one, with the exponent biased by +1 in the high 5 bits.
    """
    if i < 0:
        raise ValueError(f"only supports positive values, got {i}")
    num_bits = i.bit_length()
    if num_bits < 4:
        return i
    shift = num_bits - 4
    encoded = (i >> shift) & 0x07
    encoded |= (shift + 1) << 3
    if encoded > 255:
        return 255
    return encoded


def smallfloat_byte4_to_int(b: int) -> int:
    """Inverse of intToByte4 (returns the quantization bucket's lower bound)."""
    bits = b & 0x07
    shift = (b >> 3) - 1
    if shift == -1:
        return bits
    return (bits | 0x08) << shift


# 256-entry doc-length decode table, identical to BM25Similarity.LENGTH_TABLE
LENGTH_TABLE = np.array([smallfloat_byte4_to_int(b) for b in range(256)],
                        dtype=np.float32)


def _pad_to(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def ident_pairs(col) -> bool:
    """True when a doc-value column's (doc, value) pairs are the identity
    layout (single-valued dense column: doc k <-> lane k, -1 tail). Device
    programs then SLICE or pad per-lane results into doc space instead of
    gathering/scattering — XLA's gather/scatter lower to scalar loops on
    CPU and a serial path on TPU, and these ops sit on every query's hot
    path.

    Memoized on the column: sealed columns are immutable, and this is
    called per range/terms clause compile (the O(n_pairs) scan must not
    run per query)."""
    cached = getattr(col, "_ident_pairs", None)
    if cached is not None:
        return cached
    d = col.doc_ids
    nv = int((d >= 0).sum())
    out = bool(np.array_equal(d[:nv], np.arange(nv, dtype=d.dtype))
               and (d[nv:] < 0).all())
    col._ident_pairs = out
    return out


def token_mask_rows(token_count: np.ndarray, t_bucket: int) -> np.ndarray:
    """Host mask of the real (non-padded) rows in a flattened [D*T, dims]
    token block — seal-time PQ trains only on real token vectors."""
    lanes = np.arange(t_bucket)[None, :] < token_count[:, None]
    return lanes.reshape(-1)


def pad_bucket(n: int, minimum: int = 128) -> int:
    """Round up to the next power-of-two bucket to bound jit recompiles."""
    size = max(minimum, 1)
    while size < n:
        size *= 2
    return size


# BM25 parameters the seal-time block bounds are computed against. Query-time
# k1/b/avgdl may differ (per-request similarity overrides, shard-level vs
# segment-level avgdl); the compiler ships a >=1 correction factor (bscale)
# derived from these constants, so the bounds stay upper bounds under any
# query parameters (search/compile.py:_blockmax_scale).
SEAL_K1 = 1.2
SEAL_B = 0.75

_BOUNDS_CHUNK_ROWS = 1 << 16    # bound host memory on multi-GB postings


def _field_block_rows(seg: "Segment") -> Dict[str, np.ndarray]:
    """field → the posting-block rows of its terms (int64, run by run): a
    (field, term) entry of the term dict owns one contiguous run of blocks,
    so every real block belongs to exactly one field."""
    runs: Dict[str, List[np.ndarray]] = {}
    for (field, _term), tm in seg.term_dict.items():
        if tm.num_blocks:
            runs.setdefault(field, []).append(
                np.arange(tm.start_block, tm.start_block + tm.num_blocks,
                          dtype=np.int64))
    return {field: np.concatenate(r) for field, r in runs.items()}


def posting_norms(seg: "Segment") -> np.ndarray:
    """The SmallFloat norm byte of every posting, uint8 [NB, BLOCK] lane for
    lane with `post_docs`/`post_tf`: `seg.norms[field of block b][post_docs[b,
    l]]`. 0 in padding lanes and in blocks of fields without norms (those
    score with b=0, which multiplies the decoded length away).

    A posting's norm is fixed when the segment is sealed, so the scoring
    kernels read it beside the tf (ops/bm25.py `posting_lengths`) instead of
    gathering `norms[doc]` per query and per lane. Derived at upload from
    what the segment already holds; not memoized (one byte a lane of host
    memory for something read once).
    """
    out = np.zeros(seg.post_docs.shape, dtype=np.uint8)
    for field, rows in _field_block_rows(seg).items():
        norm = seg.norms.get(field)
        if norm is None:
            continue
        for lo in range(0, len(rows), _BOUNDS_CHUNK_ROWS):
            chunk = rows[lo:lo + _BOUNDS_CHUNK_ROWS]
            docs = seg.post_docs[chunk]
            vals = np.take(norm, docs, mode="clip")     # -1 reads doc 0
            vals[docs < 0] = 0
            out[chunk] = vals
    return out


def block_score_bounds(seg: "Segment") -> np.ndarray:
    """Per-posting-block BM25 score upper bounds: max over the block's lanes
    of tf/(tf + SEAL_K1·(1−SEAL_B+SEAL_B·dl/avgdl)), f32 [NB].

    The block-max skipping invariant (BM25S / Lucene BMW analog): every
    partial score a query can extract from block X of (field, term) is
    ≤ w·(k1+1)·bscale·bounds[X], so blocks whose summed upper bound falls
    below the competitive threshold provably hold no top-k docs. Fields
    without norms score with b=0 (denominator tf + k1), matching the
    query-side omit-norms path. Padding lanes (doc -1, tf 0) contribute 0.

    Memoized on the segment: sealed postings are immutable and this scans
    every lane once (chunked — NB can reach millions of rows at 10M docs).
    """
    cached = getattr(seg, "_block_bounds", None)
    if cached is not None:
        return cached
    nb = seg.post_docs.shape[0]
    bounds = np.zeros(nb, dtype=np.float32)
    # the denominator constant c(dl) = 1−b+b·dl/avgdl is a per-field
    # per-doc vector
    for field, rows in _field_block_rows(seg).items():
        norm = seg.norms.get(field)
        stats = seg.field_stats.get(field)
        if norm is not None and stats is not None and stats.doc_count > 0:
            avgdl = max(stats.sum_total_term_freq / stats.doc_count, 1e-9)
            dl = LENGTH_TABLE[norm]
            c_doc = (1.0 - SEAL_B + SEAL_B * dl / avgdl).astype(np.float32)
        else:
            c_doc = None        # omit-norms field: c ≡ 1
        for lo in range(0, len(rows), _BOUNDS_CHUNK_ROWS):
            chunk = rows[lo:lo + _BOUNDS_CHUNK_ROWS]
            docs = seg.post_docs[chunk]
            tfs = seg.post_tf[chunk]
            if c_doc is None:
                c = np.float32(1.0)
            else:
                c = c_doc[np.where(docs >= 0, docs, 0)]
            g = tfs / (tfs + np.float32(SEAL_K1) * c)
            g[docs < 0] = 0.0
            bounds[chunk] = g.max(axis=1)
    seg._block_bounds = bounds
    return bounds


# ------------------------------------------------------------ data classes ---

@dataclass
class TermMeta:
    """Per-(field,term) postings metadata (Lucene TermState analog)."""
    doc_freq: int
    total_term_freq: int
    start_block: int
    num_blocks: int


@dataclass
class FieldStats:
    """Per text/keyword field collection stats feeding BM25 idf/avgdl.

    Reference: Lucene CollectionStatistics as consumed by BM25Similarity.
    """
    doc_count: int = 0            # docs containing the field
    sum_total_term_freq: int = 0  # total tokens across docs
    sum_doc_freq: int = 0


@dataclass
class DocValuesColumn:
    """Value-pair doc values for one field: sorted (doc, value) pairs.

    `value_ords` rank-encodes each value into `unique` (sorted distinct f64s).
    Device kernels only ever see int32 ranks — range bounds are converted to
    rank space host-side via searchsorted, keeping comparisons exact without
    f64 emulation on TPU. `unique` stays host-side; a float32 copy is uploaded
    for metric aggregations.
    """
    doc_ids: np.ndarray      # int32 [NV]
    values: np.ndarray       # float64 [NV] exact values (host only)
    exists: np.ndarray      # bool [D]
    counts: np.ndarray       # int32 [D] values per doc
    value_ords: np.ndarray   # int32 [NV] rank into `unique`
    unique: np.ndarray       # float64 [U] sorted distinct values (host)


@dataclass
class OrdinalsColumn:
    """Ordinal-encoded string doc values: sorted dictionary + (doc, ord) pairs."""
    doc_ids: np.ndarray      # int32 [NV]
    ords: np.ndarray         # int32 [NV]
    exists: np.ndarray       # bool [D]
    dictionary: List[str]    # ord → term, lexicographically sorted
    ord_hashes: np.ndarray   # uint64 [card] murmur-style hash per dictionary entry


@dataclass
class VectorColumn:
    vectors: np.ndarray      # float32 [D, dims]
    exists: np.ndarray       # bool [D]
    ivf: Any = None          # Optional[opensearch_tpu.ops.knn.IVFIndex]


@dataclass
class RankVectorsColumn:
    """Late-interaction multi-vector doc values (rank_vectors fields):
    one padded [T_bucket, dims] token matrix per doc, scored by the
    fused MaxSim kernels (ops/maxsim.py). `t_bucket` is the segment's
    power-of-two token bucket (pad_bucket of the longest stored doc,
    capped by the mapping's max_tokens bucket) so device executables
    key on the bucket, not the raw token count. PQ-compressed mappings
    additionally carry seal-trained uint8 codes + the codebook; the
    raw f32 matrices stay host-side for rescoring and differentials."""
    tokens: np.ndarray       # float32 [D, T_bucket, dims], padded lanes 0
    token_count: np.ndarray  # int32 [D] real tokens per doc
    exists: np.ndarray       # bool [D] doc has >= 1 token vector
    t_bucket: int
    codes: Optional[np.ndarray] = None      # uint8 [D, T_bucket, M]
    codebook: Optional[np.ndarray] = None   # float32 [M, 256, dsub]


_SEGMENT_UID = itertools.count(1)


class PrefixedIds:
    """`_id`s `f"{prefix}{i}"` for i in [0, n), made when one is read: a
    sequence a bulk builder hands to `Segment` in place of a list of n
    `str`, which at tens of millions of documents is gigabytes of
    Python objects that a `size: 0` workload never renders. Answers
    what the list answered: length, index, slice, iteration, equality,
    and the ordinal of an id (`ord_of_id`, the arithmetic inverse that
    `Segment` uses in place of a dict)."""

    __slots__ = ("prefix", "n")

    def __init__(self, prefix: str, n: int):
        self.prefix, self.n = prefix, int(n)

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [f"{self.prefix}{j}" for j in range(*i.indices(self.n))]
        i = int(i)
        if i < 0:
            i += self.n
        if not 0 <= i < self.n:
            raise IndexError(i)
        return f"{self.prefix}{i}"

    def __iter__(self):
        prefix = self.prefix
        return (f"{prefix}{i}" for i in range(self.n))

    def __eq__(self, other):
        if isinstance(other, PrefixedIds):
            return (self.prefix, self.n) == (other.prefix, other.n)
        return len(other) == self.n and all(
            a == b for a, b in zip(self, other))

    __hash__ = None

    def ord_of_id(self, doc_id) -> Optional[int]:
        """i where `self[i] == doc_id`, else None: only the canonical
        decimal spelling of an ordinal in range is an id."""
        if not isinstance(doc_id, str) \
                or not doc_id.startswith(self.prefix):
            return None
        digits = doc_id[len(self.prefix):]
        if not digits.isascii() or not digits.isdigit() \
                or (digits[0] == "0" and digits != "0"):
            return None
        i = int(digits)
        return i if i < self.n else None


class _IdOrds:
    """`Segment._id_to_ord` over a `PrefixedIds`: the `get` of the dict
    the list form builds, by arithmetic."""

    __slots__ = ("ids",)

    def __init__(self, ids: PrefixedIds):
        self.ids = ids

    def get(self, doc_id, default=None):
        i = self.ids.ord_of_id(doc_id)
        return default if i is None else i

    def __contains__(self, doc_id) -> bool:
        return self.ids.ord_of_id(doc_id) is not None


class Segment:
    """A sealed, immutable columnar segment (host numpy representation)."""

    def __init__(self, seg_id: str, num_docs: int, doc_ids: List[str],
                 sources: List[Optional[dict]],
                 term_dict: Dict[Tuple[str, str], TermMeta],
                 post_docs: np.ndarray, post_tf: np.ndarray,
                 norms: Dict[str, np.ndarray],
                 field_stats: Dict[str, FieldStats],
                 numeric_dv: Dict[str, DocValuesColumn],
                 ordinal_dv: Dict[str, OrdinalsColumn],
                 vector_dv: Dict[str, VectorColumn],
                 positions: Optional[Dict[Tuple[str, str], List[np.ndarray]]] = None,
                 parent_ptr: Optional[np.ndarray] = None,
                 path_ords: Optional[np.ndarray] = None,
                 nested_paths: Optional[List[str]] = None,
                 rank_vectors_dv: Optional[Dict[str, RankVectorsColumn]] = None):
        self.seg_id = seg_id
        # process-unique identity: seg_id is a per-engine counter and can
        # repeat across indices/engines, so caches keyed on segments (e.g.
        # the SPMD HbmShardSet residency cache) must use `uid`
        self.uid = next(_SEGMENT_UID)
        self.num_docs = num_docs
        self.doc_ids = doc_ids              # _id per local doc ord
        self.sources = sources              # _source per local doc ord
        self.term_dict = term_dict
        self.post_docs = post_docs          # int32 [NB, BLOCK], -1 padded
        self.post_tf = post_tf              # float32 [NB, BLOCK]
        self.norms = norms                  # field → uint8 [D]
        self.field_stats = field_stats
        self.numeric_dv = numeric_dv
        self.ordinal_dv = ordinal_dv
        self.vector_dv = vector_dv
        self.rank_vectors_dv = rank_vectors_dv or {}
        # host-only term positions per (field, term), lists parallel to the
        # postings entries — consumed by the phrase-query host verifier
        # (reference: Lucene's .pos files feeding PhraseQuery's ExactPhraseMatcher)
        self.positions = positions or {}
        self.live = np.ones(num_docs, dtype=bool)  # deletes bitmap
        # doc-block structure (Lucene block-join layout): nested child rows
        # sit immediately before their parent row. parent_ptr[-1 for
        # roots]; path_ords indexes nested_paths (-1 for roots). Root-only
        # segments get the trivial all-root encoding.
        self.parent_ptr = parent_ptr if parent_ptr is not None \
            else np.full(num_docs, -1, dtype=np.int32)
        self.path_ords = path_ords if path_ords is not None \
            else np.full(num_docs, -1, dtype=np.int32)
        self.nested_paths = list(nested_paths or [])
        self.root = self.parent_ptr < 0
        # _id -> local ord, built on first use (`_id_to_ord`): a search
        # that fetches no document by id never pays for it
        self._id_ords = None
        # doc_id → (version, seq_no, primary_term) — Lucene stores these as
        # per-doc fields (_version docvalue, _seq_no); here a host-side map
        # attached by the engine at seal/merge time
        self.doc_meta: Dict[str, Tuple[int, int, int]] = {}

    @property
    def _id_to_ord(self):
        """_id -> local ord, on first use: a dict over a list of ids
        (the last row of a repeated id wins, as a dict comprehension
        has it), arithmetic over a `PrefixedIds`. Clones made before
        the first use build their own; the ids are immutable, so every
        copy builds the same map."""
        ords = self.__dict__.get("_id_ords")
        if ords is None:
            ids = self.doc_ids
            ords = _IdOrds(ids) if isinstance(ids, PrefixedIds) else \
                {d: i for i, d in enumerate(ids) if d is not None}
            self._id_ords = ords
        return ords

    @property
    def live_doc_count(self) -> int:
        return int(self.live.sum())

    def ord_of(self, doc_id: str) -> Optional[int]:
        ord_ = self._id_to_ord.get(doc_id)
        if ord_ is None or not self.live[ord_]:
            return None
        return ord_

    def delete(self, doc_id: str) -> bool:
        ord_ = self._id_to_ord.get(doc_id)
        if ord_ is None or not self.live[ord_]:
            return False
        self.live[ord_] = False
        if self.nested_paths:
            # the whole doc block dies with its root (Lucene deletes the
            # child docs of a block together with the parent)
            self.live[self.parent_ptr == ord_] = False
        return True

    def clone_for_copy(self) -> "Segment":
        """Shallow copy for recovery/segment-replication installs: immutable
        columns shared, mutable per-copy state (live bitmap, doc_meta)
        cloned — the in-memory analog of copying segment files while each
        copy keeps its own .liv deletes file."""
        import copy as _copy
        clone = _copy.copy(self)
        clone.uid = next(_SEGMENT_UID)
        clone.live = self.live.copy()
        clone.doc_meta = dict(self.doc_meta)
        return clone

    def __setstate__(self, state):
        # a segment arriving over the wire (recovery) carries the SENDER's
        # uid; re-mint locally so process-wide uniqueness holds
        self.__dict__.update(state)
        self.uid = next(_SEGMENT_UID)

    def get_term(self, field: str, term: str) -> Optional[TermMeta]:
        return self.term_dict.get((field, term))

    def _positions_for(self, field: str, term: str) -> Optional[Dict[int, np.ndarray]]:
        """doc ord → positions array for one term (host phrase matching)."""
        key = (field, term)
        pos_lists = self.positions.get(key)
        meta = self.term_dict.get(key)
        if pos_lists is None or meta is None:
            return None
        cache = getattr(self, "_pos_cache", None)
        if cache is None:
            cache = self._pos_cache = {}
        if key not in cache:
            docs = self.post_docs[
                meta.start_block:meta.start_block + meta.num_blocks].ravel()
            docs = docs[docs >= 0]
            cache[key] = {int(d): pos_lists[i] for i, d in enumerate(docs)}
        return cache[key]

    def terms_for_field(self, field: str) -> List[str]:
        return [t for (f, t) in self.term_dict if f == field]

    def memory_bytes(self) -> int:
        total = self.post_docs.nbytes + self.post_tf.nbytes
        for arr in self.norms.values():
            total += arr.nbytes
        for col in self.numeric_dv.values():
            total += (col.doc_ids.nbytes + col.values.nbytes + col.exists.nbytes
                      + col.counts.nbytes + col.value_ords.nbytes
                      + col.unique.nbytes)
        for col in self.ordinal_dv.values():
            total += (col.doc_ids.nbytes + col.ords.nbytes + col.exists.nbytes
                      + col.ord_hashes.nbytes)
        for col in self.vector_dv.values():
            total += col.vectors.nbytes + col.exists.nbytes
        for col in self.rank_vectors_dv.values():
            total += (col.tokens.nbytes + col.token_count.nbytes
                      + col.exists.nbytes)
            if col.codes is not None:
                total += col.codes.nbytes + col.codebook.nbytes
        for pos_lists in self.positions.values():
            total += sum(p.nbytes for p in pos_lists)
        return total


def _hash64(s: str) -> int:
    """Stable 64-bit hash for HLL cardinality (host-side, seal-time)."""
    return int.from_bytes(hashlib.blake2b(s.encode("utf-8"), digest_size=8).digest(),
                          "little")


# ------------------------------------------------------------ the builder ----

class SegmentBuilder:
    """In-memory segment under construction (Lucene IndexWriter's RAM buffer analog).

    Reference write path: index/engine/InternalEngine.java:1098 indexIntoLucene
    → IndexWriter.addDocument. Here documents accumulate host-side; `seal()`
    produces the immutable columnar arrays in one vectorized pass.
    """

    def __init__(self, mapper: MapperService, seg_id: str = "seg_0"):
        self.mapper = mapper
        self.seg_id = seg_id
        self.doc_ids: List[str] = []
        self.sources: List[Optional[dict]] = []
        # (field, term) → [(doc_ord, tf)] accumulated in insertion doc order
        self._postings: Dict[Tuple[str, str], List[Tuple[int, int]]] = {}
        self._positions: Dict[Tuple[str, str], List[np.ndarray]] = {}
        self._field_lengths: Dict[str, Dict[int, int]] = {}
        self._numeric: Dict[str, List[Tuple[int, float]]] = {}
        self._ordinal_raw: Dict[str, List[Tuple[int, str]]] = {}
        self._vectors: Dict[str, Dict[int, List[float]]] = {}
        self._rank_vectors: Dict[str, Dict[int, List[List[float]]]] = {}
        self._field_stats: Dict[str, FieldStats] = {}
        # doc-block structure (Lucene block-join layout: nested child rows
        # precede their parent row): parent row ord per row (-1 = root) and
        # nested-path ordinal per row (-1 = root)
        self._parent_ptr: List[int] = []
        self._path_ords: List[int] = []
        self._nested_paths: List[str] = []

    def __len__(self):
        return len(self.doc_ids)

    @property
    def num_docs(self):
        return len(self.doc_ids)

    def add(self, doc: ParsedDocument) -> int:
        child_ords = []
        for path, child_fields in getattr(doc, "children", ()):
            if path not in self._nested_paths:
                self._nested_paths.append(path)
            child_ords.append(self._add_row(
                None, None, child_fields,
                path_ord=self._nested_paths.index(path)))
        parent_ord = self._add_row(doc.doc_id, doc.source, doc.fields)
        for c in child_ords:
            self._parent_ptr[c] = parent_ord
        return parent_ord

    def _add_row(self, doc_id, source, fields,
                 path_ord: int = -1) -> int:
        ord_ = len(self.doc_ids)
        self.doc_ids.append(doc_id)
        self.sources.append(source)
        self._parent_ptr.append(-1)
        self._path_ords.append(path_ord)
        for field, pf in fields.items():
            ft = self.mapper.get_field(field)
            if ft is None:
                continue
            if pf.terms is not None and ft.index:
                tf_map: Dict[str, int] = {}
                pos_map: Dict[str, List[int]] = {}
                for term, pos in pf.terms:
                    tf_map[term] = tf_map.get(term, 0) + 1
                    pos_map.setdefault(term, []).append(pos)
                for term, tf in tf_map.items():
                    self._postings.setdefault((field, term), []).append((ord_, tf))
                    self._positions.setdefault((field, term), []).append(
                        np.asarray(sorted(pos_map[term]), dtype=np.int32))
                self._field_lengths.setdefault(field, {})[ord_] = pf.length
                stats = self._field_stats.setdefault(field, FieldStats())
                stats.doc_count += 1
                stats.sum_total_term_freq += pf.length
                stats.sum_doc_freq += len(tf_map)
            if pf.exact_values is not None:
                if ft.index:
                    seen = set()
                    for v in pf.exact_values:
                        if v not in seen:
                            seen.add(v)
                            self._postings.setdefault((field, v), []).append((ord_, 1))
                    stats = self._field_stats.setdefault(field, FieldStats())
                    stats.doc_count += 1
                    stats.sum_total_term_freq += len(pf.exact_values)
                    stats.sum_doc_freq += len(seen)
                if ft.doc_values and ft.has_ordinals:
                    for v in pf.exact_values:
                        self._ordinal_raw.setdefault(field, []).append((ord_, v))
            if pf.numeric_values is not None and ft.doc_values:
                for v in pf.numeric_values:
                    self._numeric.setdefault(field, []).append((ord_, v))
            if pf.vector is not None:
                self._vectors.setdefault(field, {})[ord_] = pf.vector
            if pf.token_vectors is not None:
                self._rank_vectors.setdefault(field, {})[ord_] = pf.token_vectors
        return ord_

    def seal(self) -> Segment:
        n_docs = len(self.doc_ids)

        # ---- postings: sort terms (field, term) for deterministic layout
        term_dict: Dict[Tuple[str, str], TermMeta] = {}
        block_rows_docs: List[np.ndarray] = []
        block_rows_tf: List[np.ndarray] = []
        next_block = 0
        for key in sorted(self._postings.keys()):
            plist = self._postings[key]  # already in ascending doc order
            docs = np.fromiter((d for d, _ in plist), dtype=np.int32, count=len(plist))
            tfs = np.fromiter((t for _, t in plist), dtype=np.float32, count=len(plist))
            padded = _pad_to(len(plist), BLOCK)
            docs_p = np.full(padded, -1, dtype=np.int32)
            tfs_p = np.zeros(padded, dtype=np.float32)
            docs_p[:len(plist)] = docs
            tfs_p[:len(plist)] = tfs
            nb = padded // BLOCK
            block_rows_docs.append(docs_p.reshape(nb, BLOCK))
            block_rows_tf.append(tfs_p.reshape(nb, BLOCK))
            term_dict[key] = TermMeta(doc_freq=len(plist),
                                      total_term_freq=int(tfs.sum()),
                                      start_block=next_block, num_blocks=nb)
            next_block += nb
        if block_rows_docs:
            post_docs = np.concatenate(block_rows_docs, axis=0)
            post_tf = np.concatenate(block_rows_tf, axis=0)
        else:
            post_docs = np.full((1, BLOCK), -1, dtype=np.int32)
            post_tf = np.zeros((1, BLOCK), dtype=np.float32)

        # ---- norms (SmallFloat-quantized field lengths)
        norms: Dict[str, np.ndarray] = {}
        for field, lengths in self._field_lengths.items():
            arr = np.zeros(n_docs, dtype=np.uint8)
            for ord_, length in lengths.items():
                arr[ord_] = smallfloat_int_to_byte4(length)
            norms[field] = arr

        # ---- numeric doc values as sorted (doc, value) pairs
        numeric_dv: Dict[str, DocValuesColumn] = {}
        for field, pairs in self._numeric.items():
            pairs.sort(key=lambda p: p[0])
            doc_arr = np.fromiter((d for d, _ in pairs), dtype=np.int32, count=len(pairs))
            val_arr = np.fromiter((v for _, v in pairs), dtype=np.float64, count=len(pairs))
            exists = np.zeros(n_docs, dtype=bool)
            if len(doc_arr):
                exists[doc_arr] = True
            counts = np.bincount(doc_arr, minlength=n_docs).astype(np.int32)
            unique, value_ords = np.unique(val_arr, return_inverse=True)
            numeric_dv[field] = DocValuesColumn(doc_arr, val_arr, exists, counts,
                                                value_ords.astype(np.int32), unique)

        # ---- ordinal doc values: sorted dictionary, (doc, ord) pairs
        ordinal_dv: Dict[str, OrdinalsColumn] = {}
        for field, pairs in self._ordinal_raw.items():
            dictionary = sorted({v for _, v in pairs})
            ord_of = {v: i for i, v in enumerate(dictionary)}
            pairs.sort(key=lambda p: p[0])
            doc_arr = np.fromiter((d for d, _ in pairs), dtype=np.int32, count=len(pairs))
            ords = np.fromiter((ord_of[v] for _, v in pairs), dtype=np.int32,
                               count=len(pairs))
            exists = np.zeros(n_docs, dtype=bool)
            if len(doc_arr):
                exists[doc_arr] = True
            hashes = np.array([_hash64(v) for v in dictionary], dtype=np.uint64) \
                if dictionary else np.zeros(0, dtype=np.uint64)
            ordinal_dv[field] = OrdinalsColumn(doc_arr, ords, exists, dictionary, hashes)

        # ---- vectors: dense [D, dims]; IVF built at seal for ANN mappings
        vector_dv: Dict[str, VectorColumn] = {}
        for field, rows in self._vectors.items():
            ft = self.mapper.get_field(field)
            mat = np.zeros((n_docs, ft.dims), dtype=np.float32)
            exists = np.zeros(n_docs, dtype=bool)
            for ord_, vec in rows.items():
                mat[ord_] = np.asarray(vec, dtype=np.float32)
                exists[ord_] = True
            col = VectorColumn(mat, exists)
            if ft.knn_method == "ivf" and int(exists.sum()) >= 256:
                from opensearch_tpu.ops.knn import build_ivf
                col.ivf = build_ivf(mat, exists, nlist=ft.knn_nlist,
                                    nprobe=ft.knn_nprobe)
            vector_dv[field] = col

        # ---- rank_vectors: padded [D, T_bucket, dims] token matrices with
        # token-count mask lanes; PQ mappings train their codebook at seal
        # (the Lucene-analog moment — expensive work happens once per
        # segment, never on the query path)
        rank_vectors_dv: Dict[str, RankVectorsColumn] = {}
        for field, rows in self._rank_vectors.items():
            ft = self.mapper.get_field(field)
            max_seen = max((len(toks) for toks in rows.values()), default=0)
            t_bucket = min(pad_bucket(max(max_seen, 1), minimum=8),
                           pad_bucket(ft.max_tokens, minimum=8))
            tokens = np.zeros((n_docs, t_bucket, ft.dims), dtype=np.float32)
            token_count = np.zeros(n_docs, dtype=np.int32)
            exists = np.zeros(n_docs, dtype=bool)
            for ord_, toks in rows.items():
                nt = len(toks)
                if nt:
                    tokens[ord_, :nt] = np.asarray(toks, dtype=np.float32)
                token_count[ord_] = nt
                exists[ord_] = nt > 0
            col = RankVectorsColumn(tokens, token_count, exists, t_bucket)
            if ft.compression == "pq":
                from opensearch_tpu.ops.maxsim import train_pq, encode_pq
                flat = tokens.reshape(-1, ft.dims)
                real = flat[token_mask_rows(token_count, t_bucket)]
                col.codebook = train_pq(real, ft.pq_m)
                codes = encode_pq(flat, col.codebook)
                col.codes = codes.reshape(n_docs, t_bucket, ft.pq_m)
            rank_vectors_dv[field] = col

        return Segment(self.seg_id, n_docs, list(self.doc_ids), list(self.sources),
                       term_dict, post_docs, post_tf, norms, self._field_stats,
                       numeric_dv, ordinal_dv, vector_dv,
                       positions=dict(self._positions),
                       parent_ptr=np.asarray(self._parent_ptr, np.int32),
                       path_ords=np.asarray(self._path_ords, np.int32),
                       nested_paths=list(self._nested_paths),
                       rank_vectors_dv=rank_vectors_dv)


def merge_segments(mapper: MapperService, segments: List[Segment],
                   seg_id: str) -> Segment:
    """Merge live docs of several segments into one (Lucene TieredMergePolicy's
    work product; reference: index/engine merges via IndexWriter).

    Round-trips through the builder with reconstructed ParsedDocuments parsed
    from _source — correctness-first; a zero-reparse columnar merge is a later
    optimization.
    """
    builder = SegmentBuilder(mapper, seg_id=seg_id)
    doc_meta = {}
    for seg in segments:
        for ord_ in range(seg.num_docs):
            if not seg.live[ord_] or seg.doc_ids[ord_] is None:
                # child rows re-expand from their root's _source reparse
                continue
            doc = mapper.parse_document(seg.doc_ids[ord_], seg.sources[ord_] or {})
            builder.add(doc)
            if seg.doc_ids[ord_] in seg.doc_meta:
                doc_meta[seg.doc_ids[ord_]] = seg.doc_meta[seg.doc_ids[ord_]]
    merged = builder.seal()
    merged.doc_meta = doc_meta
    return merged
