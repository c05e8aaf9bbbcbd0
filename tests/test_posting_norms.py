"""The posting's norm byte rides in the posting block (ISSUE 26).

The device image carries `post_norm` lane for lane with `post_docs`/`post_tf`
and the BM25 kernels decode it arithmetically (`ops/bm25.py posting_lengths`).
Nothing about an answer may change: these tests hold the leaf to
`seg.norms`, the decode to Lucene's LENGTH_TABLE, and all three kernels
(dense, candidate, block-max phase A) to the expression they replaced,
`LENGTH_TABLE[seg.norms[field][doc]]`.
"""

import contextlib
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import (LENGTH_TABLE, SegmentBuilder,
                                          posting_norms)
from opensearch_tpu.ops import bm25 as _bm25
from opensearch_tpu.ops import device_segment as devseg
from opensearch_tpu.search import executor as _executor
from opensearch_tpu.search.compile import Compiler, ShardStats
from opensearch_tpu.search.executor import SearchExecutor, ShardReader

MAPPING = {"properties": {"title": {"type": "text"},
                          "body": {"type": "text"},
                          "tag": {"type": "keyword"}}}
N_DOCS = 2600
K1 = np.float32(1.2)


@pytest.fixture(autouse=True)
def _gate_off_pristine():
    _bm25.BLOCKMAX = False
    yield
    _bm25.BLOCKMAX = False


@pytest.fixture(scope="module")
def corpus():
    """Two text fields whose lengths differ doc by doc (titles 1-12 tokens,
    bodies 1-400: norm bytes on both sides of the e == 0 boundary), and a
    norm-less keyword field. `alpha`/`beta`/`gamma` are common enough that
    a two-term clause clears block-max admission (16 blocks)."""
    rng = random.Random(26)
    mapper = MapperService(MAPPING)
    builder = SegmentBuilder(mapper)
    common = ["alpha", "beta", "gamma"]
    rare = [f"r{i}" for i in range(40)]
    for i in range(N_DOCS):
        body_len = rng.choice([1, 3, 7, 15, 16, 40, 90, 200, 400])
        body = [w for w in common if rng.random() < 0.8]
        # a burst of one common term, clustered in the first docs, gives
        # phase A something to prune
        if i < 50:
            body += ["alpha"] * 30
        body += rng.choices(rare, k=max(body_len - len(body), 0))
        title = rng.choices(common + rare, k=rng.randint(1, 12))
        builder.add(mapper.parse_document(
            f"d{i}", {"title": " ".join(title), "body": " ".join(body),
                      "tag": rng.choice(["red", "green", "blue"])}))
    seg = builder.seal()
    image, meta = devseg.upload_segment(seg)
    return mapper, seg, image, meta


def _block_fields(seg):
    field_of = np.full(seg.post_docs.shape[0], None, dtype=object)
    for (field, _t), tm in seg.term_dict.items():
        field_of[tm.start_block:tm.start_block + tm.num_blocks] = field
    return field_of


# ------------------------------------------------------------ (a) decode

def test_decode_equals_length_table_for_all_256_bytes_under_jit():
    seg = {"post_norm": jnp.arange(256, dtype=jnp.uint8).reshape(2, 128)}
    got = np.asarray(jax.jit(_bm25.posting_lengths)(
        seg, jnp.arange(2, dtype=jnp.int32))).ravel()
    assert got.dtype == np.float32
    assert np.array_equal(got.view(np.int32), LENGTH_TABLE.view(np.int32))
    # every decoded length is a normal f32 or an exact small integer: no
    # denormal bit pattern (PR 21: those read back 0 on the chip)
    bits = got.view(np.int32)
    assert all(b == 0 or (b >> 23) & 0xFF > 0 for b in bits.tolist())


# -------------------------------------------------------------- (b) leaf

def test_post_norm_is_the_docs_norm_in_the_blocks_field(corpus):
    _, seg, image, _ = corpus
    assert set(seg.norms) == {"title", "body"}
    arrays = {k: np.asarray(image[k])
              for k in ("post_norm", "post_docs", "post_tf")}
    leaf = arrays["post_norm"]
    assert leaf.dtype == np.uint8
    assert leaf.shape == arrays["post_docs"].shape == arrays["post_tf"].shape
    nb = seg.post_docs.shape[0]
    want = np.zeros(leaf.shape, np.uint8)
    fields_seen = set()
    for b, field in enumerate(_block_fields(seg)):
        norm = seg.norms.get(field)
        fields_seen.add(field)
        if norm is None:
            continue                    # keyword blocks: 0 in every lane
        for lane, doc in enumerate(seg.post_docs[b]):
            if doc >= 0:
                want[b, lane] = norm[doc]
    assert fields_seen >= {"title", "body", "tag"}
    assert np.array_equal(leaf, want)
    assert not leaf[nb:].any()          # padding blocks
    assert (leaf[:nb][seg.post_docs < 0] == 0).all()   # padding lanes
    assert np.array_equal(posting_norms(seg), want[:nb])
    # both sides of the e == 0 boundary occur, so (c) exercises both arms
    real = leaf[:nb][seg.post_docs >= 0]
    assert (real < 8).any() and (real >= 48).any()


def test_length_table_left_the_image_and_norms_stayed(corpus):
    image = corpus[2]
    assert "length_table" not in image
    assert image["norms"].shape[0] == 2      # `exists` on text reads it


# ---------------------------------------------------- (c) the old expression
#
# Two comparisons a kernel. Bit for bit: the kernel as it is against the
# kernel with the two lines it had before swapped in for the helper
# (`LENGTH_TABLE[norms_row[doc]]`, the row padded out of seg.norms), both
# compiled by the same XLA. And to 1e-6 against that expression evaluated in
# numpy: numpy and XLA:CPU round `tf + k1 * (...)` differently (XLA contracts
# it to a fused multiply-add), so bits cannot be compared across the two.

def _clause(corpus, field, terms, min_hits=1):
    mapper, seg, _, meta = corpus
    stats = ShardStats([seg])
    comp = Compiler(mapper, stats)
    weighted = [(t, stats.idf(field, t)) for t in terms]
    plan = comp._text_clause(seg, meta, field, weighted, min_hits, 1.0,
                             constant=False)
    assert "row" not in plan.inputs
    return plan


@contextlib.contextmanager
def _the_old_two_lines(seg, field, d_pad):
    """posting_lengths replaced, in both modules that call it, by the
    per-lane gathers it took the place of."""
    norm = seg.norms.get(field)
    row = np.zeros(d_pad, np.int32)
    if norm is not None:
        row[:seg.num_docs] = norm
    norms_row, table = jnp.asarray(row), jnp.asarray(LENGTH_TABLE)

    def old_lengths(image, block_ids):
        docs = image["post_docs"][block_ids]
        return table[norms_row[jnp.where(docs >= 0, docs, 0)]]

    new = _bm25.posting_lengths
    _bm25.posting_lengths = _executor.posting_lengths = old_lengths
    try:
        yield
    finally:
        _bm25.posting_lengths = _executor.posting_lengths = new


def _numpy_scores(seg, field, blk):
    """(scores f32 [num_docs], hits int32 [num_docs]) of a clause by the old
    expression in numpy: the doc's norm out of seg.norms, through
    LENGTH_TABLE; f32 throughout, the kernels' operation order."""
    ids = np.asarray(blk["ids"])
    real_lane = ids >= 0
    safe = np.where(real_lane, ids, 0)
    docs = seg.post_docs[safe]
    tfs = seg.post_tf[safe]
    valid = docs >= 0
    norm = seg.norms.get(field)
    if norm is None:
        dl = np.zeros(docs.shape, np.float32)
    else:
        dl = LENGTH_TABLE[norm[np.where(valid, docs, 0)]]
    b = np.float32(blk["b"])
    avgdl = np.float32(blk["avgdl"])
    w = np.asarray(blk["w"], np.float32)
    denom = tfs + K1 * (np.float32(1.0) - b + b * dl / avgdl)
    partial = w[:, None] * tfs * (K1 + np.float32(1.0)) / denom
    real = valid & real_lane[:, None]
    scores = np.zeros(seg.num_docs, np.float32)
    hits = np.zeros(seg.num_docs, np.int32)
    np.add.at(scores, docs[real], partial[real])
    np.add.at(hits, docs[real], 1)
    return scores, hits


CLAUSES = [
    ("body", ["alpha", "beta", "gamma"]),
    ("body", ["r3", "alpha"]),
    ("title", ["alpha", "r7"]),
    ("tag", ["red", "blue"]),           # norm-less: b = 0
]


@pytest.mark.parametrize("score_only", [True, False],
                         ids=["score_only", "counted"])
@pytest.mark.parametrize("field,terms", CLAUSES)
def test_dense_kernel_matches_the_old_expression(corpus, field, terms,
                                                 score_only):
    """Both dense programs: the one a default `match` plans (matches off
    the score vector, one scatter) and the counted one."""
    _, seg, image, meta = corpus
    plan = _clause(corpus, field, terms)
    assert plan.static[2] is True       # min_hits 1, idf weights
    blk = {k: jnp.asarray(v) for k, v in plan.inputs.items()}

    def dense(image, blk):
        return _bm25.score_text_clause(image, blk, blk["k1"],
                                       score_only=score_only)

    scores, matches = (np.asarray(x) for x in jax.jit(dense)(image, blk))
    with _the_old_two_lines(seg, field, meta.d_pad):
        old_s, old_m = (np.asarray(x) for x in jax.jit(dense)(image, blk))
    assert scores.tobytes() == old_s.tobytes()
    assert matches.tobytes() == old_m.tobytes()
    want_s, want_h = _numpy_scores(seg, field, plan.inputs)
    assert np.array_equal(matches[:seg.num_docs], want_h >= 1)
    assert not matches[seg.num_docs:].any()
    np.testing.assert_allclose(scores[:seg.num_docs], want_s, rtol=1e-6,
                               atol=0)
    assert (want_h > 0).sum() > 100


def _candidate_rows(plan, meta, image, k, bm):
    """The candidate kernel's packed rows for a batch of one, dispatched as
    the executor dispatches it (stack, pack, one program)."""
    stacked, treedef, _ = _executor.stack_flat_inputs([[plan.inputs]])
    stacked.append(np.asarray([-np.inf], np.float32))
    buf, layout = _executor.pack_leaves(stacked)
    fn = jax.jit(_executor.build_candidate_query_phase(
        plan, meta, k, layout, treedef, bm=bm))
    return np.asarray(fn(image, jnp.asarray(buf)))


@pytest.mark.parametrize("blockmax", [False, True],
                         ids=["candidate", "candidate+phaseA"])
@pytest.mark.parametrize("field,terms", CLAUSES)
def test_candidate_kernel_and_phase_a_match_the_old_expression(
        corpus, field, terms, blockmax):
    _, seg, image, meta = corpus
    k = 10
    _bm25.BLOCKMAX = blockmax
    try:
        plan = _clause(corpus, field, terms)
    finally:
        _bm25.BLOCKMAX = False
    bm = blockmax and _executor._blockmax_admitted(plan, k)
    if blockmax and field == "body" and "beta" in terms:
        assert bm           # the clause built to clear admission does
    rows = _candidate_rows(plan, meta, image, k, bm)
    with _the_old_two_lines(seg, field, meta.d_pad):
        old_rows = _candidate_rows(plan, meta, image, k, bm)
    assert rows.dtype == np.int32 and rows.shape == (1, 2 * k + 1 + bm)
    assert rows.tobytes() == old_rows.tobytes()
    # against numpy: the page's docs in order, scores to 1e-6, the total
    want_s, want_h = _numpy_scores(seg, field, plan.inputs)
    matched = np.flatnonzero((want_h > 0) & seg.live)
    got_scores = rows[0, :k].view(np.float32)
    got_docs = rows[0, k:2 * k]
    np.testing.assert_allclose(got_scores, want_s[got_docs], rtol=1e-6)
    kth = np.sort(want_s[matched])[::-1][k - 1]
    assert (want_s[got_docs] >= kth * (1 - 1e-6)).all()
    assert (np.diff(got_scores) <= 0).all()
    pruned = int(rows[0, -1]) if bm else 0
    total = int(rows[0, 2 * k])
    assert total == len(matched) if not pruned else total <= len(matched)


def test_the_executor_serves_the_same_page(corpus):
    """End to end through multi_search (candidate kernel): ids in order,
    scores to 1e-6, exact total against the numpy expression."""
    mapper, seg, _, _ = corpus
    ex = SearchExecutor(ShardReader(mapper, [seg]))
    plan = _clause(corpus, "body", ["gamma", "r5"])
    want_s, want_h = _numpy_scores(seg, "body", plan.inputs)
    resp = ex.multi_search([{"query": {"match": {"body": "gamma r5"}},
                             "size": 10}])["responses"][0]
    order = sorted(np.flatnonzero(want_h > 0).tolist(),
                   key=lambda d: (-want_s[d], d))[:10]
    hits = resp["hits"]["hits"]
    assert [h["_id"] for h in hits] == [seg.doc_ids[d] for d in order]
    np.testing.assert_allclose([h["_score"] for h in hits], want_s[order],
                               rtol=1e-6)
    assert resp["hits"]["total"] == {"value": int((want_h > 0).sum()),
                                     "relation": "eq"}


def test_no_kernel_reads_the_norms_rows(corpus):
    """All three kernels run, and give the same bytes, on an image from
    which the `norms` leaf has been taken away: no gather from it is left
    in any of them (it stays in the image for `exists` on a text field)."""
    _, _, image, meta = corpus
    _bm25.BLOCKMAX = True
    try:
        plan = _clause(corpus, "body", ["alpha", "beta"])
    finally:
        _bm25.BLOCKMAX = False
    bare = {k: v for k, v in image.items() if k != "norms"}
    blk = {k: jnp.asarray(v) for k, v in plan.inputs.items()}

    def dense_and_phase_a(image, blk):
        keep, pruned = _bm25.blockmax_keep_mask(image, blk, blk["k1"], 2, 10)
        return _bm25.score_text_clause(image, blk, blk["k1"], keep), pruned

    got = jax.tree_util.tree_leaves(jax.jit(dense_and_phase_a)(bare, blk))
    want = jax.tree_util.tree_leaves(jax.jit(dense_and_phase_a)(image, blk))
    assert all(np.asarray(g).tobytes() == np.asarray(w).tobytes()
               for g, w in zip(got, want))
    assert _candidate_rows(plan, meta, bare, 10, True).tobytes() \
        == _candidate_rows(plan, meta, image, 10, True).tobytes()


def test_phase_a_keeps_what_the_old_expression_keeps(corpus):
    """Phase A alone, on the clause that clears admission: the keep mask
    and the pruned count with the leaf equal those with the old lines."""
    _, seg, image, meta = corpus
    _bm25.BLOCKMAX = True
    try:
        plan = _clause(corpus, "body", ["alpha", "beta"])
    finally:
        _bm25.BLOCKMAX = False
    assert plan.scan_blocks >= _bm25.BLOCKMAX_MIN_BLOCKS
    blk = {k: jnp.asarray(v) for k, v in plan.inputs.items()}

    def phase_a(image, blk):
        return _bm25.blockmax_keep_mask(image, blk, blk["k1"], 2, 10)

    keep, pruned = jax.jit(phase_a)(image, blk)
    with _the_old_two_lines(seg, "body", meta.d_pad):
        keep_old, pruned_old = jax.jit(phase_a)(image, blk)
    assert np.array_equal(np.asarray(keep), np.asarray(keep_old))
    assert int(pruned) == int(pruned_old) > 0


# ------------------------------------------------------- (d) delta publish

def test_delta_publish_ships_the_leaf_compact_and_byte_identical(
        corpus, monkeypatch):
    mapper = corpus[0]
    builder = SegmentBuilder(mapper)
    for i in range(10):
        builder.add(mapper.parse_document(
            f"s{i}", {"title": f"alpha {i}", "body": "beta " * (i + 1),
                      "tag": "red"}))
    small = builder.seal()
    ref, _ = devseg.upload_segment(small)
    spec = devseg._compact_spec(small, None)
    assert spec[("post_norm",)] == (spec[("post_docs",)][0], 0)
    monkeypatch.setattr(devseg, "DELTA_PUBLISH", True)
    arrays, _, xfer = devseg.publish_segment(small)
    assert arrays.keys() == ref.keys()
    got, want = np.asarray(arrays["post_norm"]), np.asarray(ref["post_norm"])
    assert got.dtype == want.dtype == np.uint8 and got.shape == want.shape
    assert got.tobytes() == want.tobytes() and want.any()
    for name in ("post_docs", "post_tf", "post_bound", "norms", "live"):
        assert np.asarray(arrays[name]).tobytes() \
            == np.asarray(ref[name]).tobytes(), name
    assert 0 < xfer < devseg.tree_nbytes(ref)
