"""The k-NN clause's selection from block maxima (ops/select.py
`select_top_k`, `_blocked_top_k`, `blocked_select_width`).

Where the shape qualifies, the clause's k winners of `d_pad` lanes come
from the maxima of 128-lane blocks and the lanes of the k winning
blocks; they must be what one `jax.lax.top_k` over the masked vector
gives, values and ordinals in order, ties by lowest doc."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.ops.select import (SELECT_BLOCK, _blocked_top_k,
                                       blocked_select_width, select_top_k)

W = SELECT_BLOCK


@functools.partial(jax.jit, static_argnums=2)
def _plain(scores, eligible, k):
    return jax.lax.top_k(jnp.where(eligible, scores, -jnp.inf), k)


def plain_select(scores, eligible, k):
    vals, idx = _plain(scores, eligible, k)
    return np.asarray(vals), np.asarray(idx)


def assert_same_selection(scores, eligible, k):
    """`select_top_k` (blocked at this shape) equals one plain `top_k`:
    values, ordinals and `valid`, slot by slot."""
    d = scores.shape[-1]
    assert blocked_select_width(d, k) == W
    vals, idx, valid = jax.jit(select_top_k, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(eligible), k)
    want_vals, want_idx = plain_select(jnp.asarray(scores),
                                       jnp.asarray(eligible), k)
    np.testing.assert_array_equal(np.asarray(vals), want_vals)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_array_equal(np.asarray(valid), want_vals > -np.inf)
    return want_vals, want_idx


def test_the_route_is_chosen_by_the_shape_alone():
    # under 2^15 lanes plain `top_k` is one cheap pass
    assert blocked_select_width(1 << 14, 10) == 0
    assert blocked_select_width(1 << 15, 10) == W
    # the candidates (k blocks of 128) at most a quarter of the lanes
    assert blocked_select_width(1 << 15, 100) == 0
    assert blocked_select_width(1 << 16, 100) == W
    assert blocked_select_width(1 << 21, 100) == W          # the k-NN cell
    assert blocked_select_width(1 << 21, 4096) == W
    assert blocked_select_width(1 << 21, 4097) == 0


@pytest.mark.parametrize("d_pad", [1 << 17, 1 << 18])
@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_scores(d_pad, k, seed):
    rng = np.random.default_rng([41, seed, d_pad, k])
    scores = rng.standard_normal(d_pad).astype(np.float32)
    assert_same_selection(scores, np.ones(d_pad, bool), k)


@pytest.mark.parametrize("k", [10, 100])
def test_all_scores_equal(k):
    d = 1 << 17
    vals, idx = assert_same_selection(np.full(d, 0.75, np.float32),
                                      np.ones(d, bool), k)
    assert list(idx) == list(range(k))      # the lowest docs
    assert set(vals) == {np.float32(0.75)}


def test_a_tie_at_the_kth_maximum_with_passed_over_lower_blocks():
    """T, the k-th largest block maximum, is 1.0 in fifty blocks; nine
    high blocks score 5.0 and each holds a lane at 1.0 too. The k
    chosen blocks are the nine and block 0; blocks 1..49 are passed over
    though their lanes at 1.0 lie below the high blocks' own lanes at
    1.0. The one winner at T is still the lowest doc at T."""
    d, k = 1 << 17, 10
    scores = np.zeros(d, np.float32)
    for b in range(50):
        scores[b * W + 77] = 1.0
    for j, b in enumerate(range(900, 909)):
        scores[b * W + 3] = 5.0 + j
        scores[b * W + 1] = 1.0
    vals, idx = assert_same_selection(scores, np.ones(d, bool), k)
    assert list(idx[:9]) == [b * W + 3 for b in range(908, 899, -1)]
    assert idx[9] == 77 and vals[9] == 1.0


@pytest.mark.parametrize("levels", [2, 3, 7])
@pytest.mark.parametrize("seed", [0, 1])
def test_heavy_ties(levels, seed):
    """Scores drawn from a few values: every cut falls inside a tie
    that spans many blocks, chosen and passed over."""
    rng = np.random.default_rng([41, levels, seed])
    d = 1 << 17
    scores = rng.integers(0, levels, d).astype(np.float32)
    # a sparse top band, so that the k-th winner's tie is the next level
    top = rng.random(d) < 3e-4
    scores[top] += levels
    for k in (10, 100):
        assert_same_selection(scores, np.ones(d, bool), k)


@pytest.mark.parametrize("eligible_docs", [0, 1, 37, 99])
def test_fewer_than_k_eligible(eligible_docs):
    """Fewer eligible docs than k: the tail slots are `-inf`, not
    `valid`, and carry the ordinals plain `top_k` gives them."""
    rng = np.random.default_rng([41, eligible_docs])
    d, k = 1 << 17, 100
    scores = rng.standard_normal(d).astype(np.float32)
    eligible = np.zeros(d, bool)
    eligible[rng.choice(d, eligible_docs, replace=False)] = True
    vals, _ = assert_same_selection(scores, eligible, k)
    assert int((vals > -np.inf).sum()) == eligible_docs


def test_a_filter_mask():
    rng = np.random.default_rng(4141)
    d = 1 << 18
    scores = rng.standard_normal(d).astype(np.float32)
    eligible = (np.arange(d) % 3 == 1) & (rng.random(d) < 0.5)
    vals, idx = assert_same_selection(scores, eligible, 100)
    assert eligible[idx].all() and (vals > -np.inf).all()


def test_padding_lanes_past_the_docs():
    """A segment of 70,001 docs in `d_pad` 131,072: the padding lanes
    are ineligible (`-inf`) and so is everything past the docs."""
    rng = np.random.default_rng(70001)
    d, n = 1 << 17, 70001
    scores = rng.standard_normal(d).astype(np.float32)
    scores[n:] = 0.0
    eligible = np.arange(d) < n
    _, idx = assert_same_selection(scores, eligible, 100)
    assert (idx < n).all()


def test_a_batch_of_queries_under_vmap():
    """The served programs vmap the selection over their batch: each
    row is its own plain `top_k`."""
    rng = np.random.default_rng(3)
    b, d, k = 3, 1 << 17, 100
    scores = rng.integers(0, 40, (b, d)).astype(np.float32)
    eligible = rng.random((b, d)) < 0.9
    vals, idx, _ = jax.jit(jax.vmap(
        lambda s, e: select_top_k(s, e, k)))(scores, eligible)
    for r in range(b):
        want_vals, want_idx = plain_select(jnp.asarray(scores[r]),
                                           jnp.asarray(eligible[r]), k)
        np.testing.assert_array_equal(np.asarray(vals[r]), want_vals)
        np.testing.assert_array_equal(np.asarray(idx[r]), want_idx)


def test_blocks_of_other_widths_are_exact_too():
    """The argument holds for any contiguous width: a few, on ties."""
    rng = np.random.default_rng(8)
    d = 1 << 15
    x = jnp.asarray(rng.integers(0, 5, d).astype(np.float32))
    for w in (4, 32, 512):
        for k in (1, 10, 60):
            vals, idx = jax.jit(_blocked_top_k, static_argnums=(1, 2))(
                x, k, w)
            want_vals, want_idx = jax.lax.top_k(x, k)
            np.testing.assert_array_equal(np.asarray(vals),
                                          np.asarray(want_vals))
            np.testing.assert_array_equal(np.asarray(idx),
                                          np.asarray(want_idx))


# ------------------------------------------------------- the served path
#
# 768-d vectors, k=100, at a `d_pad` where the route engages, through the
# node's REST dispatch and the B=1 envelope (`jit_knn`), held to
# `ref_knn_score` in float64 as tests/test_knn.py holds the small sizes.

SERVED_DOCS = 33000             # d_pad 65,536: blocked at k=100
SERVED_DIMS, SERVED_K = 768, 100


def install(node, index, x):
    """`x` as one sealed segment of `index`, installed the way the
    benchmark installs its corpora (ids `d0`, `d1`, ...)."""
    from opensearch_tpu.index.segment import (PrefixedIds, Segment,
                                              VectorColumn)
    n = len(x)
    node.request("PUT", f"/{index}", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"vec": {
            "type": "knn_vector", "dimension": x.shape[1],
            "method": {"space_type": "innerproduct"}}}}})
    seg = Segment(
        "v0", n, PrefixedIds("d", n), [None] * n, {},
        np.full((1, 128), -1, dtype=np.int32),
        np.zeros((1, 128), dtype=np.float32), {}, {}, {}, {},
        {"vec": VectorColumn(x, np.ones(n, dtype=bool))})
    shard = node.indices.get(index).shards[0]
    shard.engine.install_segments([seg], max_seq_no=n, local_checkpoint=n)
    shard._sync_reader()


SMALL_DOCS, SMALL_D_PAD = 200, 256


@pytest.fixture(scope="module")
def served():
    """A node with `big` (33,000 768-d vectors, doc 7 a copy of doc 3),
    `small` (the first 200 of them) and `text` (BM25)."""
    from opensearch_tpu.node import Node
    rng = np.random.default_rng(41)
    centre = rng.standard_normal(SERVED_DIMS).astype(np.float32) * 0.5
    x = centre + rng.standard_normal(
        (SERVED_DOCS, SERVED_DIMS)).astype(np.float32)
    x[7] = x[3]
    node = Node()
    install(node, "big", x)
    install(node, "small", x[:SMALL_DOCS].copy())
    node.request("PUT", "/text", {"settings": {"number_of_shards": 1},
                                  "mappings": {"properties": {
                                      "body": {"type": "text"}}}})
    for i in range(30):
        node.request("PUT", f"/text/_doc/t{i}",
                     {"body": "red fox" if i % 2 else "blue fox"})
    node.request("POST", "/text/_refresh")
    yield node, x, centre, rng
    for index in ("big", "small", "text"):
        node.request("DELETE", f"/{index}")


def knn_body(q, k=SERVED_K):
    return {"size": k, "_source": False,
            "query": {"knn": {"vec": {"vector": q.tolist(), "k": k}}}}


def test_served_parity_at_a_blocked_shape(served):
    from tests.test_knn import check_served_page
    node, x, centre, rng = served
    queries = [(centre * rng.uniform(-0.2, 1.0)
                + rng.standard_normal(SERVED_DIMS)).astype(np.float32)
               for _ in range(2)]
    queries.append(x[3] * 1.5)      # doc 3 and its copy 7 lead the page
    for q in queries:
        resp = node.request("POST", "/big/_search", knn_body(q))
        assert resp["_status"] == 200, resp
        assert resp["_shards"]["failed"] == 0 and not resp["timed_out"]
        _, want = check_served_page(resp, x, q, "innerproduct",
                                    range(SERVED_DOCS))
        assert len(want) == SERVED_K
        ids = [h["_id"] for h in resp["hits"]["hits"]]
        if "d3" in ids:
            assert ids.index("d3") + 1 == ids.index("d7")


def test_the_counter_counts_items_at_a_blocked_shape(served):
    """`search.knn_clause.blocked_select`: once an item whose program
    selects by block maxima; a small segment and a BM25 request count
    nothing."""
    from tests.test_knn import knn_clause_counters as counters, msearch
    node, x, _, _ = served
    q = x[11]
    before = counters(node)
    assert node.request("POST", "/big/_search",
                        knn_body(q))["_status"] == 200
    one = counters(node)
    assert one["blocked_select"] - before["blocked_select"] == 1
    assert one["exact"] - before["exact"] == 1
    assert one["page_from_clause"] - before["page_from_clause"] == 1
    # three items of one `_msearch`: one vmapped program, three items
    assert all(r["hits"]["hits"] for r in msearch(
        node, "big", [knn_body(q + i) for i in range(3)]))
    three = counters(node)
    assert three["blocked_select"] - one["blocked_select"] == 3
    # a clause under a parent selects by the same rule
    wrapped = {"size": 10, "_source": False, "query": {"bool": {"must": [
        knn_body(q)["query"]]}}}
    assert node.request("POST", "/big/_search", wrapped)["_status"] == 200
    four = counters(node)
    assert four["blocked_select"] - three["blocked_select"] == 1
    assert four["page_from_clause"] == three["page_from_clause"]
    # d_pad 256 and a text match: plain `top_k`, nothing counted
    resp = node.request("POST", "/small/_search", knn_body(q, 10))
    assert resp["_status"] == 200 and resp["hits"]["total"]["value"] == 10
    resp = node.request("POST", "/text/_search", {
        "size": 5, "query": {"match": {"body": "red"}}})
    assert resp["_status"] == 200 and resp["hits"]["total"]["value"] == 15
    after = counters(node)
    assert after["blocked_select"] == four["blocked_select"]
    assert after["exact"] - four["exact"] == 1


def test_the_cells_program_has_no_top_k_over_the_doc_axis(served):
    """The `jit_knn` program of the k-NN cell's shape (`d_pad`
    2,097,152, 768-d, k=100, B=1), lowered on the CPU from shapes alone:
    no `top_k` or sort reads `d_pad` lanes. One `top_k` reads the
    16,384 block maxima, one sort the 12,800 lanes of the winning
    blocks, one more the page's 100."""
    from opensearch_tpu.telemetry import TELEMETRY
    node, x, _, _ = served
    resp = node.request("POST", "/small/_search", knn_body(x[0]))
    assert resp["_status"] == 200
    span = [s["attributes"] for s in node.request(
        "GET", "/_telemetry/spans")["spans"] if s["name"] == "dispatch"][-1]
    assert span["shape"] == f"b1/d{SMALL_D_PAD}x{SERVED_DIMS}k{SERVED_K}"
    fn, structs = TELEMETRY.kernels._lowerable[span["fingerprint"]]
    d_pad = 1 << 21
    # every doc axis of the segment's image, and nothing else, is 256
    seg = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(
        tuple(d_pad if n == SMALL_D_PAD else n for n in s.shape),
        s.dtype), structs[0])
    assert seg["vector"]["vec"]["vectors"].shape == (d_pad, SERVED_DIMS)
    text = fn.lower(seg, structs[1]).as_text()
    top_ks = re.findall(
        r"chlo\.top_k\(.*?\) : tensor<((?:\d+x)*\d+)xf32>", text)
    sorts = re.findall(
        r'"stablehlo\.sort"\(.*?\}\) : \(tensor<((?:\d+x)*\d+)x', text,
        re.S)
    assert not [t for t in top_ks + sorts if t.endswith(str(d_pad))], \
        (top_ks, sorts)
    assert top_ks == ["1x16384"], top_ks
    assert sorted(sorts) == ["1x100", "1x12800"], sorts
