"""The fused hybrid program's sub-query windows (search/executor.py
`build_hybrid_query_phase`, `_hybrid_window_route`).

A sub-query that is a `knn` clause takes its window from the clause's own
k winners; every other one selects it from its dense pair through
`select_top_k`, blocked or plain by `blocked_select_width`. The fused row
must be what one `jax.lax.top_k` over each sub-query's densified pair
gives (the formulation kept below as `reference_phase`), bit for bit:
every window's scores, its ords up to its count, its count, min, max and
sum of squares, and the union total. A from-clause window's padding
ords are the one exception: the two routes fill them differently and the
host reads a window only up to its count (`_accumulate_hybrid_row`)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.node import Node
from opensearch_tpu.ops.device_segment import DeviceSegmentMeta
from opensearch_tpu.search.compile import Plan
from opensearch_tpu.search.executor import (
    _hybrid_window_route, _unpack_envelope, build_batched_hybrid_query_phase,
    pack_leaves, stack_flat_inputs)
from opensearch_tpu.search.plan_eval import _eval_plan

DIMS = 8


def reference_phase(plans, meta, k):
    """Each sub-query evaluated to its dense `[d_pad]` pair (a `knn`
    clause's winners scattered back by `knn_match_topk`) and its window
    selected by one `jax.lax.top_k` over all lanes; the union total
    counted over the OR of the eligible vectors."""

    def one(seg, flat_inputs, min_score):
        cursor = [0]
        d_pad = seg["live"].shape[0]
        in_seg = jnp.arange(d_pad, dtype=jnp.int32) < meta.num_docs
        base = seg["live"] & seg["root"] & in_seg
        union = jnp.zeros(d_pad, jnp.bool_)
        pieces = []
        k_eff = min(k, d_pad)
        for plan in plans:
            scores, matches = _eval_plan(plan, seg, flat_inputs, cursor)
            eligible = matches & base & (scores >= min_score)
            union = union | eligible
            top_scores, top_idx = jax.lax.top_k(
                jnp.where(eligible, scores, -jnp.inf), k_eff)
            valid = top_scores > -jnp.inf
            cnt = jnp.sum(valid.astype(jnp.int32))
            mn = jnp.min(jnp.where(valid, top_scores, jnp.inf))
            mx = jnp.max(jnp.where(valid, top_scores, -jnp.inf))
            vs = jnp.where(valid, top_scores, 0.0)
            ssq = jnp.sum(vs * vs)
            pieces.append(jnp.concatenate([
                jax.lax.bitcast_convert_type(top_scores, jnp.int32),
                top_idx.astype(jnp.int32), cnt[None],
                jax.lax.bitcast_convert_type(
                    jnp.stack([mn, mx, ssq]), jnp.int32)]))
        pieces.append(jnp.sum(union.astype(jnp.int32))[None])
        return jnp.concatenate(pieces)

    def run(seg, packed_buf, layout, treedef):
        batched_flat, min_scores = _unpack_envelope(packed_buf, layout,
                                                    treedef)
        return jax.vmap(one, in_axes=(None, 0, 0))(seg, batched_flat,
                                                   min_scores)

    return run


# ------------------------------------------------------------- the data

def segment(rng, d_pad: int, num_docs: int, deleted: float = 0.05):
    """One segment's device arrays: `num_docs` docs in `d_pad` lanes,
    a share of them deleted, a few nested child rows (not `root`), and
    vectors of integers on most docs, so that inner products tie
    often."""
    lanes = np.arange(d_pad)
    live = (lanes < num_docs) & (rng.random(d_pad) >= deleted)
    root = rng.random(d_pad) >= 0.02
    exists = (lanes < num_docs) & (rng.random(d_pad) < 0.9)
    vectors = np.where(exists[:, None],
                       rng.integers(0, 16, (d_pad, DIMS)), 0)
    seg = {"live": jnp.asarray(live), "root": jnp.asarray(root),
           "vector": {"v": {"exists": jnp.asarray(exists),
                            "vectors": jnp.asarray(vectors, jnp.float32)}}}
    meta = DeviceSegmentMeta(
        seg_id="s0", num_docs=num_docs, d_pad=d_pad, nb_pad=128,
        norm_rows=(), numeric_fields=(), ordinal_fields=(),
        vector_fields=("v",))
    return seg, meta


def text(rng, d_pad: int, share: float = 0.3):
    """A BM25 clause's dense pair as the text kernel leaves it: scores
    quantized to a few values (ties, as length norms make them), each
    level half as full as the one below, 0 off its matches."""
    matches = rng.random(d_pad) < share
    scores = (1.0 + rng.geometric(0.5, d_pad)
              * np.float32(0.37)).astype(np.float32)
    return Plan("precomputed", inputs={
        "scores": np.where(matches, scores, np.float32(0.0)),
        "matches": matches})


def mask(rng, d_pad: int, share: float):
    m = rng.random(d_pad) < share
    return Plan("precomputed", inputs={
        "scores": np.where(m, np.float32(1.0), np.float32(0.0)),
        "matches": m})


def knn(rng, k: int, boost: float = 1.0, query=None, filter_=None):
    q = rng.integers(0, 16, DIMS) if query is None else query
    return Plan("knn", static=("v", int(k), "innerproduct", "exact", 0),
                inputs={"query": np.asarray(q, np.float32),
                        "boost": np.float32(boost)},
                children=[] if filter_ is None else [filter_])


def under_bool_filter(plan, flt):
    return Plan("bool", static=(1, 1, 0, 0),
                inputs={"msm": np.int32(0), "boost": np.float32(1.0)},
                children=[plan, flt])


# every case a list of sub-queries made for one query of a batch; a
# batch's queries share their plans' structure and differ in inputs
CASES = {
    "text_and_knn": lambda r, d, k: [text(r, d), knn(r, k)],
    "knn_first_with_its_own_filter": lambda r, d, k: [
        knn(r, k, filter_=mask(r, d, 0.6)), text(r, d)],
    "knn_k_under_the_window": lambda r, d, k: [
        text(r, d), knn(r, max(1, k // 3), boost=2.5)],
    "fewer_eligible_than_k": lambda r, d, k: [
        text(r, d, share=37 / d),
        knn(r, k, filter_=mask(r, d, 20 / d))],
    "knn_under_a_bool_filter": lambda r, d, k: [
        text(r, d), under_bool_filter(knn(r, k), mask(r, d, 0.5))],
    "two_knn_sharing_docs": lambda r, d, k: two_knn(r, d, k, text_too=True),
    "two_knn_alone": lambda r, d, k: two_knn(r, d, k, text_too=False),
}


def two_knn(rng, d_pad, k, text_too: bool):
    """Two k-NN sub-queries of one query vector, the second boosted and
    with a narrower k, so their winner lists share most docs."""
    q = rng.integers(0, 16, DIMS)
    subs = [knn(rng, k, query=q), knn(rng, max(1, k // 2), boost=0.5,
                                       query=q)]
    return [text(rng, d_pad)] + subs if text_too else subs


def batch(case: str, seed: int, d_pad: int, k: int, b: int):
    rng = np.random.default_rng([44, seed, d_pad, k, b])
    return [CASES[case](rng, d_pad, k) for _ in range(b)]


def run_both(seg, meta, queries, k, min_scores):
    """The fused program and the reference over one packed batch: two
    int32 `[B, row]` arrays."""
    plans0 = queries[0]
    flats = []
    for plans in queries:
        flat = []
        for p in plans:
            p.flatten_inputs(flat)
        flats.append(flat)
    stacked, treedef, _ = stack_flat_inputs(flats)
    stacked.append(np.asarray(min_scores, np.float32))
    buf, layout = pack_leaves(stacked)
    got = jax.jit(build_batched_hybrid_query_phase(
        plans0, meta, k, layout, treedef))(seg, jnp.asarray(buf))
    want = jax.jit(reference_phase(plans0, meta, k),
                   static_argnums=(2, 3))(seg, jnp.asarray(buf), layout,
                                          treedef)
    return np.asarray(got), np.asarray(want)


def assert_rows_equal(got, want, plans, d_pad, k):
    """Bit for bit, slot by slot; a from-clause window's ords only up to
    its count."""
    assert got.shape == want.shape
    k_eff = min(k, d_pad)
    width = 2 * k_eff + 4
    for r in range(got.shape[0]):
        for i, plan in enumerate(plans):
            g = got[r, i * width:(i + 1) * width]
            w = want[r, i * width:(i + 1) * width]
            cnt = int(w[2 * k_eff])
            np.testing.assert_array_equal(g[:k_eff], w[:k_eff])
            n_ords = cnt if _hybrid_window_route(plan, d_pad, k) \
                == "from_clause" else k_eff
            np.testing.assert_array_equal(g[k_eff:k_eff + n_ords],
                                          w[k_eff:k_eff + n_ords])
            np.testing.assert_array_equal(g[2 * k_eff:], w[2 * k_eff:])
        assert got[r, -1] == want[r, -1]


# (d_pad, k, the route a dense sub-query's window takes)
SHAPES = [(1 << 16, 100, "blocked"), (1 << 15, 100, "plain"),
          (1 << 15, 60, "blocked"), (1 << 12, 100, "plain")]


def window_cut(row, i: int, k_eff: int) -> float:
    """A min_score inside sub-query i's window in a row: the middle of
    its distinct valid values, above the lowest of them."""
    w = 2 * k_eff + 4
    vals = row[i * w:i * w + k_eff].view(np.float32)
    levels = np.unique(vals[np.isfinite(vals)])
    assert len(levels) > 1
    return float(levels[len(levels) // 2])


@pytest.mark.parametrize("d_pad,k,route", SHAPES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_row_is_the_dense_top_k_formulation(case, d_pad, k, route):
    """B=1, and a vmapped B=3 under two sets of min_scores: none, then
    a cut inside the first sub-query's window, inside the second's, and
    one above every score."""
    rng = np.random.default_rng([44, d_pad, k])
    seg, meta = segment(rng, d_pad, d_pad - 777)
    for b in (1, 3):
        queries = batch(case, 0, d_pad, k, b)
        routes = {_hybrid_window_route(p, d_pad, k) for p in queries[0]}
        assert routes <= {"from_clause", route}
        got, want = run_both(seg, meta, queries, k, [-np.inf] * b)
        assert_rows_equal(got, want, queries[0], d_pad, k)
    k_eff = min(k, d_pad)
    cuts = [window_cut(want[0], 0, k_eff), window_cut(want[1], 1, k_eff),
            1e9]
    got, want_cut = run_both(seg, meta, queries, k, cuts)
    assert_rows_equal(got, want_cut, queries[0], d_pad, k)
    for r in (0, 1):
        cnt = int(want_cut[r, r * (2 * k_eff + 4) + 2 * k_eff])
        assert 0 < cnt < int(want[r, r * (2 * k_eff + 4) + 2 * k_eff])
    assert want_cut[2, -1] == 0


def test_two_knn_windows_count_a_shared_doc_once():
    """The union total of two winner lists that share docs, with no
    dense sub-query, is the size of their union."""
    d_pad, k = 1 << 12, 100
    rng = np.random.default_rng(4404)
    seg, meta = segment(rng, d_pad, d_pad, deleted=0.0)
    queries = batch("two_knn_alone", 1, d_pad, k, 1)
    got, want = run_both(seg, meta, queries, k, [-np.inf])
    assert_rows_equal(got, want, queries[0], d_pad, k)
    w = 2 * k + 4
    ords = [set(got[0, i * w + k:i * w + k + int(got[0, i * w + 2 * k])])
            for i in range(2)]
    assert ords[1] <= ords[0] and got[0, -1] == len(ords[0] | ords[1])


def test_deleted_docs_never_enter_a_window():
    d_pad, k = 1 << 16, 100
    rng = np.random.default_rng(4405)
    seg, meta = segment(rng, d_pad, d_pad, deleted=0.5)
    queries = batch("text_and_knn", 2, d_pad, k, 3)
    got, want = run_both(seg, meta, queries, k, [-np.inf] * 3)
    assert_rows_equal(got, want, queries[0], d_pad, k)
    live = np.asarray(seg["live"])
    w = 2 * k + 4
    for r in range(3):
        for i in range(2):
            cnt = int(got[r, i * w + 2 * k])
            # a k-NN window loses the clause's winners that are nested
            # rows, never one to a deleted doc
            assert cnt > 90 and live[got[r, i * w + k:i * w + k + cnt]].all()


def test_the_route_is_read_off_the_root_and_the_shape():
    rng = np.random.default_rng(0)
    d = 1 << 21
    # the hybrid cell: d_pad 2^21, a window of 100
    assert _hybrid_window_route(knn(rng, 100), d, 100) == "from_clause"
    assert _hybrid_window_route(text(rng, 8), d, 100) == "blocked"
    assert _hybrid_window_route(
        under_bool_filter(knn(rng, 100), mask(rng, 8, 0.5)), d, 100) \
        == "blocked"
    assert _hybrid_window_route(text(rng, 8), 1 << 12, 100) == "plain"
    assert _hybrid_window_route(knn(rng, 100), 1 << 12, 100) \
        == "from_clause"


def test_no_top_k_over_the_lanes_in_the_cell_shaped_program():
    """At a blocked shape, a text and a k-NN sub-query: every `top_k` of
    the program reads fewer than `d_pad` lanes."""
    d_pad, k = 1 << 16, 100
    rng = np.random.default_rng(4406)
    seg, meta = segment(rng, d_pad, d_pad)
    queries = batch("text_and_knn", 3, d_pad, k, 1)
    flat = []
    for p in queries[0]:
        p.flatten_inputs(flat)
    stacked, treedef, _ = stack_flat_inputs([flat])
    stacked.append(np.asarray([-np.inf], np.float32))
    buf, layout = pack_leaves(stacked)
    text_ir = jax.jit(build_batched_hybrid_query_phase(
        queries[0], meta, k, layout, treedef)).lower(
            seg, jnp.asarray(buf)).as_text()
    tops = [line for line in text_ir.splitlines() if "top_k" in line]
    assert tops, "no top_k at all: the text and the clause select"
    assert not any(f"{d_pad}xf32" in line for line in tops), tops


# ------------------------------------------------------------ counters

def windows(node):
    stats = node.request("GET", "/_nodes/stats")
    c = next(iter(stats["nodes"].values()))[
        "telemetry"]["metrics"]["counters"]
    return {r: c.get(f"search.hybrid.windows.{r}", 0)
            for r in ("from_clause", "blocked", "plain")}


def test_the_counters_count_each_sub_query_by_its_route():
    """A hybrid `_search` and a hybrid `_msearch` of four items on one
    small shard (`d_pad` under 2^15: the text window is plain); a
    non-hybrid search counts nothing."""
    node = Node()
    node.request("PUT", "/hw", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "t": {"type": "text"},
            "v": {"type": "knn_vector", "dimension": 4,
                  "method": {"space_type": "innerproduct"}}}}})
    rng = np.random.default_rng(44)
    lines = []
    for i in range(40):
        lines.append(json.dumps({"index": {"_index": "hw", "_id": str(i)}}))
        lines.append(json.dumps({"t": f"red w{i % 5}",
                                 "v": rng.random(4).round(3).tolist()}))
    r = node.request("POST", "/_bulk", "\n".join(lines) + "\n",
                     refresh="true")
    assert not r["errors"]

    def body(i):
        return {"query": {"hybrid": {"queries": [
            {"match": {"t": f"red w{i % 5}"}},
            {"knn": {"v": {"vector": rng.random(4).round(3).tolist(),
                           "k": 5}}}]}}, "size": 10}

    w0 = windows(node)
    assert node.request("POST", "/hw/_search", body(0))["_status"] == 200
    w1 = windows(node)
    assert {r: w1[r] - w0[r] for r in w0} == \
        {"from_clause": 1, "blocked": 0, "plain": 1}
    resp = node.request("POST", "/hw/_msearch", "".join(
        json.dumps(line) + "\n" for i in range(4)
        for line in ({}, body(i))))
    assert all(r.get("status", 200) == 200 for r in resp["responses"])
    w2 = windows(node)
    assert {r: w2[r] - w1[r] for r in w0} == \
        {"from_clause": 4, "blocked": 0, "plain": 4}
    assert sum(w2[r] - w0[r] for r in w0) == 2 * (1 + 4)
    node.request("POST", "/hw/_search", {"query": {"match": {"t": "red"}}})
    node.request("POST", "/hw/_search", {"query": {"knn": {"v": {
        "vector": [0.1, 0.2, 0.3, 0.4], "k": 3}}}})
    assert windows(node) == w2
