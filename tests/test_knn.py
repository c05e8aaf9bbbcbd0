"""k-NN tests: exact parity vs numpy, spaces, filtering, IVF recall, persistence.

Models the k-NN plugin's test strategy (recall-at-k against brute force);
BASELINE.md configs 4 (exact) and 5 (ANN)."""

import json
import re

import numpy as np
import pytest

from opensearch_tpu.index.service import IndexService

DIMS = 16


def np_scores(vectors, q, space):
    if space == "l2":
        d2 = ((vectors - q) ** 2).sum(axis=1)
        return 1.0 / (1.0 + d2)
    if space == "cosinesimil":
        cos = (vectors @ q) / (np.linalg.norm(vectors, axis=1)
                               * np.linalg.norm(q) + 1e-30)
        return (1.0 + np.clip(cos, -1, 1)) / 2.0
    ip = vectors @ q
    return np.where(ip >= 0, ip + 1.0, 1.0 / (1.0 - ip))


def knn_clause_counters(node):
    """`search.knn_clause.*` of `_nodes/stats`, by their last name."""
    stats = node.request("GET", "/_nodes/stats")
    c = next(iter(stats["nodes"].values()))[
        "telemetry"]["metrics"]["counters"]
    return {m: c.get(f"search.knn_clause.{m}", 0) for m in
            ("exact", "ivf", "filtered", "scanned_bytes",
             "page_from_clause", "blocked_select")}


def msearch(node, index, bodies):
    """`bodies` as one `_msearch` over `index`: its responses."""
    resp = node.request("POST", f"/{index}/_msearch", "".join(
        json.dumps(line) + "\n" for b in bodies for line in ({}, b)))
    assert len(resp["responses"]) == len(bodies), resp
    return resp["responses"]


def make_service(space="l2", method=None, n=300, seed=0, shards=1):
    mapping = {"properties": {
        "vec": {"type": "knn_vector", "dimension": DIMS,
                "method": ({"name": method, "space_type": space,
                            "parameters": {"nlist": 8}} if method
                           else {"space_type": space})},
        "tag": {"type": "keyword"},
    }}
    svc = IndexService("knn-idx", mapping=mapping,
                       settings={"number_of_shards": shards})
    rng = np.random.RandomState(seed)
    vectors = rng.randn(n, DIMS).astype(np.float32)
    for i in range(n):
        svc.index_doc(f"d{i}", {"vec": vectors[i].tolist(),
                                "tag": "even" if i % 2 == 0 else "odd"})
    svc.refresh()
    return svc, vectors


# ------------------------------------------------ the served sizes (ISSUE 33)
#
# 768-d vectors, k=100, through the node's REST dispatch (`rest.search`,
# the B=1 envelope, `jit_knn`), against `ref_knn_score` in float64.

SERVED_DIMS, SERVED_K = 768, 100
SPACES = ["l2", "cosinesimil", "innerproduct"]


def served_node(space, n, seed=11):
    """A node holding `n` 768-d vectors around one centre (so that the
    opposite of the centre has a negative inner product with every one
    of them); doc 7 is a copy of doc 3: a tie inside every page."""
    from opensearch_tpu.node import Node
    rng = np.random.default_rng([seed, SPACES.index(space)])
    centre = rng.standard_normal(SERVED_DIMS).astype(np.float32) * 0.5
    vectors = centre + rng.standard_normal(
        (n, SERVED_DIMS)).astype(np.float32)
    vectors[7] = vectors[3]
    node = Node()
    node.request("PUT", "/served", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"vec": {
            "type": "knn_vector", "dimension": SERVED_DIMS,
            "method": {"space_type": space}}}}})
    for i, v in enumerate(vectors):
        node.request("PUT", f"/served/_doc/d{i}", {"vec": v.tolist()})
    node.request("POST", "/served/_refresh")
    return node, vectors, centre, rng


def served_page(node, q, k=SERVED_K):
    resp = node.request("POST", "/served/_search", {
        "size": k, "query": {"knn": {"vec": {"vector": q.tolist(),
                                             "k": k}}},
        "_source": False})
    assert resp["_status"] == 200, resp
    assert resp["_shards"]["failed"] == 0 and resp["timed_out"] is False
    return resp


def check_served_page(body, vectors, q, space, live, k=SERVED_K):
    """The page equals the float64 ranking of the live docs: ids in
    order (ties by lowest doc; docs whose reference scores lie within
    1e-6 of one another may swap), every score to 1e-5, total
    min(k, live docs)."""
    from tests.reference_impl import ref_knn_score
    ref = {i: ref_knn_score(vectors[i], q, space) for i in live}
    want = sorted(live, key=lambda i: (-ref[i], i))[:k]
    hits = body["hits"]["hits"]
    assert body["hits"]["total"] == {"value": len(want), "relation": "eq"}
    got = [int(h["_id"][1:]) for h in hits]
    assert len(got) == len(want) and len(set(got)) == len(got)
    for h, g, w in zip(hits, got, want):
        assert h["_score"] == pytest.approx(ref[w], rel=1e-5)
        assert g == w or ref[g] == pytest.approx(ref[w], rel=1e-6)
    return ref, want


def served_parity(space, case):
    n = 60 if case == "fewer-than-k" else 400
    node, vectors, centre, rng = served_node(space, n)
    try:
        live = list(range(n))
        if case == "negative-branch":
            # the opposite of the centre: every inner product is below 0,
            # so `innerproduct` scores the whole page by 1 / (1 - ip)
            queries = [(-2.0 * centre + 0.1 * rng.standard_normal(
                SERVED_DIMS)).astype(np.float32) for _ in range(2)]
            assert max(float(np.dot(v.astype(np.float64), queries[0]))
                       for v in vectors) < 0
        else:
            queries = [(centre * rng.uniform(-0.2, 1.0)
                        + rng.standard_normal(SERVED_DIMS)
                        ).astype(np.float32) for _ in range(3)]
        if case == "deleted":
            # a doc inside the top 100 goes: the page closes up over it
            _, want = check_served_page(served_page(node, queries[0]),
                                        vectors, queries[0], space, live)
            gone = want[4]
            node.request("DELETE", f"/served/_doc/d{gone}")
            node.request("POST", "/served/_refresh")
            live.remove(gone)
        if case == "fewer-than-k":
            node.request("DELETE", "/served/_doc/d11")
            node.request("POST", "/served/_refresh")
            live.remove(11)
        for q in queries:
            body = served_page(node, q)
            ref, want = check_served_page(body, vectors, q, space, live)
            if case == "fewer-than-k":
                assert len(want) == 59
            else:
                assert len(want) == SERVED_K
                if 3 in want and 7 in want:      # the tie, lowest doc first
                    ids = [h["_id"] for h in body["hits"]["hits"]]
                    assert ids.index("d3") < ids.index("d7")
            if case == "negative-branch" and space == "innerproduct":
                assert all(0 < h["_score"] < 1
                           for h in body["hits"]["hits"])
    finally:
        node.request("DELETE", "/served")


class TestExactKnn:
    @pytest.mark.parametrize("space", SPACES)
    @pytest.mark.parametrize("case", [
        "16d-k10", "768d-k100", "768d-k100-negative-branch",
        "768d-k100-deleted", "768d-k100-fewer-than-k"])
    def test_parity_with_numpy(self, space, case):
        if case != "16d-k10":
            return served_parity(space, case[len("768d-k100-"):] or "random")
        svc, vectors = make_service(space)
        rng = np.random.RandomState(1)
        for _ in range(3):
            q = rng.randn(DIMS).astype(np.float32)
            resp = svc.search({"query": {"knn": {"vec": {
                "vector": q.tolist(), "k": 10}}}, "size": 10})
            got = [h["_id"] for h in resp["hits"]["hits"]]
            ref = np_scores(vectors, q, space)
            want = [f"d{i}" for i in np.argsort(-ref, kind="stable")[:10]]
            assert got == want
            top = resp["hits"]["hits"][0]
            assert abs(top["_score"]
                       - ref[int(top["_id"][1:])]) < 1e-4
        svc.close()

    def test_k_limits_matches(self):
        svc, _ = make_service()
        resp = svc.search({"query": {"knn": {"vec": {
            "vector": [0.0] * DIMS, "k": 7}}}, "size": 20})
        assert resp["hits"]["total"]["value"] == 7
        svc.close()

    def test_filtered_knn_exact(self):
        svc, vectors = make_service()
        q = np.zeros(DIMS, dtype=np.float32)
        resp = svc.search({"query": {"knn": {"vec": {
            "vector": q.tolist(), "k": 5,
            "filter": {"term": {"tag": "even"}}}}}, "size": 5})
        got = [h["_id"] for h in resp["hits"]["hits"]]
        ref = np_scores(vectors, q, "l2")
        even = [i for i in range(len(vectors)) if i % 2 == 0]
        want = [f"d{i}" for i in sorted(even, key=lambda i: -ref[i])[:5]]
        assert got == want
        assert all(int(h["_id"][1:]) % 2 == 0 for h in resp["hits"]["hits"])
        svc.close()

    def test_deleted_docs_excluded(self):
        svc, vectors = make_service()
        q = vectors[17]  # exact match → d17 would be top-1
        svc.delete_doc("d17")
        svc.refresh()
        resp = svc.search({"query": {"knn": {"vec": {
            "vector": q.tolist(), "k": 3}}}})
        assert "d17" not in [h["_id"] for h in resp["hits"]["hits"]]
        svc.close()

    def test_multi_shard_merge(self):
        svc, vectors = make_service(shards=3)
        q = np.zeros(DIMS, dtype=np.float32)
        resp = svc.search({"query": {"knn": {"vec": {
            "vector": q.tolist(), "k": 10}}}, "size": 10})
        ref = np_scores(vectors, q, "l2")
        want = [f"d{i}" for i in np.argsort(-ref, kind="stable")[:10]]
        assert [h["_id"] for h in resp["hits"]["hits"]] == want
        svc.close()

    def test_knn_in_bool_hybrid(self):
        svc, _ = make_service()
        resp = svc.search({"query": {"bool": {
            "must": [{"knn": {"vec": {"vector": [0.1] * DIMS, "k": 20}}}],
            "filter": [{"term": {"tag": "odd"}}],
        }}, "size": 30})
        assert 0 < resp["hits"]["total"]["value"] <= 20
        assert all(int(h["_id"][1:]) % 2 == 1 for h in resp["hits"]["hits"])
        svc.close()


# ------------------------- the page from the clause's own winners (ISSUE 34)
#
# A plan whose root is a `knn` clause takes its page and its total from
# the clause's k winners (search/executor.py build_batched_query_phase).
# Every case goes through the node's REST dispatch and is held to the
# float64 ranking of `ref_knn_score`, and to the same clause served
# densely (`bool{must: [knn]}` adds 0 and multiplies by 1: the same
# float32 scores, selected by the second `top_k` over `d_pad` lanes).

PAGE_DIMS, PAGE_DOCS = 48, 400
DUPLICATES = (50, 120, 121, 122, 300, 301)      # six copies of one vector


class ClauseIndices:
    """One node a space, four indices over the same vectors: `exact`,
    `probed` (IVF), `small` (60 docs) and `gone` (`exact` with three of
    a known query's winners deleted)."""

    def __init__(self, space):
        from opensearch_tpu.node import Node
        self.space = space
        rng = np.random.default_rng([34, SPACES.index(space)])
        self.centre = rng.standard_normal(PAGE_DIMS).astype(np.float32) * 0.4
        self.vectors = self.centre + rng.standard_normal(
            (PAGE_DOCS, PAGE_DIMS)).astype(np.float32)
        for i in DUPLICATES[1:]:
            self.vectors[i] = self.vectors[DUPLICATES[0]]
        self.queries = [(self.centre * rng.uniform(0.2, 1.0)
                         + rng.standard_normal(PAGE_DIMS)
                         ).astype(np.float32) for _ in range(3)]
        self.node = Node()
        self.live = {}
        for index, n, method in (
                ("exact", PAGE_DOCS, {"space_type": space}),
                ("gone", PAGE_DOCS, {"space_type": space}),
                ("small", 60, {"space_type": space}),
                ("probed", PAGE_DOCS, {"name": "ivf", "space_type": space,
                                       "parameters": {"nlist": 8,
                                                      "nprobes": 3}})):
            self.node.request("PUT", f"/{index}", {
                "settings": {"number_of_shards": 1},
                "mappings": {"properties": {
                    "vec": {"type": "knn_vector", "dimension": PAGE_DIMS,
                            "method": method},
                    "tag": {"type": "keyword"}}}})
            for i in range(n):
                doc = {"tag": "odd" if i % 2 else "even"}
                if i % 29 != 5:             # a few docs have no vector
                    doc["vec"] = self.vectors[i].tolist()
                self.node.request("PUT", f"/{index}/_doc/d{i}", doc)
            self.node.request("POST", f"/{index}/_refresh")
            self.live[index] = [i for i in range(n) if i % 29 != 5]
        # three of the first query's ten best leave `gone`
        for i in self.ranked("gone", self.queries[0])[1:8:3]:
            self.node.request("DELETE", f"/gone/_doc/d{i}")
            self.live["gone"].remove(i)
        self.node.request("POST", "/gone/_refresh")

    def ref(self, q):
        from tests.reference_impl import ref_knn_score
        return [ref_knn_score(v, q, self.space) for v in self.vectors]

    def ranked(self, index, q, odd_only=False):
        ref = self.ref(q)
        docs = [i for i in self.live[index] if not odd_only or i % 2]
        return sorted(docs, key=lambda i: (-ref[i], i))

    def close(self):
        for index in self.live:
            self.node.request("DELETE", f"/{index}")


@pytest.fixture(scope="module", params=SPACES)
def clause_indices(request):
    held = ClauseIndices(request.param)
    yield held
    held.close()


# (index, k, size, and what else the clause or the body carries)
CLAUSE_CASES = {
    "size-under-k": ("exact", 20, 7, {}),
    "size-equals-k": ("exact", 20, 20, {}),
    "size-over-k": ("exact", 20, 35, {}),
    "filter-size-under-k": ("exact", 20, 7, {"filter": True}),
    "filter-size-equals-k": ("exact", 20, 20, {"filter": True}),
    "filter-size-over-k": ("exact", 20, 35, {"filter": True}),
    "fewer-docs-than-k": ("small", 100, 100, {}),
    "fewer-filtered-docs-than-k": ("small", 40, 40, {"filter": True}),
    "deleted-winners": ("gone", 20, 20, {}),
    "duplicates-doc-ascending": ("exact", 20, 20, {"query": "duplicate"}),
    "duplicates-at-the-cut": ("exact", 3, 10, {"query": "duplicate"}),
    "boost-zero": ("exact", 20, 12, {"boost": 0.0}),
    "boost-tiny": ("exact", 20, 20, {"boost": "tiny"}),
    "boost-two": ("exact", 20, 20, {"boost": 2.0}),
    "min-score-cuts-winners": ("exact", 20, 20, {"min_score": 9}),
    "min-score-and-a-short-page": ("exact", 20, 5, {"min_score": 9}),
    "ivf": ("probed", 20, 20, {}),
    "ivf-size-over-k": ("probed", 20, 35, {}),
    "msearch-of-three": ("exact", 20, 20, {"msearch": True}),
}


# the least normal float32: a product under it is denormal, and a
# backend that flushes denormals (the TPU, XLA's CPU backend) reads 0
F32_TINY = float(np.finfo(np.float32).tiny)


def clause_body(q, k, size, extra, min_score, wrapped, boost=1.0):
    clause = {"vector": q.tolist(), "k": k}
    if extra.get("filter"):
        clause["filter"] = {"term": {"tag": "odd"}}
    if "boost" in extra:
        clause["boost"] = boost
    query = {"knn": {"vec": clause}}
    body = {"size": size, "_source": False,
            "query": {"bool": {"must": [query]}} if wrapped else query}
    if min_score is not None:
        body["min_score"] = min_score
    return body


def page_of(resp):
    assert resp.get("_status", 200) == 200, resp
    assert resp["_shards"]["failed"] == 0 and resp["timed_out"] is False
    return ([(h["_id"], h["_score"]) for h in resp["hits"]["hits"]],
            resp["hits"]["total"], resp["hits"]["max_score"])


@pytest.mark.parametrize("case", sorted(CLAUSE_CASES))
def test_the_page_is_the_clauses_own_winners(clause_indices, case):
    held = clause_indices
    index, k, size, extra = CLAUSE_CASES[case]
    node = held.node
    queries = [held.vectors[DUPLICATES[0]]] \
        if extra.get("query") == "duplicate" else held.queries
    if not extra.get("msearch"):
        queries = queries[:2]
    refs = [held.ref(q) for q in queries]
    winners = [held.ranked(index, q, extra.get("filter"))[:k]
               for q in queries]
    boosts = [extra.get("boost", 1.0)] * len(queries)
    if extra.get("boost") == "tiny":
        # the nine best products stay normal float32 values, the others
        # fall under the least of them and tie (at 0 where the backend
        # flushes): the tied tail has to come doc-ascending
        boosts = [F32_TINY / ((ref[w[8]] + ref[w[9]]) / 2.0)
                  for ref, w in zip(refs, winners)]
    min_scores = [None] * len(queries)
    if "min_score" in extra:
        # between the ninth and the tenth winner's score: nine stay
        cut = extra["min_score"]
        min_scores = [(ref[w[cut - 1]] + ref[w[cut]]) / 2.0
                      for ref, w in zip(refs, winners)]
    def serve(wrapped):
        bodies = [clause_body(q, k, size, extra, ms, wrapped, boost)
                  for q, ms, boost in zip(queries, min_scores, boosts)]
        if not extra.get("msearch"):
            return [page_of(node.request("POST", f"/{index}/_search", b))
                    for b in bodies]
        # three bodies of one shape: one vmapped program serves them
        return [page_of(r) for r in msearch(node, index, bodies)]

    served, dense = serve(False), serve(True)
    for ref, won, ms, boost, (hits, total, max_score), densely in zip(
            refs, winners, min_scores, boosts, served, dense):
        # the same clause served densely: ids, float32 scores, total
        assert (hits, total, max_score) == densely
        got = [int(i[1:]) for i, _ in hits]
        scores = [s for _, s in hits]
        assert len(set(got)) == len(got)
        # score descending, ties by lowest doc
        assert all(a > b or (a == b and g < h) for a, b, g, h in zip(
            scores, scores[1:], got, got[1:]))
        for g, s in zip(got, scores):
            assert s == pytest.approx(ref[g] * boost, rel=1e-5) \
                or (s == 0.0 and ref[g] * boost < F32_TINY)
        if index == "probed":
            # a probe's candidates are its own; every hit is a live doc
            # scored exactly, and the total is the winners'
            assert total["value"] <= k
            assert len(got) == min(size, total["value"])
            assert set(got) <= set(held.live[index])
            continue
        keep = [w for w in won if ms is None or ref[w] * boost >= ms]
        assert total == {"value": len(keep), "relation": "eq"}
        assert len(got) == min(size, len(keep))
        if boost == 0.0:
            # every product ties: the page is the winners' lowest docs,
            # whatever their raw rank
            assert got == sorted(keep)[:size] and set(scores) == {0.0}
            continue
        if extra.get("boost") == "tiny":
            # the tail that tied (flushed: eleven of the twenty, or all
            # twenty where the boost itself is under the least normal)
            tied = scores.index(scores[-1])
            assert tied <= 9
            assert got[:tied] == keep[:tied]
            assert got[tied:] == sorted(keep[tied:])
            continue
        for g, w in zip(got, keep):
            assert g == w or ref[g] == pytest.approx(ref[w], rel=1e-6)
    if extra.get("query") == "duplicate":
        # the copies score alike and lead the page, lowest doc first
        lead = [d for d in DUPLICATES if d in held.live[index]][:min(k, size)]
        assert got[:len(lead)] == lead
        assert len(set(scores[:len(lead)])) == 1
    if case == "fewer-docs-than-k":
        assert total["value"] == len(held.live["small"]) < k
    if case == "deleted-winners":
        assert len(held.live["gone"]) == len(held.live["exact"]) - 3


# (the query over `vec` / `probed`, `top_k`s over d_pad lanes in the
# served program, what `search.knn_clause.page_from_clause` rises by)
def _programs():
    clause = {"vector": [0.3, -1.0, 0.5, 0.1, 0.9, -0.2], "k": 9}
    odd = {"term": {"tag": "odd"}}
    return {
        "root": ({"knn": {"vec": clause}}, 1, 1),
        "root-filtered": ({"knn": {"vec": {**clause, "filter": odd}}}, 1, 1),
        "root-ivf": ({"knn": {"probed": clause}}, 1, 1),
        "bool-must": ({"bool": {"must": [{"knn": {"vec": clause}}]}}, 2, 0),
        "bool-must-and-filter": ({"bool": {
            "must": [{"knn": {"vec": clause}}], "filter": [odd]}}, 2, 0),
        "function-score": ({"function_score": {
            "query": {"knn": {"vec": clause}}, "weight": 2.0}}, 2, 0),
    }


@pytest.fixture(scope="module")
def structure_node():
    from opensearch_tpu.node import Node
    node = Node()
    node.request("PUT", "/shapes", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "vec": {"type": "knn_vector", "dimension": 6},
            "probed": {"type": "knn_vector", "dimension": 6, "method": {
                "name": "ivf", "parameters": {"nlist": 4, "nprobes": 2}}},
            "tag": {"type": "keyword"}}}})
    rng = np.random.RandomState(34)
    for i, v in enumerate(rng.randn(77, 6).astype(np.float32)):
        node.request("PUT", f"/shapes/_doc/d{i}", {
            "vec": v.tolist(), "probed": v.tolist(),
            "tag": "odd" if i % 2 else "even"})
    node.request("POST", "/shapes/_refresh")
    yield node
    node.request("DELETE", "/shapes")


@pytest.mark.parametrize("program", sorted(_programs()))
def test_a_root_clause_selects_once_over_the_doc_axis(structure_node,
                                                      program):
    """ISSUE 34: the envelope program of a plan whose root is a `knn`
    clause holds ONE `top_k` over `d_pad` lanes (the clause's), and the
    items it serves count in `search.knn_clause.page_from_clause`; a
    clause under a parent keeps the dense pair and the page's own
    `top_k`, and counts nothing."""
    from opensearch_tpu.telemetry import TELEMETRY
    node = structure_node
    query, selections, counted = _programs()[program]
    d_pad = 128                             # pad_bucket(77)

    def count():
        return knn_clause_counters(node)["page_from_clause"]

    before = count()
    resp = node.request("POST", "/shapes/_search", {
        "size": 12, "_source": False, "query": query})
    assert resp["_status"] == 200 and resp["hits"]["hits"], resp
    assert count() - before == counted
    # the ring is the process's: this request's `dispatch` is its last
    span = [s["attributes"] for s in node.request(
        "GET", "/_telemetry/spans")["spans"] if s["name"] == "dispatch"][-1]
    assert span["family"] == "knn" and f"/d{d_pad}x6k9" in span["shape"]
    fn, structs = TELEMETRY.kernels._lowerable[span["fingerprint"]]
    text = fn.lower(*structs).as_text()
    over_docs = re.findall(
        rf"chlo\.top_k\(.*\) : tensor<(?:\d+x)*{d_pad}xf32>", text)
    assert len(over_docs) == selections, re.findall(r"chlo\.top_k.*", text)
    # three items of one `_msearch` count three (one vmapped program)
    body = {"size": 12, "_source": False, "query": query}
    before = count()
    assert all(r["hits"]["hits"] for r in msearch(node, "shapes", [body] * 3))
    assert count() - before == 3 * counted


def test_child_rows_among_the_winners_are_not_returnable():
    """A root clause over a nested field's vectors selects child rows;
    none is a hit (`root`, asked of the k winners), as the dense pair
    had it; under its `nested` query the parents come back."""
    from opensearch_tpu.node import Node
    node = Node()
    node.request("PUT", "/family", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "vec": {"type": "knn_vector", "dimension": 4},
            "kids": {"type": "nested", "properties": {
                "vec": {"type": "knn_vector", "dimension": 4}}}}}})
    rng = np.random.RandomState(9)
    for i in range(40):
        node.request("PUT", f"/family/_doc/d{i}", {
            "vec": rng.randn(4).tolist(),
            "kids": [{"vec": rng.randn(4).tolist()} for _ in range(i % 3)]})
    node.request("POST", "/family/_refresh")
    q = [0.1, 0.2, 0.3, 0.4]

    def search(query):
        resp = node.request("POST", "/family/_search", {
            "size": 10, "_source": False, "query": query})
        assert resp["_status"] == 200, resp
        return resp["hits"]["total"]["value"], len(resp["hits"]["hits"])

    assert search({"knn": {"kids.vec": {"vector": q, "k": 30}}}) == (0, 0)
    total, hits = search({"nested": {"path": "kids", "query": {
        "knn": {"kids.vec": {"vector": q, "k": 30}}}}})
    assert 0 < total <= 30 and hits == 10
    # parents and children share the doc axis: the parents' own field
    assert search({"knn": {"vec": {"vector": q, "k": 30}}}) == (30, 10)
    node.request("DELETE", "/family")


class TestIvfKnn:
    def test_recall_on_clustered_data(self):
        # clustered corpus (IVF's favorable + realistic case)
        rng = np.random.RandomState(3)
        centers = rng.randn(8, DIMS).astype(np.float32) * 5
        n = 800
        assign = rng.randint(0, 8, size=n)
        vectors = (centers[assign]
                   + rng.randn(n, DIMS).astype(np.float32) * 0.5)
        mapping = {"properties": {"vec": {
            "type": "knn_vector", "dimension": DIMS,
            "method": {"name": "ivf", "space_type": "l2",
                       "parameters": {"nlist": 8, "nprobes": 4}}}}}
        svc = IndexService("ivf-idx", mapping=mapping)
        svc.bulk([{"action": "index", "id": f"d{i}",
                   "source": {"vec": vectors[i].tolist()}}
                  for i in range(n)])
        svc.refresh()
        # IVF actually built (>=256 vectors, method ivf)
        seg = svc.shards[0].engine.segments[0]
        assert seg.vector_dv["vec"].ivf is not None
        recalls = []
        for _ in range(10):
            q = (centers[rng.randint(0, 8)]
                 + rng.randn(DIMS).astype(np.float32) * 0.5)
            resp = svc.search({"query": {"knn": {"vec": {
                "vector": q.tolist(), "k": 10}}}, "size": 10})
            got = {h["_id"] for h in resp["hits"]["hits"]}
            ref = np_scores(vectors, q, "l2")
            want = {f"d{i}" for i in np.argsort(-ref)[:10]}
            recalls.append(len(got & want) / 10)
        assert np.mean(recalls) >= 0.9, f"IVF recall@10 {np.mean(recalls)}"
        svc.close()

    def test_hnsw_mapping_maps_to_ivf(self):
        from opensearch_tpu.index.mapper import MapperService
        m = MapperService({"properties": {"v": {
            "type": "knn_vector", "dimension": 4,
            "method": {"name": "hnsw", "space_type": "cosinesimil"}}}})
        ft = m.get_field("v")
        assert ft.knn_method == "ivf"
        assert ft.similarity_space == "cosinesimil"

    def test_ivf_persists_across_reopen(self, tmp_path):
        rng = np.random.RandomState(5)
        vectors = rng.randn(300, DIMS).astype(np.float32)
        mapping = {"properties": {"vec": {
            "type": "knn_vector", "dimension": DIMS,
            "method": {"name": "ivf", "parameters": {"nlist": 4}}}}}
        svc = IndexService("pivf", mapping=mapping, data_path=str(tmp_path))
        svc.bulk([{"action": "index", "id": f"d{i}",
                   "source": {"vec": vectors[i].tolist()}}
                  for i in range(300)])
        svc.flush()
        svc.close()
        svc2 = IndexService("pivf", mapping=mapping, data_path=str(tmp_path))
        seg = svc2.shards[0].engine.segments[0]
        assert seg.vector_dv["vec"].ivf is not None
        q = vectors[42]
        resp = svc2.search({"query": {"knn": {"vec": {
            "vector": q.tolist(), "k": 5}}}})
        assert resp["hits"]["hits"][0]["_id"] == "d42"
        svc2.close()


class TestScatterRegressions:
    """Pins for review findings: -1 padding / invalid top-k slots must not
    clobber doc ord 0's scatter entries."""

    def test_doc_zero_wins_exact_fewer_than_k(self):
        svc, vectors = make_service(n=5)
        q = vectors[0]  # doc ord 0 is the best hit; k > eligible count
        resp = svc.search({"query": {"knn": {"vec": {
            "vector": q.tolist(), "k": 10}}}})
        assert resp["hits"]["hits"][0]["_id"] == "d0"
        assert resp["hits"]["total"]["value"] == 5
        svc.close()

    def test_doc_zero_wins_ivf(self):
        rng = np.random.RandomState(9)
        vectors = rng.randn(400, DIMS).astype(np.float32)
        mapping = {"properties": {"vec": {
            "type": "knn_vector", "dimension": DIMS,
            "method": {"name": "ivf", "parameters": {"nlist": 4,
                                                     "nprobes": 4}}}}}
        svc = IndexService("z-ivf", mapping=mapping)
        svc.bulk([{"action": "index", "id": f"d{i}",
                   "source": {"vec": vectors[i].tolist()}}
                  for i in range(400)])
        svc.refresh()
        assert svc.shards[0].engine.segments[0].vector_dv["vec"].ivf is not None
        resp = svc.search({"query": {"knn": {"vec": {
            "vector": vectors[0].tolist(), "k": 5}}}})
        assert resp["hits"]["hits"][0]["_id"] == "d0"
        svc.close()


class TestKnnTelemetry:
    """ISSUE 33: a k-NN clause counts by the method it takes and the
    vector bytes its scan reads, and its `dispatch` span says what the
    program scans and selects."""

    def test_clause_counters_and_dispatch_shape(self):
        from opensearch_tpu.node import Node
        node = Node()

        def counters():
            return knn_clause_counters(node)

        def search(index, clause, k):
            resp = node.request("POST", f"/{index}/_search", {
                "size": k, "query": {"knn": {"vec": clause}}})
            assert resp["_status"] == 200, resp

        rng = np.random.RandomState(2)
        vectors = rng.randn(300, DIMS).astype(np.float32)
        for index, method in (("flat", {"space_type": "innerproduct"}),
                              ("probed", {"name": "ivf", "parameters": {
                                  "nlist": 4, "nprobes": 2}})):
            node.request("PUT", f"/{index}", {"mappings": {"properties": {
                "vec": {"type": "knn_vector", "dimension": DIMS,
                        "method": method},
                "tag": {"type": "keyword"}}}})
            for i, v in enumerate(vectors):
                node.request("PUT", f"/{index}/_doc/d{i}", {
                    "vec": v.tolist(), "tag": "even" if i % 2 == 0
                    else "odd"})
            node.request("POST", f"/{index}/_refresh")
        q = rng.randn(DIMS).astype(np.float32).tolist()
        d_pad = 512                         # pad_bucket(300)
        before = counters()
        search("flat", {"vector": q, "k": 7}, 7)
        one = counters()
        assert one["exact"] - before["exact"] == 1
        assert one["scanned_bytes"] - before["scanned_bytes"] \
            == d_pad * DIMS * 4
        search("flat", {"vector": q, "k": 7,
                        "filter": {"term": {"tag": "odd"}}}, 7)
        # a filter takes the exact scan, on an IVF field too
        search("probed", {"vector": q, "k": 7,
                          "filter": {"term": {"tag": "odd"}}}, 7)
        two = counters()
        assert two["filtered"] - one["filtered"] == 2
        assert two["scanned_bytes"] - one["scanned_bytes"] \
            == 2 * d_pad * DIMS * 4
        search("probed", {"vector": q, "k": 7}, 7)
        three = counters()
        assert three["ivf"] - two["ivf"] == 1
        # a probe reads blocks of the packed copy, not the column
        assert three["scanned_bytes"] == two["scanned_bytes"]
        assert (three["exact"], three["filtered"]) \
            == (one["exact"], two["filtered"])
        spans = node.request("GET", "/_telemetry/spans")["spans"]
        shapes = [s["attributes"]["shape"] for s in spans
                  if s["name"] == "dispatch"
                  and s.get("attributes", {}).get("family") == "knn"]
        assert shapes[-4:] == [f"b1/d{d_pad}x{DIMS}k7"] * 4
        for index in ("flat", "probed"):
            node.request("DELETE", f"/{index}")

    def test_a_nested_clause_names_the_shape_and_text_keeps_its_own(self):
        """The shape is the k-NN clause's wherever it sits in the plan
        (its k, not the page's); a program with no k-NN clause keeps
        `b<batch>/k<k>/d<d_pad>`."""
        from opensearch_tpu.node import Node
        node = Node()
        node.request("PUT", "/mixed", {"mappings": {"properties": {
            "vec": {"type": "knn_vector", "dimension": DIMS},
            "body": {"type": "text"}}}})
        rng = np.random.RandomState(4)
        for i, v in enumerate(rng.randn(40, DIMS).astype(np.float32)):
            node.request("PUT", f"/mixed/_doc/d{i}", {
                "vec": v.tolist(), "body": "red" if i % 2 else "blue"})
        node.request("POST", "/mixed/_refresh")
        q = rng.randn(DIMS).astype(np.float32).tolist()
        for body in ({"size": 3, "query": {"bool": {
                          "must": [{"knn": {"vec": {"vector": q, "k": 9}}}],
                          "filter": [{"match": {"body": "red"}}]}}},
                     {"size": 3, "query": {"match": {"body": "red"}}}):
            assert node.request("POST", "/mixed/_search",
                                body)["_status"] == 200
        # the ring is the process's: these two requests are its last two
        knn, text = [s["attributes"] for s in node.request(
            "GET", "/_telemetry/spans")["spans"]
            if s["name"] == "dispatch"][-2:]
        d_pad = 128                         # pad_bucket(40)
        assert (knn["family"], knn["shape"]) \
            == ("knn", f"b1/d{d_pad}x{DIMS}k9")
        assert text["family"] != "knn" and re.fullmatch(
            rf"b1/k\d+/d{d_pad}", text["shape"]), text
        node.request("DELETE", "/mixed")
