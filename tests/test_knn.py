"""k-NN tests: exact parity vs numpy, spaces, filtering, IVF recall, persistence.

Models the k-NN plugin's test strategy (recall-at-k against brute force);
BASELINE.md configs 4 (exact) and 5 (ANN)."""

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
            stats = node.request("GET", "/_nodes/stats")
            c = next(iter(stats["nodes"].values()))[
                "telemetry"]["metrics"]["counters"]
            return {m: c.get(f"search.knn_clause.{m}", 0) for m in
                    ("exact", "ivf", "filtered", "scanned_bytes")}

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
