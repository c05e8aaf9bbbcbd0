"""Late-interaction MaxSim tier (ISSUE 18): differential parity vs the
pure-Python oracle (tests/reference_impl.ref_maxsim_scores) across
batch sizes B ∈ {1, 32, 1024} and wave splits W ∈ {1, 2, 4},
multi-segment + multi-shard merge, padded-token / empty-doc / deleted
edge cases, PQ-vs-exact recall@10, the oversample → BM25 →
rescore_maxsim rerank pipeline with its OFF-by-default device-scoring
gate (pristine differential + ledger channels), and the 400-never-500
validation contract for the rank_vectors mapping, the maxsim query,
and both rescore processors.
"""

import json

import numpy as np
import pytest

from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import SegmentBuilder, merge_segments
from opensearch_tpu.index.service import IndexService
from opensearch_tpu.node import Node
from opensearch_tpu.search.executor import SearchExecutor, ShardReader

from reference_impl import ref_maxsim_scores

DIMS = 8
MAX_TOKENS = 16


def _mapping(compression="none"):
    spec = {"type": "rank_vectors", "dimension": DIMS,
            "max_tokens": MAX_TOKENS}
    if compression != "none":
        spec["compression"] = compression
    return {"properties": {
        "tok": spec,
        "title": {"type": "text"},
        "tag": {"type": "keyword"},
    }}


def _make_docs(n_docs, rng):
    """Token matrices per doc: ~8% missing field, ~4% empty token list
    (both must be ineligible), the rest 1..8 tokens of DIMS floats."""
    docs = []
    for i in range(n_docs):
        r = rng.rand()
        if r < 0.08:
            docs.append(None)
        elif r < 0.12:
            docs.append([])
        else:
            nt = int(rng.randint(1, 9))
            docs.append(rng.randn(nt, DIMS).round(3).tolist())
    return docs


def build_reader(n_docs=120, n_segments=3, seed=0, compression="none"):
    mapper = MapperService(_mapping(compression))
    rng = np.random.RandomState(seed)
    docs = _make_docs(n_docs, rng)
    per = n_docs // n_segments
    segments, seg_docs = [], []
    for s in range(n_segments):
        builder = SegmentBuilder(mapper, seg_id=f"seg_{s}")
        chunk = docs[s * per:(s + 1) * per]
        for j, toks in enumerate(chunk):
            i = s * per + j
            src = {"title": "fox red", "tag": ["even", "odd"][i % 2]}
            if toks is not None:
                src["tok"] = toks
            builder.add(mapper.parse_document(f"d{i}", src))
        segments.append(builder.seal())
        seg_docs.append(chunk)
    return mapper, segments, seg_docs


def _queries(n, seed=1, n_tokens=3):
    rng = np.random.RandomState(seed)
    return [rng.randn(n_tokens, DIMS).round(3).tolist() for _ in range(n)]


def _body(q, k=10, size=10, flt=None):
    spec = {"query_vectors": q, "k": k}
    if flt is not None:
        spec["filter"] = flt
    return {"query": {"maxsim": {"tok": spec}}, "size": size}


def _expected_ids(seg_docs, q, k):
    """Cross-segment merge of the per-segment oracle top-k."""
    per_seg = ref_maxsim_scores(seg_docs, q, k)
    merged = []
    for topk in per_seg:
        for (s, ord_), score in topk.items():
            merged.append((score, s, ord_))
    merged.sort(key=lambda e: (-e[0], e[1], e[2]))
    per = len(seg_docs[0])
    return ([f"d{s * per + o}" for _, s, o in merged[:k]],
            [sc for sc, _, _ in merged[:k]])


@pytest.fixture(scope="module")
def ex():
    mapper, segments, seg_docs = build_reader()
    executor = SearchExecutor(ShardReader(mapper, segments))
    return executor, seg_docs


def _strip(resp):
    resp = json.loads(json.dumps(resp))
    resp.pop("took", None)
    return resp


# ------------------------------------------------------------ exact parity

class TestExactParity:
    def test_parity_with_oracle_multi_segment(self, ex):
        executor, seg_docs = ex
        for q in _queries(4, seed=2):
            resp = executor.search(_body(q, k=10))
            got = [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]
            want_ids, want_scores = _expected_ids(seg_docs, q, 10)
            assert [g for g, _ in got] == want_ids
            np.testing.assert_allclose(
                [s for _, s in got], want_scores, rtol=1e-5)

    @pytest.mark.parametrize("b", [1, 32, 1024])
    def test_msearch_batch_parity(self, ex, b):
        """The msearch envelope at B ∈ {1, 32, 1024} returns exactly the
        single-search responses (modulo took)."""
        executor, _ = ex
        qs = _queries(8, seed=3)
        bodies = [_body(qs[i % len(qs)], k=5, size=5) for i in range(b)]
        singles = [_strip(executor.search(dict(body)))
                   for body in bodies[:min(b, 8)]]
        batched = executor.multi_search([dict(body) for body in bodies],
                                        _bypass_request_cache=True)
        for i, got in enumerate(batched["responses"][:len(singles)]):
            assert _strip(got) == singles[i]

    @pytest.mark.parametrize("w", [1, 2, 4])
    def test_wave_split_parity(self, ex, w):
        """W ∈ {1, 2, 4} wave splits are byte-identical (modulo took)."""
        executor, _ = ex
        qs = _queries(8, seed=4)
        bodies = [_body(qs[i % len(qs)], k=5, size=5) for i in range(32)]
        base = executor.multi_search([dict(body) for body in bodies],
                                     waves=1, _bypass_request_cache=True)
        got = executor.multi_search([dict(body) for body in bodies],
                                    waves=w, _bypass_request_cache=True)
        assert [_strip(r) for r in got["responses"]] == \
            [_strip(r) for r in base["responses"]]

    def test_multi_shard_merge(self):
        svc = IndexService("ms-shards", mapping=_mapping(),
                           settings={"number_of_shards": 3})
        rng = np.random.RandomState(5)
        docs = _make_docs(60, rng)
        for i, toks in enumerate(docs):
            src = {"title": "x", "tag": "t"}
            if toks is not None:
                src["tok"] = toks
            svc.index_doc(f"d{i}", src)
        svc.refresh()
        q = _queries(1, seed=6)[0]
        resp = svc.search(_body(q, k=10))
        want_ids, _ = _expected_ids([docs], q, 10)
        assert [h["_id"] for h in resp["hits"]["hits"]] == want_ids
        svc.close()

    def test_merge_preserves_rank_vectors(self, ex):
        """Segment merge round-trips token matrices through _source."""
        executor, seg_docs = ex
        mapper = executor.reader.mapper
        merged = merge_segments(mapper, executor.reader.segments, "m0")
        col = merged.rank_vectors_dv["tok"]
        n_real = sum(1 for chunk in seg_docs for t in chunk if t)
        assert int(col.exists.sum()) == n_real
        m_ex = SearchExecutor(ShardReader(mapper, [merged]))
        q = _queries(1, seed=7)[0]
        resp = m_ex.search(_body(q, k=10))
        want_ids, _ = _expected_ids(seg_docs, q, 10)
        assert [h["_id"] for h in resp["hits"]["hits"]] == want_ids


# --------------------------------------------------------- filters + edges

class TestFiltersAndEdges:
    def test_filtered_maxsim(self, ex):
        executor, seg_docs = ex
        q = _queries(1, seed=8)[0]
        resp = executor.search(
            _body(q, k=5, size=5, flt={"term": {"tag": "even"}}))
        ids = [h["_id"] for h in resp["hits"]["hits"]]
        assert ids and all(int(i[1:]) % 2 == 0 for i in ids)
        # exact filtered top-k: the best even-ord docs by oracle score
        flat = [t for chunk in seg_docs for t in chunk]
        per_doc = ref_maxsim_scores([flat], q, len(flat))[0]
        even = sorted(((s, o) for (_, o), s in per_doc.items()
                       if o % 2 == 0), key=lambda e: (-e[0], e[1]))
        assert ids == [f"d{o}" for _, o in even[:5]]

    def test_empty_and_missing_docs_never_match(self, ex):
        executor, seg_docs = ex
        ineligible = {f"d{s * len(seg_docs[0]) + j}"
                      for s, chunk in enumerate(seg_docs)
                      for j, t in enumerate(chunk) if not t}
        assert ineligible, "corpus should contain empty/missing docs"
        q = _queries(1, seed=9)[0]
        resp = executor.search(_body(q, k=100, size=100))
        got = {h["_id"] for h in resp["hits"]["hits"]}
        assert not (got & ineligible)

    def test_deleted_docs_excluded(self):
        svc = IndexService("ms-del", mapping=_mapping())
        rng = np.random.RandomState(10)
        toks = rng.randn(4, DIMS).round(3).tolist()
        for i in range(20):
            svc.index_doc(f"d{i}",
                          {"tok": rng.randn(3, DIMS).round(3).tolist()})
        svc.index_doc("best", {"tok": toks})
        svc.refresh()
        q = toks  # the doc's own tokens → "best" is top-1
        resp = svc.search(_body(q, k=3))
        assert resp["hits"]["hits"][0]["_id"] == "best"
        svc.delete_doc("best")
        svc.refresh()
        resp = svc.search(_body(q, k=3))
        assert "best" not in [h["_id"] for h in resp["hits"]["hits"]]
        svc.close()

    def test_doc_zero_wins_fewer_than_k(self):
        """Scatter pin (test_knn idiom): -1-padded invalid top-k slots
        must not clobber doc ord 0 when eligible docs < k."""
        svc = IndexService("ms-z", mapping=_mapping())
        rng = np.random.RandomState(11)
        docs = [rng.randn(3, DIMS).round(3).tolist() for _ in range(5)]
        for i, t in enumerate(docs):
            svc.index_doc(f"d{i}", {"tok": t})
        svc.refresh()
        resp = svc.search(_body(docs[0], k=10, size=10))
        assert resp["hits"]["hits"][0]["_id"] == "d0"
        assert resp["hits"]["total"]["value"] == 5
        svc.close()

    def test_maxsim_inside_bool(self, ex):
        executor, _ = ex
        q = _queries(1, seed=12)[0]
        resp = executor.search({"query": {"bool": {
            "must": [{"maxsim": {"tok": {"query_vectors": q, "k": 20}}}],
            "filter": [{"term": {"tag": "odd"}}]}}, "size": 30})
        # k bounds matches per segment (same contract as knn-in-bool)
        n_segments = len(executor.reader.segments)
        assert 0 < resp["hits"]["total"]["value"] <= 20 * n_segments
        assert all(int(h["_id"][1:]) % 2 == 1
                   for h in resp["hits"]["hits"])


# ------------------------------------------------------------------ PQ arm

class TestPQ:
    def test_pq_recall_vs_exact(self):
        """compression: pq recall@10 ≥ 0.95 of exact over query sweeps
        (ISSUE 18's acceptance bound)."""
        mapper_e, segs_e, seg_docs = build_reader(seed=20)
        mapper_p, segs_p, _ = build_reader(seed=20, compression="pq")
        ex_e = SearchExecutor(ShardReader(mapper_e, segs_e))
        ex_p = SearchExecutor(ShardReader(mapper_p, segs_p))
        recalls = []
        for q in _queries(10, seed=21):
            exact = {h["_id"] for h in
                     ex_e.search(_body(q, k=10))["hits"]["hits"]}
            approx = {h["_id"] for h in
                      ex_p.search(_body(q, k=10))["hits"]["hits"]}
            recalls.append(len(exact & approx) / max(len(exact), 1))
        assert np.mean(recalls) >= 0.95, f"PQ recall@10 {np.mean(recalls)}"

    def test_pq_seal_artifacts_and_mapping(self):
        mapper, segments, _ = build_reader(seed=22, compression="pq")
        col = segments[0].rank_vectors_dv["tok"]
        assert col.codes is not None and col.codes.dtype == np.uint8
        m = DIMS // 4
        assert col.codebook.shape == (m, 256, DIMS // m)
        assert col.codes.shape == (segments[0].num_docs, col.t_bucket, m)
        rendered = mapper.mapping_dict()["properties"]["tok"]
        assert rendered["compression"] == "pq"
        assert rendered["pq_m"] == m


# --------------------------------------------------------- rerank pipeline

def _rerank_node(seed=30, n_docs=30):
    node = Node()
    rng = np.random.RandomState(seed)
    r = node.request("PUT", "/idx", {
        "settings": {"number_of_shards": 1},
        "mappings": _mapping()})
    assert r["_status"] == 200, r
    docs = {}
    for i in range(n_docs):
        toks = rng.randn(int(rng.randint(1, 6)), DIMS).round(3).tolist()
        docs[f"d{i}"] = toks
        node.request("PUT", f"/idx/_doc/d{i}",
                     {"title": "fox red dog", "tok": toks, "tag": "t"})
    node.request("POST", "/idx/_refresh", {})
    return node, docs, rng


class TestRescorePipeline:
    def test_oversample_bm25_rescore_truncate(self):
        """The full multi-stage chain: oversample → BM25 candidates →
        MaxSim rerank → truncate back to the requested size, checked
        against the host-side MaxSim ranking of the candidate pool."""
        node, docs, rng = _rerank_node()
        q = rng.randn(3, DIMS).round(3).tolist()
        r = node.request("PUT", "/_search/pipeline/rr", {
            "request_processors": [{"oversample": {"sample_factor": 3}}],
            "response_processors": [
                {"rescore_maxsim": {"field": "tok", "query_vectors": q,
                                    "model_dims": DIMS}},
                {"truncate_hits": {}}]})
        assert r["_status"] == 200, r
        res = node.request("POST", "/idx/_search",
                           {"query": {"match": {"title": "fox"}},
                            "size": 5},
                           search_pipeline="rr")
        assert res["_status"] == 200, res
        hits = res["hits"]["hits"]
        assert len(hits) == 5
        qa = np.asarray(q, np.float32)
        # all docs match "fox" and tie on BM25 → the oversampled pool is
        # the first 15 docs in doc order; rerank re-ranks within it
        pool = [f"d{i}" for i in range(15)]
        want = {d: float((np.asarray(docs[d], np.float32) @ qa.T)
                         .max(axis=0).sum()) for d in pool}
        top = sorted(want, key=lambda d: -want[d])[:5]
        assert [h["_id"] for h in hits] == top
        for h in hits:
            assert h["_score"] == pytest.approx(want[h["_id"]], rel=1e-5)

    def test_device_gate_pristine_differential(self):
        """MAXSIM_DEVICE_RESCORE is OFF by default; flipping it ON ranks
        identically (device f32 vs host f32 mirror) and records the
        upload.maxsim_query / maxsim_scores ledger channels; flipping it
        back OFF restores byte-identical pristine responses."""
        import opensearch_tpu.searchpipeline.processors as procs
        from opensearch_tpu.telemetry import TELEMETRY
        assert procs.MAXSIM_DEVICE_RESCORE is False
        node, docs, rng = _rerank_node(seed=31)
        q = rng.randn(3, DIMS).round(3).tolist()
        node.request("PUT", "/_search/pipeline/rr", {
            "response_processors": [
                {"rescore_maxsim": {"field": "tok",
                                    "query_vectors": q}}]})
        body = {"query": {"match": {"title": "fox"}}, "size": 5}
        pristine = _strip(node.request("POST", "/idx/_search", dict(body),
                                       search_pipeline="rr"))
        saved = TELEMETRY.ledger.enabled
        TELEMETRY.ledger.enabled = True
        TELEMETRY.ledger.reset()
        procs.MAXSIM_DEVICE_RESCORE = True
        try:
            gated = node.request("POST", "/idx/_search", dict(body),
                                 search_pipeline="rr")
        finally:
            procs.MAXSIM_DEVICE_RESCORE = False
            snap = TELEMETRY.ledger.snapshot()
            TELEMETRY.ledger.enabled = saved
        assert gated["_status"] == 200
        assert [h["_id"] for h in gated["hits"]["hits"]] == \
            [h["_id"] for h in pristine["hits"]["hits"]]
        for a, b in zip(gated["hits"]["hits"], pristine["hits"]["hits"]):
            assert a["_score"] == pytest.approx(b["_score"], rel=1e-5)
        assert snap["channels"]["h2d"]["upload.maxsim_query"]["bytes"] > 0
        assert snap["channels"]["d2h"]["maxsim_scores"]["bytes"] > 0
        # gate back off → byte-identical pristine response
        again = _strip(node.request("POST", "/idx/_search", dict(body),
                                    search_pipeline="rr"))
        assert again == pristine


# --------------------------------------------------- 400-never-500 contract

class TestValidation:
    def test_mapping_rejections(self):
        node = Node()
        bad = [
            {"type": "rank_vectors"},                               # no dims
            {"type": "rank_vectors", "dimension": 0},
            {"type": "rank_vectors", "dimension": 8, "max_tokens": 0},
            {"type": "rank_vectors", "dimension": 8,
             "compression": "zip"},
            {"type": "rank_vectors", "dimension": 8,
             "compression": "pq", "pq_m": 3},                       # 3 ∤ 8
        ]
        for i, spec in enumerate(bad):
            r = node.request("PUT", f"/bad{i}",
                             {"mappings": {"properties": {"tok": spec}}})
            assert r["_status"] == 400, (spec, r)

    def test_query_rejections(self):
        node, docs, rng = _rerank_node(seed=32, n_docs=5)
        cases = [
            _body([[0.0] * (DIMS + 1)]),                    # dims mismatch
            _body([[0.0] * DIMS] * (MAX_TOKENS + 1)),       # too many tokens
            {"query": {"maxsim": {"tok": {"query_vectors": []}}}},
            {"query": {"maxsim": {"tok": {}}}},
            {"query": {"maxsim": {"title": {                # not rank_vectors
                "query_vectors": [[0.0] * DIMS]}}}},
        ]
        for body in cases:
            r = node.request("POST", "/idx/_search", body)
            assert r["_status"] == 400, (body, r)

    def test_rescore_processor_rejections(self):
        node, docs, rng = _rerank_node(seed=33, n_docs=5)
        # PUT-time: bad model_dims on both rescore processors
        for proc in ("rescore_maxsim", "rescore_knn"):
            for md in (-1, 0, "four", True):
                r = node.request("PUT", "/_search/pipeline/bad", {
                    "response_processors": [{proc: {
                        "field": "tok", "model_dims": md}}]})
                assert r["_status"] == 400, (proc, md, r)
        q_body = {"query": {"match": {"title": "fox"}}, "size": 3}
        # query-time: dims mismatch / missing field / non-rank_vectors
        for pipeline_id, spec in [
            ("mm", {"field": "tok",
                    "query_vectors": [[0.0] * (DIMS + 1)]}),
            ("mf", {"field": "nope",
                    "query_vectors": [[0.0] * DIMS]}),
            ("tf", {"field": "title",
                    "query_vectors": [[0.0] * DIMS]}),
            ("rg", {"field": "tok", "query_vectors": [[0.0] * DIMS],
                    "model_dims": DIMS + 1}),
            ("nv", {"field": "tok"}),       # no vectors, no maxsim clause
        ]:
            r = node.request("PUT", f"/_search/pipeline/{pipeline_id}", {
                "response_processors": [{"rescore_maxsim": spec}]})
            assert r["_status"] == 200, (pipeline_id, r)
            res = node.request("POST", "/idx/_search", dict(q_body),
                               search_pipeline=pipeline_id)
            assert res["_status"] == 400, (pipeline_id, res)
        # rescore_knn: model_dims mismatch and non-vector field → 400
        for pipeline_id, spec in [
            ("kmm", {"field": "tok",
                     "query_vector": [0.0] * DIMS}),        # not knn_vector
            ("kmd", {"field": "tok", "query_vector": [0.0] * DIMS,
                     "model_dims": DIMS + 1}),
        ]:
            r = node.request("PUT", f"/_search/pipeline/{pipeline_id}", {
                "response_processors": [{"rescore_knn": spec}]})
            assert r["_status"] == 200, (pipeline_id, r)
            res = node.request("POST", "/idx/_search", dict(q_body),
                               search_pipeline=pipeline_id)
            assert res["_status"] == 400, (pipeline_id, res)
