"""SPMD distributed search over the 8-virtual-device mesh.

The analog of the reference's InternalTestCluster multi-node tests
(test/framework/.../test/InternalTestCluster.java:195): many shards, one
process. Correctness contract: the one-program mesh search must return the
same global top-k scores and total as running the single-shard executor on
each shard and merging on the host (SearchPhaseController.mergeTopDocs
semantics).
"""

import numpy as np
import pytest

from opensearch_tpu.ops.device_segment import upload_segment
from opensearch_tpu.parallel import DistributedSearcher, make_mesh
from opensearch_tpu.search import dsl
from opensearch_tpu.search.compile import Compiler, ShardStats
from opensearch_tpu.search.executor import SearchExecutor, ShardReader
from opensearch_tpu.search.aggs.engine import compile_aggs
from opensearch_tpu.search.aggs.parse import parse_aggs
from opensearch_tpu.utils.demo import build_shards

N_SHARDS = 8


@pytest.fixture(scope="module")
def corpus():
    mapper, segments = build_shards(
        n_docs=400, n_shards=N_SHARDS, vocab_size=300, avg_len=30, seed=11)
    return mapper, segments


@pytest.fixture(scope="module")
def mesh(eight_devices):
    return make_mesh(N_SHARDS)


def _payloads(mapper, segments, query, aggs=None):
    from opensearch_tpu.parallel.distributed import align_agg_plans, plan_struct
    stats = ShardStats(segments)
    compiler = Compiler(mapper, stats)
    node = dsl.parse_query(query)
    agg_nodes = parse_aggs(aggs) if aggs else []
    plan = None
    per_shard_aggs = []
    uploaded = []
    for seg in segments:
        arrays, meta = upload_segment(seg, to_device=False)
        p = compiler.compile(node, seg, meta)
        aps = compile_aggs(agg_nodes, mapper, seg, meta, compiler) \
            if agg_nodes else []
        if plan is None:
            plan = p
        else:
            assert plan_struct(p) == plan_struct(plan)
        per_shard_aggs.append(aps)
        uploaded.append((arrays, p, meta))
    if agg_nodes:
        align_agg_plans(per_shard_aggs)
    payloads = []
    for (arrays, p, meta), aps in zip(uploaded, per_shard_aggs):
        flat = p.flatten_inputs([])
        for ap in aps:
            ap.flatten_inputs(flat)
        payloads.append((arrays, flat, meta))
    return payloads, plan, per_shard_aggs


def _host_reference(mapper, segments, query, k):
    """Oracle: one reader over all segments (global stats, host merge)."""
    reader = ShardReader(mapper, list(segments))
    res = SearchExecutor(reader).search({"query": query, "size": k})
    scores = [h["_score"] for h in res["hits"]["hits"]]
    return scores, res["hits"]["total"]["value"]


QUERIES = [
    {"match": {"body": "w00003 w00007"}},
    {"bool": {"must": [{"match": {"body": "w00002"}}],
              "filter": [{"range": {"views": {"gte": 2000}}}]}},
    {"bool": {"should": [{"term": {"tag": "cat3"}},
                         {"match": {"body": "w00010"}}]}},
]


@pytest.mark.parametrize("query", QUERIES)
def test_spmd_matches_host_merge(corpus, mesh, query):
    mapper, segments = corpus
    payloads, plan, _ = _payloads(mapper, segments, query)
    searcher = DistributedSearcher(mesh)
    k = 12
    scores, _, shard_idx, ords, total, _ = searcher.search(payloads, plan,
                                                           k=k)

    ref_scores, ref_total = _host_reference(mapper, segments, query, k)
    assert total == ref_total
    np.testing.assert_allclose(scores[:len(ref_scores)], ref_scores,
                               rtol=1e-5, atol=1e-6)
    # merged keys strictly descending-or-equal
    assert np.all(np.diff(scores) <= 1e-6)


def test_hbm_resident_segments_not_reuploaded_per_query(corpus, mesh):
    """Regression (round 1): segments upload to HBM once;
    subsequent queries move only flat plan inputs. Asserts via the
    module's transfer accounting that the second query's host→device
    traffic is a small fraction of the segment bytes."""
    from opensearch_tpu.parallel.distributed import TRANSFER_BYTES

    mapper, segments = corpus
    payloads, plan, _ = _payloads(mapper, segments, QUERIES[0])
    searcher = DistributedSearcher(mesh)

    TRANSFER_BYTES[0] = 0
    shard_set = searcher.build_shard_set([p[0] for p in payloads],
                                         [p[2] for p in payloads])
    segment_bytes = TRANSFER_BYTES[0]
    assert segment_bytes > 0

    flat = [p[1] for p in payloads]
    TRANSFER_BYTES[0] = 0
    r1 = searcher.search_resident(shard_set, flat, plan, k=12)
    first_query_bytes = TRANSFER_BYTES[0]

    # second query (different terms → fresh flat inputs, same shapes)
    payloads2, plan2, _ = _payloads(mapper, segments, QUERIES[2])
    TRANSFER_BYTES[0] = 0
    r2 = searcher.search_resident(shard_set, [p[1] for p in payloads2],
                                  plan2, k=12)
    second_query_bytes = TRANSFER_BYTES[0]

    assert first_query_bytes < segment_bytes * 0.05, \
        f"query moved {first_query_bytes}B vs {segment_bytes}B segments"
    assert second_query_bytes < segment_bytes * 0.05, \
        f"2nd query re-uploaded segments: {second_query_bytes}B"
    # parity with the one-shot path
    ref = searcher.search(payloads, plan, k=12)
    np.testing.assert_allclose(r1[0], ref[0], rtol=1e-6)
    assert r1[4] == ref[4]


def test_spmd_agg_partials_reduce(corpus, mesh):
    """Sharded terms-agg partials must reduce to the single-reader answer."""
    mapper, segments = corpus
    query = {"match_all": {}}
    aggs = {"by_tag": {"terms": {"field": "tag", "size": 20}}}
    payloads, plan, per_shard_aggs = _payloads(mapper, segments, query, aggs)
    searcher = DistributedSearcher(mesh)
    _, _, _, _, total, agg_outs = searcher.search(
        payloads, plan, k=4, agg_plans=tuple(per_shard_aggs[0]))

    # host-side final reduce over the sharded partials (each agg output dict
    # carries a leading shard dimension out of the SPMD program); each shard's
    # slice decodes with that shard's own plans — ordinal→term mappings are
    # segment-local, exactly like the reference's global-ordinals-per-segment
    from opensearch_tpu.search.aggs.reduce import decode_outputs, reduce_aggs
    per_shard = []
    for s in range(N_SHARDS):
        shard_outs = [{k: np.asarray(v[s]) for k, v in out.items()}
                      for out in agg_outs]
        per_shard.append(decode_outputs(per_shard_aggs[s], shard_outs))
    reduced = reduce_aggs(per_shard)

    reader = ShardReader(mapper, list(segments))
    ref = SearchExecutor(reader).search(
        {"query": query, "aggs": aggs, "size": 0})
    ref_buckets = {b["key"]: b["doc_count"]
                   for b in ref["aggregations"]["by_tag"]["buckets"]}
    got_buckets = {b["key"]: b["doc_count"]
                   for b in reduced["by_tag"]["buckets"]}
    assert got_buckets == ref_buckets
    assert total == sum(s.live_doc_count for s in segments)


def test_spmd_nested_sub_agg(corpus, mesh):
    """Nested sub-aggregations: one output slot per node in traversal order
    (regression: out_specs was sized by top-level plan count)."""
    mapper, segments = corpus
    aggs = {"by_tag": {"terms": {"field": "tag", "size": 20},
                       "aggs": {"v": {"avg": {"field": "views"}}}}}
    payloads, plan, per_shard_aggs = _payloads(
        mapper, segments, {"match_all": {}}, aggs)
    searcher = DistributedSearcher(mesh)
    _, _, _, _, _, agg_outs = searcher.search(
        payloads, plan, k=4, agg_plans=tuple(per_shard_aggs[0]))

    from opensearch_tpu.search.aggs.reduce import decode_outputs, reduce_aggs
    per_shard = []
    for s in range(N_SHARDS):
        shard_outs = [{k: np.asarray(v[s]) for k, v in out.items()}
                      for out in agg_outs]
        per_shard.append(decode_outputs(per_shard_aggs[s], shard_outs))
    reduced = reduce_aggs(per_shard)

    reader = ShardReader(mapper, list(segments))
    ref = SearchExecutor(reader).search(
        {"query": {"match_all": {}}, "aggs": aggs, "size": 0})
    got = {b["key"]: (b["doc_count"], round(b["v"]["value"], 4))
           for b in reduced["by_tag"]["buckets"]}
    want = {b["key"]: (b["doc_count"], round(b["v"]["value"], 4))
            for b in ref["aggregations"]["by_tag"]["buckets"]}
    assert got == want


def test_graft_dryrun_multichip(eight_devices):
    import importlib
    import sys
    sys.path.insert(0, "/root/repo")
    mod = importlib.import_module("__graft_entry__")
    mod.dryrun_multichip(8)


def test_dryrun_parity_bodies_4of4(eight_devices):
    """ISSUE 14 satellite: pin the multichip dryrun's FOUR hit-bearing
    parity cases at 4/4 (seeded, small scale, 16 rows packed 2/device).

    Diagnosis of an early multichip record's `hit_parity=3/4`: a
    DENOMINATOR artifact, not rank divergence — the pre-PR-8 harness
    printed a hardcoded "/4" while its size:0 date_histogram body has
    no hits page to compare (its strict per-body asserts all passed,
    rc=0 — real divergence would have crashed the run). This test pins
    the repaired contract: every hit-bearing body, INCLUDING the
    all-scores-equal constant-score case where the page order is
    nothing but the cross-shard tie-break, matches the host loop
    exactly."""
    import json

    import opensearch_tpu.search.spmd as spmd_mod
    from opensearch_tpu.node import Node
    from opensearch_tpu.search import spmd
    from opensearch_tpu.utils.demo import build_shards

    mapper, segments = build_shards(4000, n_shards=16, vocab_size=2000,
                                    avg_len=40, seed=3)
    node = Node()
    node.request("PUT", "/p44", {
        "settings": {"number_of_shards": 16},
        "mappings": {"properties": {
            "body": {"type": "text"}, "tag": {"type": "keyword"},
            "views": {"type": "integer"}, "ts": {"type": "date"}}}})
    svc = node.indices.get("p44")
    for shard, seg in zip(svc.shards, segments):
        shard.engine.install_segments([seg], max_seq_no=seg.num_docs,
                                      local_checkpoint=seg.num_docs)
        shard._sync_reader()

    bodies = [
        {"query": {"bool": {
            "must": [{"match": {"body": "w00120 w00077"}}],
            "should": [{"term": {"tag": "cat1"}}]}}, "size": 8},
        {"query": {"match": {"body": "w00400 w01999"}}, "size": 12},
        {"query": {"match_all": {}}, "size": 10,
         "sort": [{"views": {"order": "desc"}}]},
        # constant-score: every hit ties, the page order IS the
        # cross-shard tie-break (gather order vs host sort)
        {"query": {"bool": {"filter": [
            {"range": {"views": {"gte": 500}}}]}}, "size": 10},
    ]
    hit_parity = 0
    for body in bodies:
        before = spmd.SPMD_QUERIES.value
        got = node.request("POST", "/p44/_search", body)
        assert spmd.SPMD_QUERIES.value == before + 1, \
            f"SPMD path not taken for {json.dumps(body)[:80]}"
        with spmd_mod.force_host_loop():
            want = node.request("POST", "/p44/_search", body)
        assert got["hits"]["total"] == want["hits"]["total"], body
        assert want["hits"]["hits"], \
            f"parity body must bear hits: {json.dumps(body)[:80]}"
        gh = [(h["_id"], h.get("sort", round(h["_score"] or 0, 4)))
              for h in got["hits"]["hits"]]
        wh = [(h["_id"], h.get("sort", round(h["_score"] or 0, 4)))
              for h in want["hits"]["hits"]]
        assert gh == wh, (body, gh[:3], wh[:3])
        hit_parity += 1
    assert hit_parity == 4


def test_graft_entry_compiles():
    import importlib
    import sys
    import jax
    sys.path.insert(0, "/root/repo")
    mod = importlib.import_module("__graft_entry__")
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    keys = np.asarray(out[0])
    assert keys.shape == (10,)


class TestSpmdServingPath:
    """Round 3: the SPMD program must BE the serving
    path — a REST _search against a multi-shard index executes the
    shard_map program, with HBM residency across queries."""

    @pytest.fixture(scope="class")
    def node(self):
        import json

        from opensearch_tpu.node import Node
        from opensearch_tpu.utils.demo import synth_docs

        node = Node()
        node.request("PUT", "/sp", {
            "settings": {"number_of_shards": 4},
            "mappings": {"properties": {
                "body": {"type": "text"}, "tag": {"type": "keyword"},
                "views": {"type": "integer"}, "ts": {"type": "date"}}}})
        docs = synth_docs(400, vocab_size=300, avg_len=30, seed=5)
        lines = []
        for i, d in enumerate(docs):
            lines.append(json.dumps({"index": {"_id": f"d{i}"}}))
            lines.append(json.dumps(d))
        node.handle("POST", "/sp/_bulk", body="\n".join(lines) + "\n")
        node.request("POST", "/sp/_refresh")
        return node

    def test_rest_search_executes_spmd_program(self, node):
        from opensearch_tpu.search import spmd

        before = spmd.SPMD_QUERIES.value
        out = node.request("POST", "/sp/_search", {
            "query": {"match": {"body": "w00011 w00042"}}, "size": 10})
        assert spmd.SPMD_QUERIES.value == before + 1
        assert out["hits"]["total"]["value"] > 0

    def test_residency_across_queries(self, node):
        from opensearch_tpu.parallel.distributed import TRANSFER_BYTES
        from opensearch_tpu.search import spmd

        body = {"query": {"match": {"body": "w00007"}}, "size": 5}
        node.request("POST", "/sp/_search", body)   # builds the shard set
        uploads = spmd.SPMD_UPLOADS.value
        tb0 = TRANSFER_BYTES[0]
        for _ in range(3):
            node.request("POST", "/sp/_search", body)
        assert spmd.SPMD_UPLOADS.value == uploads, "shard set rebuilt per query"
        per_query = (TRANSFER_BYTES[0] - tb0) / 3
        assert per_query < 1 << 16, \
            f"per-query transfer {per_query} B suggests segment re-upload"

    def test_spmd_aggs_match_host_loop(self, node):
        from opensearch_tpu.search import spmd

        body = {"size": 0, "query": {"match_all": {}},
                "aggs": {"tags": {"terms": {"field": "tag", "size": 20}},
                         "v": {"avg": {"field": "views"}}}}
        before = spmd.SPMD_QUERIES.value
        got = node.request("POST", "/sp/_search", body)
        assert spmd.SPMD_QUERIES.value == before + 1
        # host loop ground truth: force fallback by monkeypatching
        import opensearch_tpu.search.spmd as spmd_mod
        orig = spmd_mod.eligible
        try:
            spmd_mod.eligible = lambda *a, **k: False
            want = node.request("POST", "/sp/_search", body)
        finally:
            spmd_mod.eligible = orig
        assert got["aggregations"] == want["aggregations"]
        assert got["hits"]["total"] == want["hits"]["total"]

    def test_spmd_hits_match_host_loop(self, node):
        import opensearch_tpu.search.spmd as spmd_mod

        body = {"query": {"bool": {
            "must": [{"match": {"body": "w00005 w00013"}}],
            "filter": [{"range": {"views": {"gte": 1000}}}]}},
            "size": 20}
        got = node.request("POST", "/sp/_search", body)
        orig = spmd_mod.eligible
        try:
            spmd_mod.eligible = lambda *a, **k: False
            want = node.request("POST", "/sp/_search", body)
        finally:
            spmd_mod.eligible = orig
        assert got["hits"]["total"] == want["hits"]["total"]
        assert [(h["_id"], round(h["_score"], 4))
                for h in got["hits"]["hits"]] == \
               [(h["_id"], round(h["_score"], 4))
                for h in want["hits"]["hits"]]


class TestSpmdPackingAndFieldSort:
    """Round-5 demands: >devices rows pack onto the mesh (no host-loop
    cliff at n_devices), and numeric field sorts ride the collective
    merge."""

    @pytest.fixture(scope="class")
    def node16(self):
        import json

        from opensearch_tpu.node import Node
        from opensearch_tpu.utils.demo import synth_docs

        node = Node()
        node.request("PUT", "/pk", {
            "settings": {"number_of_shards": 16},
            "mappings": {"properties": {
                "body": {"type": "text"}, "tag": {"type": "keyword"},
                "views": {"type": "integer"}, "ts": {"type": "date"}}}})
        docs = synth_docs(480, vocab_size=300, avg_len=30, seed=9)
        lines = []
        for i, d in enumerate(docs):
            lines.append(json.dumps({"index": {"_id": f"p{i}"}}))
            lines.append(json.dumps(d))
        node.handle("POST", "/pk/_bulk", body="\n".join(lines) + "\n")
        node.request("POST", "/pk/_refresh")
        return node

    def _host_loop(self, node, body):
        from opensearch_tpu.search.spmd import force_host_loop
        with force_host_loop():
            return node.request("POST", "/pk/_search", body)

    def test_sixteen_rows_pack_onto_eight_devices(self, node16):
        import jax

        from opensearch_tpu.search import spmd

        assert len(jax.devices()) == 8
        body = {"query": {"match": {"body": "w00004 w00019"}}, "size": 15}
        before = spmd.SPMD_QUERIES.value
        got = node16.request("POST", "/pk/_search", body)
        assert spmd.SPMD_QUERIES.value == before + 1, \
            "16 rows on an 8-device mesh fell back to the host loop"
        want = self._host_loop(node16, body)
        assert got["hits"]["total"] == want["hits"]["total"]
        assert [(h["_id"], round(h["_score"], 4))
                for h in got["hits"]["hits"]] == \
               [(h["_id"], round(h["_score"], 4))
                for h in want["hits"]["hits"]]

    def test_packed_rows_aggs_match_host_loop(self, node16):
        from opensearch_tpu.search import spmd

        body = {"size": 0, "query": {"match_all": {}},
                "aggs": {"tags": {"terms": {"field": "tag", "size": 20}},
                         "v": {"avg": {"field": "views"}}}}
        before = spmd.SPMD_QUERIES.value
        got = node16.request("POST", "/pk/_search", body)
        assert spmd.SPMD_QUERIES.value == before + 1
        want = self._host_loop(node16, body)
        assert got["aggregations"] == want["aggregations"]
        assert got["hits"]["total"] == want["hits"]["total"]

    def test_numeric_field_sort_through_spmd(self, node16):
        from opensearch_tpu.search import spmd

        for order in ("desc", "asc"):
            body = {"query": {"match_all": {}}, "size": 20,
                    "sort": [{"views": {"order": order}}]}
            before = spmd.SPMD_QUERIES.value
            got = node16.request("POST", "/pk/_search", body)
            assert spmd.SPMD_QUERIES.value == before + 1, \
                f"field sort ({order}) fell back to the host loop"
            want = self._host_loop(node16, body)
            assert got["hits"]["total"] == want["hits"]["total"]
            assert [h["sort"] for h in got["hits"]["hits"]] == \
                   [h["sort"] for h in want["hits"]["hits"]], order

    def test_keyword_sort_still_host_loop(self, node16):
        from opensearch_tpu.search import spmd

        body = {"query": {"match_all": {}}, "size": 5,
                "sort": [{"tag": {"order": "asc"}}]}
        before = spmd.SPMD_QUERIES.value
        out = node16.request("POST", "/pk/_search", body)
        assert spmd.SPMD_QUERIES.value == before, \
            "keyword sorts must take the host sort-key path"
        assert out["hits"]["hits"]


@pytest.mark.slow
def test_spmd_parity_100k_docs(eight_devices):
    """>=100K-doc cross-shard parity: SPMD merged page + totals + terms agg
    must match the host-loop execution at realistic scale."""
    import json

    import opensearch_tpu.search.spmd as spmd_mod
    from opensearch_tpu.node import Node
    from opensearch_tpu.search import spmd
    from opensearch_tpu.utils.demo import build_shards

    mapper, segments = build_shards(100_000, n_shards=8, vocab_size=5000,
                                    avg_len=40, seed=21)
    node = Node()
    node.request("PUT", "/big", {
        "settings": {"number_of_shards": 8},
        "mappings": {"properties": {
            "body": {"type": "text"}, "tag": {"type": "keyword"},
            "views": {"type": "integer"}, "ts": {"type": "date"}}}})
    # install the pre-built segments directly into the index's shards
    # (bulk-indexing 100K docs through REST would dominate the test's
    # runtime without adding coverage)
    svc = node.indices.get("big")
    for shard, seg in zip(svc.shards, segments):
        shard.engine.install_segments([seg], max_seq_no=seg.num_docs,
                                      local_checkpoint=seg.num_docs)
        shard._sync_reader()

    queries = ["w00120 w00077", "w00400 w01999", "w00033"]
    for q in queries:
        body = {"query": {"match": {"body": q}}, "size": 25,
                "aggs": {"tags": {"terms": {"field": "tag"}}}}
        before = spmd.SPMD_QUERIES.value
        got = node.request("POST", "/big/_search", body)
        assert spmd.SPMD_QUERIES.value == before + 1, "SPMD path not taken"
        orig = spmd_mod.eligible
        try:
            spmd_mod.eligible = lambda *a, **k: False
            want = node.request("POST", "/big/_search", body)
        finally:
            spmd_mod.eligible = orig
        assert got["hits"]["total"] == want["hits"]["total"], q
        assert [(h["_id"], round(h["_score"], 4))
                for h in got["hits"]["hits"]] == \
               [(h["_id"], round(h["_score"], 4))
                for h in want["hits"]["hits"]], q
        assert got["aggregations"] == want["aggregations"], q
