"""The `vectorsearch-cohere10m-shard` configuration and its cell off the
chip (ISSUE 33): the page the node serves through `launcher.start_node`
equals the builder's plain reference at dry-run size on three seeds,
every clause by the exact scan; the vectors are the seed's whatever the
thread count; a query vector never repeats; the sizes the roofline
reads are the cell's own, by arithmetic alone; the reference is plain
numpy in blocks; and the control of `correct`: the reference's own
scores put in the program's place in bfloat16 have to come out NOT
correct, as do a swapped pair of ids and a wrong `hits.total`. No
timing is asserted.
"""

import inspect
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import oracle                # noqa: E402
from benchmark import roofline              # noqa: E402
from benchmark import run as bench_run      # noqa: E402

FILES = bench_run.Files(REPO)
CELL = "vectorsearch-knn-closed-8"
CONFIG = FILES.config("vectorsearch-cohere10m-shard")
BUILDER = FILES.builder(CONFIG)
TRAFFIC = FILES.traffic("knn-closed-8")
SEEDS = [5, 2147483659, 3000000019]


@pytest.fixture(scope="module")
def corpora():
    return {seed: BUILDER.build(CONFIG, seed, True) for seed in SEEDS}


def queries(corpus, seed, n):
    return corpus.draw(TRAFFIC.get("query", {}), TRAFFIC["classes"] * n,
                       seed)


# ---------------------------------------------------- served = reference

@pytest.mark.parametrize("seed", SEEDS)
def test_the_served_page_equals_the_reference(seed):
    assert os.environ["JAX_PLATFORMS"] == "cpu"     # conftest pinned it
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "2",
         "--trace", "1", "--dry-run"], cwd=REPO, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    compared = line["compared"]
    assert compared["pages_differing"]["value"] == 0
    assert compared["pages_judged"]["value"] == 20
    gap = compared["score_rel_err_max"]
    assert 0 <= gap["value"] <= gap["limit"] == oracle.KNN_RTOL == 1e-4
    m = line["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["resident_corpus_gb"]["value"] * 1e9 >= 2048 * 768 * 4
    # every request of the window planned one exact scan (the window's
    # last requests answer after it: planned, not counted)
    assert 0 <= m["knn_exact_clauses_in_window"]["value"] \
        - line["attempted"] <= TRAFFIC["clients"]
    for name in ("http_self_ms.knn", "rest_self_ms.knn",
                 "envelope_host_ms.knn", "enqueue_ms.knn",
                 "device_wait_ms.knn", "respond_ms.knn"):
        assert m[name]["value"] > 0, name
    # no device plane on the CPU: no device metric, under any name
    assert not {"device_ms_per_query.knn", "knn_distance_ms.knn",
                "knn_topk_ms.knn", "knn_other_ms", "knn_roofline"} & set(m)


# ---------------------------------------------------------------- corpus

def test_the_vectors_are_the_seeds_whatever_fills_them(corpora):
    """A block of rows comes from a generator of its own, so the corpus
    does not depend on how many threads filled it, and two seeds give
    other vectors."""
    corpus = corpora[SEEDS[0]]
    n, dims = corpus.x.shape
    assert (n, dims) == (CONFIG["dry_run"]["vectors"], 768)
    assert corpus.x.dtype == np.float32 and corpus.x.flags.c_contiguous
    again = np.empty_like(corpus.x)
    for b in range(-(-n // BUILDER.GEN_BLOCK)):
        BUILDER.mixture_rows(
            SEEDS[0], b, corpus.centers,
            again[b * BUILDER.GEN_BLOCK:(b + 1) * BUILDER.GEN_BLOCK])
    assert np.array_equal(again, corpus.x)
    assert not np.array_equal(corpora[SEEDS[1]].x[:8], corpus.x[:8])
    # a mixture: a vector is nearer its own centre than the others are
    d2 = ((corpus.x[:64, None, :] - corpus.centers[None]) ** 2).sum(axis=2)
    assert np.median(d2.min(axis=1)) < 0.6 * np.median(d2)


def test_one_sealed_segment_of_one_vector_column(corpora):
    from opensearch_tpu.index.segment import PrefixedIds, pad_bucket
    corpus = corpora[SEEDS[0]]
    (seg,) = corpus.segments
    n = len(corpus.x)
    assert seg.num_docs == n and isinstance(seg.doc_ids, PrefixedIds)
    assert seg.doc_ids[0] == "v0" and seg.ord_of(f"v{n - 1}") == n - 1
    assert set(seg.vector_dv) == {"target_field"}
    col = seg.vector_dv["target_field"]
    assert col.vectors is corpus.x and col.exists.all() and col.ivf is None
    assert seg.term_dict == {} and seg.numeric_dv == {}
    assert corpus.sizes == {"d_pad": pad_bucket(n), "dimension": 768,
                            "num_docs": n}
    field = corpus.mapping["properties"]["target_field"]
    assert field == {"type": "knn_vector", "dimension": 768,
                     "method": {"space_type": "innerproduct"}}
    assert corpus.index == "vectors"
    assert corpus.index_settings["number_of_shards"] == 1


def test_full_size_by_arithmetic_alone():
    """The cell's own size without its 6 GB: what the configuration's
    file states, and what the roofline makes of it."""
    from opensearch_tpu.index.segment import pad_bucket
    assert CONFIG["architecture"] is None and CONFIG["node_settings"] == {}
    assert (CONFIG["dimension"], CONFIG["space_type"], CONFIG["dtype"],
            CONFIG["k"]) == (768, "innerproduct", "float32", 100)
    assert CONFIG["vectors"] * CONFIG["shards"] == CONFIG["source_vectors"]
    d_pad = pad_bucket(CONFIG["vectors"])
    assert d_pad == 2_097_152
    sizes = {"d_pad": d_pad, "dimension": 768}
    nbytes, flops = roofline.knn_exact(sizes, {})
    assert nbytes == 6_442_450_944 and flops == 2 * d_pad * 768
    t, bound = roofline.least_seconds(nbytes, flops,
                                      roofline.peaks("TPU v5 lite"))
    assert bound == "memory" and 7.8e-3 < t < 7.9e-3
    assert len(CONFIG["source"]) <= 200
    for word in ("vectorsearch", "cohere-10m", "768", "innerproduct",
                 "query_k 100"):
        assert word in CONFIG["source"]
    assert TRAFFIC["clients"] == 8 and TRAFFIC["loop"] == "closed"
    assert TRAFFIC["batch"] == 1 and TRAFFIC["judge"]["sample"] == 20


# --------------------------------------------------------------- traffic

def test_a_query_vector_never_repeats_and_is_the_seeds(corpora):
    corpus = corpora[SEEDS[0]]
    qs = queries(corpus, SEEDS[0], 3000)
    assert len({q.vector.tobytes() for q in qs}) == len(qs)
    assert {q.klass for q in qs} == {("knn", 1)}
    same = queries(corpus, SEEDS[0], 3000)
    assert all(np.array_equal(a.vector, b.vector)
               for a, b in zip(qs, same))
    other = queries(corpus, SEEDS[1], 40)
    assert not np.array_equal(other[0].vector, qs[0].vector)
    body = json.loads(corpus.payload(qs[0]))
    assert body["size"] == 100 and body["_source"] is False
    clause = body["query"]["knn"]["target_field"]
    assert clause["k"] == 100 and len(clause["vector"]) == 768
    # the request carries the float32 values exactly
    assert np.array_equal(np.asarray(clause["vector"], dtype=np.float32),
                          qs[0].vector)


# ------------------------------------------------------------- reference

def test_the_reference_is_plain_numpy_in_blocks():
    for fn in (BUILDER.plugin_score, BUILDER.reference_scores,
               BUILDER.exact_inner_products):
        assert "opensearch_tpu" not in inspect.getsource(fn)
    assert BUILDER.REF_BLOCK == 131072
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1000, 24)).astype(np.float32)
    q = rng.standard_normal((3, 24))
    got = BUILDER.reference_scores(x, q, block=77)
    assert got.dtype == np.float64 and got.shape == (1000, 3)
    for i in (0, 76, 77, 999):
        for j in range(3):
            want = sum(float(a) * float(b) for a, b in zip(x[i], q[j]))
            assert got[i, j] == pytest.approx(want, rel=1e-12, abs=1e-12)
    ords = np.array([5, 900])
    assert BUILDER.exact_inner_products(x, ords, q[1]) \
        == pytest.approx(got[ords, 1], rel=1e-12, abs=1e-12)
    # both branches of the k-NN plugin's innerproduct score
    assert BUILDER.plugin_score(np.array([0.0, 2.5, -1.0, -3.0])).tolist() \
        == [1.0, 3.5, 0.5, 0.25]


def test_the_reference_agrees_with_the_tests_own(corpora):
    """Two plain references written apart: the benchmark's and
    tests/reference_impl.py `ref_knn_score`."""
    from tests.reference_impl import ref_knn_score
    corpus = corpora[SEEDS[0]]
    (q,) = queries(corpus, 9, 1)
    page = corpus.reference_responses([q])[0]["hits"]["hits"]
    assert len(page) == 100
    for h in page[:5] + page[-5:]:
        want = ref_knn_score(corpus.x[int(h["_id"][1:])], q.vector,
                             "innerproduct")
        assert h["_score"] == pytest.approx(want, rel=1e-12)
    best = max(range(len(corpus.x)), key=lambda i: float(
        np.dot(corpus.x[i].astype(np.float64), q.vector)))
    assert page[0]["_id"] == f"v{best}"


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_passes_and_its_bfloat16_control_fails(corpora, seed):
    corpus = corpora[seed]
    qs = queries(corpus, seed, 6)
    # the reference in the program's place, as float32 serves it: correct
    seen = {}
    sound = list(zip(qs, corpus.reference_responses(
        qs, lambda s: s.astype(np.float32).astype(np.float64))))
    assert corpus.judge(sound, seen) == []
    assert seen["hits_compared"] == len(qs) * corpus.k
    lower = seen["score_rel_err_max"]
    assert lower <= 2.0 ** -24 < seen["score_rel_err_limit"] \
        == oracle.KNN_RTOL
    # the control: the same scores in bfloat16. Every page has to
    # differ, and the widest gap reads far over the limit
    seen = {}
    control = list(zip(qs, corpus.reference_responses(
        qs, oracle.lower_precision)))
    bad = corpus.judge(control, seen)
    assert len(bad) == len(qs)
    upper = seen["score_rel_err_max"]
    assert upper > 5 * oracle.KNN_RTOL and upper > 3 * max(lower, 1e-12)


def test_swapped_ids_a_wrong_total_and_a_failed_shard_are_caught(corpora):
    corpus = corpora[SEEDS[0]]
    (q,) = queries(corpus, 9, 1)

    def judged(change):
        (resp,) = corpus.reference_responses([q])
        change(resp)
        return corpus.judge([(q, resp)])

    def swap(resp, i, j):
        hits = resp["hits"]["hits"]
        hits[i]["_id"], hits[j]["_id"] = hits[j]["_id"], hits[i]["_id"]
    assert judged(lambda r: None) == []
    assert judged(lambda r: swap(r, 0, 1))
    assert judged(lambda r: swap(r, 40, 99))
    assert judged(lambda r: r["hits"]["total"].__setitem__("value", 99))
    assert judged(lambda r: r["hits"]["total"].__setitem__(
        "value", len(corpus.x)))
    assert judged(lambda r: r["hits"]["hits"].pop())
    assert judged(lambda r: r["hits"]["hits"][3].__setitem__(
        "_id", r["hits"]["hits"][2]["_id"]))
    assert judged(lambda r: r["hits"]["hits"][0].__setitem__(
        "_score", r["hits"]["hits"][0]["_score"] * 1.001))
    assert judged(lambda r: r["_shards"].__setitem__("failed", 1))
    assert judged(lambda r: r.__setitem__("timed_out", True))
    assert judged(lambda r: r.pop("hits"))
