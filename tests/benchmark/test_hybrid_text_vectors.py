"""The `msmarco-hybrid-shard` configuration and its cell off the chip:
the builder's corpus served by a real node over REST, a `hybrid` query
with the normalization pipeline inline, gives the pages of the
builder's plain reference, which agrees with the independent oracle
`tests/reference_impl.ref_hybrid_scores`; the control of `correct`
(the reference's own sub-scores in bfloat16) and a broken page come
out NOT correct; a whole dry run of the cell ends `correct` with no
compile in its window; over a window of the served node, the hybrid
counter equals the requests it answered and each request's ring row
holds every span of the route; the classes do not depend on the seed
at full size; the dry-run corpus keeps the shard's vector width. No
timing is asserted.
"""

import collections
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import oracle, spans         # noqa: E402
from benchmark import run as bench_run      # noqa: E402
from reference_impl import ref_hybrid_scores    # noqa: E402

FILES = bench_run.Files(REPO)
CELL = "msmarco-hybrid-closed-4"
CONFIG = FILES.config("msmarco-hybrid-shard")
BUILDER = FILES.builder(CONFIG)
TRAFFIC = FILES.traffic("hybrid-closed-4")
CONFIG_FILE = "benchmark/configs/msmarco-hybrid-shard.json"
TRAFFIC_FILE = "benchmark/traffic/hybrid-closed-4.json"
SEEDS = [5, 2147483659, 3000000019]
# the spans the B=1 hybrid route records under `rest.search`
ROUTE_SPANS = ("hybrid.compile", "dispatch", "device_wait", "hybrid.merge",
               "respond")


@pytest.fixture(scope="module")
def served():
    """The cell's dry-run corpus installed in a node started as the
    benchmark starts it; 8 queries of the traffic's classes, and the
    run that serves them."""
    import jax
    run = bench_run.Run(FILES, bench_run.parse_args(
        ["--workload", CELL, "--seed", "2147483659", "--dry-run"]))
    run.jax = jax
    run.start_node()
    run.corpus = BUILDER.build(CONFIG, 2147483659, True)
    run.install()
    warm, window = run.draw()
    queries = [reqs[0] for reqs in (warm + window)[:8]]
    pages = [run.call("POST", f"/{run.corpus.index}/_search",
                      run.corpus.body(q)) for q in queries]
    yield run.corpus, queries, pages, run
    run.stop()


def test_the_served_pages_equal_the_reference(served):
    corpus, queries, pages, _ = served
    seen = {}
    assert corpus.judge(list(zip(queries, pages)), seen) == []
    assert seen["hits_compared"] == sum(len(p["hits"]["hits"])
                                        for p in pages) > 0
    assert 0 <= seen["score_rel_err_max"] < seen["score_rel_err_limit"]
    for page in pages:
        assert len(page["hits"]["hits"]) == corpus.size
        assert page["_shards"]["failed"] == 0


def test_the_reference_agrees_with_the_independent_oracle(served):
    """`ref_hybrid_scores` normalizes and combines the reference's own
    windows the straightforward way: the same combined score for every
    document of either window, and the served page is its top `size`."""
    corpus, queries, pages, _ = served
    windows = corpus._windows(queries)
    for page, (bm25, knn, _m, _w) in zip(pages, windows):
        pools = [{int(o): float(s) for o, s in zip(win.ords, win.scores)}
                 for win in (bm25, knn)]
        want = ref_hybrid_scores([pools], "min_max", "arithmetic_mean",
                                 corpus.weights)
        ranked = sorted(want.items(), key=lambda e: (-e[1], e[0]))
        served_ids = [int(h["_id"][1:]) for h in page["hits"]["hits"]]
        for h, o in zip(page["hits"]["hits"], served_ids):
            lo, hi, values, _s = corpus._combined((bm25, knn), o)
            assert values[0] == pytest.approx(want[o], rel=1e-12)
            assert lo <= want[o] <= hi
            assert lo - 1e-12 <= h["_score"] <= hi + 1e-12
        # the page is the oracle's top `size` wherever the oracle's
        # scores at the page's edge are apart by more than the tolerance
        edge = ranked[corpus.size - 1][1] - ranked[corpus.size][1] \
            if len(ranked) > corpus.size else 1.0
        if edge > 1e-4:
            assert set(served_ids) == {o for o, _ in ranked[:corpus.size]}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_passes_and_its_bfloat16_control_fails(seed):
    corpus = BUILDER.build(CONFIG, seed, True)
    queries = corpus.draw(TRAFFIC["query"], TRAFFIC["dry_run"]["classes"]
                          * 2, seed)
    pairs = lambda resps: list(zip(queries, resps))   # noqa: E731
    seen = {}
    assert corpus.judge(pairs(corpus.reference_responses(queries)),
                        seen) == []
    assert seen["score_rel_err_max"] < 1e-12
    # the reference served in float32, the configuration's precision
    f32 = corpus.reference_responses(
        queries, lambda s: s.astype(np.float32).astype(np.float64))
    assert corpus.judge(pairs(f32)) == []
    # one precision below: bfloat16 sub-scores, every page refused
    seen = {}
    bad = corpus.judge(pairs(corpus.reference_responses(
        queries, oracle.lower_precision)), seen)
    assert len(bad) == len(queries)
    assert seen["score_rel_err_max"] > 10 * seen["score_rel_err_limit"]


def test_a_broken_page_is_refused(served):
    corpus, queries, pages, _ = served
    q, page = queries[0], pages[0]

    def judged(mutate):
        p = json.loads(json.dumps(page))
        mutate(p)
        return corpus.judge([(q, p)])

    hits = lambda p: p["hits"]["hits"]      # noqa: E731

    def swap(p):
        h = hits(p)
        i = next(i for i in range(len(h) - 1)
                 if h[i]["_score"] - h[i + 1]["_score"] > 1e-3)
        h[i]["_id"], h[i + 1]["_id"] = h[i + 1]["_id"], h[i]["_id"]

    def raise_score(p):
        hits(p)[0]["_score"] += 1e-3

    def total(p):
        p["hits"]["total"]["value"] += 1

    def drop(p):
        hits(p).pop()

    def failed(p):
        p["_shards"]["failed"] = 1

    assert judged(lambda p: None) == []
    for mutate in (swap, raise_score, total, drop, failed):
        assert len(judged(mutate)) == 1, mutate.__name__


def test_a_whole_dry_run_ends_correct():
    assert os.environ["JAX_PLATFORMS"] == "cpu"     # conftest pinned it
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147485405", "--seconds", "3",
         "--trace", "1", "--dry-run"], cwd=REPO, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["compiles_in_window"] == 0
    assert line["compared"]["pages_judged"]["value"] \
        == TRAFFIC["dry_run"]["judge"]["sample"]


def test_the_hybrid_counter_and_spans_cover_the_served_requests(served):
    """Over a window of requests the served node answers one at a time:
    `search.hybrid.queries` rises by one a request (read as the accepted
    `counter_in_window` reads it), and each request's ring row holds
    every span of the route, each of a positive duration."""
    corpus, queries, _, run = served
    run.stats["before"] = run.node_stats()
    t0 = time.monotonic()
    for q in queries:
        run.call("POST", f"/{corpus.index}/_search", corpus.body(q))
    run.window = (t0, time.monotonic())
    run.drained = run.window[1]
    run.stats["after"] = run.node_stats()
    counter = bench_run.load_module("reader", os.path.join(
        REPO, "benchmark", "metrics", "readers", "counter_in_window.py"))
    assert counter.read(run, {"counter": "search.hybrid.queries"}) \
        == len(queries)
    assert counter.read(run, {"counter": "search.hybrid.candidates"}) \
        >= len(queries) * corpus.size
    run.__dict__.pop("_spans", None)        # the ring, fetched anew
    ring = spans.fetch(run)
    requests = ring.requests(*spans.window_ns(run))
    assert len(requests) == len(queries)
    for req in requests:
        for name in ROUTE_SPANS:
            (span,) = ring.named(req["trace_id"], name)
            assert span["end_ns"] > span["start_ns"], name


def test_full_size_classes_do_not_depend_on_the_seed():
    """By the block table alone, at the shard's full size: two seeds
    send the same classes; every query's terms fill its QB bucket."""
    table = BUILDER.zipf_text.zipf_table(CONFIG, CONFIG["documents"])
    cycle = bench_run.class_cycle(TRAFFIC["classes"])
    classes = [cycle[i % len(cycle)] for i in range(len(cycle) * 6)]
    drawn = [BUILDER.zipf_text.draw_queries(table, TRAFFIC["query"],
                                            classes, seed)
             for seed in (1, 2147483659)]
    want = collections.Counter((c["terms"], c["qb"]) for c in classes)
    for qs in drawn:
        assert collections.Counter(q.klass for q in qs) == want
        for q in qs:
            assert BUILDER.zipf_text.qb_bucket(
                q.work["lanes"] // 128) == q.klass[1]
    # natural-closed-1's six classes a fifth the blocks: two buckets,
    # two requests in three in the larger
    assert sorted(want) == [(t, qb) for t in (4, 6, 8)
                            for qb in (16384, 32768)]
    larger = sum(n for (_t, qb), n in want.items() if qb == 32768)
    assert 3 * larger == 2 * sum(want.values())
    assert [q.text for q in drawn[0]] != [q.text for q in drawn[1]]


def test_the_dry_run_corpus_keeps_the_shards_widths():
    """Cut in passages only: the shard pads to 2^21 lanes at full size,
    and the dry run's corpus keeps the 768-d vector width."""
    from opensearch_tpu.index.segment import pad_bucket
    assert pad_bucket(CONFIG["documents"]) == 1 << 21
    small = BUILDER.build(CONFIG, 1, True)
    assert small.sizes["d_pad"] == pad_bucket(CONFIG["dry_run"]["documents"])
    assert small.sizes["dimension"] == CONFIG["dimension"] == 768


def test_the_cell_is_an_entry_that_reports_its_metrics():
    """The cell takes one chip, names files that exist, and reports
    `setup_s`, `closed_search_p50_ms` and the per-layer metrics that
    list no cells."""
    bench = FILES.bench
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("msmarco-hybrid-shard", "hybrid-closed-4", 1)
    for path in (CONFIG_FILE, TRAFFIC_FILE):
        assert os.path.exists(os.path.join(REPO, path)), path
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["closed_search_p50_ms"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    general = [m["name"] for m in bench["per_layer"]
               if "workloads" not in m]
    assert general and all(
        os.path.exists(os.path.join(REPO, "benchmark", "metrics",
                                    name + ".json")) for name in general)
