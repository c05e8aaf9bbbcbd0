"""Config-1 (BM25 match) scaling curve: 100K / 300K / 1M docs.

Writes one JSON line per scale to SCALING_raw.json: batched QPS, single-
query p50/p99, the numpy-CSR baseline, and the per-query bytes the
candidate kernel actually touches (posting blocks of the query's terms)
vs what a dense scan would touch. Run on whatever backend is up; the
driver's TPU bench covers the flagship number.

SCALE_FAST=1 (ISSUE 20) swaps the per-doc builder for the vectorized
`build_shards_fast` corpus (burst-clustered mid-band terms, queries
drawn from the materialized band) so the curve extends to 10M docs —
`build_shards` takes hours there; the fast seal takes seconds.
SCALE_BLOCKMAX=1 additionally runs the pruned arm: flips the
`search.blockmax.enabled` module gate and records the live scan
counters' effective (post-pruning) bytes + pruned fraction next to the
static column. The numpy baseline is skipped for fast corpora (the
CSR scorer rebuilds per-doc structures the fast seal never makes)."""
import json
import os
import sys
import time

import jax
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def run_scale(n_docs: int, out):
    from opensearch_tpu.search.executor import SearchExecutor, ShardReader
    from opensearch_tpu.utils.demo import (build_shards, build_shards_fast,
                                           fast_query_terms, query_terms)
    fast = os.environ.get("SCALE_FAST") == "1"
    blockmax = os.environ.get("SCALE_BLOCKMAX") == "1"
    t0 = time.perf_counter()
    if fast:
        mapper, segments, fterms = build_shards_fast(
            n_docs, n_shards=1, vocab_size=20000, avg_len=60, seed=42,
            materialize_terms=64, burst_tf=30, burst_window=256,
            doc_len_cv=0.5)
    else:
        mapper, segments = build_shards(n_docs, n_shards=1,
                                        vocab_size=20000,
                                        avg_len=60, seed=42)
    seg = segments[0]
    build_s = time.perf_counter() - t0
    if blockmax:
        from opensearch_tpu.ops import bm25 as _bm25
        from opensearch_tpu.telemetry import TELEMETRY
        _bm25.BLOCKMAX = True
        TELEMETRY.scan.reset()  # per-scale counters (multi-scale runs)
    reader = ShardReader(mapper, segments)
    ex = SearchExecutor(reader)
    queries = fast_query_terms(1024, fterms, seed=7) if fast \
        else query_terms(1024, 20000, seed=7, terms_per_query=2)
    bodies = [{"query": {"match": {"body": q}}, "size": 10} for q in queries]
    ex.multi_search(bodies)                      # compile all shape buckets
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        ex.multi_search(bodies)
        times.append(time.perf_counter() - t0)
    qps = len(bodies) / sorted(times)[1]
    for q in queries[:32]:
        ex.search({"query": {"match": {"body": q}}, "size": 10})
    lat = []
    for q in queries[:64]:
        t0 = time.perf_counter()
        ex.search({"query": {"match": {"body": q}}, "size": 10})
        lat.append((time.perf_counter() - t0) * 1000)
    lat.sort()
    # bytes the candidate kernel touches per query: the terms' posting
    # blocks (docs int32 + tf f32 = 8B per lane incl. padding lanes)
    per_q_bytes = []
    for q in queries:
        b = 0
        for t in q.split():
            tm = seg.get_term("body", t)
            if tm is not None:
                b += tm.num_blocks * 128 * 8
        per_q_bytes.append(b)
    dense_bytes = seg.post_docs.shape[0] * 128 * 8
    rec = {
        "n_docs": n_docs,
        "platform": jax.devices()[0].platform,
        "build_s": round(build_s, 1),
        "qps_batched": round(qps, 1),
        "p50_ms": round(lat[len(lat) // 2], 2),
        "p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))], 2),
        "scanned_bytes_per_query_p50": int(np.median(per_q_bytes)),
        "scanned_bytes_per_query_max": int(max(per_q_bytes)),
        "dense_scan_bytes": int(dense_bytes),
        "total_postings_blocks": int(seg.post_docs.shape[0]),
    }
    if fast:
        rec["fast_corpus"] = True
    else:
        # numpy-CSR baseline (same scorer as bench.py); classic corpora
        # only — the scorer rebuilds per-doc CSR structures the fast
        # seal never materializes
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        import bench
        base_qps = bench.numpy_baseline(seg, queries[:256])
        rec["numpy_baseline_qps"] = round(base_qps, 1)
        rec["vs_baseline"] = round(qps / base_qps, 3)
    if blockmax:
        from opensearch_tpu.telemetry import TELEMETRY
        scan = TELEMETRY.scan.stats()
        post_total = scan["posting_bytes_total"]
        rec["blockmax"] = True
        rec["effective_bytes_per_query_p50"] = \
            scan["per_query"]["effective_posting_bytes"].get("p50")
        rec["pruned_fraction"] = round(
            scan["pruned_bytes_total"] / max(post_total, 1), 4)
    out.write(json.dumps(rec) + "\n")
    out.flush()
    print(json.dumps(rec))


if __name__ == "__main__":
    scales = [int(s) for s in
              os.environ.get("SCALES", "100000,300000,1000000").split(",")]
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SCALING_raw.json")
    with open(path, "a") as out:
        for n in scales:
            run_scale(n, out)
