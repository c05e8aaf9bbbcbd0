"""Per-phase profile of the agg bench configs (2/3: agg_terms, date_hist).

Round-6 counterpart of profile_bench.py for the aggregation path: runs the
bench workload through the msearch envelope, reports the telemetry
`msearch.phase.*` histograms per config plus an ablation (query-only / each agg alone / both), and times
the executable-warmup subsystem (cold compile vs post-warmup replay).
Writes PROFILE_AGGS_RUN.md.

Usage: python tools/profile_aggs.py   [BENCH_DOCS=50000 BENCH_AGG_QUERIES=32]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

RESULTS: list = []


def log(name, ms, note=""):
    RESULTS.append((name, ms, note))
    print(f"{name:42s} {ms:9.1f} ms  {note}", flush=True)


def main():
    os.environ.setdefault("BENCH_DOCS", "50000")
    import bench
    platform = bench.require_device().platform
    print(f"platform: {platform}")
    executor, seg = bench.build_index()
    n_q = int(os.environ.get("BENCH_AGG_QUERIES", "32"))
    rng = np.random.RandomState(13)
    day = 86400_000
    spans = 1 + 79 * rng.permutation(n_q) / max(n_q, 1)

    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    from opensearch_tpu.telemetry import TELEMETRY

    def q(s):
        return {"range": {"ts": {"lt": int(1700000000000 + s * day)}}}

    def run(tag, mk_body, reps=5):
        bodies = [mk_body(s) for s in spans]
        t0 = time.perf_counter()
        executor.multi_search(bodies)
        cold = (time.perf_counter() - t0) * 1000
        TELEMETRY.metrics.reset()
        times = []
        for _ in range(reps):
            REQUEST_CACHE.clear()
            t0 = time.perf_counter()
            executor.multi_search(bodies)
            times.append((time.perf_counter() - t0) * 1000)
        med = sorted(times)[reps // 2]
        hists = TELEMETRY.metrics.to_dict()["histograms"]
        ph = {name[len("msearch.phase."):-len("_ms")]:
              round(h["sum_ms"] / reps, 2)
              for name, h in sorted(hists.items())
              if name.startswith("msearch.phase.")}
        log(f"{tag}: warm batch median", med, f"cold={cold:.0f}ms B={n_q}")
        for k, v in ph.items():
            log(f"{tag}:   phase {k}", v)
        return med

    dh = {"per_day": {"date_histogram": {"field": "ts",
                                         "fixed_interval": "1d"}}}
    cd = {"uniq": {"cardinality": {"field": "tag"}}}
    run("query-only", lambda s: {"size": 0, "query": q(s)})
    run("date_hist", lambda s: {"size": 0, "query": q(s), "aggs": dh})
    run("cardinality", lambda s: {"size": 0, "query": q(s), "aggs": cd})
    run("both", lambda s: {"size": 0, "query": q(s), "aggs": {**dh, **cd}})

    # warmup subsystem: cold-compile cost vs post-warmup replay of the
    # registered (plan-struct, shape-bucket) executables
    from opensearch_tpu.search import executor as ex_mod
    from opensearch_tpu.search.warmup import WARMUP
    n_reg = WARMUP.stats()["registered"]
    ex_mod._JIT_CACHE.clear()
    t0 = time.perf_counter()
    r = WARMUP.warm_executor(executor)
    log("warmup: replay after executable-cache wipe",
        (time.perf_counter() - t0) * 1000,
        f"{r['warmed']} entries of {n_reg} registered")
    t0 = time.perf_counter()
    r = WARMUP.warm_executor(executor)
    log("warmup: second replay (all compiled)",
        (time.perf_counter() - t0) * 1000, f"{r['warmed']} entries")

    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "PROFILE_AGGS_RUN.md")
    with open(out, "w") as f:
        f.write(f"# agg bench profile run ({platform})\n\n")
        f.write("| phase | ms | note |\n|---|---|---|\n")
        for name, ms, note in RESULTS:
            f.write(f"| {name} | {ms:.1f} | {note} |\n")
    print("\nwrote PROFILE_AGGS_RUN.md")


if __name__ == "__main__":
    main()
