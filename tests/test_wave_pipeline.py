"""Differential parity + chaos suite for the overlapped multi-wave
msearch pipeline (ROADMAP item 1).

Contract under test: splitting an envelope into W waves — wave N+1's
host work and async dispatch overlapping wave N's device_get on the
collector thread — must change WHEN the bytes move and nothing else:

  - W ∈ {1, 2, 4} produce byte-identical responses (modulo `took`) to
    the single-wave path and float-tolerant parity vs the pure-Python
    oracle, across B ∈ {1, 32, 1024}, hybrid and agg bodies included;
  - a deadline passed mid-flight renders ONLY the unlaunched waves'
    items as zero-hit `timed_out: true` partials — dispatched waves'
    hits survive in the same envelope;
  - cancellation between waves drains the in-flight waves (the
    `wave_buffers` device-memory gauge and the ledger's inflight gauge
    return to baseline) before the cancellation propagates;
  - a fault injected at `query.dispatch` / `fetch.gather` downgrades
    ONLY the owning wave's items to error objects;
  - the session-wide host-sync sanitizer (tests/conftest.py) stays
    clean with the collector thread active — every wave's device_get
    runs inside a ledger-attributed region on that thread.
"""

import json
import time

import numpy as np
import pytest

from opensearch_tpu.common import faults
from opensearch_tpu.common.errors import TaskCancelledError
from opensearch_tpu.search import executor as executor_mod
from opensearch_tpu.search.executor import (SearchExecutor, ShardReader,
                                            _StagingPool, _wave_sizes)
from opensearch_tpu.telemetry import TELEMETRY
from opensearch_tpu.utils.demo import build_shards, query_terms

from reference_impl import RefField


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def executor():
    mapper, segments = build_shards(320, n_shards=2, vocab_size=180,
                                    avg_len=24, seed=11)
    # two segments under one reader: per-wave dispatch fans out to both,
    # so the cross-segment merge and the per-segment fault boundaries
    # are both exercised inside every wave
    return SearchExecutor(ShardReader(mapper, segments))


def _mixed_bodies(n_match=24):
    qs = query_terms(max(n_match, 6), 180, seed=3, terms_per_query=2)
    bodies = [{"query": {"match": {"body": q}}, "size": 5}
              for q in qs[:n_match]]
    bodies += [
        {"query": {"bool": {"must": [{"match": {"body": qs[1]}}],
                            "filter": [{"range": {"views": {"gte": 50}}}]}},
         "size": 4},
        {"query": {"term": {"tag": "cat3"}}, "size": 6},
        {"query": {"range": {"views": {"gte": 100, "lt": 5000}}},
         "size": 3, "from": 2},
        {"query": {"match_all": {}}, "size": 0,
         "aggs": {"t": {"terms": {"field": "tag"}}}},
        {"query": {"hybrid": {"queries": [
            {"match": {"body": qs[2]}},
            {"match": {"body": qs[3]}}]}}, "size": 5},
    ]
    return bodies


def _strip(resp):
    resp = json.loads(json.dumps(resp))
    resp.pop("took", None)
    return resp


def _run(executor, bodies, waves):
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    REQUEST_CACHE.clear()
    # twice: cold (compile for this wave bucketing) + warm
    executor.multi_search([dict(b) for b in bodies], waves=waves)
    REQUEST_CACHE.clear()
    return executor.multi_search([dict(b) for b in bodies], waves=waves)


# ----------------------------------------------------------------- parity

@pytest.mark.parametrize("b", [1, 32, 1024])
def test_wave_split_parity_match_only(executor, b):
    """W ∈ {1, 2, 4} byte-identical (modulo took) across batch sizes —
    including B=1 (the degenerate single-wave pipeline) and B=1024 (the
    bench shape, waves of 256)."""
    qs = query_terms(min(b, 64), 180, seed=7, terms_per_query=2)
    bodies = [{"query": {"match": {"body": qs[i % len(qs)]}},
               "size": 5} for i in range(b)]
    base = [_strip(r) for r in _run(executor, bodies, 1)["responses"]]
    for w in (2, 4):
        got = [_strip(r) for r in _run(executor, bodies, w)["responses"]]
        assert got == base, f"W={w} diverged from single-wave at B={b}"


def test_wave_split_parity_mixed_hybrid_aggs(executor):
    """Mixed envelope (match/bool/term/range/agg/hybrid): every wave
    count agrees with the single-wave path item by item."""
    bodies = _mixed_bodies()
    base = [_strip(r) for r in _run(executor, bodies, 1)["responses"]]
    for w in (2, 4):
        got = [_strip(r) for r in _run(executor, bodies, w)["responses"]]
        for body, g, bse in zip(bodies, got, base):
            assert json.dumps(g, sort_keys=True) == \
                   json.dumps(bse, sort_keys=True), (w, body)


def test_wave_split_matches_reference_oracle(executor):
    """W=4 BM25 parity vs the pure-Python oracle (absolute ground truth,
    not just wave-vs-wave consistency)."""
    segs = executor.reader.segments
    docs, ids = [], []
    for seg in segs:
        for ord_ in range(seg.num_docs):
            docs.append(seg.sources[ord_]["body"].split())
            ids.append(seg.doc_ids[ord_])
    ref = RefField(docs)
    qs = query_terms(8, 180, seed=21, terms_per_query=2)
    bodies = [{"query": {"match": {"body": q}}, "size": 8} for q in qs]
    responses = _run(executor, bodies, 4)["responses"]
    for q, resp in zip(qs, responses):
        expected = ref.match_scores(q.split())
        order = sorted(range(len(docs)), key=lambda i: (-expected[i], i))
        want = [(ids[i], expected[i]) for i in order
                if expected[i] > 0][:8]
        got = [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]
        assert [g[0] for g in got] == [w[0] for w in want], q
        for (gid, gs), (_wid, ws) in zip(got, want):
            assert gs == pytest.approx(ws, rel=1e-4), (q, gid)
        assert resp["hits"]["total"]["value"] == \
               int(np.count_nonzero(expected))


def test_wave_sizes_power_of_two_bucketed():
    """Wave chunks stay power-of-two buckets so the warmup registry's
    (plan-struct, shape-bucket, b_pad) signatures are reused."""
    assert _wave_sizes(1024, 4) == [256, 256, 256, 256]
    assert _wave_sizes(1000, 4) == [256, 256, 256, 232]
    assert _wave_sizes(1024, 1) == [1024]
    assert _wave_sizes(1, 4) == [1]
    assert _wave_sizes(300, 2) == [256, 44]
    for n, w in ((1024, 4), (1000, 4), (300, 2), (7, 3)):
        sizes = _wave_sizes(n, w)
        assert sum(sizes) == n
        head = sizes[:-1]
        assert all(s & (s - 1) == 0 for s in head)


# ----------------------------------------------------- ledger attribution

def test_wave_ledger_overlap_and_gauges(executor):
    """A pipelined run records W waves, W-1 overlap events and a drained
    inflight gauge; the request scope carries waves + overlap_ms."""
    qs = query_terms(16, 180, seed=9, terms_per_query=2)
    bodies = [{"query": {"match": {"body": qs[i % 16]}}, "size": 5}
              for i in range(64)]
    _run(executor, bodies, 4)          # warm compile for this bucketing
    TELEMETRY.ledger.enabled = True
    TELEMETRY.ledger.reset()
    try:
        from opensearch_tpu.indices.request_cache import REQUEST_CACHE
        REQUEST_CACHE.clear()
        phase_times = {}
        executor.multi_search([dict(b) for b in bodies], waves=4,
                              phase_times=phase_times)
        snap = TELEMETRY.ledger.snapshot()
        assert snap["waves"] == 4
        assert snap["pipeline"]["overlap_events"] == 3
        assert snap["pipeline"]["inflight_waves"] == 0
        assert snap["pipeline"]["max_inflight_waves"] <= \
            executor_mod.MSEARCH_INFLIGHT_WINDOW
        assert phase_times["waves"] == 4
        assert phase_times["overlap_ms"] >= 0.0
        assert TELEMETRY.ledger.inflight_waves() == 0
        assert TELEMETRY.device_memory.live_bytes("wave_buffers") == 0
    finally:
        TELEMETRY.ledger.enabled = False
        TELEMETRY.ledger.reset()


def test_staging_pool_reuses_exact_size_buffers():
    pool = _StagingPool()
    a = pool.acquire(1024)
    pool.release(a)
    assert pool.acquire(1024) is a          # exact-size reuse
    b = pool.acquire(1024)
    assert b is not a                       # pool drained: fresh alloc
    pool.release(a)
    pool.release(b)
    c = pool.acquire(512)
    assert c.shape == (512,) and c is not a


def test_staging_steady_state_allocates_nothing(executor):
    """After the first window fills, repeated same-shape waves pack into
    recycled buffers: the pool's free lists cycle instead of growing."""
    qs = query_terms(16, 180, seed=13, terms_per_query=2)
    bodies = [{"query": {"match": {"body": qs[i % 16]}}, "size": 5}
              for i in range(64)]
    _run(executor, bodies, 4)
    pool = executor._staging
    with pool._lock:
        sizes_before = {n: len(bufs) for n, bufs in pool._free.items()
                        if bufs}
    _run(executor, bodies, 4)
    with pool._lock:
        sizes_after = {n: len(bufs) for n, bufs in pool._free.items()
                       if bufs}
    assert sizes_after == sizes_before      # recycled, not regrown


# ------------------------------------------------- timeout / cancellation

def test_mid_flight_deadline_renders_tail_waves_timed_out(executor):
    """Wave 1 is slowed past the deadline (seeded delay fault on its
    dispatches); the boundary checkpoint then times out waves 2..4 as
    zero-hit partials while wave 1's dispatched results survive."""
    qs = query_terms(16, 180, seed=15, terms_per_query=2)
    bodies = [{"query": {"match": {"body": qs[i % 16]}}, "size": 5}
              for i in range(16)]
    clean = _run(executor, bodies, 4)["responses"]
    # both segments of wave 1 dispatch slowly: 2 fires × 40ms > 50ms
    faults.install({"site": "query.dispatch", "kind": "delay",
                    "delay_ms": 40, "max_fires": 2, "seed": 0})
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    REQUEST_CACHE.clear()
    resp = executor.multi_search(
        [dict(b) for b in bodies], waves=4,
        deadline=time.monotonic() + 0.05)
    faults.clear()
    responses = resp["responses"]
    timed_out = [r for r in responses if r.get("timed_out")]
    finished = [r for r in responses
                if not r.get("timed_out") and "hits" in r]
    assert timed_out, "expected post-deadline tail waves to time out"
    assert finished, "expected the dispatched wave's items to survive"
    for r in timed_out:
        assert r["hits"]["hits"] == [] and r["hits"]["total"]["value"] == 0
    # surviving items carry the same hits as an unfaulted run
    for i, r in enumerate(responses):
        if not r.get("timed_out"):
            assert _strip(r) == _strip(clean[i])
    assert TELEMETRY.device_memory.live_bytes("wave_buffers") == 0


class _CancellingTask:
    """Cancels itself after `after` checkpoint visits."""

    def __init__(self, after: int):
        self.calls = 0
        self.after = after

    def check_cancelled(self):
        self.calls += 1
        if self.calls > self.after:
            raise TaskCancelledError("cancelled between waves")


def test_cancel_between_waves_drains_inflight(executor):
    """_tasks/_cancel firing at a wave boundary: the pipeline drains the
    dispatched waves (collector joins, buffers release, gauges return
    to baseline) and THEN propagates the cancellation."""
    import threading
    qs = query_terms(16, 180, seed=17, terms_per_query=2)
    bodies = [{"query": {"match": {"body": qs[i % 16]}}, "size": 5}
              for i in range(16)]
    _run(executor, bodies, 4)                       # warm compiles
    threads_before = threading.active_count()
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    REQUEST_CACHE.clear()
    # checkpoints: envelope entry, parse i=0, wave-1 boundary, wave-2
    # boundary → cancel fires after the first wave dispatched
    with pytest.raises(TaskCancelledError):
        executor.multi_search([dict(b) for b in bodies], waves=4,
                              task=_CancellingTask(3))
    assert TELEMETRY.device_memory.live_bytes("wave_buffers") == 0
    assert TELEMETRY.ledger.inflight_waves() == 0
    # the collector thread joined — no leaked threads
    deadline = time.monotonic() + 2.0
    while threading.active_count() > threads_before and \
            time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= threads_before


def test_inline_cancel_between_dispatch_and_collect_releases_gauges(
        executor):
    """Pinned regression: the degenerate single-wave (inline) path's
    pre-collect cancellation checkpoint fires AFTER the inflight gauge
    rose — the pipeline backstop must release both gauges, or every
    such cancel drifts `pipeline.inflight_waves` upward forever."""
    qs = query_terms(4, 180, seed=31, terms_per_query=2)
    bodies = [{"query": {"match": {"body": q}}, "size": 5} for q in qs]
    _run(executor, bodies, 1)                       # warm compiles
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    REQUEST_CACHE.clear()
    base = TELEMETRY.ledger.inflight_waves()
    # checkpoints: envelope entry, parse i=0, wave boundary, PRE-COLLECT
    with pytest.raises(TaskCancelledError):
        executor.multi_search([dict(b) for b in bodies], waves=1,
                              task=_CancellingTask(3))
    assert TELEMETRY.ledger.inflight_waves() == base
    assert TELEMETRY.device_memory.live_bytes("wave_buffers") == 0


# ------------------------------------------------------- fault isolation

def _wave_items(n, waves):
    """Item index ranges per wave for n uniform batchable bodies."""
    out, off = [], 0
    for size in _wave_sizes(n, waves):
        out.append(list(range(off, off + size)))
        off += size
    return out


def test_dispatch_fault_isolated_to_owning_wave(executor):
    """query.dispatch exception during wave 2's dispatches: wave 2's
    items become error objects; waves 1/3/4 serve clean hits."""
    qs = query_terms(16, 180, seed=19, terms_per_query=2)
    bodies = [{"query": {"match": {"body": qs[i % 16]}}, "size": 5}
              for i in range(16)]
    clean = _run(executor, bodies, 4)["responses"]
    # uniform bodies = 1 group/wave × 2 segments = 2 dispatches per
    # wave, waves prepared in order: skip wave 1's two, fail wave 2's
    # first (the group handler then breaks — one fire kills the group)
    faults.install({"site": "query.dispatch", "kind": "exception",
                    "skip": 2, "max_fires": 1, "seed": 0})
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    REQUEST_CACHE.clear()
    responses = executor.multi_search(
        [dict(b) for b in bodies], waves=4)["responses"]
    faults.clear()
    waves = _wave_items(16, 4)
    for i in waves[1]:
        assert responses[i].get("status") == 500 and \
            responses[i]["error"]["type"] == "injected_fault_exception", i
    for wave in (waves[0], waves[2], waves[3]):
        for i in wave:
            assert _strip(responses[i]) == _strip(clean[i]), i
    assert TELEMETRY.device_memory.live_bytes("wave_buffers") == 0


def test_gather_fault_isolated_to_owning_wave(executor):
    """fetch.gather exception during wave 2's collect (combined fetch +
    both per-program fallbacks): only wave 2's items degrade."""
    qs = query_terms(16, 180, seed=23, terms_per_query=2)
    bodies = [{"query": {"match": {"body": qs[i % 16]}}, "size": 5}
              for i in range(16)]
    clean = _run(executor, bodies, 4)["responses"]
    # collects are serialized on the collector thread in wave order:
    # skip wave 1's combined fetch, then fail wave 2's combined fetch
    # AND its two per-program fallback fetches
    faults.install({"site": "fetch.gather", "kind": "exception",
                    "skip": 1, "max_fires": 3, "seed": 0})
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    REQUEST_CACHE.clear()
    responses = executor.multi_search(
        [dict(b) for b in bodies], waves=4)["responses"]
    faults.clear()
    waves = _wave_items(16, 4)
    for i in waves[1]:
        assert responses[i].get("status") == 500, i
    for wave in (waves[0], waves[2], waves[3]):
        for i in wave:
            assert _strip(responses[i]) == _strip(clean[i]), i
    assert TELEMETRY.device_memory.live_bytes("wave_buffers") == 0


# ----------------------------------------------------------- sanitizer

def test_pipelined_run_stays_sanitizer_clean(executor):
    """The tier-1 sanitizer is active for this whole suite (conftest);
    pin it explicitly: a W=4 pipelined envelope with the collector
    thread doing the device_gets adds ZERO unattributed-sync
    violations."""
    from opensearch_tpu.common.sanitize import SANITIZER
    assert SANITIZER.enabled and SANITIZER.installed
    before = SANITIZER.violations
    qs = query_terms(16, 180, seed=29, terms_per_query=2)
    bodies = [{"query": {"match": {"body": qs[i % 16]}}, "size": 5}
              for i in range(32)]
    resp = _run(executor, bodies, 4)
    assert all("hits" in r for r in resp["responses"])
    assert SANITIZER.violations == before
