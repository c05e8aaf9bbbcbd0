"""Executable-warmup subsystem (search/warmup.py): registry round-trip
(persist → reload → warm → no recompile on live traffic), index-open /
node-start hooks, and the _nodes/stats surface. CPU-backend tier-1 safe.
"""

import json
from collections import OrderedDict

import numpy as np
import pytest

from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import SegmentBuilder
from opensearch_tpu.search.executor import SearchExecutor, ShardReader
from opensearch_tpu.search.warmup import WARMUP, WarmupRegistry

MAPPING = {"properties": {"body": {"type": "text"},
                          "ts": {"type": "date"},
                          "tag": {"type": "keyword"}}}

BASE_TS = 1700000000000
DAY = 86400_000


@pytest.fixture()
def clean_warmup():
    """Isolate the node-wide singleton from entries other tests recorded."""
    saved_entries, saved_memo = WARMUP._entries, WARMUP._sig_memo
    saved_path, saved_dirty = WARMUP._path, WARMUP._dirty
    WARMUP._entries = OrderedDict()
    WARMUP._sig_memo = {}
    WARMUP._path = None
    WARMUP._dirty = False
    yield WARMUP
    WARMUP._entries = saved_entries
    WARMUP._sig_memo = saved_memo
    WARMUP._path = saved_path
    WARMUP._dirty = saved_dirty


def _executor(n=64, seed=5):
    rng = np.random.RandomState(seed)
    mapper = MapperService(MAPPING)
    b = SegmentBuilder(mapper, "w0")
    for i in range(n):
        b.add(mapper.parse_document(f"d{i}", {
            "body": f"w{rng.randint(0, 20):02d} w{rng.randint(0, 20):02d}",
            "ts": int(BASE_TS + rng.randint(0, 30 * DAY)),
            "tag": f"t{rng.randint(0, 4)}"}))
    return SearchExecutor(ShardReader(mapper, [b.seal()]))


BODY = {"size": 0,
        "query": {"range": {"ts": {"lt": BASE_TS + 20 * DAY}}},
        "aggs": {"per_day": {"date_histogram": {"field": "ts",
                                                "fixed_interval": "1d"}},
                 "uniq": {"cardinality": {"field": "tag"}}}}


def test_registry_roundtrip_and_no_recompile(tmp_path, clean_warmup):
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    from opensearch_tpu.search import executor as ex_mod

    ex = _executor()
    want = ex.multi_search([BODY] * 3)["responses"][0]
    assert clean_warmup.stats()["registered"] >= 1

    # persist → reload round-trip: a fresh registry sees the same entries
    path = str(tmp_path / "warmup_registry.json")
    clean_warmup._path = path
    clean_warmup._dirty = True
    clean_warmup.flush()
    fresh = WarmupRegistry()
    assert fresh.load(path) == clean_warmup.stats()["registered"]
    assert fresh.entries() == clean_warmup.entries()
    with open(path) as f:
        assert json.load(f)["version"] == 1

    # cold process simulation: wipe the executable cache, warm from the
    # RELOADED registry, then re-drive the original traffic — it must hit
    # warmed executables (no new compile cache entries) and agree
    ex_mod._JIT_CACHE.clear()
    res = fresh.warm_executor(ex)
    assert res["warmed"] >= 1 and res["errors"] == 0
    n_exec = len(ex_mod._JIT_CACHE)
    assert n_exec >= 1
    REQUEST_CACHE.clear()
    got = ex.multi_search([BODY] * 3)["responses"][0]
    assert len(ex_mod._JIT_CACHE) == n_exec, \
        "warmed traffic recompiled an executable"
    assert got["aggregations"] == want["aggregations"]
    assert got["hits"]["total"] == want["hits"]["total"]


def test_warm_bypasses_request_cache(clean_warmup):
    from opensearch_tpu.indices.request_cache import REQUEST_CACHE
    ex = _executor()
    ex.multi_search([BODY])            # records + populates request cache
    before = REQUEST_CACHE.stats()["hit_count"]
    res = clean_warmup.warm_executor(ex)
    assert res["warmed"] >= 1
    # replay executed (no cache hit consumed) — a hit would compile nothing
    assert REQUEST_CACHE.stats()["hit_count"] == before


def test_nodes_stats_surfaces_warmup(clean_warmup):
    from opensearch_tpu.node import Node
    node = Node()
    stats = node.request("GET", "/_nodes/stats")
    section = stats["nodes"][node.node_id]["search_warmup"]
    assert {"registered", "warmed_entries", "last_warmup_ms",
            "warmup_runs"} <= set(section)


def test_index_open_warmup_hook(tmp_path, clean_warmup):
    from opensearch_tpu.node import Node
    node = Node()
    node.request("PUT", "/wi", {"mappings": MAPPING})
    node.request("PUT", "/wi/_doc/1", {"ts": BASE_TS, "tag": "a"},
                 refresh="true")
    node.request("POST", "/wi/_search",
                 {"size": 0, "aggs": {"u": {"cardinality": {
                     "field": "tag"}}}})
    runs = clean_warmup.stats()["warmup_runs"]
    node.request("POST", "/wi/_close")
    node.request("POST", "/wi/_open")
    assert clean_warmup.stats()["warmup_runs"] > runs


def test_burst_records_persist_via_steady_traffic(tmp_path, clean_warmup):
    """Entries recorded inside one persist-throttle window must still land
    on disk once steady-state (already-known-sig) traffic passes the
    window — the early-return for known sigs may not skip persistence."""
    path = str(tmp_path / "r.json")
    clean_warmup._path = path
    clean_warmup._last_persist = 0.0
    clean_warmup.record("i", {"a": 1}, 1, "sig-one")
    clean_warmup.record("i", {"a": 2}, 1, "sig-two")     # throttled: dirty
    with open(path) as f:
        assert len(json.load(f)["entries"]) == 1
    clean_warmup._last_persist = 0.0                     # window elapsed
    clean_warmup.record("i", {"a": 1}, 1, "sig-one")     # known sig
    with open(path) as f:
        assert len(json.load(f)["entries"]) == 2


def test_parse_duration_ms_forms():
    from opensearch_tpu.search.aggs.engine import _parse_duration_ms
    assert _parse_duration_ms("500ms") == 500
    assert _parse_duration_ms("-500ms") == -500
    assert _parse_duration_ms("3h") == 3 * 3600_000
    assert _parse_duration_ms("-45m") == -45 * 60_000
    assert _parse_duration_ms(250) == 250


# ------------------------------------------------ compile-cache placement

def test_compile_cache_is_fixed_in_the_checkout(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR unset the XLA cache sits at one
    fixed place in the checkout — never under path.data — so two nodes
    with different (temporary) data paths share it and a later process
    finds it again."""
    import os

    import jax

    from opensearch_tpu.node import Node
    from opensearch_tpu.search.warmup import DEFAULT_COMPILE_CACHE_DIR
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    seen = []
    for sub in ("a", "b", None):
        node = Node(data_path=str(tmp_path / sub) if sub else None)
        stats = node.request("GET", "/_nodes/stats")
        seen.append(next(iter(stats["nodes"].values()))[
            "search_warmup"]["compile_cache_dir"])
        assert jax.config.jax_compilation_cache_dir == seen[-1]
    assert seen == [DEFAULT_COMPILE_CACHE_DIR] * 3
    assert not (tmp_path / "a" / "_state" / "xla_cache").exists()


def test_compile_cache_env_var_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the caller placed the cache; the
    program sets no directory in code and reports the caller's."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    placed = str(tmp_path / "placed")
    code = (
        "import jax\n"
        "from opensearch_tpu.node import Node\n"
        "from opensearch_tpu.search import warmup\n"
        "calls = []\n"
        "orig = jax.config.update\n"
        "jax.config.update = lambda k, v: (calls.append(k), orig(k, v))\n"
        "node = Node()\n"
        "assert 'jax_compilation_cache_dir' not in calls, calls\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(warmup.WARMUP.stats()['compile_cache_dir'])\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": placed})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [placed, placed]
