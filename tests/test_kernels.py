"""Executable census and roofline classes (ISSUE 19; the sampled
host-clock timer went in PR 31: kernel time comes from the device trace).

Pins:
  - census/compile-histogram reconciliation: the census `compile_ms`
    total and the always-on `search.xla_compile_ms` histogram are fed
    by the SAME note_compile call, so window deltas match exactly;
  - a cache hit of each of the executor's five executable caches is the
    cached executable itself, and it carries `exec_info`;
  - the REST face (GET, `?scopes=true`, `_clear`, the `GET /_telemetry`
    gate index, the `_nodes/stats` block) + the two peak settings; the
    timer's routes, keys and joins are gone;
  - the two environment switches no longer reach the executor;
  - ops-layer compile visibility: the knn `_kmeans` and delta-publish
    `_expand_fn` jit sites reach the compile counters AND the census.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import opensearch_tpu.search.executor as executor_mod
import opensearch_tpu.search.spmd as spmd_mod
import opensearch_tpu.telemetry.kernels as kernels_mod
from opensearch_tpu.search.executor import SearchExecutor, ShardReader
from opensearch_tpu.telemetry import TELEMETRY
from opensearch_tpu.telemetry.kernels import (
    DEVICE_PEAKS, KERNEL_FAMILIES, KERNELS, KernelProfiler, fingerprint,
    timed_first_call)
from opensearch_tpu.utils.demo import build_shards, query_terms


@pytest.fixture(scope="module")
def executor():
    mapper, segments = build_shards(320, n_shards=2, vocab_size=180,
                                    avg_len=24, seed=11)
    return SearchExecutor(ShardReader(mapper, segments))


def _bodies(n=10):
    qs = query_terms(6, 180, seed=5, terms_per_query=2)
    # sizes deliberately off the values sibling test modules use, so
    # this module owns its own compile keys when it needs fresh ones
    return [{"query": {"match": {"body": qs[i % len(qs)]}},
             "size": 7 + 2 * (i % 3)} for i in range(n)]


def _metric_window():
    m = TELEMETRY.metrics
    h = m.histogram("search.xla_compile_ms")
    return (m.counter("search.xla_cache_miss").value, h.count, h.sum,
            KERNELS.snapshot()["census"]["compile_ms_total"],
            KERNELS.snapshot()["census"]["entries"])


# ---------------------------------------------------------- singleton

# what a family row and the body of `GET /_telemetry/kernels` hold: the
# census' counts and the roofline classes, no clock but the compile wall
FAMILY_KEYS = {"compiles", "compile_ms", "flops", "bytes",
               "arithmetic_intensity", "bound"}
BODY_KEYS = {"peak_flops", "peak_bw", "ridge_intensity", "census",
             "families"}


class TestGateDiscipline:
    def test_singleton_is_wired(self):
        assert TELEMETRY.kernels is KERNELS
        # the census has no gate: nothing to turn on, no timer to wrap
        # an executable in
        for gone in ("enabled", "gate", "timed"):
            assert not hasattr(KERNELS, gone)
        assert set(KERNELS.snapshot()) == BODY_KEYS

    def test_clear_keeps_config_drops_state(self):
        p = KernelProfiler()
        p.peak_flops = 2.0e12
        p.peak_bw = 2.0e11
        p.census_note(None, (), "other", "s", "deadbeef", 1.5,
                      (10.0, 20.0))
        assert p.snapshot()["census"]["entries"] == 1
        p.clear()
        snap = p.snapshot()
        assert snap["peak_flops"] == 2.0e12 and snap["peak_bw"] == 2.0e11
        assert snap["census"]["entries"] == 0
        assert snap["families"] == {}


# ------------------------------------------------------------- census

class TestCensus:
    def test_census_registers_on_first_call_always_on(self):
        import jax
        import jax.numpy as jnp
        miss0, cnt0, sum0, cms0, n0 = _metric_window()
        fn = jax.jit(lambda x: x * 2.0 + 1.0)
        key = ("test-census", 3)
        wrapped = timed_first_call(fn, family="other", shape="t3",
                                   key=key, cost=(6.0, 24.0))
        out = wrapped(jnp.ones((3,), dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(out), [3.0, 3.0, 3.0])
        miss1, cnt1, sum1, cms1, n1 = _metric_window()
        assert miss1 - miss0 == 1 and cnt1 - cnt0 == 1
        assert n1 - n0 == 1
        rec = KERNELS.snapshot()["census"]["executables"][-1]
        assert rec["family"] == "other" and rec["shape"] == "t3"
        assert rec["fingerprint"] == fingerprint(key)
        assert rec["compile_ms"] > 0
        # XLA's own cost model where the backend provides one, the
        # analytic scan estimate otherwise — never "none" when a cost
        # hint rides along
        assert rec["cost_source"] in ("xla", "analytic")
        assert rec["flops"] is not None and rec["bytes"] is not None

    def test_census_reconciles_with_compile_histogram(self):
        # same note_compile feeds both sinks: window deltas must agree
        # to the census's round(ms, 3) write precision
        import jax
        import jax.numpy as jnp
        _, cnt0, sum0, cms0, n0 = _metric_window()
        for i in range(3):
            fn = jax.jit(lambda x, _i=i: x + float(_i))
            wrapped = timed_first_call(
                fn, family="other", shape=f"r{i}",
                key=("test-reconcile", i), cost=(1.0, 4.0))
            wrapped(jnp.ones((2 + i,), dtype=jnp.float32))
        _, cnt1, sum1, cms1, n1 = _metric_window()
        assert cnt1 - cnt0 == 3 and n1 - n0 == 3
        assert abs((sum1 - sum0) - (cms1 - cms0)) < 0.01

    def test_cost_source_fallbacks(self):
        # host fn: fn.lower() raises -> analytic hint wins; without a
        # hint the record degrades to "none", never fails the call
        KERNELS.census_note(None, (), "other", "hf", "0" * 8, 1.0,
                            (10.0, 20.0))
        rec = KERNELS.snapshot()["census"]["executables"][-1]
        assert rec["cost_source"] == "analytic"
        assert rec["flops"] == 10.0 and rec["bytes"] == 20.0
        KERNELS.census_note(None, (), "other", "hf2", "1" * 8, 1.0, None)
        rec = KERNELS.snapshot()["census"]["executables"][-1]
        assert rec["cost_source"] == "none"
        assert rec["flops"] is None and rec["bytes"] is None

    def test_census_overflow_counts_drops(self, monkeypatch):
        monkeypatch.setattr(kernels_mod, "MAX_CENSUS_ENTRIES", 2)
        p = KernelProfiler()
        for i in range(4):
            p.census_note(None, (), "other", f"s{i}", "ab" * 4, 1.0,
                          (1.0, 2.0))
        snap = p.snapshot()
        assert snap["census"]["entries"] == 2
        assert snap["census"]["dropped"] == 2

    def test_fingerprint_stable_8_hex(self):
        key = ("env", ("match", "body"), (64, 128), 10)
        fp = fingerprint(key)
        assert fp == fingerprint(key)
        assert len(fp) == 8 and int(fp, 16) >= 0
        assert fp != fingerprint(key + (1,))

    def test_roofline_classification(self):
        p = KernelProfiler()
        p.peak_flops = 1.0e12
        p.peak_bw = 1.0e11            # ridge intensity = 10 flop/byte
        p.census_note(None, (), "knn", "hot", "a" * 8, 1.0,
                      (1000.0, 10.0))   # ai 100 -> compute-bound
        p.census_note(None, (), "expand", "cold", "b" * 8, 1.0,
                      (10.0, 1000.0))   # ai 0.01 -> memory-bound
        fams = p.snapshot()["families"]
        assert p.snapshot()["ridge_intensity"] == 10.0
        assert fams["knn"]["bound"] == "compute"
        assert fams["expand"]["bound"] == "memory"
        assert fams["knn"]["arithmetic_intensity"] == 100.0

    def test_unlisted_device_is_not_classified(self):
        # the test backend's device_kind ("cpu") has no DEVICE_PEAKS row:
        # no ridge, `bound: null` — never a guessed default
        import jax
        assert jax.devices()[0].device_kind not in DEVICE_PEAKS
        p = KernelProfiler()
        p.census_note(None, (), "knn", "hot", "a" * 8, 1.0,
                      (1000.0, 10.0))
        snap = p.snapshot()
        assert snap["peak_flops"] is None and snap["peak_bw"] is None
        assert snap["ridge_intensity"] is None
        assert snap["families"]["knn"]["bound"] is None
        assert snap["families"]["knn"]["arithmetic_intensity"] == 100.0

    def test_v5e_row_is_the_published_peak(self):
        assert DEVICE_PEAKS["TPU v5 lite"] == (197.0e12, 819.0e9)


# --------------------------------------------------------- cache hits

def _serve_general(executor):
    # off the B=1 envelope and off the SPMD route: the host loop
    with spmd_mod.force_host_loop():
        executor.search({"query": {"match": {"body": "w00003"}},
                         "size": 6}, _direct=True)


def _serve_envelope(executor):
    executor.multi_search([dict(b) for b in _bodies(4)])


def _serve_agg_envelope(executor):
    executor.multi_search([
        dict(b, aggs={"m": {"max": {"field": "views"}}})
        for b in _bodies(4)])


def _serve_page(executor):
    executor_mod.RESULT_PAGE = True
    try:
        with spmd_mod.force_host_loop():
            executor.search({"query": {"match": {"body": "w00003"}},
                             "size": 5, "sort": [{"views": "asc"}],
                             "docvalue_fields": ["views"]})
    finally:
        executor_mod.RESULT_PAGE = False


def _serve_hybrid(_executor):
    from opensearch_tpu.node import Node
    node = Node()
    node.request("PUT", "/hyb", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "title": {"type": "text"},
            "vec": {"type": "knn_vector", "dimension": 4}}}})
    for i in range(12):
        node.request("PUT", f"/hyb/_doc/{i}", {
            "title": f"red dog {i}" if i % 2 else f"blue cat {i}",
            "vec": [0.1 * i, 0.2, 0.3, 0.05 * i]})
    node.request("POST", "/hyb/_refresh")
    body = {"query": {"hybrid": {"queries": [
        {"match": {"title": "red dog"}},
        {"knn": {"vec": {"vector": [0.5, 0.2, 0.3, 0.1], "k": 3}}}]}},
        "size": 5}
    node.indices.get("hyb").shards[0].executor.multi_search(
        [dict(body), dict(body)])


class TestCacheHits:
    """The five executable caches of search/executor.py: the second
    lookup of a key hands back the very object `_JIT_CACHE` holds, no
    wrapper around it, and that object names its executable
    (`exec_info`) for the `dispatch` span."""

    @pytest.mark.parametrize("name,serve", [
        ("_runner", _serve_general),
        ("_envelope_runner", _serve_envelope),
        ("_agg_envelope_runner", _serve_agg_envelope),
        ("_batched_hybrid_runner", _serve_hybrid),
        ("_page_merger", _serve_page)])
    def test_second_lookup_is_the_cached_executable(
            self, executor, monkeypatch, name, serve):
        lookup = getattr(executor_mod, name)
        seen = []

        def spy(*args, **kwargs):
            seen.append((args, kwargs))
            return lookup(*args, **kwargs)

        monkeypatch.setattr(executor_mod, name, spy)
        serve(executor)
        assert seen, f"the request never reached {name}"
        args, kwargs = seen[-1]
        hit = lookup(*args, **kwargs)
        assert lookup(*args, **kwargs) is hit
        cached = [v for v in executor_mod._JIT_CACHE.values() if v is hit]
        assert len(cached) == 1, f"{name} returned no _JIT_CACHE entry"
        fn = hit[0] if isinstance(hit, tuple) else hit
        assert fn.exec_info.family in KERNEL_FAMILIES
        assert len(fn.exec_info.fingerprint) == 8

    def test_e2e_timed_families_are_known_vocabulary(self, executor):
        # sizes no other test of the module asks for: fresh compile keys
        executor.multi_search([dict(b, size=15 + i)
                               for i, b in enumerate(_bodies(3))])
        snap = KERNELS.snapshot()
        served = {r["family"] for r in snap["census"]["executables"]}
        assert served and served <= set(KERNEL_FAMILIES)
        assert set(snap["families"]) == served


# ---------------------------------------------------------- REST face

class TestRestFace:
    @pytest.fixture()
    def node(self):
        from opensearch_tpu.node import Node
        n = Node()
        n.request("PUT", "/kern", {"mappings": {"properties": {
            "msg": {"type": "text"}}}})
        for i in range(20):
            n.request("PUT", f"/kern/_doc/{i}",
                      {"msg": f"profiled message number {i}"})
        n.request("POST", "/kern/_refresh")
        yield n
        KERNELS.clear()

    def test_telemetry_index_lists_ten_gates(self, node):
        # nine since PR 31: the kernel census has no gate
        r = node.request("GET", "/_telemetry")
        assert r["_status"] == 200
        subs = r["subsystems"]
        assert set(subs) == {"tracer", "transfers", "devices", "tail",
                             "ingest", "churn", "insights", "scheduler",
                             "faults"}
        for name, row in subs.items():
            assert isinstance(row["enabled"], bool)
            assert row["endpoint"].startswith("/_")

    def _search(self, node):
        for term in ("profiled", "message", "number"):
            node.request("POST", "/kern/_search",
                         {"query": {"match": {"msg": term}}, "size": 3})

    def test_roundtrip(self, node):
        self._search(node)
        snap = node.request("GET", "/_telemetry/kernels")["kernels"]
        assert snap["census"]["entries"] > 0
        # full GET carries the per-executable dump, each record's map
        # only on demand
        assert all("scopes" not in rec
                   for rec in snap["census"]["executables"])
        scoped = node.request("GET", "/_telemetry/kernels",
                              scopes="true")["kernels"]
        assert all(isinstance(rec["scopes"], dict)
                   for rec in scoped["census"]["executables"])
        r = node.request("POST", "/_telemetry/kernels/_clear")
        assert r["acknowledged"] is True
        snap = node.request("GET", "/_telemetry/kernels")["kernels"]
        assert snap["census"] == {"entries": 0, "dropped": 0,
                                  "compile_ms_total": 0.0,
                                  "executables": []}
        assert snap["families"] == {}

    @pytest.mark.parametrize("verb", ["_enable", "_disable"])
    def test_timer_routes_are_unknown(self, node, verb):
        unknown = node.request("POST", "/_telemetry/no_such_face/_enable")
        r = node.request("POST", f"/_telemetry/kernels/{verb}")
        assert r["_status"] == unknown["_status"] != 200
        assert r["error"]["type"] == unknown["error"]["type"]

    @pytest.mark.parametrize("face", ["get", "nodes_stats"])
    def test_census_only_body(self, node, face):
        self._search(node)
        # the searches above compile nothing new once the process has
        # their executables: one record of this test's own
        KERNELS.census_note(None, (), "other", "s", "0" * 8, 1.0,
                            (10.0, 20.0))
        if face == "get":
            body = node.request("GET", "/_telemetry/kernels")["kernels"]
            for rec in body["census"]["executables"]:
                assert {"family", "fingerprint", "shape", "compile_ms",
                        "from_cache", "flops", "bytes",
                        "cost_source"} == set(rec)
        else:
            stats = node.request("GET", "/_nodes/stats")
            body = stats["nodes"][node.node_id]["telemetry"]["kernels"]
            assert "executables" not in body["census"]
        assert set(body) == BODY_KEYS
        assert body["families"]
        for row in body["families"].values():
            assert set(row) == FAMILY_KEYS

    def test_profile_has_no_kernel_entry(self, node):
        res = node.request("POST", "/kern/_search", {
            "profile": True, "query": {"match": {"msg": "profiled"}}})
        shards = res["profile"]["shards"]
        assert shards
        for shard in shards:
            assert "transfers" in shard
            assert not [k for k in shard if "kernel" in k]
            for search in shard["searches"]:
                assert not [k for k in search["query"][0]["breakdown"]
                            if "kernel" in k]

    def test_insights_row_has_no_kernel_column(self, node):
        from opensearch_tpu.telemetry.insights import INSIGHTS
        INSIGHTS.enabled = True
        INSIGHTS.clear()
        try:
            self._search(node)
            node.request("POST", "/kern/_msearch", [
                {}, {"query": {"match": {"msg": "message"}}}])
            shapes = INSIGHTS.snapshot()["shapes"]
            assert shapes
            for row in shapes.values():
                assert not [k for k in row if "kernel" in k]
        finally:
            INSIGHTS.enabled = False
            INSIGHTS.clear()

    def test_node_setting_enables_and_sets_roofline(self):
        # the two settings left: the roofline peaks
        from opensearch_tpu.node import Node
        try:
            Node(settings={
                "telemetry.kernels.peak_flops": "2.5e12",
                "telemetry.kernels.peak_bw": "5e11"})
            assert KERNELS.peak_flops == 2.5e12
            assert KERNELS.peak_bw == 5.0e11
            assert KERNELS.snapshot()["ridge_intensity"] == 5.0
        finally:
            KERNELS.peak_flops = None
            KERNELS.peak_bw = None
            KERNELS.clear()
            Node()      # re-configure the singleton back to defaults


# ------------------------------------------------- environment switches

class TestEnvironmentSwitchesAreGone:
    """PR 31 made the two whole-process A/B switches plain module
    attributes: what the environment says no longer reaches them."""

    @pytest.mark.parametrize("switch,value,attribute,default", [
        ("DISABLE_INTERNING", "1", "TEMPLATE_INTERNING", "True"),
        ("MSEARCH_WAVES", "4", "FORCED_WAVES", "None")])
    def test_import_ignores_the_environment(self, switch, value,
                                            attribute, default):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env[f"OPENSEARCH_TPU_{switch}"] = value
        out = subprocess.run(
            [sys.executable, "-c",
             "import opensearch_tpu.search.executor as e; "
             f"print(repr(e.{attribute}))"],
            env=env, capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == default


# ------------------------------------------- ops compile visibility

class TestOpsCompileVisibility:
    """The two formerly invisible jit sites (ISSUE 19 satellite): their
    XLA compiles must reach `search.xla_cache_miss`, the compile-ms
    histogram, and the executable census."""

    def test_kmeans_compile_reaches_counters_and_census(self):
        from opensearch_tpu.ops.knn import _kmeans
        vecs = np.random.RandomState(0).randn(37, 8).astype(np.float32)
        miss0, cnt0, _, _, n0 = _metric_window()
        cents = _kmeans(vecs, nlist=4, iters=2, seed=3)
        assert cents.shape == (4, 8)
        miss1, cnt1, _, _, n1 = _metric_window()
        assert miss1 - miss0 == 1 and cnt1 - cnt0 == 1
        assert n1 - n0 == 1
        rec = KERNELS.snapshot()["census"]["executables"][-1]
        assert rec["family"] == "knn"
        assert rec["shape"] == "n37/d8/c4"

    def test_kmeans_zero_iters_compiles_nothing(self):
        from opensearch_tpu.ops.knn import _kmeans
        vecs = np.random.RandomState(1).randn(21, 4).astype(np.float32)
        miss0, cnt0, _, _, n0 = _metric_window()
        cents = _kmeans(vecs, nlist=3, iters=0, seed=3)
        assert cents.shape == (3, 4)
        miss1, cnt1, _, _, n1 = _metric_window()
        assert (miss1, cnt1, n1) == (miss0, cnt0, n0)

    def test_expand_fn_compile_visible_then_cached(self):
        import jax.numpy as jnp
        from opensearch_tpu.ops.device_segment import _expand_fn
        miss0, cnt0, _, _, n0 = _metric_window()
        f = _expand_fn((11,), (29,), 0, "int32")
        # building the wrapper compiles nothing; the first CALL does
        assert _metric_window()[0] == miss0
        out = f(jnp.arange(11, dtype=jnp.int32))
        arr = np.asarray(out)
        assert arr.shape == (29,)
        np.testing.assert_array_equal(arr[:11], np.arange(11))
        assert not arr[11:].any()
        miss1, cnt1, _, _, n1 = _metric_window()
        assert miss1 - miss0 == 1 and cnt1 - cnt0 == 1
        assert n1 - n0 == 1
        rec = KERNELS.snapshot()["census"]["executables"][-1]
        assert rec["family"] == "expand"
        assert rec["flops"] is not None and rec["bytes"] is not None
        # second lookup is a cache HIT: the raw executable, no wrapper,
        # no new compile event
        f2 = _expand_fn((11,), (29,), 0, "int32")
        np.testing.assert_array_equal(
            np.asarray(f2(jnp.arange(11, dtype=jnp.int32))), arr)
        assert _metric_window()[0] == miss1
