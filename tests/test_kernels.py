"""Kernel-level device-compute profiler (ISSUE 19): executable census,
XLA cost/roofline ledger, per-family device-time attribution.

Pins the acceptance behaviors:
  - gate discipline: disabled by default, None-returning gate, clear()
    keeps config while dropping state;
  - census/compile-histogram reconciliation: the census `compile_ms`
    total and the always-on `search.xla_compile_ms` histogram are fed
    by the SAME note_compile call, so window deltas match exactly;
  - sampled-timing determinism: the call-count modulus makes the
    sample schedule a pure function of the global per-family call
    index — total sampled count is exact under 4-thread load;
  - device-ms conservation: with sample_every=1 the timed walls plus
    the residual result-pull wall reproduce the clean run's collect
    wall (async dispatch means the collect absorbs compute when the
    profiler is off);
  - instrumentation-off differential: responses byte-identical (modulo
    took) across off/on/off, and the disabled path records nothing;
  - REST roundtrip (enable/disable/clear, the `GET /_telemetry` gate
    index, `_nodes/stats` block) + node-setting wiring;
  - insights kernel-breakdown join (per-shape kernels dict and the
    dominant_kernel column);
  - ops-layer compile visibility: the knn `_kmeans` and delta-publish
    `_expand_fn` jit sites — formerly invisible — reach the compile
    counters AND the census;
  - tools/kernel_report.py smoke over every accepted input shape.
"""

import json
import threading
import time

import numpy as np
import pytest

import opensearch_tpu.telemetry.kernels as kernels_mod
from opensearch_tpu.search.executor import SearchExecutor, ShardReader
from opensearch_tpu.telemetry import TELEMETRY
from opensearch_tpu.telemetry.kernels import (
    DEFAULT_SAMPLE_EVERY, DEVICE_PEAKS, KERNEL_FAMILIES, KERNELS,
    KernelProfiler, fingerprint, timed_first_call)
from opensearch_tpu.utils.demo import build_shards, query_terms


@pytest.fixture(scope="module")
def executor():
    mapper, segments = build_shards(320, n_shards=2, vocab_size=180,
                                    avg_len=24, seed=11)
    return SearchExecutor(ShardReader(mapper, segments))


@pytest.fixture()
def kernels_on():
    """Enable the profiler for one test at sample_every=1 (every
    dispatch timed — zero extrapolation error), restore the pristine
    default and clear state both ways."""
    KERNELS.enabled = True
    KERNELS.sample_every = 1
    KERNELS.clear()
    yield KERNELS
    KERNELS.enabled = False
    KERNELS.sample_every = DEFAULT_SAMPLE_EVERY
    KERNELS.clear()


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


# --------------------------------------------------------------- gate

class TestGateDiscipline:
    def test_default_off_and_gate_none(self):
        fresh = KernelProfiler()
        assert fresh.enabled is False
        assert fresh.gate() is None
        fresh.enabled = True
        assert fresh.gate() is fresh

    def test_singleton_is_wired(self):
        assert TELEMETRY.kernels is KERNELS
        assert KERNELS.sample_every == DEFAULT_SAMPLE_EVERY

    def test_clear_keeps_config_drops_state(self):
        p = KernelProfiler()
        p.enabled = True
        p.sample_every = 3
        p.peak_flops = 2.0e12
        p.peak_bw = 2.0e11
        p.census_note(None, (), "other", "s", "deadbeef", 1.5,
                      (10.0, 20.0))
        p.timed(lambda: 1, "other", "s")()
        p.clear()
        snap = p.snapshot()
        assert p.enabled is True and p.sample_every == 3
        assert snap["peak_flops"] == 2.0e12 and snap["peak_bw"] == 2.0e11
        assert snap["census"]["entries"] == 0
        assert snap["families"] == {}


# ------------------------------------------------------------- census

class TestCensus:
    def test_census_registers_on_first_call_always_on(self):
        # census is ALWAYS-ON: the gate flag only guards timed dispatch
        assert KERNELS.enabled is False
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


# ------------------------------------------------------------- timing

class TestSampledTiming:
    def test_tick_modulus_deterministic(self):
        p = KernelProfiler()
        p.enabled = True
        p.sample_every = 4
        run = p.timed(lambda: 1, "other", "s")
        for _ in range(10):
            run()
        fam = p.snapshot()["families"]["other"]
        # calls 1, 5, 9 sampled (first call always is)
        assert fam["calls"] == 10 and fam["sampled"] == 3
        # est extrapolates the raw sampled walls over every dispatch
        # (snapshot rounds sampled_ms after the division)
        assert fam["device_ms_est"] == pytest.approx(
            fam["sampled_ms"] * 10 / 3, abs=0.002)

    def test_sampling_deterministic_under_threads(self):
        # the modulus runs over the GLOBAL per-family call counter
        # under the lock: total sampled count is exact no matter how
        # 4 threads interleave
        p = KernelProfiler()
        p.enabled = True
        p.sample_every = 4
        run = p.timed(lambda: 1, "knn", "s0")
        barrier = threading.Barrier(4)

        def work():
            barrier.wait()
            for _ in range(25):
                run()

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fam = p.snapshot()["families"]["knn"]
        assert fam["calls"] == 100
        assert fam["sampled"] == 25
        assert fam["shapes"]["s0"]["calls"] == 100

    def test_sample_every_one_no_extrapolation(self):
        p = KernelProfiler()
        p.enabled = True
        p.sample_every = 1

        def fn():
            time.sleep(0.002)
            return 1

        run = p.timed(fn, "maxsim", "q8")
        for _ in range(5):
            run()
        fam = p.snapshot()["families"]["maxsim"]
        assert fam["sampled"] == fam["calls"] == 5
        assert fam["device_ms_est"] == round(fam["sampled_ms"], 3)
        assert fam["sampled_ms"] >= 5.0     # 5 sleeps of >=2ms
        assert fam["p50_ms"] is not None and fam["p99_ms"] is not None
        assert fam["shapes"]["q8"]["device_ms_est"] == \
            fam["device_ms_est"]


# ------------------------------------------------------- conservation

class TestConservation:
    def test_timed_walls_conserve_against_collect_wall(self):
        """The bench's A/B identity, pinned on a synthetic kernel heavy
        enough to dominate fixed overheads: clean-arm collect wall
        (async dispatch -> device_get absorbs compute) equals the
        instrumented arm's timed wall + residual collect."""
        import jax
        import jax.numpy as jnp
        n, chain, reps = 512, 6, 3

        @jax.jit
        def mm(x):
            for _ in range(chain):
                x = x @ x / jnp.float32(n)
            return x

        x = jnp.ones((n, n), dtype=jnp.float32)
        jax.device_get(mm(x))           # compile + warm
        clean = 0.0
        for _ in range(reps):
            out = mm(x)
            t0 = time.perf_counter_ns()
            jax.device_get(out)
            clean += (time.perf_counter_ns() - t0) / 1e6
        if clean < 5.0:
            pytest.skip("dispatch not async on this backend: the "
                        "collect wall does not absorb compute")
        p = KernelProfiler()
        p.enabled = True
        p.sample_every = 1
        run = p.timed(mm, "other", f"n{n}")
        inst_collect = 0.0
        for _ in range(reps):
            out = run(x)                # blocks until ready (sampled)
            t0 = time.perf_counter_ns()
            jax.device_get(out)
            inst_collect += (time.perf_counter_ns() - t0) / 1e6
        kernel_ms = p.snapshot()["families"]["other"]["device_ms_est"]
        drift = abs(kernel_ms + inst_collect - clean) / clean
        assert drift < 0.5, (kernel_ms, inst_collect, clean)
        # the timed wall owns most of the wait: the residual collect is
        # just the copy
        assert kernel_ms > inst_collect


# --------------------------------------------------- off differential

class TestOffDifferential:
    @staticmethod
    def _strip(res):
        return [{k: v for k, v in r.items() if k != "took"}
                for r in res["responses"]]

    def test_disabled_path_is_byte_identical_and_silent(self, executor):
        bodies = _bodies()
        assert KERNELS.enabled is False
        KERNELS.clear()
        r_off = executor.multi_search([dict(b) for b in bodies])
        snap = KERNELS.snapshot()
        assert all(f["calls"] == 0 and f["sampled_ms"] == 0.0
                   for f in snap["families"].values())
        KERNELS.enabled = True
        KERNELS.sample_every = 1
        try:
            r_on = executor.multi_search([dict(b) for b in bodies])
            fams = KERNELS.snapshot()["families"]
            assert any(f["calls"] > 0 for f in fams.values())
        finally:
            KERNELS.enabled = False
            KERNELS.sample_every = DEFAULT_SAMPLE_EVERY
        calls_after = {f: r["calls"] for f, r in
                       KERNELS.snapshot()["families"].items()}
        r_off2 = executor.multi_search([dict(b) for b in bodies])
        assert self._strip(r_off) == self._strip(r_on) \
            == self._strip(r_off2)
        assert {f: r["calls"] for f, r in
                KERNELS.snapshot()["families"].items()} == calls_after
        KERNELS.clear()

    def test_e2e_timed_families_are_known_vocabulary(self, executor,
                                                     kernels_on):
        executor.multi_search([dict(b) for b in _bodies()])
        fams = kernels_on.snapshot()["families"]
        dispatched = {f for f, r in fams.items() if r["calls"] > 0}
        assert dispatched
        assert dispatched <= set(KERNEL_FAMILIES)
        for f in dispatched:
            assert fams[f]["device_ms_est"] >= 0.0
            assert fams[f]["sampled"] == fams[f]["calls"]


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
        KERNELS.enabled = False
        KERNELS.sample_every = DEFAULT_SAMPLE_EVERY
        KERNELS.clear()

    def test_telemetry_index_lists_ten_gates(self, node):
        r = node.request("GET", "/_telemetry")
        assert r["_status"] == 200
        subs = r["subsystems"]
        assert set(subs) == {"tracer", "transfers", "devices", "tail",
                             "ingest", "churn", "insights", "scheduler",
                             "faults", "kernels"}
        for name, row in subs.items():
            assert isinstance(row["enabled"], bool)
            assert row["endpoint"].startswith("/_")
        assert subs["kernels"]["enabled"] is False
        assert subs["kernels"]["endpoint"] == "/_telemetry/kernels"

    def test_roundtrip(self, node):
        r = node.request("POST", "/_telemetry/kernels/_enable",
                         sample_every=1)
        assert r["_status"] == 200 and r["enabled"] is True
        assert r["sample_every"] == 1
        assert node.request("GET", "/_telemetry")["subsystems"][
            "kernels"]["enabled"] is True
        for term in ("profiled", "message", "number"):
            node.request("POST", "/kern/_search",
                         {"query": {"match": {"msg": term}}, "size": 3})
        snap = node.request("GET", "/_telemetry/kernels")["kernels"]
        assert snap["enabled"] is True
        assert any(f["calls"] > 0 for f in snap["families"].values())
        # full GET carries the per-executable dump; _nodes/stats does not
        assert "executables" in snap["census"]
        stats = node.request("GET", "/_nodes/stats")
        kblock = stats["nodes"][node.node_id]["telemetry"]["kernels"]
        assert kblock["enabled"] is True
        assert "executables" not in kblock["census"]
        r = node.request("POST", "/_telemetry/kernels/_clear")
        assert r["acknowledged"] is True
        snap = node.request("GET", "/_telemetry/kernels")["kernels"]
        assert snap["census"]["entries"] == 0
        assert all(f["calls"] == 0 for f in snap["families"].values())
        r = node.request("POST", "/_telemetry/kernels/_disable")
        assert r["enabled"] is False
        assert KERNELS.gate() is None

    def test_enable_rejects_bad_sample_every(self, node):
        r = node.request("POST", "/_telemetry/kernels/_enable",
                         sample_every="every-so-often")
        assert r["_status"] == 400

    def test_node_setting_enables_and_sets_roofline(self):
        from opensearch_tpu.node import Node
        try:
            Node(settings={
                "telemetry.kernels.enabled": "true",
                "telemetry.kernels.peak_flops": "2.5e12",
                "telemetry.kernels.peak_bw": "5e11",
                "telemetry.kernels.sample_every": "4"})
            assert KERNELS.enabled is True
            assert KERNELS.peak_flops == 2.5e12
            assert KERNELS.peak_bw == 5.0e11
            assert KERNELS.sample_every == 4
        finally:
            KERNELS.enabled = False
            KERNELS.sample_every = DEFAULT_SAMPLE_EVERY
            KERNELS.peak_flops = None
            KERNELS.peak_bw = None
            KERNELS.clear()
            Node()      # re-configure the singleton back to defaults


# ------------------------------------------------------- insights join

class TestInsightsJoin:
    def test_note_kernels_accumulates_and_names_dominant(self):
        from opensearch_tpu.telemetry.insights import QueryInsights
        ins = QueryInsights()
        ins.enabled = True
        ins.note("s1", kind="template", took_ms=1.0, device_ms=3.0,
                 kernels={"bm25_dense": 2.0, "page_merger": 1.0})
        ins.note("s1", kind="template", took_ms=1.0, device_ms=2.0,
                 kernels={"bm25_dense": 2.0})
        row = ins.snapshot()["shapes"]["s1"]
        assert row["kernels"] == {"bm25_dense": 4.0, "page_merger": 1.0}
        assert row["dominant_kernel"] == "bm25_dense"

    def test_e2e_shape_rows_carry_kernel_breakdown(self, executor,
                                                   kernels_on):
        from opensearch_tpu.telemetry.insights import INSIGHTS
        INSIGHTS.enabled = True
        INSIGHTS.clear()
        try:
            executor.multi_search([dict(b) for b in _bodies()])
            shapes = INSIGHTS.snapshot()["shapes"]
            assert shapes
            joined = [r for r in shapes.values() if r["kernels"]]
            assert joined, "no shape row carried a kernel breakdown"
            for r in joined:
                assert r["dominant_kernel"] in KERNEL_FAMILIES
                assert set(r["kernels"]) <= set(KERNEL_FAMILIES)
        finally:
            INSIGHTS.enabled = False
            INSIGHTS.clear()


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


# ----------------------------------------------------- tool satellite

class TestKernelReportTool:
    def _tool(self):
        import os
        import sys
        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        if tools not in sys.path:
            sys.path.insert(0, tools)
        import kernel_report
        return kernel_report

    def _snapshot_doc(self):
        return {"kernels": {
            "enabled": True, "sample_every": 1,
            "peak_flops": 1.0e12, "peak_bw": 1.0e11,
            "ridge_intensity": 10.0,
            "census": {"entries": 2, "dropped": 0,
                       "compile_ms_total": 12.5,
                       "executables": [
                           {"family": "bm25_dense", "shape": "b8/k10",
                            "fingerprint": "aa" * 4, "compile_ms": 10.0,
                            "flops": 1.0e9, "bytes": 1.0e7,
                            "cost_source": "xla"},
                           {"family": "expand", "shape": "64x32",
                            "fingerprint": "bb" * 4, "compile_ms": 2.5,
                            "flops": 2.0e3, "bytes": 8.0e6,
                            "cost_source": "analytic"}]},
            "families": {
                "bm25_dense": {
                    "compiles": 1, "compile_ms": 10.0, "flops": 1.0e9,
                    "bytes": 1.0e7, "arithmetic_intensity": 100.0,
                    "bound": "compute", "calls": 10, "sampled": 10,
                    "sampled_ms": 5.0, "device_ms_est": 5.0,
                    "p50_ms": 0.5, "p99_ms": 0.6, "shapes": {}},
                "expand": {
                    "compiles": 1, "compile_ms": 2.5, "flops": 2.0e3,
                    "bytes": 8.0e6, "arithmetic_intensity": 0.0003,
                    "bound": "memory", "calls": 0, "sampled": 0,
                    "sampled_ms": 0.0}}}}

    def test_report_over_snapshot(self, tmp_path, capsys):
        kr = self._tool()
        path = tmp_path / "KERNELS.json"
        path.write_text(json.dumps(self._snapshot_doc()))
        assert kr.main(["kernel_report.py", str(path)]) == 0
        out = capsys.readouterr().out
        assert "2 kernel families" in out
        # device-ms sort: the timed family ranks above the census-only
        assert out.index("bm25_dense") < out.index("expand")
        assert "ridge intensity" in out and "compute" in out
        assert "aaaaaaaa" in out    # census fingerprint column

    def test_assert_families_gate(self, tmp_path, capsys):
        kr = self._tool()
        path = tmp_path / "KERNELS.json"
        path.write_text(json.dumps(self._snapshot_doc()))
        assert kr.main(["kernel_report.py", "--assert-families", "3",
                        str(path)]) == 1
        assert "FAIL" in capsys.readouterr().out

    def test_bench_rows_upconvert(self, tmp_path, capsys):
        kr = self._tool()
        path = tmp_path / "BENCH_KERNELS_r99.json"
        rows = [
            {"mode": "kernels_bm25_bm25_dense", "bench": "bm25",
             "family": "bm25_dense", "calls": 12, "device_ms": 8.0,
             "p50_ms": 0.7, "p99_ms": 0.9, "compiles": 1,
             "compile_ms": 11.0, "flops": 1e9, "bytes": 1e7,
             "arithmetic_intensity": 100.0, "bound": "compute"},
            {"metric": "kernels_profile_cpu", "benches": 1}]
        path.write_text("\n".join(json.dumps(r) for r in rows))
        assert kr.main(["kernel_report.py", str(path)]) == 0
        assert "bm25/bm25_dense" in capsys.readouterr().out

    def test_no_block_found(self, tmp_path, capsys):
        kr = self._tool()
        path = tmp_path / "empty.json"
        path.write_text('{"unrelated": 1}')
        assert kr.main(["kernel_report.py", str(path)]) == 1
        assert "no kernel-profiler block" in capsys.readouterr().out
