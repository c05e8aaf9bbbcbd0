"""Sharded-serving observability tests (ISSUE 14).

The load-bearing property is per-device CONSERVATION: for every
(channel, direction), the sum of the per-device ledger table's bytes
must equal the transfer ledger's channel total — across msearch batch
sizes B ∈ {1, 32, 1024} on the envelope path (everything attributes to
DEFAULT_DEVICE: the host loop talks to exactly one chip) and mesh
sizes D ∈ {1, 2, 4} on the SPMD path (the sharded uploads split
exactly over the mesh). Also pinned: the instrumentation-off path is
byte-identical (differential, the PR 13 method), the per-chip phase
capture (partials per device, skew, analytic collective bytes), the
SPMD timeline's fanout/partial/merge events, the Profile API's
per-device shard entry, the always-on scan counters' exact agreement
with the offline posting-block formula, and the per-tenant usage
split.
"""

import json

import numpy as np
import pytest

from opensearch_tpu.parallel import DistributedSearcher, make_mesh
from opensearch_tpu.search.executor import SearchExecutor, ShardReader
from opensearch_tpu.telemetry import TELEMETRY, DeviceScope, Timeline
from opensearch_tpu.telemetry.ledger import DEFAULT_DEVICE, DeviceLedger
from opensearch_tpu.utils.demo import build_shards, query_terms

N_DOCS = 400
VOCAB = 300


@pytest.fixture(autouse=True)
def _clean_telemetry():
    def _reset():
        TELEMETRY.ledger.enabled = False
        TELEMETRY.ledger.reset()
        TELEMETRY.device_ledger.enabled = False
        TELEMETRY.device_ledger.reset()
        TELEMETRY.spmd_timeline.enabled = False
        TELEMETRY.flight.enabled = False
        TELEMETRY.flight.clear()
    _reset()
    yield
    _reset()


@pytest.fixture(scope="module")
def ex():
    mapper, segments = build_shards(N_DOCS, n_shards=1, vocab_size=VOCAB,
                                    avg_len=30, seed=42)
    return SearchExecutor(ShardReader(mapper, segments))


@pytest.fixture(scope="module")
def sharded():
    mapper, segments = build_shards(800, n_shards=4, vocab_size=VOCAB,
                                    avg_len=30, seed=11)
    readers = [ShardReader(mapper, [s], index_name="dv")
               for s in segments]
    return mapper, [SearchExecutor(r) for r in readers]


def _bodies(n, seed=7):
    return [{"query": {"match": {"body": q}}, "size": 5}
            for q in query_terms(n, VOCAB, seed=seed, terms_per_query=2)]


def _assert_conserves(ledger):
    """Per (channel, direction): per-device bytes sum == channel total."""
    snap = ledger.snapshot()
    per_dev = ledger.devices.device_bytes()
    for direction in ("h2d", "d2h"):
        for channel, ent in snap["channels"][direction].items():
            dev_sum = sum(
                chans.get(channel, {}).get(direction, 0)
                for chans in per_dev.values())
            assert dev_sum == ent["bytes"], \
                (channel, direction, dev_sum, ent["bytes"])


# --------------------------------------------------------------- conservation

class TestDeviceConservation:
    @pytest.mark.parametrize("b", [1, 32, 1024])
    def test_envelope_per_device_sums_to_channel_totals(self, ex, b):
        """Envelope path, B in {1, 32, 1024}: every channel's bytes
        land on DEFAULT_DEVICE and the table conserves exactly."""
        ex.multi_search(_bodies(b), _bypass_request_cache=True)  # warm
        TELEMETRY.ledger.enabled = True
        TELEMETRY.device_ledger.enabled = True
        TELEMETRY.ledger.reset()
        TELEMETRY.device_ledger.reset()
        ex.multi_search(_bodies(b), _bypass_request_cache=True)
        snap = TELEMETRY.ledger.snapshot()
        assert snap["bytes_total"]["d2h"] > 0
        _assert_conserves(TELEMETRY.ledger)
        per_dev = TELEMETRY.ledger.devices.device_bytes()
        assert set(per_dev) == {DEFAULT_DEVICE}

    @pytest.mark.parametrize("n_dev", [1, 2, 4])
    def test_spmd_per_device_sums_to_channel_totals(
            self, sharded, eight_devices, n_dev):
        """SPMD path, D in {1, 2, 4}: the sharded corpus/literal
        uploads split exactly over the mesh and still conserve."""
        from opensearch_tpu.ops.device_segment import upload_segment
        from opensearch_tpu.search import dsl
        from opensearch_tpu.search.compile import Compiler, ShardStats

        mapper, exs = sharded
        segments = [e.reader.segments[0] for e in exs]
        stats = ShardStats(segments)
        compiler = Compiler(mapper, stats)
        node = dsl.parse_query({"match": {"body": "w00003 w00007"}})
        payloads, plan = [], None
        for seg in segments:
            arrays, meta = upload_segment(seg, to_device=False)
            p = compiler.compile(node, seg, meta)
            plan = plan or p
            payloads.append((arrays, p.flatten_inputs([]), meta))

        TELEMETRY.ledger.enabled = True
        TELEMETRY.device_ledger.enabled = True
        TELEMETRY.ledger.reset()
        TELEMETRY.device_ledger.reset()
        searcher = DistributedSearcher(make_mesh(n_dev))
        searcher.search(payloads, plan, k=10)
        snap = TELEMETRY.ledger.snapshot()
        assert snap["channels"]["h2d"]["upload.corpus"]["bytes"] > 0
        assert snap["channels"]["d2h"]["spmd.results"]["bytes"] > 0
        _assert_conserves(TELEMETRY.ledger)
        per_dev = TELEMETRY.ledger.devices.device_bytes()
        # the corpus upload must actually SPREAD over a multi-chip mesh
        corpus_devs = [d for d, chans in per_dev.items()
                       if chans.get("upload.corpus", {}).get("h2d", 0)]
        assert len(corpus_devs) == n_dev


# --------------------------------------------------- off-path differential

class TestDisabledPath:
    def test_gates_return_none_when_disabled(self):
        assert TELEMETRY.device_ledger.enabled is False
        assert TELEMETRY.device_ledger.scope() is None
        assert TELEMETRY.spmd_timeline.enabled is False
        assert TELEMETRY.spmd_timeline.gate() is None

    def test_off_path_byte_identical_and_table_untouched(self, sharded):
        """Differential (the PR 13 method): responses with the device
        ledger ON equal the responses with it OFF byte-for-byte, and
        the OFF run leaves the per-device table empty."""
        from opensearch_tpu.search.controller import execute_search

        mapper, exs = sharded
        body = {"query": {"match": {"body": "w00003 w00007"}},
                "size": 10}

        def _run():
            out = execute_search(exs, dict(body))
            out.pop("took", None)
            return json.dumps(out, sort_keys=True, default=str)

        off = _run()
        assert TELEMETRY.ledger.devices.device_bytes() == {}
        TELEMETRY.ledger.enabled = True
        TELEMETRY.device_ledger.enabled = True
        on = _run()
        assert on == off
        TELEMETRY.ledger.enabled = False
        TELEMETRY.device_ledger.enabled = False
        off2 = _run()
        assert off2 == off


# ----------------------------------------------------- phase capture / skew

class TestPhaseCapture:
    def test_spmd_capture_fills_partials_and_skew(
            self, sharded, eight_devices):
        from opensearch_tpu.search.controller import execute_search

        mapper, exs = sharded
        TELEMETRY.ledger.enabled = True
        TELEMETRY.device_ledger.enabled = True
        body = {"query": {"match": {"body": "w00003 w00007"}}, "size": 5}
        execute_search(exs, body)       # warm (compile excluded anyway)
        TELEMETRY.device_ledger.reset()
        execute_search(exs, body)
        snap = TELEMETRY.device_ledger.snapshot()
        assert snap["queries"] == 1
        # 4 rows over >=4 virtual devices: one partial per chip
        assert len(snap["devices"]) == 4
        for ent in snap["devices"].values():
            assert ent["queries"] == 1
            assert ent["partial_ms"] >= 0
        assert snap["collective"]["ici_bytes_per_query"] > 0
        assert snap["rolling"]["straggler_skew_ms"]["count"] == 1

    def test_device_scope_skew_math(self):
        sc = DeviceScope()
        sc.partials = [(0, 1.0), (1, 2.0), (2, 9.0), (3, 3.0)]
        # sorted walls [1,2,3,9]: LOWER median index 1 -> 2.0; max 9.0
        assert sc.skew_ms() == pytest.approx(7.0)
        assert sc.straggler() == 2
        assert sc.to_dict()["straggler_skew_ms"] == pytest.approx(7.0)

    def test_two_chip_skew_not_structurally_zero(self):
        # upper-median regression: on a 2-chip mesh the median must be
        # the MIN, else skew is identically 0 and the gate is blind
        sc = DeviceScope()
        sc.partials = [(0, 5.0), (1, 50.0)]
        assert sc.skew_ms() == pytest.approx(45.0)
        assert sc.straggler() == 1

    def test_profile_entry_carries_devices_block(self, sharded):
        from opensearch_tpu.search.controller import execute_search

        mapper, exs = sharded
        TELEMETRY.device_ledger.enabled = True
        out = execute_search(exs, {
            "query": {"match": {"body": "w00003"}}, "size": 5,
            "profile": True})
        shards = out["profile"]["shards"]
        assert shards and "[spmd]" in shards[0]["id"]
        dev = shards[0]["devices"]
        assert dev["devices"] >= 1 and dev["rows"] == 4
        assert len(dev["partials"]) >= 1
        assert dev["collective"]["ici_bytes"] >= 0

    def test_timeline_fanout_partial_merge_events(self, sharded):
        from opensearch_tpu.search.controller import execute_search

        mapper, exs = sharded
        TELEMETRY.flight.enabled = True
        TELEMETRY.spmd_timeline.enabled = True
        tl = Timeline()
        prev = TELEMETRY.flight.bind(tl)
        try:
            execute_search(exs, {"query": {"match": {"body": "w00003"}},
                                 "size": 5})
        finally:
            TELEMETRY.flight.unbind(prev)
        names = [e[0] for e in tl.events]
        assert "fanout" in names
        assert "partial" in names
        assert "merge" in names
        fanout = next(f for n, _, f in tl.events if n == "fanout")
        assert fanout["rows"] == 4
        merge = next(f for n, _, f in tl.events if n == "merge")
        assert "skew_ms" in merge and "ici_bytes" in merge
        partials = [f for n, _, f in tl.events if n == "partial"]
        assert len(partials) >= 1
        assert all("device" in p and "ms" in p for p in partials)

    def test_tail_report_renders_device_groups(self, sharded):
        from tools.tail_report import device_groups

        records = [{
            "took_ms": 12.0,
            "events": [
                {"event": "partial", "device": 0, "ms": 3.0},
                {"event": "partial", "device": 1, "ms": 9.0},
                {"event": "merge", "skew_ms": 6.0, "straggler": 1,
                 "ici_bytes": 960},
            ]}]
        groups = device_groups(records)
        assert groups["1"]["straggler_hits"] == 1
        assert groups["0"]["wall_p50_ms"] == 3.0
        assert groups["_skew"]["wall_p50_ms"] == 6.0


# ------------------------------------------------------- device memory dim

def test_shard_set_registers_per_device_memory(sharded, eight_devices):
    from opensearch_tpu.search.controller import execute_search

    mapper, exs = sharded
    execute_search(exs, {"query": {"match": {"body": "w00005"}},
                         "size": 5})
    classes = TELEMETRY.device_memory.stats()["classes"]
    ent = classes.get("spmd_shard_sets")
    assert ent and ent["live_bytes"] > 0
    by_dev = ent.get("by_device")
    assert by_dev and sum(by_dev.values()) == ent["live_bytes"]
    assert len(by_dev) == 4     # one share per mesh device (4 rows)


# ------------------------------------------------------------- tenant usage

def test_scheduler_splits_wave_wall_across_tenants():
    from opensearch_tpu.common.admission import AdmissionController
    from opensearch_tpu.search.scheduler import WaveScheduler

    ctrl = AdmissionController()

    class _Target:
        def multi_search(self, bodies, deadline=None, timelines=None,
                         phase_times=None, tenants=None):
            import time
            time.sleep(0.02)    # a measurable shared-wave wall
            return {"responses": [{} for _ in bodies]}

    sched = WaveScheduler(admission=ctrl, autostart=False)
    tl_a, tl_b = Timeline(), Timeline()
    # two tenants, 1 + 3 bodies, dispatched as ONE shared wave
    from opensearch_tpu.search.scheduler import _SchedItem
    target = _Target()
    it_a = _SchedItem(target, [{"q": 1}], None, tl_a, "acme", None, 0.0)
    it_b = _SchedItem(target, [{"q": 2}] * 3, None, tl_b, "globex", None,
                      0.0)
    sched._dispatch_group([it_a, it_b])
    usage = ctrl.usage()
    assert set(usage) == {"acme", "globex"}
    assert usage["acme"]["items"] == 1
    assert usage["globex"]["items"] == 3
    # proportional: globex carries 3x acme's share of the same wall
    # (compared on the unrounded timeline fields; the stats block
    # rounds to 3 decimals)
    assert usage["globex"]["device_ms"] == pytest.approx(
        3 * usage["acme"]["device_ms"], rel=0.05)
    assert tl_a.device_share_ms > 0
    assert tl_b.device_share_ms == pytest.approx(
        3 * tl_a.device_share_ms, rel=0.01)
    ev = next(f for n, _, f in tl_a.events if n == "device_share")
    assert ev["co_batched"] == 4
    # the lifecycle dict surfaces the field
    assert "device_share_ms" in tl_a.to_dict()


# ------------------------------------------------------------------- scan

class TestScanAccounting:
    def test_envelope_matches_offline_posting_formula(self, ex):
        """The live counter must agree EXACTLY with the formula computed
        from term metadata: sum over query terms of
        num_blocks x 128 lanes x 8 B."""
        seg = ex.reader.segments[0]
        q = "w00003 w00007"
        want = 0
        for t in q.split():
            tm = seg.get_term("body", t)
            if tm is not None:
                want += tm.num_blocks * 128 * 8
        assert want > 0
        scan = TELEMETRY.scan
        scan.reset()
        ex.multi_search([{"query": {"match": {"body": q}}, "size": 5}],
                        _bypass_request_cache=True)
        stats = scan.stats()
        assert stats["queries"] == 1
        assert stats["posting_bytes_total"] == want
        # candidate-buffer kernel at this scale: no dense-lane bytes
        assert stats["dense_bytes_total"] == 0
        row = stats["shards"]["_index[0]"]
        assert row["kernels"] == {"candidate": 1}
        assert row["segments"][seg.seg_id]["posting_bytes"] == want

    def test_scan_is_always_on(self, ex):
        """No gate: counters move with every query, all telemetry off."""
        scan = TELEMETRY.scan
        scan.reset()
        assert TELEMETRY.ledger.enabled is False
        ex.multi_search(_bodies(4), _bypass_request_cache=True)
        assert scan.stats()["queries"] == 4

    def test_spmd_path_notes_spmd_kernel(self, sharded):
        from opensearch_tpu.search.controller import execute_search

        mapper, exs = sharded
        scan = TELEMETRY.scan
        scan.reset()
        execute_search(exs, {"query": {"match": {"body": "w00003"}},
                             "size": 5})
        stats = scan.stats()
        assert stats["queries"] == 1
        kernels = set()
        for row in stats["shards"].values():
            kernels |= set(row["kernels"])
        assert kernels == {"spmd"}
        # the SPMD program evaluates the dense per-doc vector per row
        assert stats["dense_bytes_total"] > 0

    def test_host_loop_notes_dense_kernel(self, sharded):
        import opensearch_tpu.search.spmd as spmd_mod
        from opensearch_tpu.search.controller import execute_search

        mapper, exs = sharded
        scan = TELEMETRY.scan
        scan.reset()
        with spmd_mod.force_host_loop():
            execute_search(exs, {"query": {"match": {"body": "w00003"}},
                                 "size": 5})
        stats = scan.stats()
        kernels = set()
        for row in stats["shards"].values():
            kernels |= set(row["kernels"])
        assert kernels == {"dense"}

    def test_nodes_stats_carries_scan_and_devices_blocks(self):
        stats = TELEMETRY.stats()
        assert "scan" in stats and "devices" in stats
        assert "per_query" in stats["scan"]
        assert "rolling" in stats["devices"]


# --------------------------------------------------------------------- REST

def test_rest_devices_endpoints():
    from opensearch_tpu.node import Node

    node = Node()
    out = node.request("GET", "/_telemetry/devices")
    assert "devices" in out and "scan" in out
    on = node.request("POST", "/_telemetry/devices/_enable")
    assert on["enabled"] is True
    assert TELEMETRY.device_ledger.enabled is True
    assert TELEMETRY.spmd_timeline.enabled is True
    off = node.request("POST", "/_telemetry/devices/_disable")
    assert off["enabled"] is False
    node.request("POST", "/_telemetry/devices/_clear")
    assert TELEMETRY.device_ledger.snapshot()["queries"] == 0
