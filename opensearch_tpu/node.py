"""Node: the top-level container wiring services + REST dispatch.

Re-design of the reference Node (node/Node.java:372): constructs the
IndicesService, cluster-level settings, and the RestController with the full
route table (rest/action/*), and exposes `handle()` — the analog of
RestController.dispatchRequest — plus a programmatic client facade.
"""

from __future__ import annotations

import json
import secrets
import time
from typing import Any, Dict, List, Optional

from opensearch_tpu.common.errors import IllegalArgumentError
from opensearch_tpu.indices.service import IndicesService
from opensearch_tpu.rest.controller import (
    RestController, RestRequest, RestResponse)
from opensearch_tpu.version import __version__ as VERSION


class Node:
    def __init__(self, node_name: str = "node-0",
                 cluster_name: str = "opensearch-tpu",
                 data_path: Optional[str] = None,
                 settings: Optional[dict] = None,
                 plugins: Optional[list] = None):
        # plugins install before any service construction so their
        # registry contributions (analyzers, queries, processors,
        # repository types) are visible to everything built below
        # (reference: PluginsService is constructed first, Node.java:432)
        if plugins:
            from opensearch_tpu.plugins import install_plugin
            for plugin in plugins:
                install_plugin(plugin)
        self.node_name = node_name
        self.node_id = secrets.token_urlsafe(16)
        self.cluster_name = cluster_name
        self.settings = settings or {}
        self.start_time_ms = int(time.time() * 1000)
        from opensearch_tpu.ingest.service import IngestService
        from opensearch_tpu.script.service import ScriptService
        from opensearch_tpu.searchpipeline import SearchPipelineService
        self.script_service = ScriptService()
        self.ingest = IngestService()
        self.search_pipelines = SearchPipelineService()
        self.indices = IndicesService(data_path=data_path,
                                      script_service=self.script_service)
        self.cluster_settings: Dict[str, Any] = {"persistent": {},
                                                 "transient": {}}
        self.scroll_contexts: Dict[str, Any] = {}
        self.pit_contexts: Dict[str, Any] = {}
        from opensearch_tpu.repositories import RepositoriesService
        from opensearch_tpu.datastreams import DataStreamService
        from opensearch_tpu.common.breakers import (
            CircuitBreakerService, IndexingPressure, SearchBackpressure)
        from opensearch_tpu.tasks import TaskManager
        path_repo = self.settings.get("path.repo") or []
        if isinstance(path_repo, str):
            path_repo = [path_repo]
        self.repositories = RepositoriesService(path_repo=path_repo)
        self.data_streams = DataStreamService(self)
        self.task_manager = TaskManager()
        from opensearch_tpu.common.threadpool import ThreadPool
        self.threadpool = ThreadPool(self.settings, node_name=node_name)
        self.breaker_service = CircuitBreakerService()
        self.indexing_pressure = IndexingPressure()
        # adaptive admission controller (common/admission.py): quota ->
        # breaker -> deadline-shed -> permits; every adaptive stage OFF
        # by default, configured from node settings here and re-applied
        # on every PUT /_cluster/settings
        from opensearch_tpu.common.settings import Settings as _Settings
        self.search_backpressure = SearchBackpressure()
        self.search_backpressure.apply_settings(
            _Settings(self.settings).as_dict())
        # async wave scheduler (search/scheduler.py): coalesce
        # concurrent independent searches into shared device waves. OFF
        # by default (None-returning gate); `search.scheduler.enabled`
        # node/dynamic cluster setting or POST /_scheduler/_enable
        # turns it on. The admission controller prices deadline sheds
        # against the scheduler's real queue once wired.
        from opensearch_tpu.search.scheduler import WaveScheduler
        self.wave_scheduler = WaveScheduler(
            admission=self.search_backpressure)
        self.search_backpressure.queue_depth_extra = \
            self.wave_scheduler.queue_depth
        self.wave_scheduler.apply_settings(
            _Settings(self.settings).as_dict())
        # off-path shape precompiler (search/warmup.py Precompiler,
        # ISSUE 16): replays the warmup registry on a helper thread
        # whenever a segment publish lands a novel device shape. OFF by
        # default (None-returning gate); `search.precompile.enabled`
        # node/dynamic cluster setting or POST /_warmup/_precompile.
        from opensearch_tpu.search.warmup import PRECOMPILE
        PRECOMPILE.apply_settings(_Settings(self.settings).as_dict())
        # delta segment publish (ops/device_segment.py, ISSUE 16):
        # module-level gate, compact-prefix host→device transfers. A
        # node-level static setting — flipping it mid-flight would split
        # the ledger's byte accounting across two regimes.
        raw_delta = self.settings.get("indices.publish.delta")
        if raw_delta is not None:
            from opensearch_tpu.common.settings import \
                _parse_bool as _pb
            from opensearch_tpu.ops import device_segment as _devseg
            _devseg.DELTA_PUBLISH = _pb(raw_delta,
                                        "indices.publish.delta")
        # single-round-trip result page (search/executor.py, ISSUE 17):
        # module-level gate, the whole result-assembly tail (cross-
        # segment merge, sort-key extraction, fused docvalue gather)
        # runs on device and one `device_get` lands the wave. A static
        # node setting — flipping it mid-flight would split the ledger's
        # round-trip accounting across two regimes.
        raw_page = self.settings.get("search.result_page.enabled")
        if raw_page is not None:
            from opensearch_tpu.common.settings import \
                _parse_bool as _pb
            from opensearch_tpu.search import executor as _executor_mod
            _executor_mod.RESULT_PAGE = _pb(raw_page,
                                            "search.result_page.enabled")
        # block-max pruning (ops/bm25.py, ISSUE 20): module-level gate;
        # the compiler emits tid/bscale plan inputs and the candidate /
        # SPMD kernels mask non-competitive posting blocks. OFF by
        # default; node setting here, dynamic via PUT /_cluster/settings
        # (apply_admission_settings re-applies it — compiled plans memo
        # on the gate value, so a flip recompiles rather than mis-serves)
        raw_bm = self.settings.get("search.blockmax.enabled")
        if raw_bm is not None:
            from opensearch_tpu.common.settings import \
                _parse_bool as _pb
            from opensearch_tpu.ops import bm25 as _bm25_mod
            _bm25_mod.BLOCKMAX = _pb(raw_bm, "search.blockmax.enabled")
        self.gateway = None
        if data_path is not None:
            from opensearch_tpu.gateway import Gateway
            self.gateway = Gateway(data_path)
            loaded = self.gateway.load(self.indices)
            if loaded and loaded.get("cluster_settings"):
                self.cluster_settings.update(loaded["cluster_settings"])
                self.apply_admission_settings()
            if loaded and loaded.get("search_pipelines"):
                self.search_pipelines.load(loaded["search_pipelines"])
        # executable warmup (search/warmup.py): every node keeps XLA's
        # persistent compilation cache at one fixed place; a node with a
        # data dir also loads its persisted (plan-struct, shape-bucket)
        # registry and AOT-compiles the registered executables for any
        # gateway-restored indices BEFORE the first query can hit the
        # cold-compile cliff
        from opensearch_tpu.search.warmup import (WARMUP,
                                                  configure_compile_cache)
        configure_compile_cache()
        if data_path is not None:
            WARMUP.configure(data_path)
            WARMUP.default_budget_s = float(self.settings.get(
                "search.warmup.budget_ms", 10000)) / 1000.0
            WARMUP.warm_on_open = bool(self.settings.get(
                "search.warmup_on_open", True))
            if self.settings.get("search.warmup_at_start", True) \
                    and self.indices.indices:
                WARMUP.warm_all(self.indices,
                                budget_s=WARMUP.default_budget_s)
        # telemetry (opensearch_tpu/telemetry): tracing is OFF by default
        # — the tracer is a no-op until telemetry.tracing.enabled (or a
        # runtime POST /_telemetry/_enable) turns it on; the metrics
        # registry is always on. JSONL trace export lands under the data
        # dir's _state/ next to the warmup registry.
        from opensearch_tpu.common.settings import _parse_bool
        from opensearch_tpu.telemetry import TELEMETRY

        def _tel_bool(key: str) -> bool:
            raw = self.settings.get(key)
            # strict boolean parse, same contract as every other boolean
            # setting (a typo'd value fails node start, never silently
            # disables tracing)
            return False if raw is None else _parse_bool(raw, key)

        def _tel_float(key: str):
            raw = self.settings.get(key)
            return None if raw is None else float(raw)

        _tail_thr = self.settings.get("telemetry.tail.threshold_ms")
        TELEMETRY.configure(
            data_path=data_path,
            enabled=_tel_bool("telemetry.tracing.enabled"),
            jsonl=_tel_bool("telemetry.tracing.jsonl"),
            ring_size=int(self.settings.get("telemetry.tracing.ring_size",
                                            256)),
            transfers=_tel_bool("telemetry.transfers.enabled"),
            tail=_tel_bool("telemetry.tail.enabled"),
            tail_threshold_ms=None if _tail_thr is None
            else float(_tail_thr),
            # write-path observability (ISSUE 13): ingest lifecycle
            # recorder + segment-churn ledger, OFF by default like the
            # tracer/ledger/flight gates
            ingest=_tel_bool("telemetry.ingest.enabled"),
            churn=_tel_bool("telemetry.churn.enabled"),
            # sharded-serving observability (ISSUE 14): per-device
            # ledger + SPMD collective-phase timeline, OFF by default
            # like every other gate (the scan counters are always-on
            # and take no setting)
            devices=_tel_bool("telemetry.devices.enabled"),
            spmd_timeline=_tel_bool("telemetry.spmd_timeline.enabled"),
            # query insights (ISSUE 15): per-shape cost attribution +
            # top-N heavy-query registry, OFF by default like every
            # other gate (POST /_insights/_enable at runtime)
            insights=_tel_bool("telemetry.insights.enabled"),
            # kernel census (ISSUE 19): always on, no gate; the
            # roofline peaks are plain floats that override the
            # attached device's row of DEVICE_PEAKS
            kernels_peak_flops=_tel_float(
                "telemetry.kernels.peak_flops"),
            kernels_peak_bw=_tel_float("telemetry.kernels.peak_bw"))
        self.controller = RestController()
        from opensearch_tpu.rest.actions import register_all
        register_all(self)

    def apply_admission_settings(self):
        """Re-apply the admission controller's settings from the live
        cluster-settings store (persistent first, transient wins — the
        standard precedence) on top of the node's static settings."""
        from opensearch_tpu.common.settings import Settings
        merged = Settings(self.settings).as_dict()
        merged.update(
            Settings(self.cluster_settings.get("persistent") or {})
            .as_dict())
        merged.update(
            Settings(self.cluster_settings.get("transient") or {})
            .as_dict())
        self.search_backpressure.apply_settings(merged)
        self.wave_scheduler.apply_settings(merged)
        from opensearch_tpu.search.warmup import PRECOMPILE
        PRECOMPILE.apply_settings(merged)
        # dynamic block-max gate (ISSUE 20): plan memo keys include the
        # gate value, so flipped settings produce fresh plans/programs
        # instead of reusing a mismatched trace
        raw_bm = merged.get("search.blockmax.enabled")
        if raw_bm is not None:
            from opensearch_tpu.common.settings import _parse_bool
            from opensearch_tpu.ops import bm25 as _bm25_mod
            _bm25_mod.BLOCKMAX = _parse_bool(raw_bm,
                                             "search.blockmax.enabled")

    def persist_metadata(self):
        """Write node metadata through the gateway (no-op without a data
        path — pure in-memory node)."""
        if self.gateway is not None:
            self.gateway.persist(self.indices, self.cluster_settings,
                                 search_pipelines=self.search_pipelines
                                 .to_dict())
            from opensearch_tpu.search.warmup import WARMUP
            WARMUP.flush()

    # ------------------------------------------------------------- dispatch

    def handle(self, method: str, path: str,
               params: Optional[Dict[str, str]] = None,
               body: Any = None,
               raw_body: Optional[bytes] = None,
               headers: Optional[Dict[str, str]] = None) -> RestResponse:
        """Entry point for both the HTTP server and in-process tests."""
        if isinstance(body, (str, bytes)) and body:
            raw_body = body if isinstance(body, bytes) else body.encode()
            try:
                body = json.loads(body)
            except (json.JSONDecodeError, UnicodeDecodeError):
                body = None
        req = RestRequest(method=method.upper(), path=path,
                          params=dict(params or {}), body=body,
                          raw_body=raw_body, headers=dict(headers or {}))
        return self.controller.dispatch(req)

    # -------------------------------------------------- convenience client

    def request(self, method: str, path: str, body: Any = None,
                **params) -> dict:
        """Like handle() but raises nothing and returns the parsed body —
        the shape tests use."""
        resp = self.handle(method, path, params={k: str(v)
                                                 for k, v in params.items()},
                           body=body)
        if isinstance(resp.body, str):
            return {"_raw": resp.body, "_status": resp.status}
        out = resp.body if isinstance(resp.body, dict) else {"_body": resp.body}
        out = dict(out)
        out["_status"] = resp.status
        return out

    # ----------------------------------------------------------- cluster info

    def root_info(self) -> dict:
        return {
            "name": self.node_name,
            "cluster_name": self.cluster_name,
            "cluster_uuid": self.node_id,
            "version": {
                "distribution": "opensearch-tpu",
                "number": VERSION,
                "build_type": "source",
                "minimum_wire_compatibility_version": VERSION,
                "minimum_index_compatibility_version": VERSION,
            },
            "tagline": "The OpenSearch-TPU Project: search at MXU speed",
        }

    def cluster_health(self, index: Optional[str] = None) -> dict:
        names = (self.indices.resolve(index) if index
                 else list(self.indices.indices))
        total_shards = sum(self.indices.indices[n].num_shards for n in names)
        return {
            "cluster_name": self.cluster_name,
            "status": "green",
            "timed_out": False,
            "number_of_nodes": 1,
            "number_of_data_nodes": 1,
            "discovered_cluster_manager": True,
            "active_primary_shards": total_shards,
            "active_shards": total_shards,
            "relocating_shards": 0,
            "initializing_shards": 0,
            "unassigned_shards": 0,
            "delayed_unassigned_shards": 0,
            "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0,
            "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number": 100.0,
        }
