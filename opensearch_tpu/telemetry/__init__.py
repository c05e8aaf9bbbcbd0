"""Node-wide telemetry: request-scoped tracing + the metrics registry.

The analog of the reference's `libs/telemetry` (TracerFactory +
MetricsRegistry behind the OTel plugin), reduced to what a single-process
node needs: one `TELEMETRY` singleton (the same pattern as
`REQUEST_CACHE` / `WARMUP`) holding

  - `TELEMETRY.tracer`  — request-scoped spans over the search path
    (rest → parse → can_match → per-shard query/device dispatch →
    reduce → fetch → pipeline processors), ring-buffered and dumpable
    via `GET /_telemetry/traces`; OFF by default, a no-op on the hot
    path until enabled;
  - `TELEMETRY.metrics` — always-on named counters and fixed-bucket
    latency histograms (each carrying a rolling live-percentile
    estimator, telemetry/rolling.py) surfaced as the `telemetry`
    section of `GET /_nodes/stats`;
  - `TELEMETRY.ledger` — the transfer ledger (telemetry/ledger.py):
    per-channel host↔device byte/round-trip attribution on the query
    path, OFF by default with the tracer's no-op discipline, served by
    `GET /_telemetry/transfers`;
  - `TELEMETRY.device_memory` — live-bytes gauges per device-memory
    class (corpus columns, interned bundles, in-flight wave buffers,
    ...) plus raw backend `memory_stats()` — the HBM analog of the
    reference's JVM mem stats on `_nodes/stats`;
  - `TELEMETRY.flight` — the request-lifecycle flight recorder
    (telemetry/lifecycle.py): per-request arrive/admit/queue_wait/
    coalesce/dispatch/collect/respond timelines with SLO-breach tail
    capture, OFF by default with the same no-op gate discipline, served
    by `GET /_telemetry/tail`.

Node wires it from settings (`telemetry.tracing.enabled`,
`telemetry.tracing.ring_size`, `telemetry.tracing.jsonl`,
`telemetry.transfers.enabled`, `telemetry.tail.enabled`,
`telemetry.tail.threshold_ms`) and the data dir (`_state/traces.jsonl`,
`_state/tail.jsonl`); tests drive it directly.
"""

from __future__ import annotations

import os
from typing import Optional

from opensearch_tpu.telemetry.ledger import (
    ChurnLedger, ChurnScope, DeviceLedger, DeviceMemoryAccounting,
    DeviceScope, LedgerScope, TransferLedger)
from opensearch_tpu.telemetry.lifecycle import (
    INGEST_EVENTS, FlightRecorder, IngestEventLog, IngestRecorder,
    SpmdTimeline, Timeline)
from opensearch_tpu.telemetry.insights import INSIGHTS, QueryInsights
from opensearch_tpu.telemetry.kernels import KERNELS, KernelProfiler
from opensearch_tpu.telemetry.metrics import MetricsRegistry
from opensearch_tpu.telemetry.rolling import RollingEstimator
from opensearch_tpu.telemetry.scan import SCAN, ScanAccounting
from opensearch_tpu.telemetry.tracer import (
    DEFAULT_RING_SIZE, NOOP_SPAN, Span, Tracer)

__all__ = ["TELEMETRY", "TelemetryService", "Span", "NOOP_SPAN",
           "MetricsRegistry", "Tracer", "TransferLedger", "LedgerScope",
           "DeviceMemoryAccounting", "RollingEstimator",
           "FlightRecorder", "Timeline", "IngestRecorder",
           "IngestEventLog", "INGEST_EVENTS", "ChurnLedger",
           "ChurnScope", "DeviceLedger", "DeviceScope", "SpmdTimeline",
           "ScanAccounting", "SCAN", "QueryInsights", "INSIGHTS",
           "KernelProfiler", "KERNELS"]


class TelemetryService:
    """Tracer + metrics + transfer ledger + device-memory accounting +
    lifecycle flight recorder under one configuration surface."""

    def __init__(self):
        self.tracer = Tracer()
        self.metrics = MetricsRegistry()
        # the collections `tracer.gc` counts as plain ints (its callback
        # may take no lock), among the counters of `_nodes/stats`
        gc_spans = self.tracer.gc
        self.metrics.publish("process.gc.collections.gen2",
                             lambda: gc_spans.full)
        self.metrics.publish("process.gc.pause_us",
                             lambda: gc_spans.pause_ns // 1000)
        self.ledger = TransferLedger()
        self.device_memory = DeviceMemoryAccounting()
        self.flight = FlightRecorder()
        # write-path observability (ISSUE 13): ingest lifecycle recorder
        # + segment-churn ledger, both OFF by default behind
        # None-returning gates; the always-on engine event log rides the
        # lifecycle module singleton (INGEST_EVENTS)
        self.ingest = IngestRecorder()
        self.churn = ChurnLedger()
        # sharded-serving observability (ISSUE 14): the per-device
        # ledger rides the transfer ledger (its `device` dimension);
        # the SPMD collective-phase timeline emitter is its own gate;
        # the scan counters are ALWAYS-ON (the block-max trigger metric
        # — inflight-wave-gauge contract, not the per-request gate
        # discipline)
        self.device_ledger = self.ledger.devices
        self.spmd_timeline = SpmdTimeline()
        self.scan = SCAN
        # query insights (ISSUE 15): per-shape cost attribution + the
        # heavy-query top-N registry, OFF by default behind a
        # None-returning gate() — the "which queries cost what" join
        # over interning + lifecycle + scan + ledger
        self.insights = INSIGHTS
        # kernel census (ISSUE 19): executable records written at
        # compile time only + roofline classification per kernel family
        self.kernels = KERNELS

    def configure(self, data_path: Optional[str] = None,
                  enabled: bool = False, jsonl: bool = False,
                  ring_size: int = DEFAULT_RING_SIZE,
                  transfers: bool = False, tail: bool = False,
                  tail_threshold_ms: Optional[float] = None,
                  ingest: bool = False, churn: bool = False,
                  devices: bool = False,
                  spmd_timeline: bool = False,
                  insights: bool = False,
                  kernels_peak_flops: Optional[float] = None,
                  kernels_peak_bw: Optional[float] = None) -> None:
        """Bind to a node's settings/data dir. Called from Node.__init__;
        re-configuration by a later Node in the same process wins (the
        singleton is process-wide, like WARMUP)."""
        self.tracer.enabled = bool(enabled)
        self.ledger.enabled = bool(transfers)
        self.flight.enabled = bool(tail)
        self.flight.threshold_ms = tail_threshold_ms
        self.ingest.enabled = bool(ingest)
        self.churn.enabled = bool(churn)
        self.device_ledger.enabled = bool(devices)
        self.spmd_timeline.enabled = bool(spmd_timeline)
        self.insights.enabled = bool(insights)
        if kernels_peak_flops is not None:
            self.kernels.peak_flops = float(kernels_peak_flops)
        if kernels_peak_bw is not None:
            self.kernels.peak_bw = float(kernels_peak_bw)
        self.tracer.resize(ring_size)
        # the heap's collections go onto the span ring's process track
        # from here on (one `gc.callbacks` entry a process)
        self.tracer.gc.install()
        self.tracer.jsonl_path = None
        self.flight.jsonl_path = None
        if jsonl and data_path is not None:
            state_dir = os.path.join(data_path, "_state")
            try:
                os.makedirs(state_dir, exist_ok=True)
                self.tracer.jsonl_path = os.path.join(state_dir,
                                                      "traces.jsonl")
                self.flight.jsonl_path = os.path.join(state_dir,
                                                      "tail.jsonl")
            except OSError:
                pass

    def enable(self) -> None:
        self.tracer.enabled = True

    def disable(self) -> None:
        self.tracer.enabled = False

    def stats(self) -> dict:
        return {"tracing": self.tracer.stats(),
                "metrics": self.metrics.to_dict(),
                "transfers": self.ledger.snapshot(),
                "device_memory": self.device_memory.stats(),
                "tail": self.flight.stats(),
                # the write-path block (ISSUE 13): ingest lifecycle +
                # engine event log + segment-churn attribution
                "indexing": {"ingest": self.ingest.stats(),
                             "churn": self.churn.snapshot()},
                # sharded-serving observability (ISSUE 14): per-chip
                # attribution + the always-on scanned-bytes heat map
                # (the block-max trigger metric, live)
                "devices": self.device_ledger.snapshot(),
                "scan": self.scan.stats(),
                # query insights (ISSUE 15): per-shape cost attribution
                # (the top-N rings ride GET /_insights, not this block)
                "insights": self.insights.snapshot(),
                # kernel profiler (ISSUE 19): executable census +
                # per-family device-ms/roofline (compact — the full
                # census dump rides GET /_telemetry/kernels)
                "kernels": self.kernels.stats()}


# process-wide singleton, like REQUEST_CACHE / QUERY_CACHE / WARMUP
TELEMETRY = TelemetryService()
