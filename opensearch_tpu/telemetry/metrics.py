"""Metrics registry: named counters + fixed-bucket latency histograms.

Re-design of the reference telemetry metrics surface (libs/telemetry
MetricsRegistry + the OTel plugin's DefaultMetricsRegistry): producers
grab a counter or histogram by name and record; the registry renders one
JSON-able snapshot for `GET /_nodes/stats` (the `telemetry` section).

Histograms use FIXED bucket boundaries (milliseconds) shared node-wide,
so p50/p99 are estimated by linear interpolation inside the winning
bucket — the same fidelity/overhead trade the reference's explicit-bucket
histograms make. Recording is always-on (like the request-cache hit/miss
counters): one dict lookup + a few float ops per observation, cheap
enough to sit under the query path whether or not tracing is enabled.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Tuple

from opensearch_tpu.telemetry.rolling import RollingEstimator

# default latency buckets (upper bounds, milliseconds): sub-ms resolution
# for warmed device queries up to the multi-second compile cliff
DEFAULT_BUCKETS_MS: Tuple[float, ...] = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0)


class Counter:
    """A monotonically increasing named count. Lock-guarded: `value +=`
    is a read-modify-write the interpreter can interleave, and the
    concurrent-clients serving path (ROADMAP item 2) drives these from
    N threads — a drifting counter reads as a lost
    request (tests/test_rolling_concurrent.py pins exactness)."""

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += n


class Histogram:
    """Fixed-bucket latency histogram (values in milliseconds).

    Every histogram also feeds a rolling live-percentile estimator
    (telemetry/rolling.py): the fixed buckets keep the since-start
    distribution, `rolling` answers "what is the p99 RIGHT NOW" in O(1)
    — the read the wave scheduler (ROADMAP item 2) budgets against."""

    __slots__ = ("name", "buckets", "counts", "count", "sum", "min",
                 "max", "rolling", "_lock")

    def __init__(self, name: str,
                 buckets: Optional[Tuple[float, ...]] = None):
        self.name = name
        self.buckets: Tuple[float, ...] = tuple(buckets or
                                                DEFAULT_BUCKETS_MS)
        self.counts: List[int] = [0] * (len(self.buckets) + 1)  # +overflow
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.rolling = RollingEstimator()
        # concurrent writers (the N-client serving path) must not lose
        # observations: count/sum are read-modify-write races unguarded.
        # Reads (percentile/to_dict) stay lock-free — estimates tolerate
        # a torn snapshot, the ingest path does not.
        self._lock = threading.Lock()

    def observe(self, value_ms: float) -> None:
        i = 0
        n = len(self.buckets)
        while i < n and value_ms > self.buckets[i]:
            i += 1
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum += value_ms
            if self.min is None or value_ms < self.min:
                self.min = value_ms
            if self.max is None or value_ms > self.max:
                self.max = value_ms
        self.rolling.observe(value_ms)

    def percentile(self, p: float) -> Optional[float]:
        """Estimated p-quantile (0 < p < 1) by linear interpolation inside
        the winning bucket; the overflow bucket reports the observed max."""
        if self.count == 0:
            return None
        target = p * self.count
        cum = 0
        lower = 0.0
        for i, upper in enumerate(self.buckets):
            prev = cum
            cum += self.counts[i]
            if cum >= target:
                frac = (target - prev) / max(self.counts[i], 1)
                return round(lower + (upper - lower) * frac, 4)
            lower = upper
        return self.max

    def to_dict(self) -> dict:
        live = self.rolling.summary()
        return {
            "count": self.count,
            "sum_ms": round(self.sum, 3),
            "min_ms": round(self.min, 4) if self.min is not None else None,
            "max_ms": round(self.max, 4) if self.max is not None else None,
            "p50_ms": self.percentile(0.5),
            "p95_ms": self.percentile(0.95),
            "p99_ms": self.percentile(0.99),
            # server-computed LIVE percentiles (exponentially decayed
            # rolling window) — distinct from the since-start estimates
            # above; what `GET /_telemetry/metrics` consumers and the
            # future wave scheduler should read for "current" tail
            "summary": {"p50_ms": live["p50"], "p95_ms": live["p95"],
                        "p99_ms": live["p99"], "count": live["count"]},
            "buckets": {
                **{f"le_{b:g}": c
                   for b, c in zip(self.buckets, self.counts)},
                "le_inf": self.counts[-1],
            },
        }


class MetricsRegistry:
    """All named counters/histograms on this node."""

    def __init__(self):
        self._counters: Dict[str, Counter] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._published: Dict[str, Callable[[], int]] = {}
        self._lock = threading.Lock()

    def publish(self, name: str, read: Callable[[], int]) -> None:
        """A count that its writer keeps as a plain int because it may
        take no lock (the `gc` callback, telemetry/tracer.py `GcSpans`):
        `read()` is called when the snapshot is rendered, and the value
        stands among the counters under `name`. `reset` leaves it."""
        self._published[name] = read

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(name, Counter(name))
        return c

    def histogram(self, name: str,
                  buckets: Optional[Tuple[float, ...]] = None) -> Histogram:
        h = self._histograms.get(name)
        if h is None:
            with self._lock:
                h = self._histograms.setdefault(name,
                                                Histogram(name, buckets))
        return h

    def to_dict(self) -> dict:
        counters = {name: c.value for name, c in self._counters.items()}
        counters.update((name, read())
                        for name, read in self._published.items())
        return {
            "counters": dict(sorted(counters.items())),
            "histograms": {name: h.to_dict()
                           for name, h in sorted(self._histograms.items())},
        }

    def reset(self) -> None:
        """Test/bench helper: zero every series IN PLACE — producers hold
        module-level Counter/Histogram handles, so instances must
        survive a reset."""
        with self._lock:
            for c in self._counters.values():
                with c._lock:
                    c.value = 0
            for h in self._histograms.values():
                with h._lock:
                    h.counts = [0] * (len(h.buckets) + 1)
                    h.count = 0
                    h.sum = 0.0
                    h.min = None
                    h.max = None
                h.rolling.reset()
