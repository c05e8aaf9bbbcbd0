"""Rolling live percentiles: fixed-memory streaming quantile estimators.

The metrics registry's fixed-bucket histograms (metrics.py) answer "what
was the latency distribution since node start" — an all-time view that a
p99-budget-aware scheduler cannot use: after an hour of traffic a burst
of slow waves barely moves the cumulative p99. This module is the LIVE
view: a geometric-bucket histogram with exponential time decay, so
`quantile(p)` reflects roughly the last `half_life_s` of traffic and is
queryable in O(1) with respect to the number of samples (a fixed ~170
bucket walk, no sample retention).

Design constraints (the ROADMAP item-2 wave scheduler is the consumer):

- `observe()` is one bisect + one float add — cheap enough to ride
  every histogram observation in the always-on registry;
- decay is applied LAZILY in whole intervals (one O(buckets) scale per
  `decay interval`, not per observation);
- buckets are geometric (ratio 1.15 over [1e-3, 1e7]) so one estimator
  shape serves milliseconds and bytes alike with a bounded ~7% worst-
  case relative quantile error (geometric interpolation inside the
  winning bucket); convergence against an offline numpy percentile is
  pinned in tests/test_transfer_ledger.py.

Thread-safety: `observe`/`quantile`/`reset` are lock-guarded. The old
"lost float increments under the GIL are tolerable" stance broke once
the decay path existed — two threads entering `_maybe_decay` in the
same interval would BOTH scale the counts (a real distortion, not a
lost sample), and concurrent clients drive N writer threads through
every estimator. The lock is
uncontended in steady state and costs well under the per-observation
bisect it guards (pinned by tests/test_rolling_concurrent.py).
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from typing import List, Optional, Tuple

_LO = 1e-3
_HI = 1e7
_RATIO = 1.15


def _make_bounds(lo: float, hi: float, ratio: float) -> Tuple[float, ...]:
    out: List[float] = []
    v = lo
    while v < hi:
        out.append(v)
        v *= ratio
    out.append(v)
    return tuple(out)


_SHARED_BOUNDS = _make_bounds(_LO, _HI, _RATIO)


class RollingEstimator:
    """Exponentially-decayed geometric histogram with p50/p95/p99 reads.

    `half_life_s`: observations lose half their weight every this many
    seconds (None disables decay — the estimator becomes an all-time
    geometric histogram, used by tests for deterministic convergence).
    """

    __slots__ = ("bounds", "counts", "total", "half_life_s",
                 "_decay_interval", "_last_decay", "max", "_clock",
                 "_lock")

    def __init__(self, half_life_s: Optional[float] = 300.0,
                 clock=time.monotonic):
        self.bounds = _SHARED_BOUNDS
        self.counts: List[float] = [0.0] * (len(self.bounds) + 1)
        self.total = 0.0
        self.half_life_s = half_life_s
        # scale at most once per 1/8th half-life: decay stays O(1)
        # amortized per observation while the window error stays small
        self._decay_interval = (half_life_s / 8.0) if half_life_s else None
        self._last_decay = clock()
        self.max: Optional[float] = None
        self._clock = clock
        self._lock = threading.Lock()

    # ------------------------------------------------------------- recording

    def _maybe_decay(self) -> None:
        if self._decay_interval is None:
            return
        now = self._clock()
        elapsed = now - self._last_decay
        if elapsed < self._decay_interval:
            return
        factor = 0.5 ** (elapsed / self.half_life_s)
        counts = self.counts
        for i, c in enumerate(counts):
            if c:
                counts[i] = c * factor
        self.total *= factor
        self._last_decay = now

    def observe(self, value: float) -> None:
        with self._lock:
            self._maybe_decay()
            i = bisect_left(self.bounds, value)
            self.counts[i] += 1.0
            self.total += 1.0
            if self.max is None or value > self.max:
                self.max = value

    # --------------------------------------------------------------- reading

    def quantile(self, p: float) -> Optional[float]:
        """Estimated p-quantile of the decayed window; None when empty.
        Geometric interpolation inside the winning bucket; the overflow
        bucket reports the observed max."""
        with self._lock:
            return self._quantile_locked(p)

    def _quantile_locked(self, p: float) -> Optional[float]:
        self._maybe_decay()
        total = self.total
        if total <= 0.0:
            return None
        target = p * total
        cum = 0.0
        n = len(self.bounds)
        for i, c in enumerate(self.counts):
            if not c:
                continue
            cum += c
            if cum >= target:
                if i >= n:
                    return self.max
                upper = self.bounds[i]
                lower = self.bounds[i - 1] if i else upper / _RATIO
                frac = (target - (cum - c)) / c
                val = lower * (upper / lower) ** frac
                # in-bucket interpolation can overshoot the largest value
                # actually seen; an estimator that reports p95 > max reads
                # as broken to a scheduler, so clamp
                return val if self.max is None else min(val, self.max)
        return self.max

    def summary(self) -> dict:
        """O(1) live digest — what a p99-budget-aware scheduler reads."""
        return {
            "count": round(self.total, 1),
            "p50": _round(self.quantile(0.5)),
            "p95": _round(self.quantile(0.95)),
            "p99": _round(self.quantile(0.99)),
            "max": _round(self.max),
        }

    def reset(self) -> None:
        with self._lock:
            self.counts = [0.0] * (len(self.bounds) + 1)
            self.total = 0.0
            self.max = None
            self._last_decay = self._clock()


def _round(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 4)
