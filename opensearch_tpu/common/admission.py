"""Adaptive deadline-aware admission: the overload-resilience layer.

Re-design of the reference's search admission stack —
`SearchBackpressureService` (search/backpressure/SearchBackpressureService
.java:63), the per-tenant sandboxing QueryGroup work, and
`HierarchyCircuitBreakerService`'s memory breakers — rebuilt around what
this node actually measures. The PR 6 gate was a *static permit count*:
admit until `max_concurrent`, then 429, blind to deadlines, tenants,
queue depth and device memory. That collapses at saturation: every
admitted request burns a slot until it finishes, so past the knee the
node spends its wall serving requests that will miss their deadline
anyway.

`AdmissionController` keeps the permit gate as the final stage and
layers three adaptive stages in FRONT of it, in a fixed pipeline order:

    tenant quota  ->  device-memory breaker  ->  deadline shed  ->  permits

- **Tenant quotas** (`TenantQuotas`): per-tenant token buckets (tenant
  from the `X-Opaque-Id` header or `?tenant=` param). A hot tenant
  drains its own bucket and starts eating 429s while the other tenants'
  buckets — and the shared permit pool they fund — stay live. Rates are
  cluster-settings-configurable per tenant; per-tenant admit/reject
  counts surface on `_nodes/stats`.

- **Device-memory breaker** (`DeviceMemoryBreaker`): a trip/half-open/
  close state machine over the PR 7 `DeviceMemoryAccounting` gauges.
  The executor consults it at wave boundaries (`pre_wave`) so a node
  whose in-flight wave buffers exceed the budget sheds WAVES as
  per-item 429s through the PR 6 per-item-error machinery — never a
  5xx — and the admission path consults the same state (`blocking`) so
  new arrivals shed at the door while the breaker is open.

- **Deadline shed** (`DeadlineShedder`): the adaptive core. The live
  rolling service-time estimator (telemetry/rolling.py, the PR 7
  machinery) prices a request at arrival: predicted wait + service =
  `service_p50 * (queue_depth + 1)` (the device serializes waves, so
  in-flight requests are, to first order, a serial queue ahead of the
  newcomer). A request whose parsed `timeout=` deadline — or the node
  SLO setting `admission.shed.slo_ms` — cannot be met is rejected at
  arrival in microseconds with a computed `Retry-After`, instead of
  burning a permit for tens of milliseconds only to time out. BM25S's
  framing (arXiv 2407.03618) applies: at saturation the win is in
  controlling *when* work is admitted, not how fast it runs.

Every rejection renders the reference-shaped 429 body
(`circuit_breaking_exception` with `bytes_wanted`/`bytes_limit`/
`durability`) plus the structured `reject_reason`
(`deadline_shed` | `tenant_quota` | `breaker:<name>` | `backpressure`),
the tenant, and `retry_after_ms` derived from the live rolling queue
estimate; the REST layer turns that into a real `Retry-After` header.

No-op discipline (gate-lint registry rows): the adaptive stages are all
OFF by default — `enabled = False`, `gate()` returns None — so the
default node behaves exactly like the PR 6 static permit gate: one
attribute load and a branch per disabled stage.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Tuple

from opensearch_tpu.common.errors import AdmissionRejectedError
from opensearch_tpu.telemetry.rolling import RollingEstimator

# structured reject reasons (lifecycle `reject` events and the 429
# body's `reject_reason` field carry exactly these, plus breaker:<name>)
REASON_BACKPRESSURE = "backpressure"
REASON_DEADLINE = "deadline_shed"
REASON_QUOTA = "tenant_quota"

DEFAULT_TENANT = "_default"


def predict_queue_ms(service_ms: Optional[float],
                     queue_depth: int) -> Optional[float]:
    """The shed predictor: expected wait-plus-service for a request
    arriving behind `queue_depth` in-flight requests, given the node's
    EXCLUSIVE per-request service-time estimate. The device executes
    waves serially, so the in-flight set is modeled as a serial queue:
    (depth + 1) * service.

    The estimate fed in is the rolling `floor_quantile` (default: the
    median) of NEAR-EXCLUSIVE walls only — releases observed while at
    most `exclusive_depth` other requests were in flight — the BBR
    min-RTT idea: walls measured under concurrency already CONTAIN the
    queueing delay of `depth` siblings, so pricing with a contended
    wall re-multiplies that delay by depth (a quadratic overestimate
    that measurably death-spiraled the controller into shedding 100%
    of a load it could serve), while an unfiltered LOW quantile is
    pinned by any >=5% slice of trivially-cheap traffic (cache hits,
    fast failures) and silently disables shedding. Shallow-depth walls
    approximate what one request costs alone; depth supplies the
    contention term exactly once. None when the estimator has no
    samples yet (never shed blind). Pure math —
    tests/reference_impl.ref_predict_queue_ms mirrors it."""
    if service_ms is None or service_ms <= 0.0:
        return None
    return service_ms * (max(queue_depth, 0) + 1)


class TokenBucket:
    """Seeded-deterministic token bucket: `rate` tokens/s, capacity
    `burst`. Lazy refill off an injectable clock, so unit tests drive
    time explicitly and two runs with the same clock sequence make the
    same decisions."""

    __slots__ = ("rate", "burst", "tokens", "_last", "_clock")

    def __init__(self, rate: float, burst: float, clock=time.monotonic):
        self.rate = float(rate)
        self.burst = float(burst)
        self.tokens = float(burst)
        self._clock = clock
        self._last = clock()

    def _refill(self) -> None:
        now = self._clock()
        if now > self._last:
            self.tokens = min(self.burst,
                              self.tokens + (now - self._last) * self.rate)
            self._last = now

    def take_up_to(self, n: int) -> int:
        """Admit as many of `n` as whole tokens allow (batch-aware, the
        acquire_batch analog); 0..n."""
        self._refill()
        got = min(int(self.tokens), max(int(n), 0))
        self.tokens -= got
        return got

    def seconds_until(self, n: float = 1.0) -> float:
        """Time until `n` tokens are available — the Retry-After basis
        for quota rejections."""
        self._refill()
        deficit = n - self.tokens
        if deficit <= 0:
            return 0.0
        return deficit / max(self.rate, 1e-9)


class TenantQuotas:
    """Per-tenant token-bucket admission with fair-share isolation.

    OFF by default (`enabled = False`; `gate()` returns None — the
    disabled admission path costs one attribute load and a branch).
    Enabled, every tenant gets a bucket at `default_rate`/`default_burst`
    unless an override was configured (cluster settings
    `admission.quota.tenant.<name>.tokens_per_sec` / `.burst`). Fair
    share is structural: buckets are independent, so one tenant
    exhausting its refill cannot consume another's tokens or the permit
    pool headroom its siblings' admitted requests ride."""

    # bound on distinct TRACKED tenants: the tenant id is client-
    # supplied (?tenant= / X-Opaque-Id), so an unbounded per-tenant
    # dict would be a memory-DoS vector inside the overload-protection
    # layer itself. Past the cap, unrecognized tenants share the
    # overflow bucket (they still can't starve configured tenants).
    MAX_TRACKED_TENANTS = 1024
    OVERFLOW_TENANT = "_overflow"

    def __init__(self, clock=time.monotonic):
        self.enabled = False
        self.default_rate = 100.0
        self.default_burst = 200.0
        self._clock = clock
        self._buckets: Dict[str, TokenBucket] = {}
        self._overrides: Dict[str, Tuple[float, float]] = {}
        self._admitted: Dict[str, int] = {}
        self._rejected: Dict[str, int] = {}
        self._lock = threading.Lock()

    def gate(self) -> Optional["TenantQuotas"]:
        """The per-request gate: None when quotas are disabled."""
        if not self.enabled:
            return None
        return self

    def _bucket(self, tenant: str) -> Tuple[str, TokenBucket]:
        """(tracked tenant key, its bucket) — the key degrades to the
        shared overflow bucket past MAX_TRACKED_TENANTS (configured
        tenants always track: their override slot pre-exists)."""
        b = self._buckets.get(tenant)
        if b is None:
            if len(self._buckets) >= self.MAX_TRACKED_TENANTS and \
                    tenant not in self._overrides and \
                    tenant != self.OVERFLOW_TENANT:
                return self._bucket(self.OVERFLOW_TENANT)
            rate, burst = self._overrides.get(
                tenant, (self.default_rate, self.default_burst))
            b = self._buckets[tenant] = TokenBucket(rate, burst,
                                                    self._clock)
        return tenant, b

    def take_up_to(self, tenant: str, n: int) -> Tuple[int, float]:
        """(admitted count, retry-after seconds for the first rejected
        item — 0.0 when everything was admitted)."""
        with self._lock:
            tenant, b = self._bucket(tenant)
            got = b.take_up_to(n)
            self._admitted[tenant] = self._admitted.get(tenant, 0) + got
            retry = 0.0
            if got < n:
                self._rejected[tenant] = \
                    self._rejected.get(tenant, 0) + (n - got)
                retry = b.seconds_until(1.0)
            return got, retry

    def refund(self, tenant: str, n: int) -> None:
        """Return tokens a DOWNSTREAM stage's rejection forfeited: a
        request that consumed quota but never executed must not count
        against its tenant's fair share (the permit pool being full of
        OTHER tenants' work would otherwise starve this tenant for a
        full refill after load drains)."""
        if n <= 0:
            return
        with self._lock:
            tenant, b = self._bucket(tenant)
            b.tokens = min(b.burst, b.tokens + n)
            self._admitted[tenant] = \
                max(self._admitted.get(tenant, 0) - n, 0)

    def set_tenant(self, tenant: str, rate: float, burst: float) -> None:
        with self._lock:
            spec = (float(rate), float(burst))
            if self._overrides.get(tenant) != spec:
                # only a CHANGED override rebuilds the bucket — a
                # settings re-apply must not refill a drained tenant
                self._overrides[tenant] = spec
                self._buckets.pop(tenant, None)

    def configure(self, rate: Optional[float] = None,
                  burst: Optional[float] = None) -> None:
        with self._lock:
            new_rate = self.default_rate if rate is None else float(rate)
            new_burst = self.default_burst if burst is None \
                else float(burst)
            if (new_rate, new_burst) == (self.default_rate,
                                         self.default_burst):
                return      # unchanged: keep live bucket levels — a
                # settings re-apply must not refill drained tenants
            self.default_rate = new_rate
            self.default_burst = new_burst
            # defaults changed: rebuild non-overridden buckets lazily
            for t in [t for t in self._buckets
                      if t not in self._overrides]:
                self._buckets.pop(t)

    def stats(self) -> dict:
        with self._lock:
            tenants = {}
            for t in set(self._buckets) | set(self._admitted) \
                    | set(self._rejected):
                b = self._buckets.get(t)
                rate, burst = self._overrides.get(
                    t, (self.default_rate, self.default_burst))
                tenants[t] = {
                    "admitted": self._admitted.get(t, 0),
                    "rejected": self._rejected.get(t, 0),
                    "tokens_per_sec": rate,
                    "burst": burst,
                    "tokens": round(b.tokens, 2) if b is not None
                    else burst,
                }
            return {"enabled": self.enabled,
                    "tokens_per_sec": self.default_rate,
                    "burst": self.default_burst,
                    "tenants": tenants}


class DeadlineShedder:
    """Deadline-aware shed: reject at arrival what cannot finish in
    time, priced by the live rolling service-time estimator.

    OFF by default (`enabled = False`; `gate()` returns None). Enabled,
    a request carrying a parsed `timeout=` deadline — or, absent one,
    the node SLO `slo_ms` — is shed when `predict_queue_ms` says the
    queue ahead of it already spends its budget. Shedding is O(1)
    (one estimator quantile read), so a rejected request costs
    microseconds, not a permit-holding timeout."""

    def __init__(self, clock=time.monotonic):
        self.enabled = False
        self.slo_ms: Optional[float] = None
        # fed by AdmissionController.release() with measured per-request
        # service walls; ~minutes half-life so the predictor tracks the
        # node's CURRENT speed, not its lifetime average
        self.service_ms = RollingEstimator()
        self.shed_total = 0
        # anti-starvation machinery. Without it the shedder death-
        # spirals: one cold-compile sample (hundreds of ms) poisons the
        # p50, EVERYTHING sheds, and — since shed requests never run —
        # no fresh sample ever corrects the estimate (measured: a
        # single 349ms cold request turned a 0.1ms-service node into a
        # 100% shed rate, forever). Two guards:
        #   min_samples  never shed before this many LIFETIME
        #                observations (the FlightRecorder warmup shape);
        #   probe        while shedding, admit one would-be-shed
        #                request per probe_interval_s as an estimator
        #                probe — its measured wall re-feeds the
        #                predictor, so a stale estimate decays in
        #                seconds instead of holding forever.
        self.min_samples = 8
        self.observed_total = 0
        self.probe_interval_s = 0.25
        self.probes = 0
        self._last_probe = 0.0
        # the predictor prices with the median of NEAR-EXCLUSIVE walls:
        # observe() records only releases that ran with at most
        # exclusive_depth other requests in flight — see
        # predict_queue_ms for why contended walls double-count depth
        # and why an unfiltered low quantile gets pinned by cheap
        # traffic
        self.floor_quantile = 0.5
        self.exclusive_depth = 1
        # shape-aware pricing (ISSUE 15): per-shape rolling service
        # medians keyed on the query-insights shape id (telemetry/
        # insights.py query_shape), behind its OWN off-by-default gate —
        # a cheap `match_all` median must not price a heavy aggs
        # arrival, and vice versa. Below shape_min_samples (or for an
        # untracked shape / shape=None caller) pricing falls back to
        # the global near-exclusive median, so the stage can never shed
        # blinder than the global predictor. Bounded like the quota
        # buckets: past the cap, new shapes fold into the overflow row.
        self.shape_enabled = False
        self.shape_min_samples = 8
        self.max_tracked_shapes = 256
        self._shape_rows: Dict[str, RollingEstimator] = {}
        self._shape_counts: Dict[str, int] = {}
        self.shape_hits = 0
        self.shape_fallbacks = 0
        self._clock = clock
        self._lock = threading.Lock()

    def gate(self) -> Optional["DeadlineShedder"]:
        """The per-request gate: None when deadline shed is disabled."""
        if not self.enabled:
            return None
        return self

    def shape_gate(self) -> Optional["DeadlineShedder"]:
        """The shape-pricing gate (its own flag ON TOP of the shed
        stage's): None when shape-aware pricing is off — the REST layer
        then never computes a shape key at admission, so the default
        shed path costs nothing extra."""
        if not self.shape_enabled:
            return None
        return self

    def observe(self, service_ms: float, depth: int = 0,
                shape: Optional[str] = None) -> None:
        """Record a measured service wall. `depth` = how many OTHER
        requests were in flight when this one released: contended
        walls are discarded (they would double-count queueing in the
        predictor — see predict_queue_ms). The estimator probes are
        admitted while everything else sheds, so they release at low
        depth and keep this stream alive under sustained overload.
        `shape` (the caller's resolved shape id, shape pricing on)
        feeds that shape's own estimator under the SAME near-exclusive
        filter — a per-shape median of contended walls would re-import
        exactly the double-count the global filter exists to kill."""
        if depth > self.exclusive_depth:
            return
        self.service_ms.observe(service_ms)
        with self._lock:
            self.observed_total += 1
            if shape is not None and self.shape_enabled:
                row = self._shape_rows.get(shape)
                if row is None:
                    if len(self._shape_rows) >= self.max_tracked_shapes:
                        shape = "_other"
                        row = self._shape_rows.get(shape)
                    if row is None:
                        row = self._shape_rows[shape] = \
                            RollingEstimator()
                self._shape_counts[shape] = \
                    self._shape_counts.get(shape, 0) + 1
            else:
                row = None
        if row is not None:
            row.observe(service_ms)

    def service_estimate(self, shape: Optional[str] = None) \
            -> Optional[float]:
        """The arrival's OWN-service term: the arriving shape's rolling
        median once that shape has `shape_min_samples` near-exclusive
        releases (shape pricing on), else the global median — the
        fallback contract tests/test_insights.py pins. Counters record
        which branch priced each call."""
        if self.shape_enabled and shape is not None:
            with self._lock:
                row = self._shape_rows.get(shape)
                warm = row is not None and \
                    self._shape_counts.get(shape, 0) \
                    >= self.shape_min_samples
            if warm:
                q = row.quantile(self.floor_quantile)
                if q:
                    with self._lock:
                        self.shape_hits += 1
                    return q
            with self._lock:
                self.shape_fallbacks += 1
        return self.service_ms.quantile(self.floor_quantile)

    def predicted_ms(self, queue_depth: int,
                     shape: Optional[str] = None) -> Optional[float]:
        """The live queue-time estimate for a request arriving behind
        `queue_depth` in-flight requests — the Retry-After basis.

        Shape pricing uses the MIXED model `global × depth + own`:
        the queue ahead of the arrival is other requests of unknown
        classes, so its drain time is priced with the global (mix)
        median, while the arrival's OWN service slot is priced with
        its shape's median. Pricing the whole queue at the arriving
        shape's cost (`own × (depth+1)`) is measurably wrong in both
        directions — a heavy arrival behind a queue of cache hits was
        charged heavy × depth and shed work the node could serve
        (goodput 327 → 120 in the A/B that caught it), and a cheap
        arrival behind heavy in-flight work would be waved into a
        deadline miss. A cold/unknown shape's `own` falls back to the
        global median, collapsing to exactly the global model."""
        base = self.service_ms.quantile(self.floor_quantile)
        if self.shape_enabled and shape is not None:
            own = self.service_estimate(shape)
            if own is not None and base is not None:
                return base * max(queue_depth, 0) + own
        return predict_queue_ms(base, queue_depth)

    def budget_ms(self, deadline: Optional[float],
                  now: Optional[float] = None) -> Optional[float]:
        """Remaining budget for a request: its own monotonic deadline
        when it set one, else the node SLO; None = unbounded."""
        if deadline is not None:
            return (deadline - (time.monotonic() if now is None
                                else now)) * 1000.0
        return self.slo_ms

    def _probe_due(self) -> bool:
        """Called under _lock: claim the periodic estimator probe."""
        now = self._clock()
        if now - self._last_probe >= self.probe_interval_s:
            self._last_probe = now
            self.probes += 1
            return True
        return False

    def check(self, queue_depth: int, deadline: Optional[float],
              shape: Optional[str] = None) -> Optional[float]:
        """None = admit; else the predicted queue time in ms (the shed
        verdict + the Retry-After basis). `shape` routes pricing to
        the arriving shape's own service median when shape pricing is
        on and warm (global-median fallback otherwise)."""
        budget = self.budget_ms(deadline)
        if budget is None:
            return None
        with self._lock:
            if self.observed_total < self.min_samples:
                return None     # never shed blind
        predicted = self.predicted_ms(queue_depth, shape)
        if predicted is None or predicted <= budget:
            return None
        with self._lock:
            if self._probe_due():
                return None     # estimator probe: admit one anyway
            self.shed_total += 1
        return predicted

    def max_admissible(self, queue_depth: int,
                       budget_ms: Optional[float], n: int) -> int:
        """Batch form: the largest m <= n such that the m-th admitted
        item still fits the budget — `q * (depth + m) <= budget` with
        the same tail quantile as check(). Unknown estimate or no
        budget admits everything (never shed blind)."""
        if budget_ms is None:
            return n
        with self._lock:
            if self.observed_total < self.min_samples:
                return n
        q = self.service_ms.quantile(self.floor_quantile)
        if q is None or q <= 0.0:
            return n
        m = int(budget_ms / q) - max(queue_depth, 0)
        m = max(0, min(m, n))
        if m < n:
            with self._lock:
                if m == 0 and self._probe_due():
                    m = 1       # estimator probe: one item through
                self.shed_total += n - m
        return m

    def stats(self) -> dict:
        with self._lock:
            shape_block = {
                "enabled": self.shape_enabled,
                "min_samples": self.shape_min_samples,
                "tracked": len(self._shape_rows),
                "priced_by_shape": self.shape_hits,
                "priced_by_global": self.shape_fallbacks,
            }
        return {"enabled": self.enabled,
                "slo_ms": self.slo_ms,
                "shed_total": self.shed_total,
                "probes": self.probes,
                "min_samples": self.min_samples,
                "service_ms": self.service_ms.summary(),
                "shape_pricing": shape_block}


class DeviceMemoryBreaker:
    """Trip / half-open / close breaker over a live device-memory gauge.

    OFF by default (`enabled = False`; `gate()` returns None). The
    executor calls `pre_wave(live_bytes)` before dispatching each wave:

      closed     live_bytes over `limit_bytes` trips the breaker open
                 (the wave renders per-item 429s, never a 5xx);
      open       every wave/admission rejects until `cooldown_s`
                 elapses, then ONE probe wave is admitted (half-open);
      half-open  the probe's collect outcome (`on_result`) closes the
                 breaker on success or re-opens it on failure; siblings
                 keep rejecting while the probe flies.

    The reference analog is HierarchyCircuitBreakerService's parent
    real-memory breaker; the state machine is the standard electrical
    shape its cousins (e.g. resilience4j) use, driven here by the PR 7
    `DeviceMemoryAccounting` wave-buffer gauge instead of JVM heap."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, name: str = "wave_memory",
                 limit_bytes: int = 256 << 20,
                 cooldown_s: float = 1.0, clock=time.monotonic):
        self.enabled = False
        self.name = name
        self.limit_bytes = int(limit_bytes)
        self.cooldown_s = float(cooldown_s)
        self.state = self.CLOSED
        self.trip_count = 0
        self.rejections = 0
        self._opened_at = 0.0
        self._probe_inflight = False
        self._trip_bytes = 0        # gauge reading at the last trip
        self._clock = clock
        self._lock = threading.Lock()

    def gate(self) -> Optional["DeviceMemoryBreaker"]:
        """The per-wave gate: None when the breaker is disabled."""
        if not self.enabled:
            return None
        return self

    def _reject(self, live_bytes: Optional[int]) -> AdmissionRejectedError:
        """`live_bytes` None = an admission-path rejection while the
        breaker is open: report the bytes observed AT THE TRIP (the
        admission path holds no gauge reading, and rendering a literal
        0 'over the limit' would be self-contradictory)."""
        self.rejections += 1
        if live_bytes is None:
            live_bytes = self._trip_bytes
        return AdmissionRejectedError(
            f"[{self.name}] device memory breaker is {self.state}: "
            f"in-flight wave buffers [{live_bytes}] over the limit "
            f"[{self.limit_bytes}]",
            reject_reason=f"breaker:{self.name}",
            bytes_wanted=int(live_bytes),
            bytes_limit=self.limit_bytes,
            retry_after_ms=self.cooldown_s * 1000.0)

    def pre_wave(self, live_bytes: int) \
            -> Tuple[Optional[AdmissionRejectedError], bool]:
        """Wave-boundary check: (None, is_probe) admits the wave —
        `is_probe` marks the single half-open probe whose collect
        outcome must be reported back via `on_result` — and
        (error, False) sheds it."""
        with self._lock:
            now = self._clock()
            if self.state == self.CLOSED:
                if live_bytes <= self.limit_bytes:
                    return None, False
                self.state = self.OPEN
                self.trip_count += 1
                self._opened_at = now
                self._trip_bytes = int(live_bytes)
                return self._reject(live_bytes), False
            if self.state == self.OPEN:
                if now - self._opened_at < self.cooldown_s:
                    return self._reject(live_bytes), False
                self.state = self.HALF_OPEN
                self._probe_inflight = True
                return None, True
            # HALF_OPEN: exactly one probe at a time
            if self._probe_inflight:
                return self._reject(live_bytes), False
            self._probe_inflight = True
            return None, True

    def blocking(self) -> Optional[AdmissionRejectedError]:
        """Admission-path check: sheds new arrivals while the breaker is
        open/probing, WITHOUT consuming the half-open probe slot (the
        probe belongs to the wave engine, which owns the gauge)."""
        with self._lock:
            if self.state == self.CLOSED:
                return None
            now = self._clock()
            if self.state == self.OPEN and \
                    now - self._opened_at >= self.cooldown_s:
                return None     # cooldown over: let a probe through
            if self.state == self.HALF_OPEN and not self._probe_inflight:
                return None
            return self._reject(None)

    def on_result(self, ok: bool) -> None:
        """Probe outcome: success closes, failure re-opens. No-op in
        the closed state (ordinary waves don't move the machine)."""
        with self._lock:
            if self.state != self.HALF_OPEN:
                return
            self._probe_inflight = False
            if ok:
                self.state = self.CLOSED
            else:
                self.state = self.OPEN
                self._opened_at = self._clock()

    def reset(self) -> None:
        with self._lock:
            self.state = self.CLOSED
            self._probe_inflight = False

    def stats(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled, "state": self.state,
                    "limit_bytes": self.limit_bytes,
                    "cooldown_ms": round(self.cooldown_s * 1000.0, 1),
                    "tripped": self.trip_count,
                    "rejections": self.rejections}


# Process-wide breaker singleton (the REQUEST_CACHE/WARMUP/TELEMETRY
# pattern): the executor has no node reference, so the wave engine and
# the node's admission controller share this instance. Mutation happens
# only through the instance's own lock-guarded methods.
WAVE_BREAKER = DeviceMemoryBreaker()


class AdmissionController:
    """The node's search admission gate: quota -> breaker -> deadline
    shed -> permits, in that order, every stage but the last OFF by
    default (the default node is exactly the PR 6 static permit gate).

    API compatibility: `acquire`/`release`, `acquire_batch`/
    `release_batch`, `max_concurrent`, `current`, `rejections`,
    `rejection_error()` and the `search_task` stats block keep the
    SearchBackpressure contract (common/breakers.py re-exports this
    class under that name); the adaptive stages ride optional kwargs."""

    def __init__(self, max_concurrent: int = 100,
                 clock=time.monotonic):
        self.max_concurrent = max_concurrent
        self.current = 0
        self.rejections = 0
        self.cancellations = 0
        # counter-based permit invariant: current == admitted - released
        # at all times, and both drain to equality after quiesce — the
        # leak tripwire tools/chaos_sweep.py checks after every row
        self.admitted_total = 0
        self.released_total = 0
        self._lock = threading.Lock()
        self._reject_by_reason: Dict[str, int] = {}
        self.quotas = TenantQuotas(clock=clock)
        self.shedder = DeadlineShedder()
        self.wave_breaker = WAVE_BREAKER
        # per-tenant resource USAGE (ISSUE 14) — the other side of the
        # quota story: quotas bound what a tenant may ask for, this
        # records what it actually consumed. Fed by the wave scheduler
        # splitting each shared dispatch's device wall (and, ledger on,
        # its fetched bytes) proportionally across co-batched owners.
        # Bounded like the quota buckets: past the cap, new tenants
        # fold into the overflow row.
        self._usage: Dict[str, Dict[str, float]] = {}
        self._usage_lock = threading.Lock()
        # the wave scheduler's queue-depth feed (search/scheduler.py):
        # when the scheduler is enabled, admitted requests WAIT in its
        # bounded queue before executing, so the deadline-shed stage
        # must price arrivals against permits-in-flight PLUS that real
        # queue — set by Node to the scheduler's queue_depth. None =
        # no scheduler (the PR 11 behavior exactly).
        self.queue_depth_extra: Optional[Any] = None

    def queue_depth(self) -> int:
        """The serial-queue depth the shed predictor prices with —
        `predict_queue_ms`'s depth term. MAX of permits in flight and
        the wave scheduler's queued count, never their sum: a
        scheduler-queued REST request HOLDS its permit across the
        coalesce window, so it is already inside `current` and adding
        the queue on top would price arrivals at ~2× the real depth
        (exactly the over-estimate the predictor's docstring warns
        death-spirals the shed). The max still covers direct callers
        whose queued work holds no permit."""
        extra = self.queue_depth_extra
        if extra is None:
            return self.current
        return max(self.current, int(extra()))

    def note_usage(self, tenant: Optional[str], device_ms: float,
                   d2h_bytes: int = 0, items: int = 1) -> None:
        """Accumulate one request's measured resource consumption
        (ISSUE 14): its proportional slice of a shared wave's device
        wall (`device_share_ms`) and fetched bytes. Always-on once the
        scheduler dispatches (one lock + dict update per ITEM per
        wave, never per doc) — the `usage` block on `_nodes/stats`
        admission answers "which tenant is actually eating the
        device", the number the quota knobs are tuned against."""
        tenant = tenant or DEFAULT_TENANT
        with self._usage_lock:
            u = self._usage.get(tenant)
            if u is None:
                if len(self._usage) >= TenantQuotas.MAX_TRACKED_TENANTS \
                        and tenant != TenantQuotas.OVERFLOW_TENANT:
                    tenant = TenantQuotas.OVERFLOW_TENANT
                    u = self._usage.get(tenant)
                if u is None:
                    u = self._usage[tenant] = {
                        "device_ms": 0.0, "d2h_bytes": 0, "items": 0,
                        "waves": 0}
            u["device_ms"] += float(device_ms)
            u["d2h_bytes"] += int(d2h_bytes)
            u["items"] += int(items)
            u["waves"] += 1

    def usage(self) -> Dict[str, dict]:
        with self._usage_lock:
            return {t: {"device_ms": round(u["device_ms"], 3),
                        "d2h_bytes": int(u["d2h_bytes"]),
                        "items": int(u["items"]),
                        "waves": int(u["waves"])}
                    for t, u in sorted(self._usage.items())}

    def refund_unserved(self, tenant: Optional[str] = None) -> None:
        """Refund the quota token of an ADMITTED request that a post-
        admission stage (the wave scheduler shedding at deadline, or
        its bounded queue rejecting) dropped before execution: the
        request never ran, so it must not count against its tenant's
        fair share (the TenantQuotas.refund contract, extended across
        the coalesce window). The PERMIT needs no special handling —
        the request thread holds it across the window and the REST
        layer's finally releases it, which is exactly what keeps the
        admitted_total == released_total invariant checkable for
        scheduler-queued requests."""
        quotas = self.quotas.gate()
        if quotas is not None:
            quotas.refund(tenant or DEFAULT_TENANT, 1)

    # ------------------------------------------------------------ rejection

    def _count_reject(self, reason: str, n: int = 1) -> None:
        with self._lock:
            self.rejections += n
            self._reject_by_reason[reason] = \
                self._reject_by_reason.get(reason, 0) + n
        from opensearch_tpu.telemetry import TELEMETRY
        TELEMETRY.metrics.counter("search.backpressure_rejections").inc(n)
        TELEMETRY.metrics.counter(
            f"search.admission_reject.{reason}").inc(n)

    def retry_after_ms(self) -> float:
        """Retry-After from the live rolling queue estimate: how long
        until the queue ahead of a new arrival likely drains one slot —
        the per-request service p50, floored at 1ms so the header never
        renders as 'retry immediately' while the node is shedding."""
        p50 = self.shedder.service_ms.quantile(0.5)
        return max(p50 if p50 else 0.0, 1.0)

    def rejection_error(
            self, reason: str = REASON_BACKPRESSURE,
            tenant: Optional[str] = None,
            retry_after_ms: Optional[float] = None,
    ) -> AdmissionRejectedError:
        """The reference-shaped 429 (circuit_breaking_exception with
        bytes_wanted/bytes_limit/durability) carrying the structured
        reject reason + computed Retry-After. For the permit and quota
        stages the byte fields are the documented permit analogs
        (wanted = the over-limit permit count, limit = the cap)."""
        if retry_after_ms is None:
            retry_after_ms = self.retry_after_ms()
        texts = {
            REASON_BACKPRESSURE:
                f"rejected execution of search: node is under duress "
                f"[{self.current} >= {self.max_concurrent} concurrent "
                f"searches]",
            REASON_DEADLINE:
                f"rejected execution of search: predicted queue time "
                f"exceeds the request deadline/SLO "
                f"[{self.current} in flight]",
            REASON_QUOTA:
                f"rejected execution of search: tenant "
                f"[{tenant or DEFAULT_TENANT}] is over its quota",
        }
        return AdmissionRejectedError(
            texts.get(reason,
                      f"rejected execution of search [{reason}]"),
            reject_reason=reason, tenant=tenant,
            bytes_wanted=self.current + 1,
            bytes_limit=self.max_concurrent,
            retry_after_ms=retry_after_ms)

    # ------------------------------------------------------------ admission

    def acquire(self, tenant: Optional[str] = None,
                deadline: Optional[float] = None,
                shape: Optional[str] = None) -> None:
        """Admit one search or raise the typed 429. Stage order is the
        documented pipeline; every adaptive stage is one attribute load
        and a branch when disabled. `shape` (resolved by the REST layer
        only while the shed stage's shape_gate is on) routes deadline-
        shed pricing to the arriving shape's own service median."""
        tenant = tenant or DEFAULT_TENANT
        quotas = self.quotas.gate()
        if quotas is not None:
            got, retry_s = quotas.take_up_to(tenant, 1)
            if not got:
                self._count_reject(REASON_QUOTA)
                raise self.rejection_error(
                    REASON_QUOTA, tenant=tenant,
                    retry_after_ms=retry_s * 1000.0)

        def _downstream_reject(err: AdmissionRejectedError):
            # a request the quota admitted but a later stage rejected
            # never executed: refund its token or the tenant starves
            # on OTHER tenants' congestion
            if quotas is not None:
                quotas.refund(tenant, 1)
            self._count_reject(err.reject_reason)
            err.metadata["tenant"] = tenant
            raise err

        breaker = self.wave_breaker.gate()
        if breaker is not None:
            err = breaker.blocking()
            if err is not None:
                _downstream_reject(err)
        shedder = self.shedder.gate()
        if shedder is not None:
            predicted = shedder.check(self.queue_depth(), deadline,
                                      shape=shape)
            if predicted is not None:
                _downstream_reject(self.rejection_error(
                    REASON_DEADLINE, tenant=tenant,
                    retry_after_ms=predicted))
        with self._lock:
            if self.current >= self.max_concurrent:
                pass            # reject below, outside the lock
            else:
                self.current += 1
                self.admitted_total += 1
                return
        _downstream_reject(self.rejection_error(REASON_BACKPRESSURE,
                                                tenant=tenant))

    def release(self, service_ms: Optional[float] = None,
                shape: Optional[str] = None) -> None:
        with self._lock:
            self.current = max(0, self.current - 1)
            self.released_total += 1
            depth = self.current
        if service_ms is not None and self.shedder.enabled:
            # depth AT RELEASE rides along: the shedder keeps only
            # near-exclusive walls (contended ones double-count depth).
            # `shape` feeds the per-shape estimator the shape-pricing
            # stage reads (same near-exclusive filter).
            self.shedder.observe(service_ms, depth=depth, shape=shape)

    def acquire_batch(self, n: int,
                      tenant: Optional[str] = None,
                      deadline: Optional[float] = None) -> int:
        """Compatibility wrapper: admitted count only."""
        return self.acquire_batch_ex(n, tenant=tenant,
                                     deadline=deadline)[0]

    def acquire_batch_ex(
            self, n: int, tenant: Optional[str] = None,
            deadline: Optional[float] = None,
    ) -> Tuple[int, Optional[AdmissionRejectedError]]:
        """Batch-aware admission for the _msearch envelope: run the
        pipeline per stage over the whole batch, admit what every stage
        allows, and return (admitted, error-for-the-overflow) — the
        caller renders the error as per-item 429 objects for the tail
        and MUST release_batch(admitted) when done. The overflow error
        carries the FIRST stage that clipped the batch (the most
        upstream cause is the actionable one)."""
        n = max(int(n), 0)
        tenant = tenant or DEFAULT_TENANT
        err: Optional[AdmissionRejectedError] = None
        m = n
        quotas = self.quotas.gate()
        quota_taken = 0
        if quotas is not None and m > 0:
            got, retry_s = quotas.take_up_to(tenant, m)
            if got < m:
                self._count_reject(REASON_QUOTA, m - got)
                err = self.rejection_error(
                    REASON_QUOTA, tenant=tenant,
                    retry_after_ms=retry_s * 1000.0)
            m = quota_taken = got
        breaker = self.wave_breaker.gate()
        if breaker is not None and m > 0:
            berr = breaker.blocking()
            if berr is not None:
                self._count_reject(berr.reject_reason, m)
                berr.metadata["tenant"] = tenant
                err, m = err or berr, 0
        shedder = self.shedder.gate()
        if shedder is not None and m > 0:
            depth = self.queue_depth()
            fit = shedder.max_admissible(
                depth, shedder.budget_ms(deadline), m)
            if fit < m:
                self._count_reject(REASON_DEADLINE, m - fit)
                # Retry-After = the predicted queue time for the FIRST
                # clipped item (behind the queue + the fit just
                # admitted) — the same estimate the single path reports
                err = err or self.rejection_error(
                    REASON_DEADLINE, tenant=tenant,
                    retry_after_ms=shedder.predicted_ms(
                        depth + fit) or None)
                m = fit
        with self._lock:
            free = max(0, self.max_concurrent - self.current)
            admitted = min(m, free)
            self.current += admitted
            self.admitted_total += admitted
        if admitted < m:
            self._count_reject(REASON_BACKPRESSURE, m - admitted)
            err = err or self.rejection_error(REASON_BACKPRESSURE,
                                              tenant=tenant)
        elif admitted < n and err is None:
            err = self.rejection_error(REASON_BACKPRESSURE,
                                       tenant=tenant)
        if quotas is not None and quota_taken > admitted:
            # tokens the breaker/shed/permit stages forfeited cover
            # items that never executed — refund them (fair share)
            quotas.refund(tenant, quota_taken - admitted)
        return admitted, err

    def release_batch(self, n: int,
                      service_ms: Optional[float] = None) -> None:
        n = max(int(n), 0)
        with self._lock:
            self.current = max(0, self.current - n)
            self.released_total += n
        if service_ms is not None and self.shedder.enabled and n:
            # one envelope wall spread over its admitted items — a
            # coarse per-item estimate, subject to the same
            # near-exclusive depth filter as the single path
            with self._lock:
                depth = self.current
            self.shedder.observe(service_ms / n, depth=depth)

    # ------------------------------------------------------------- settings

    @staticmethod
    def parse_settings(flat: Dict[str, Any]) -> Dict[str, Any]:
        """Parse + validate the admission keys out of a flat settings
        map WITHOUT mutating anything — the REST layer dry-runs this
        before committing a cluster-settings update, so a malformed
        value 400s instead of persisting and then 500ing every later
        update (and node restart). Every malformed value raises
        SettingsError."""
        from opensearch_tpu.common.errors import SettingsError
        from opensearch_tpu.common.settings import (
            _parse_bool, parse_byte_size)

        def _num(key, cast=float):
            v = flat.get(key)
            if v is None:
                return None
            try:
                return cast(v)
            except (TypeError, ValueError):
                raise SettingsError(
                    f"Failed to parse value [{v}] for setting [{key}]")

        def _bool(key):
            v = flat.get(key)
            return None if v is None else _parse_bool(v, key)

        out: Dict[str, Any] = {
            "max_concurrent": _num("search.backpressure.max_concurrent",
                                   int),
            "shed_enabled": _bool("admission.shed.enabled"),
            "slo_ms": _num("admission.shed.slo_ms"),
            "shape_enabled": _bool(
                "admission.shed.shape_pricing.enabled"),
            "shape_min_samples": _num(
                "admission.shed.shape_pricing.min_samples", int),
            "quota_enabled": _bool("admission.quota.enabled"),
            "quota_rate": _num("admission.quota.tokens_per_sec"),
            "quota_burst": _num("admission.quota.burst"),
            "breaker_enabled": _bool(
                "admission.breaker.wave_memory.enabled"),
            "breaker_cooldown_ms": _num(
                "admission.breaker.wave_memory.cooldown_ms"),
        }
        v = flat.get("admission.breaker.wave_memory.limit_bytes")
        out["breaker_limit"] = None if v is None else parse_byte_size(
            v, "admission.breaker.wave_memory.limit_bytes")
        tenants = []
        for key in flat:
            if key.startswith("admission.quota.tenant.") and \
                    key.endswith(".tokens_per_sec"):
                t = key[len("admission.quota.tenant."):
                        -len(".tokens_per_sec")]
                rate = _num(key)
                burst = _num(f"admission.quota.tenant.{t}.burst")
                tenants.append((t, rate,
                                burst if burst is not None else rate))
        out["tenants"] = tenants
        return out

    def apply_settings(self, flat: Dict[str, Any]) -> None:
        """Apply node/cluster settings (flat `a.b.c` keys). Called at
        node start with node settings and again on every cluster
        settings update with the FULL merged map — unknown keys are
        ignored (the cluster settings store is a raw map), malformed
        values raise SettingsError. The breaker keys are full-spec:
        absent means reset-to-default, because WAVE_BREAKER is the
        process-wide singleton the executor reads — a later Node in
        the same process must not inherit a previous node's breaker
        config."""
        p = self.parse_settings(flat)
        if p["max_concurrent"] is not None:
            self.max_concurrent = p["max_concurrent"]
        if p["shed_enabled"] is not None:
            self.shedder.enabled = p["shed_enabled"]
        if p["slo_ms"] is not None:
            self.shedder.slo_ms = p["slo_ms"] if p["slo_ms"] > 0 else None
        if p["shape_enabled"] is not None:
            self.shedder.shape_enabled = p["shape_enabled"]
        if p["shape_min_samples"] is not None:
            self.shedder.shape_min_samples = \
                max(int(p["shape_min_samples"]), 1)
        if p["quota_enabled"] is not None:
            self.quotas.enabled = p["quota_enabled"]
        self.quotas.configure(rate=p["quota_rate"],
                              burst=p["quota_burst"])
        for t, rate, burst in p["tenants"]:
            self.quotas.set_tenant(t, rate, burst)
        # breaker: full-spec (singleton reset semantics, see docstring)
        self.wave_breaker.enabled = bool(p["breaker_enabled"])
        self.wave_breaker.limit_bytes = p["breaker_limit"] \
            if p["breaker_limit"] is not None else 256 << 20
        self.wave_breaker.cooldown_s = \
            (p["breaker_cooldown_ms"] / 1000.0
             if p["breaker_cooldown_ms"] is not None else 1.0)

    # ---------------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._lock:
            by_reason = dict(self._reject_by_reason)
        return {
            "search_task": {"current": self.current,
                            "rejections": self.rejections,
                            "cancellation_count": self.cancellations},
            "admission": {
                "order": ["tenant_quota", "breaker", "deadline_shed",
                          "permits"],
                "max_concurrent": self.max_concurrent,
                "admitted_total": self.admitted_total,
                "released_total": self.released_total,
                "rejections_by_reason": by_reason,
                "deadline_shed": self.shedder.stats(),
                "tenant_quota": self.quotas.stats(),
                "breakers": {self.wave_breaker.name:
                             self.wave_breaker.stats()},
                # measured per-tenant consumption (ISSUE 14): the
                # usage side of the quota story, fed by the wave
                # scheduler's proportional device-wall split
                "usage": self.usage(),
            },
        }
