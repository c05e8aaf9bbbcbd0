"""Async wave scheduler: coalesce concurrent users into shared device
waves.

ROADMAP item 1's centerpiece. Concurrent clients each paying a full
B=1 dispatch get a fraction of what the same box does when independent
requests ride ONE interned envelope — the
O(unique-templates) batched path PR 5 built and PR 9 turned into a
double-buffered wave pipeline. Every request the REST layer serves
inline burns a full dispatch; this module makes independent users
share one device round trip instead.

Architecture — the scheduler sits BETWEEN admission and the executor:

    REST _run_search / _msearch     (admission already passed; the
        |                            permit + quota token are HELD
        v                            across the coalesce window)
    WaveScheduler.execute[_many]    (bounded queue, blocking submit)
        |
    scheduler thread: adaptive micro-batch delay window
        | groups compatible sub-requests by target shard executor
        v (template/segment/shape-bucket grouping happens INSIDE the
           envelope — dsl.intern_query + compile_interned already key
           plan skeletons on exactly that tuple)
    SearchExecutor.multi_search(bodies, timelines=...)  — the existing
        wave pipeline (_run_wave_pipeline) dispatches shared waves and
        emits per-request coalesce/dispatch/collect/overlap lifecycle
        events through the timeline fan
        |
        v
    per-request demux: each queued request gets its own slice of the
    envelope's per-item responses (error items / timed-out partials
    ride the PR 6 per-item machinery) and its blocked thread wakes.

The adaptive window (`plan_window_ms`, mirrored by
tests/reference_impl.ref_window_ms) is p99-budget aware: it reuses the
admission controller's serial-queue model (`predict_queue_ms`, the
PR 11 shed predictor) priced with the LIVE rolling service estimate,
and never spends delay a queued request's `timeout=`/SLO budget cannot
afford. It is also pressure-aware: the live arrival-gap estimate
decides whether waiting can plausibly buy a companion at all — an
idle node dispatches immediately (zero added latency at low offered
load), a saturated node batches the backlog that forms naturally while
the previous wave executes.

Invariants (pinned by tests/test_scheduler.py + tools/chaos_sweep.py):
  - permits/quota tokens acquired at admission are HELD by the blocked
    request thread across the coalesce window and released in the REST
    layer's existing finally — the PR 11 counter invariant
    (admitted_total == released_total) extends to scheduler-queued
    requests, and a request the scheduler sheds at deadline (or
    rejects queue-full) gets its quota token refunded
    (`AdmissionController.refund_unserved`): it never executed;
  - scheduler-off is byte-identical: eligible bodies ride the SAME
    B=1 envelope inline (controller's allow_envelope delegation), and
    batching is score-bit-identical by the PR 5 parity suite — the
    differential test pins scheduler-on == scheduler-off across
    B ∈ {1, 32, 1024};
  - a deadline that expires INSIDE the window renders the reference
    timed-out partial shape (zero hits, `timed_out: true`), never an
    error — timeout is a budget decision;
  - cancellation drains: a queued request whose task was cancelled
    leaves the queue with the cancellation error at the next pump, and
    disabling the scheduler dispatches every queued request before the
    thread exits (no stranded waiter).

No-op discipline (gate-lint registry row): `enabled = False` by default
and `gate()` returns None — the disabled query path costs one attribute
load and a branch, and the disabled scheduler owns no thread.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from opensearch_tpu.common.admission import predict_queue_ms
from opensearch_tpu.common.errors import (
    AdmissionRejectedError, OpenSearchTpuError)
from opensearch_tpu.telemetry.rolling import RollingEstimator

REASON_QUEUE_FULL = "scheduler_queue_full"

DEFAULT_WINDOW_MS = 2.0
DEFAULT_MAX_QUEUE = 1024
DEFAULT_MAX_BATCH = 1024


def plan_window_ms(budgets_ms: List[Optional[float]],
                   service_ms: Optional[float],
                   queue_depth: int,
                   arrival_gap_ms: Optional[float],
                   window_max_ms: float) -> float:
    """The adaptive micro-batch delay window, in milliseconds. Pure
    math — tests/reference_impl.ref_window_ms mirrors it.

    Two terms, ANDed:

    budget cap   the window may only spend latency every queued
                 request can afford: for each request with a budget
                 (its `timeout=` deadline remainder, else the node
                 SLO), headroom = budget − predicted queue time, where
                 the prediction is the PR 11 serial-queue model
                 `predict_queue_ms(service, depth)` on the live
                 rolling service estimate. The window is the MINIMUM
                 headroom, clamped to [0, window_max_ms]. Requests
                 without a budget afford the full window; an unknown
                 service estimate predicts 0 (never starve the window
                 blind — the budget itself still caps).

    pressure     waiting only pays if a companion is likely to arrive
                 within the cap: when the live arrival-gap estimate
                 (median enqueue-to-enqueue spacing) exceeds the cap,
                 the expected yield of waiting is zero requests, so
                 dispatch immediately — an idle or lightly-loaded node
                 adds NO latency. Under pressure (gap <= cap) the full
                 cap is spent; the backlog that forms while a wave
                 executes coalesces on top of it for free.
    """
    cap = float(window_max_ms)
    predicted = predict_queue_ms(service_ms, queue_depth)
    if predicted is None:
        predicted = 0.0
    for budget in budgets_ms:
        if budget is None:
            continue
        cap = min(cap, budget - predicted)
    cap = max(0.0, min(cap, float(window_max_ms)))
    if cap <= 0.0:
        return 0.0
    if arrival_gap_ms is None or arrival_gap_ms > cap:
        return 0.0
    return cap


class _RehydratedItemError(OpenSearchTpuError):
    """Re-raise a per-item envelope error object as the typed exception
    the inline (non-scheduler) path would have raised: same
    `to_xcontent` payload, same status — the REST error body stays
    byte-identical whether the request rode the scheduler or not."""

    def __init__(self, payload: dict, status: int):
        super().__init__(str(payload.get("reason", "")))
        self._payload = dict(payload)
        self.status = int(status)
        self.error_type = str(payload.get("type", "exception"))

    def to_xcontent(self) -> dict:
        return dict(self._payload)


class _SchedItem:
    """One queued submission: a single search (one body) or a whole
    msearch envelope's admitted bodies (the envelope coalesces as a
    unit — queue bookkeeping stays O(1) per envelope)."""

    __slots__ = ("target", "bodies", "deadline", "timeline", "tenant",
                 "task", "enq_t", "done", "responses", "error", "shed",
                 "trace_id")

    def __init__(self, target, bodies, deadline, timeline, tenant, task,
                 enq_t):
        self.target = target
        self.bodies = bodies
        self.deadline = deadline
        self.timeline = timeline
        self.tenant = tenant
        self.task = task
        self.enq_t = enq_t
        # the submitting request's trace in the always-on span ring
        # (telemetry/tracer.py): the shared envelope runs on the
        # scheduler's thread and lists the traces it serves
        from opensearch_tpu.telemetry import TELEMETRY
        trace = TELEMETRY.tracer.spans.current()
        self.trace_id = trace.trace_id if trace is not None else None
        self.done = threading.Event()
        self.responses: Optional[List[dict]] = None
        self.error: Optional[BaseException] = None
        self.shed = 0           # sub-requests shed at deadline (the
        # quota-refund count the REST layer settles)


def _timed_out_partial(enq_t: float) -> dict:
    """The reference per-request timeout shape for a sub-request whose
    deadline expired inside the coalesce window: a zero-hit partial
    with `timed_out: true` — a budget decision, never an error (the
    executor's `_timed_out_item` contract, anchored on enqueue so
    `took` covers the real wait)."""
    from opensearch_tpu.search.executor import _timed_out_item
    return _timed_out_item(enq_t)


class WaveScheduler:
    """The node's cross-request micro-batching layer. OFF by default;
    `gate()` returns None when disabled (one attribute load + branch on
    the hot path — the tracer/ledger/injector/flight-recorder
    discipline, gate-lint registered).

    `admission` (the node's AdmissionController) supplies the live
    service estimate the window math prices with and receives this
    queue's depth through `queue_depth_extra`, so the deadline-shed
    stage prices arrivals against the REAL scheduler queue.

    Threading: request threads block in `execute`/`execute_many` on a
    per-item Event while ONE scheduler thread windows, groups,
    dispatches and demultiplexes. `autostart=False` +
    `pump_once()` give tests a fully synchronous, seeded-deterministic
    harness — no thread, explicit clock."""

    # msearch envelopes at or under this many sub-requests ride the
    # coalescing queue (cross-envelope shared waves); larger envelopes
    # are ALREADY the batch the scheduler exists to build and dispatch
    # inline — queueing them would only add per-item bookkeeping
    msearch_coalesce_max = 64

    def __init__(self, admission=None, clock: Callable[[], float]
                 = time.monotonic, autostart: bool = True):
        self.enabled = False
        self.admission = admission
        self.window_max_ms = DEFAULT_WINDOW_MS
        self.max_queue = DEFAULT_MAX_QUEUE
        self.max_batch = DEFAULT_MAX_BATCH
        self.slo_ms: Optional[float] = None
        self._clock = clock
        self._autostart = autostart
        self._cv = threading.Condition(threading.Lock())
        self._queue: "deque[_SchedItem]" = deque()
        self._depth = 0             # queued sub-requests (bounded)
        self._thread: Optional[threading.Thread] = None
        self._running = False
        # live estimators: per-sub-request amortized service wall (own
        # stream — the admission shedder's, when present and warm, is
        # preferred for the window math so both layers price with ONE
        # model) and the enqueue-to-enqueue arrival gap. The WINDOW
        # prices with the median of the last few gaps (deque below),
        # not the rolling estimator: offered load shifts in
        # milliseconds and a minutes-half-life estimate left stale-low
        # by a burst would charge the window to serial traffic
        # (measured: a post-burst closed loop paid the full cap per
        # request); the rolling stream still feeds stats.
        self.service_est = RollingEstimator()
        self.arrival_gap_est = RollingEstimator()
        self._recent_gaps: "deque[float]" = deque(maxlen=16)
        self._last_enq: Optional[float] = None
        # stats (all read under _cv's lock in stats())
        self.submitted = 0          # sub-requests ever enqueued
        self.dispatches = 0         # shared dispatch calls
        self.coalesced_total = 0    # sub-requests in co_batched>1 waves
        self.solo_total = 0
        self.shed_deadline = 0
        self.rejected_full = 0
        self.cancelled = 0
        self.co_batched_max = 0
        self.last_window_ms = 0.0
        self.co_batched_est = RollingEstimator()
        self.window_est = RollingEstimator()
        self.queue_wait_est = RollingEstimator()

    # ------------------------------------------------------------- gating

    def gate(self) -> Optional["WaveScheduler"]:
        """The per-request gate: None when the scheduler is disabled —
        callers fall straight through to the inline execute path."""
        if not self.enabled:
            return None
        return self

    def queue_depth(self) -> int:
        """Queued sub-requests — the `queue_depth_extra` feed for the
        admission controller's deadline-shed pricing (a plain int read;
        staleness by one item is fine for a shed estimate)."""
        return self._depth

    @staticmethod
    def eligible(body: Optional[dict]) -> bool:
        """A body the batched envelope serves bit-identically to the
        inline path: the plain batchable shape (PR 5 interning family)
        or the hybrid envelope shape — everything else (scroll, sort,
        inner_hits, aggs-with-pipelines, ...) executes inline, so an
        exotic request can never head-of-line-block the wave queue."""
        from opensearch_tpu.search.executor import (
            _hybrid_msearch_batchable, _msearch_batchable)
        body = body or {}
        return _msearch_batchable(body) or _hybrid_msearch_batchable(body)

    # ---------------------------------------------------------- lifecycle

    def set_enabled(self, on: bool) -> None:
        """Enable starts the scheduler thread; disable stops it AFTER
        draining — every queued request is dispatched (windowless)
        before the thread exits, so no waiter strands."""
        with self._cv:
            if on and not self._running:
                self.enabled = True
                self._running = True
                if self._autostart:
                    self._thread = threading.Thread(
                        target=self._loop, name="wave-scheduler",
                        daemon=True)
                    self._thread.start()
                return
            if not on:
                self.enabled = False
                self._running = False
                self._cv.notify_all()
                thread = self._thread
                self._thread = None
        if not on and thread is not None:
            thread.join(timeout=30)

    # ------------------------------------------------------------- submit

    def execute(self, target, body: dict,
                deadline: Optional[float] = None, timeline=None,
                tenant: Optional[str] = None, task=None) \
            -> Tuple[dict, bool]:
        """Blocking single-search submit. Returns (response, shed) —
        `shed` True when the deadline expired inside the window and the
        response is the timed-out partial (the caller refunds the
        quota token: the request never executed). A per-item error
        object re-raises as the typed exception the inline path would
        have raised (byte-identical REST error body)."""
        responses, shed = self.execute_many(
            target, [body], deadline=deadline, timeline=timeline,
            tenant=tenant, task=task)
        res = responses[0]
        if isinstance(res, dict) and "error" in res and "status" in res \
                and not shed:
            raise _RehydratedItemError(res["error"], res["status"])
        return res, bool(shed)

    def execute_many(self, target, bodies: List[dict],
                     deadline: Optional[float] = None, timeline=None,
                     tenant: Optional[str] = None, task=None) \
            -> Tuple[List[dict], int]:
        """Blocking envelope submit: the bodies coalesce as a unit with
        whatever else the window collects for the same target. Returns
        (per-item responses, shed-count). Raises the queue-full 429
        when the bounded queue cannot take the envelope — the caller
        refunds and renders it through the PR 11 machinery."""
        n = len(bodies)
        now = self._clock()
        item = _SchedItem(target, bodies, deadline, timeline, tenant,
                          task, now)
        inline = False
        with self._cv:
            if not self._running:
                # disabled between the caller's gate() and here (or a
                # synchronous test harness): serve inline — never
                # hang. Dispatch happens OUTSIDE the lock below:
                # device work under _cv would block every concurrent
                # submitter and stats() reader for its duration.
                inline = True
            elif self._depth + n > self.max_queue:
                self.rejected_full += 1
                raise AdmissionRejectedError(
                    f"rejected execution of search: scheduler queue is "
                    f"full [{self._depth} + {n} > {self.max_queue}]",
                    reject_reason=REASON_QUEUE_FULL, tenant=tenant,
                    bytes_wanted=self._depth + n,
                    bytes_limit=self.max_queue,
                    retry_after_ms=self._retry_after_ms())
            else:
                if self._last_enq is not None:
                    gap = max((now - self._last_enq) * 1000.0, 0.0)
                    self.arrival_gap_est.observe(gap)
                    self._recent_gaps.append(gap)
                self._last_enq = now
                self.submitted += n
                self._queue.append(item)
                self._depth += n
                self._cv.notify_all()
        if inline:
            self._dispatch_group([item])
        item.done.wait()
        if item.error is not None:
            raise item.error
        return item.responses, item.shed

    def _retry_after_ms(self) -> float:
        """Queue-full Retry-After: the predicted time for the CURRENT
        queue to drain ahead of a retry — the PR 11 serial-queue
        estimate, not one item's service wall (a full 1024-deep queue
        advertising 'retry in 1ms' just re-rejects honest clients in a
        tight loop). Floored at 1ms like every admission header."""
        predicted = predict_queue_ms(self._service_estimate_ms(),
                                     self._depth)
        return max(predicted if predicted else 0.0, 1.0)

    # ----------------------------------------------------- window sizing

    def _service_estimate_ms(self) -> Optional[float]:
        """The per-request service estimate the window math prices
        with: the admission shedder's near-exclusive median when it has
        one (so scheduler and shed price with the SAME model), else
        this scheduler's own amortized-wall stream."""
        if self.admission is not None:
            q = self.admission.shedder.service_ms.quantile(0.5)
            if q:
                return q
        return self.service_est.quantile(0.5)

    def _gap_estimate_ms(self) -> Optional[float]:
        """Median of the last few enqueue gaps — adapts to an offered-
        load shift within one deque-full of arrivals. None until a
        handful of gaps exist (an unknown rate never opens the
        window)."""
        gaps = sorted(self._recent_gaps)
        if len(gaps) < 4:
            return None
        return gaps[len(gaps) // 2]

    def _window_ms(self) -> float:
        """Size the window for the CURRENT queue (called with _cv
        held): budgets from each queued item's deadline remainder (or
        the node SLO), depth = everything queued ahead."""
        now = self._clock()
        budgets: List[Optional[float]] = []
        for it in self._queue:
            if it.deadline is not None:
                budgets.append((it.deadline - now) * 1000.0)
            else:
                budgets.append(self.slo_ms)
        w = plan_window_ms(
            budgets, self._service_estimate_ms(), self._depth,
            self._gap_estimate_ms(), self.window_max_ms)
        self.last_window_ms = w
        return w

    # ----------------------------------------------------------- dispatch

    def _loop(self) -> None:
        while True:
            with self._cv:
                while self._running and not self._queue:
                    self._cv.wait(0.1)
                if not self._queue:
                    if not self._running:
                        return
                    continue
                window_ms = self._window_ms() if self._running else 0.0
                if window_ms > 0:
                    # hold the window open: collect arrivals until it
                    # closes or the batch is full. Anchored at the
                    # FIRST waiter's enqueue so a request never waits
                    # more than one full window.
                    end = self._queue[0].enq_t + window_ms / 1000.0
                    while self._running and self._depth < self.max_batch:
                        left = end - self._clock()
                        if left <= 0:
                            break
                        self._cv.wait(left)
                batch: List[_SchedItem] = []
                taken = 0
                while self._queue and taken < self.max_batch:
                    item = self._queue.popleft()
                    self._depth -= len(item.bodies)
                    taken += len(item.bodies)
                    batch.append(item)
            self._pump(batch)

    def pump_once(self) -> int:
        """Synchronous test harness: drain the queue and dispatch it on
        the calling thread (no window wait). Returns the number of
        sub-requests served."""
        with self._cv:
            batch = list(self._queue)
            self._queue.clear()
            served = self._depth
            self._depth = 0
        self._pump(batch)
        return served

    def _pump(self, batch: List[_SchedItem]) -> None:
        """Group a drained batch by target executor and dispatch each
        group as one shared envelope. Grouping preserves arrival order
        inside a group; finer (template, segment, shape-bucket)
        grouping is the envelope's own interning machinery."""
        if not batch:
            return
        groups: Dict[int, List[_SchedItem]] = {}
        for item in batch:
            groups.setdefault(id(item.target), []).append(item)
        for items in groups.values():
            self._dispatch_group(items)

    def _dispatch_group(self, items: List[_SchedItem]) -> None:
        """One shared wave dispatch: expire/cancel the dead, send the
        live bodies through the target's wave pipeline with the
        timeline fan, demux per-item responses, wake every waiter.
        EVERY item's Event is set on EVERY path — a failed dispatch
        wakes its waiters with the error, never strands them."""
        now = self._clock()
        live: List[_SchedItem] = []
        for item in items:
            if item.task is not None:
                try:
                    item.task.check_cancelled()
                except OpenSearchTpuError as e:
                    # cancellation drains the queue: the cancelled
                    # request leaves with its typed error at the next
                    # pump instead of burning a shared wave slot
                    self.cancelled += len(item.bodies)
                    item.error = e
                    item.done.set()
                    continue
            if item.deadline is not None and now > item.deadline:
                n = len(item.bodies)
                self.shed_deadline += n
                item.shed = n
                item.responses = [_timed_out_partial(item.enq_t)
                                  for _ in range(n)]
                if item.timeline is not None:
                    item.timeline.queue_wait((now - item.enq_t) * 1000.0)
                item.done.set()
                continue
            live.append(item)
        if not live:
            return
        bodies: List[dict] = []
        timelines: List[Any] = []
        group_deadline: Optional[float] = None
        saw_unbounded = False
        for item in live:
            wait_ms = (now - item.enq_t) * 1000.0
            self.queue_wait_est.observe(wait_ms)
            if item.timeline is not None:
                # the REAL queue_wait the lifecycle contract reserved
                # this field for (PR 10: "the field the wave scheduler
                # fills") — emitted from the scheduler thread, read
                # only after completion
                item.timeline.queue_wait(wait_ms)
            bodies.extend(item.bodies)
            timelines.extend(item.timeline for _ in item.bodies)
            if item.deadline is None:
                saw_unbounded = True
            elif group_deadline is None or item.deadline > group_deadline:
                group_deadline = item.deadline
        # the shared envelope runs under the LOOSEST member deadline (a
        # tight sibling is served a touch late rather than killing the
        # whole wave's work); any unbounded member unbounds the wave
        if saw_unbounded:
            group_deadline = None
        n = len(bodies)
        self.dispatches += 1
        self.co_batched_est.observe(float(n))
        self.window_est.observe(self.last_window_ms)
        if n > self.co_batched_max:
            self.co_batched_max = n
        if n > 1:
            self.coalesced_total += n
        else:
            self.solo_total += 1
        from opensearch_tpu.telemetry import TELEMETRY
        TELEMETRY.metrics.counter("scheduler.dispatches").inc()
        TELEMETRY.metrics.histogram("scheduler.co_batched").observe(n)
        # per-tenant byte attribution rides the ledger: when it is on,
        # the envelope fills phase_times with the wave's fetched bytes,
        # split below proportionally like the wall (None keeps the
        # disabled path at one attribute load + branch)
        pt = {} if TELEMETRY.ledger.enabled else None
        # per-item tenants ride along for the insights recorder's
        # per-shape tenant breakdown (ISSUE 15): the shared dispatch
        # runs on the scheduler thread, so the REST layer's thread-local
        # binding cannot reach it — the owning requests' tenants go per
        # item, aligned with `timelines` (None = recorder off, one
        # attribute load + branch)
        tenants = [item.tenant for item in live
                   for _ in item.bodies] \
            if TELEMETRY.insights.enabled else None
        # the span ring's traces the shared envelope serves, per body;
        # passed only where a request opened one (the HTTP path)
        traced = {}
        if any(item.trace_id is not None for item in live):
            traced["trace_ids"] = [item.trace_id for item in live
                                   for _ in item.bodies]
        t0 = time.monotonic()
        try:
            res = live[0].target.multi_search(
                bodies, deadline=group_deadline, timelines=timelines,
                phase_times=pt, tenants=tenants, **traced)
            responses = res["responses"]
        except BaseException as e:  # except-ok: waiter wakeup -- a dispatch failure delivers the error to every blocked request thread instead of stranding them on the Event
            for item in live:
                item.error = e
                item.done.set()
            return
        wall_ms = (time.monotonic() - t0) * 1000.0
        self.service_est.observe(wall_ms / max(n, 1))
        wave_bytes = int(pt.get("bytes_fetched", 0)) if pt else 0
        off = 0
        for item in live:
            item.responses = responses[off:off + len(item.bodies)]
            off += len(item.bodies)
            # per-tenant resource attribution (ISSUE 14): the shared
            # wave's device wall (and fetched bytes) split across its
            # co-batched owners by item count — each request's
            # `device_share_ms` lifecycle field plus the per-tenant
            # totals the admission `usage` block accumulates
            n_items = len(item.bodies)
            share_ms = wall_ms * n_items / n
            if item.timeline is not None:
                item.timeline.device_share(share_ms, wall_ms, n)
            if self.admission is not None:
                self.admission.note_usage(
                    item.tenant, share_ms,
                    d2h_bytes=wave_bytes * n_items // n,
                    items=n_items)
            if item.timeline is not None:
                # response assembled HERE: complete() turns the
                # ready→completed interval into the `handoff` phase —
                # under contention that is the waiter's measured
                # wakeup/GIL starvation, otherwise-invisible wall
                item.timeline.mark_ready()
            item.done.set()

    # ------------------------------------------------------------ settings

    @staticmethod
    def parse_settings(flat: Dict[str, Any]) -> Dict[str, Any]:
        """Parse + validate the scheduler keys out of a flat settings
        map without mutating anything — the REST layer dry-runs this
        before committing a cluster-settings update (the PR 11
        validate-then-commit contract)."""
        from opensearch_tpu.common.errors import SettingsError
        from opensearch_tpu.common.settings import _parse_bool

        def _num(key, cast=float):
            v = flat.get(key)
            if v is None:
                return None
            try:
                out = cast(v)
            except (TypeError, ValueError):
                raise SettingsError(
                    f"Failed to parse value [{v}] for setting [{key}]")
            if out < 0:
                raise SettingsError(
                    f"Failed to parse value [{v}] for setting [{key}]: "
                    f"must be >= 0")
            return out

        v = flat.get("search.scheduler.enabled")
        return {
            "enabled": None if v is None
            else _parse_bool(v, "search.scheduler.enabled"),
            "window_ms": _num("search.scheduler.window_ms"),
            "max_queue": _num("search.scheduler.max_queue", int),
            "max_batch": _num("search.scheduler.max_batch", int),
            "slo_ms": _num("search.scheduler.slo_ms"),
        }

    def apply_settings(self, flat: Dict[str, Any]) -> None:
        """Apply node/cluster settings (flat keys, dynamic — the REST
        cluster-settings path re-runs this on every update)."""
        p = self.parse_settings(flat)
        if p["window_ms"] is not None:
            self.window_max_ms = p["window_ms"]
        if p["max_queue"] is not None:
            self.max_queue = max(int(p["max_queue"]), 1)
        if p["max_batch"] is not None:
            self.max_batch = max(int(p["max_batch"]), 1)
        if p["slo_ms"] is not None:
            self.slo_ms = p["slo_ms"] if p["slo_ms"] > 0 else None
        if p["enabled"] is not None and p["enabled"] != self.enabled:
            self.set_enabled(p["enabled"])

    # --------------------------------------------------------------- stats

    def stats(self) -> dict:
        """The `scheduler` block on `_nodes/stats`: queue depth, live
        window size, coalesce ratio, per-wave co_batched histogram."""
        with self._cv:
            submitted = self.submitted
            coalesced = self.coalesced_total
            return {
                "enabled": self.enabled,
                "queue_depth": self._depth,
                "max_queue": self.max_queue,
                "max_batch": self.max_batch,
                "window_max_ms": self.window_max_ms,
                "last_window_ms": round(self.last_window_ms, 3),
                "slo_ms": self.slo_ms,
                "submitted": submitted,
                "dispatched_waves": self.dispatches,
                "coalesced": coalesced,
                "solo": self.solo_total,
                "coalesce_ratio": round(coalesced / submitted, 3)
                if submitted else 0.0,
                "shed_deadline": self.shed_deadline,
                "rejected_queue_full": self.rejected_full,
                "cancelled": self.cancelled,
                "co_batched": {**self.co_batched_est.summary(),
                               "max": self.co_batched_max},
                "window_ms": self.window_est.summary(),
                "queue_wait_ms": self.queue_wait_est.summary(),
                "service_ms": self.service_est.summary(),
            }
