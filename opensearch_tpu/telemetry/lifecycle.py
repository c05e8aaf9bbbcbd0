"""Request lifecycle timeline + tail-latency flight recorder + the
write-path ingest lifecycle (ISSUE 13).

ROADMAP item 2 (cross-request dynamic batching) needs to be judged
against numbers, and the numbers that matter under contention are
per-request *when-did-you-wait* numbers: how long a request queued, which
device wave it shared with how many co-batched siblings, and where the
p99.9 outliers actually spent their wall. Nothing in the repo could see
any of that — the tracer times phases, the ledger counts bytes, but
neither records the request's *schedule*. This module is that contract:

- `Timeline` — one request's monotonic-timestamped lifecycle events:
  `arrive` (implicit at construction), `admit`/`reject`, `queue_wait`
  (how long admission held the request; the future wave scheduler fills
  this with real queue delay — today the backpressure gate admits
  immediately, so it reads ~0), `coalesce` (wave id + co-batched
  request count), `dispatch` (wave id + in-flight pipeline depth),
  `collect`, `overlap` (per-wave dispatch/collect overlap, the PR 9
  pipeline win) and `respond`. Phase milliseconds (the controller's
  phase dict / the msearch envelope's ph map) merge in so a completed
  timeline decomposes its own wall. Completed timelines attach to the
  request's root span as the `lifecycle` attribute.

- `FlightRecorder` — the tail-latency capture ring: a completed
  timeline is retained when the request breached an explicit SLO
  threshold (`threshold_ms`) or beat the LIVE rolling p99 of recent
  takes (telemetry/rolling.py, min_samples warmup so the first requests
  don't all self-trigger). Served by `GET /_telemetry/tail`, togglable
  via `POST /_telemetry/tail/_enable|_disable|_clear`, optional JSONL
  export under `_state/tail.jsonl`, rendered by tools/tail_report.py.
  Every capture carries an `ingest_events` annotation: the engine
  refresh/merge/flush events whose wall overlapped the captured
  request's window (empty list when the write path was quiet) — the
  "did a merge cause this p99" join tools/tail_report.py renders.

- `IngestEventLog` — the engine's write-path event log: one bounded
  record per refresh/merge/flush (seg ids, docs, seal wall, live-doc
  ratio) on the monotonic clock, fed by index/engine.py. Live
  regardless of any gate (the inflight-wave-gauge contract: one lock +
  append per REFRESH, never per op) so a tail capture can always be
  joined against the write path that ran under it.

- `IngestRecorder` — the write path's FlightRecorder analog (ISSUE 13):
  per-op and per-bulk ingest timelines (arrive/admit/parse/
  version_plan/translog_append/refresh_wait/respond) recorded into a
  bounded ring with rolling took percentiles, OFF by default behind the
  same None-returning `timeline()` gate (gate-lint registry row).
  The engine reads the thread-bound
  timeline via `current()` — write ops run start-to-finish on one
  thread, so ambient context is safe here (unlike the msearch
  envelope). Served by `GET /_telemetry/ingest`.

No-op discipline (the tracer/ledger/faults contract, statically enforced
by gate-lint's subsystem registry): the
recorder is OFF by default and the hot-path gate is `timeline()`
returning None — one attribute load and a branch, nothing else runs.
Event appends are plain list appends (GIL-atomic): a timeline is written
by at most the request thread + the wave collector thread, and only read
after the pipeline drained.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from opensearch_tpu.telemetry.rolling import RollingEstimator

DEFAULT_TAIL_RING = 64

# the lifecycle event vocabulary (README Observability documents each);
# fanout/partial/merge are the collective-phase events the SPMD path
# emits (ISSUE 14) — "which chip was the straggler" answered the way
# coalesce/dispatch/collect already answer "did a merge cause this p99"
EVENTS = ("arrive", "admit", "reject", "queue_wait", "coalesce",
          "dispatch", "collect", "overlap", "respond",
          "fanout", "partial", "merge", "device_share")

# phase_times carries non-time fields next to the millisecond ones
# (LedgerScope.publish writes bytes/waves into the same dict the slow
# log reads); a timeline's phase map keeps only durations
_NON_TIME_PHASES = frozenset({"bytes_fetched", "bytes_to_device",
                              "waves"})


class Timeline:
    """One request's lifecycle: monotonic event offsets + phase times.

    `t_arrive` anchors every event at construction time; offsets are
    milliseconds since arrival, so a dumped timeline reads as the
    request's own clock. `queue_wait_ms` is a first-class field (not
    just an event) because it is THE number the wave-scheduler's
    admission work will be judged by."""

    __slots__ = ("t_arrive", "t_ready", "events", "phases",
                 "queue_wait_ms", "device_share_ms", "took_ms", "status",
                 "detail", "shape")

    def __init__(self):
        self.t_arrive = time.monotonic()
        self.t_ready: Optional[float] = None
        # (event name, ms since arrive, extra fields or None)
        self.events: List[Tuple[str, float, Optional[dict]]] = [
            ("arrive", 0.0, None)]
        self.phases: Dict[str, float] = {}
        self.queue_wait_ms = 0.0
        # this request's proportional slice of the shared wave's device
        # wall (ISSUE 14 per-tenant attribution): filled by the wave
        # scheduler after dispatch — wall × (own items / wave items)
        self.device_share_ms = 0.0
        self.took_ms: Optional[float] = None
        self.status = "ok"
        # detail=True: producers may append per-step events in addition
        # to phase accumulation (set for single-op ingest timelines; a
        # 1000-op bulk accumulates phases only, or its event list would
        # balloon to 3N tuples)
        self.detail = False
        # the request's shape class (ISSUE 15): the interned-template /
        # structural-hash id telemetry/insights.py groups costs by —
        # stamped by the executor/controller when they resolve it, so a
        # tail capture answers "which shape owns this p99" the way
        # ingest_events answers "did a merge cause it" (None = the
        # serving path never resolved one, e.g. a rejected request)
        self.shape: Optional[str] = None

    def event(self, name: str, **fields) -> None:
        self.events.append(
            (name, round((time.monotonic() - self.t_arrive) * 1000, 3),
             fields or None))

    def queue_wait(self, ms: float) -> None:
        """Time the request spent waiting for admission/scheduling —
        measured by whoever held it (the backpressure gate today, the
        wave scheduler's queue tomorrow)."""
        self.queue_wait_ms += ms
        self.event("queue_wait", ms=round(ms, 3))

    def device_share(self, ms: float, wave_ms: float,
                     co_batched: int) -> None:
        """This request's proportional slice of a shared wave's device
        wall (ISSUE 14): the scheduler splits each dispatch's wall
        across its co-batched owners by item count — the usage-side
        number the per-tenant accounting accumulates."""
        self.device_share_ms += ms
        self.event("device_share", ms=round(ms, 3),
                   wave_ms=round(wave_ms, 3), co_batched=int(co_batched))

    def route(self) -> None:
        """Attribute the so-far-unexplained arrive→now interval as the
        `route` phase: REST glue, pipeline resolution and parse/
        validation plumbing between a request's arrival and the phase-
        timed engine taking over. Anchored on the arrive clock and
        called at engine entry points (controller impl, msearch
        envelope) — each call covers only the gap not yet explained by
        queue_wait + recorded phases, so the calls compose and a slow
        request's pre-engine wall (GIL starvation under concurrent
        clients lives exactly here) stops reading as unattributed."""
        gap = (time.monotonic() - self.t_arrive) * 1000 \
            - self.queue_wait_ms - sum(self.phases.values())
        if gap > 0:
            self.phases["route"] = self.phases.get("route", 0.0) + gap

    def mark_ready(self) -> None:
        """Stamp the response-assembled instant. `complete()` turns the
        ready→completed interval into the `handoff` phase: coordinator
        exit glue + response processors + GIL/scheduler starvation on
        the way out. Under N concurrent clients this is real, otherwise
        invisible wall (a slow request can spend tens of ms here), and
        it is measured from two clock reads, never derived as a
        remainder."""
        self.t_ready = time.monotonic()
        self.event("ready")

    def phase_add(self, name: str, ms: float) -> None:
        """Accumulate one phase's milliseconds; when `detail` is set,
        also append a discrete event (the per-op ingest timeline shape —
        arrive/parse/version_plan/translog_append read as a sequence)."""
        self.phases[name] = self.phases.get(name, 0.0) + ms
        if self.detail:
            self.event(name, ms=round(ms, 3))

    def merge_phases(self, phase_ms: Dict[str, float]) -> None:
        """Accumulate per-phase milliseconds (controller phase dict or
        msearch ph map); non-duration fields riding the same dict
        (bytes, wave counts) are dropped."""
        for name, ms in phase_ms.items():
            if name in _NON_TIME_PHASES:
                continue
            self.phases[name] = self.phases.get(name, 0.0) + float(ms)

    def to_dict(self) -> dict:
        out: Dict[str, Any] = {
            "status": self.status,
            "took_ms": self.took_ms,
            "queue_wait_ms": round(self.queue_wait_ms, 3),
            "events": [
                {"event": name, "t_ms": t, **(fields or {})}
                for name, t, fields in self.events],
        }
        if self.device_share_ms:
            out["device_share_ms"] = round(self.device_share_ms, 3)
        if self.shape is not None:
            out["shape"] = self.shape
        if self.phases:
            out["phases"] = {name: round(ms, 3)
                             for name, ms in self.phases.items()}
        return out


class IngestEventLog:
    """Bounded node-wide log of engine write-path events (refresh /
    merge / flush), fed by index/engine.py on the monotonic clock.

    Live regardless of any enable flag — the inflight-wave-gauge
    contract, not the per-request gate discipline: the cost is one lock
    acquire + deque append per REFRESH (never per op), and a
    `_nodes/stats` poll or a tail capture must be able to join against
    the write path that actually ran, whether or not anyone thought to
    enable ingest telemetry first."""

    def __init__(self, ring_size: int = 256):
        self._lock = threading.Lock()
        self._ring: "deque[dict]" = deque(maxlen=ring_size)
        self._seq = 0
        self.counts: Dict[str, int] = {}

    def note(self, kind: str, t0_mono: float, t1_mono: float,
             **fields) -> dict:
        """Record one engine event; returns the stored record (the
        engine's refresh/merge paths hand it to the churn ledger so a
        churn record and its event share an `event_id`)."""
        ev = {"kind": kind,
              "t0_mono": round(t0_mono, 6),
              "t1_mono": round(t1_mono, 6),
              "wall_ms": round((t1_mono - t0_mono) * 1000, 3),
              **fields}
        with self._lock:
            self._seq += 1
            ev["event_id"] = self._seq
            self._ring.append(ev)
            self.counts[kind] = self.counts.get(kind, 0) + 1
        return ev

    def overlapping(self, t0_mono: float, t1_mono: float) -> List[dict]:
        """Events whose wall intersects [t0, t1] on the monotonic clock
        — the `ingest_events` annotation a flight capture carries. Event
        times are rebased to ms offsets from t0 so the annotation reads
        on the capture's own clock."""
        with self._lock:
            evs = list(self._ring)
        out = []
        for ev in evs:
            if ev["t0_mono"] <= t1_mono and ev["t1_mono"] >= t0_mono:
                rec = {k: v for k, v in ev.items()
                       if k not in ("t0_mono", "t1_mono")}
                rec["t_rel_ms"] = round(
                    (ev["t0_mono"] - t0_mono) * 1000, 3)
                out.append(rec)
        return out

    def recent(self, size: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = [{k: v for k, v in ev.items()
                    if k not in ("t0_mono", "t1_mono")}
                   for ev in self._ring]
        out.reverse()
        return out[:size] if size is not None else out

    def events_by_id(self) -> Dict[int, dict]:
        """{event_id: record} over the retained ring (consistency checks
        in tests join capture annotations against this)."""
        with self._lock:
            return {ev["event_id"]: dict(ev) for ev in self._ring}

    def stats(self) -> dict:
        with self._lock:
            return {"events": self._seq, "retained": len(self._ring),
                    "by_kind": dict(self.counts)}

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.counts = {}


# node-wide write-path event log: engine feeds it, flight captures and
# GET /_telemetry/ingest read it
INGEST_EVENTS = IngestEventLog()


class FlightRecorder:
    """Bounded ring of slow requests' complete timelines.

    Capture policy (decided at `complete()`):
      - `threshold_ms` set and took >= it  -> trigger "threshold";
      - otherwise, once `min_samples` takes have been observed, took
        above the LIVE rolling p99 of recent takes -> trigger "p99".
    The rolling estimator decays (telemetry/rolling.py), so "p99" means
    the p99 of the last few minutes of traffic, not since node start —
    a latency regression shows up as captures within its half-life.
    """

    def __init__(self, ring_size: int = DEFAULT_TAIL_RING):
        self.enabled = False
        self.threshold_ms: Optional[float] = None
        self.p99_trigger = True
        self.min_samples = 32
        self.took = RollingEstimator()
        self._ring: "deque[dict]" = deque(maxlen=ring_size)
        self._lock = threading.Lock()
        self._io_lock = threading.Lock()
        self.jsonl_path: Optional[str] = None
        self.completed = 0
        self.events_total = 0
        self.captures = {"threshold": 0, "p99": 0}
        self.export_errors = 0
        self._tls = threading.local()

    # ------------------------------------------------------------- hot path

    def timeline(self) -> Optional[Timeline]:
        """The per-request gate: a Timeline when the recorder is on,
        else None — callers guard with `if tl is not None`, so the
        disabled query path costs one attribute load and a branch."""
        if not self.enabled:
            return None
        return Timeline()

    def current(self) -> Optional[Timeline]:
        """The thread's bound request timeline, if a caller bound one."""
        return getattr(self._tls, "timeline", None)

    def bind(self, tl: Optional[Timeline]) -> Optional[Timeline]:
        """Bind a request's timeline to this thread (the REST layer owns
        the request; the controller/executor read it back via
        `current()`). Returns the previous binding for `unbind`."""
        prev = getattr(self._tls, "timeline", None)
        self._tls.timeline = tl
        return prev

    def unbind(self, prev: Optional[Timeline]) -> None:
        self._tls.timeline = prev

    def complete(self, tl: Timeline, status: str = "ok",
                 span=None) -> Optional[str]:
        """Close a request's timeline: stamp took, feed the live take
        estimator, decide capture, attach to the root span. Returns the
        capture trigger (or None). Idempotence is the caller's job
        (guard on `tl.took_ms is None` when two exit paths can race)."""
        tl.status = status
        t_done = time.monotonic()
        tl.took_ms = round((t_done - tl.t_arrive) * 1000, 3)
        if tl.t_ready is not None:
            handoff = (t_done - tl.t_ready) * 1000
            if handoff > 0:
                tl.phases["handoff"] = \
                    tl.phases.get("handoff", 0.0) + handoff
        trigger = None
        thr = self.threshold_ms
        if thr is not None and tl.took_ms >= thr:
            trigger = "threshold"
        elif self.p99_trigger:
            # trigger reads the estimator BEFORE this sample lands, so
            # one slow request cannot raise the bar it is judged against.
            # warmup gates on LIFETIME completions (self.completed, not
            # the estimator's decayed total): on a sparse-traffic node
            # the decayed mass can sit below min_samples forever, which
            # would silence the p99 trigger exactly where an explicit
            # threshold is least likely to be configured
            p99 = self.took.quantile(0.99)
            if p99 is not None and self.completed >= self.min_samples \
                    and tl.took_ms > p99:
                trigger = "p99"
        self.took.observe(tl.took_ms)
        if span is not None and getattr(span, "recording", False):
            span.set_attribute("lifecycle", tl.to_dict())
        rec = None
        if trigger is not None:
            # the write-path join (ISSUE 13): every capture carries the
            # engine refresh/merge/flush events whose wall overlapped
            # this request's window — "did a merge cause this p99" is
            # answerable from the capture alone (empty list = the write
            # path was quiet). Built outside the ring lock.
            ingest_events = INGEST_EVENTS.overlapping(tl.t_arrive, t_done)
        with self._lock:
            self.completed += 1
            self.events_total += len(tl.events)
            if trigger is not None:
                rec = {"ts_ms": int(time.time() * 1000),
                       "trigger": trigger, **tl.to_dict(),
                       "ingest_events": ingest_events}
                self._ring.append(rec)
                self.captures[trigger] += 1
        if rec is not None and self.jsonl_path is not None:
            line = json.dumps(rec, default=str) + "\n"
            try:
                with self._io_lock, open(self.jsonl_path, "a") as f:
                    f.write(line)
            except OSError:
                self.export_errors += 1
        return trigger

    # --------------------------------------------------------------- reading

    def captured(self, size: Optional[int] = None) -> List[dict]:
        """Most-recent-first dump of the capture ring."""
        with self._lock:
            out = list(self._ring)
        out.reverse()
        return out[:size] if size is not None else out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.completed = 0
            self.events_total = 0
            self.captures = {"threshold": 0, "p99": 0}
        self.took.reset()

    def resize(self, ring_size: int) -> None:
        with self._lock:
            self._ring = deque(self._ring, maxlen=max(int(ring_size), 1))

    def stats(self) -> dict:
        with self._lock:
            retained = len(self._ring)
            maxlen = self._ring.maxlen
            completed = self.completed
            events_total = self.events_total
            captures = dict(self.captures)
        return {"enabled": self.enabled,
                "threshold_ms": self.threshold_ms,
                "p99_trigger": self.p99_trigger,
                "min_samples": self.min_samples,
                "completed": completed,
                "events_total": events_total,
                "captured": retained,
                "captures": captures,
                "ring_size": maxlen,
                "jsonl_path": self.jsonl_path,
                "export_errors": self.export_errors,
                "took_rolling": self.took.summary()}


class SpmdTimeline:
    """The collective-phase timeline gate (ISSUE 14): when enabled, the
    SPMD query phase (search/spmd.py) emits `fanout` (devices, rows),
    per-device `partial` (device, wall) and `merge` (straggler skew +
    analytic collective bytes) events onto whatever request Timeline is
    bound — so a tail capture of an SPMD-served request answers "which
    chip was the straggler" from the capture alone, the way it already
    answers "did a merge cause this p99" via ingest_events.

    This is a gate over EMISSION, not a recorder: the events land on
    the FlightRecorder's per-request timelines and ride its capture
    ring; rendering is tools/tail_report.py's per-device table.

    No-op discipline (tracer/ledger/faults contract, gate-lint registry
    row): OFF by default, `gate()` returns None —
    the disabled SPMD path costs one attribute load and a branch."""

    def __init__(self):
        self.enabled = False

    def gate(self) -> Optional["SpmdTimeline"]:
        """The per-query gate: None when collective-phase timeline
        emission is off — search/spmd.py falls straight through."""
        if not self.enabled:
            return None
        return self


DEFAULT_INGEST_RING = 64


class IngestRecorder:
    """Write-path lifecycle recorder: per-op and per-bulk ingest
    timelines (ISSUE 13), the FlightRecorder's ingest analog.

    No-op discipline (the tracer/ledger/faults contract, gate-lint
    registry row): OFF by default, the per-request
    gate is `timeline()` returning None, and the engine-side ambient
    read `current()` tests the flag BEFORE touching thread-local state —
    the disabled write path costs one attribute load and a branch per
    op. Binding is thread-local (`bound()`): a write op runs
    start-to-finish on one thread, so ambient context is safe here,
    unlike the msearch envelope's B-requests-one-thread fan-in.

    Completed timelines land in a bounded ring (most recent first via
    `captured()`) with rolling took percentiles split per kind (op vs
    bulk) — there is no SLO trigger: ingest tails are joined against
    search tails through INGEST_EVENTS, not captured independently."""

    def __init__(self, ring_size: int = DEFAULT_INGEST_RING):
        self.enabled = False
        self._ring: "deque[dict]" = deque(maxlen=ring_size)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.took_op = RollingEstimator()
        self.took_bulk = RollingEstimator()
        self.completed = {"op": 0, "bulk": 0}
        self.ops_total = 0
        self.errors = 0

    # ------------------------------------------------------------- hot path

    def timeline(self, detail: bool = True) -> Optional[Timeline]:
        """The per-request gate: a Timeline when the recorder is on,
        else None. `detail` marks single-op timelines (discrete
        parse/version_plan/translog_append events next to the phase
        sums); bulk timelines pass detail=False and accumulate phases
        only."""
        if not self.enabled:
            return None
        tl = Timeline()
        tl.detail = detail
        return tl

    def current(self) -> Optional[Timeline]:
        """The thread's bound ingest timeline — the engine's read. Tests
        the flag first so the disabled path never touches the TLS."""
        if not self.enabled:
            return None
        return getattr(self._tls, "timeline", None)

    def bind(self, tl: Optional[Timeline]) -> Optional[Timeline]:
        prev = getattr(self._tls, "timeline", None)
        self._tls.timeline = tl
        return prev

    def unbind(self, prev: Optional[Timeline]) -> None:
        self._tls.timeline = prev

    @contextmanager
    def bound(self, tl: Optional[Timeline]):
        """Bind a request's ingest timeline for the duration of the
        engine call chain. A None timeline still binds (clears any stale
        outer binding) — cheap, and only reached when enabled."""
        prev = self.bind(tl)
        try:
            yield tl
        finally:
            self.unbind(prev)

    def complete(self, tl: Timeline, status: str = "ok",
                 kind: str = "op", ops: int = 1) -> None:
        tl.status = status
        tl.took_ms = round((time.monotonic() - tl.t_arrive) * 1000, 3)
        (self.took_bulk if kind == "bulk" else self.took_op).observe(
            tl.took_ms)
        rec = {"ts_ms": int(time.time() * 1000), "kind": kind,
               "ops": int(ops), **tl.to_dict()}
        with self._lock:
            self.completed[kind] = self.completed.get(kind, 0) + 1
            self.ops_total += int(ops)
            if status != "ok":
                self.errors += 1
            self._ring.append(rec)

    # --------------------------------------------------------------- reading

    def captured(self, size: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self._ring)
        out.reverse()
        return out[:size] if size is not None else out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.completed = {"op": 0, "bulk": 0}
            self.ops_total = 0
            self.errors = 0
        self.took_op.reset()
        self.took_bulk.reset()

    def stats(self) -> dict:
        with self._lock:
            retained = len(self._ring)
            completed = dict(self.completed)
            ops_total = self.ops_total
            errors = self.errors
        return {"enabled": self.enabled,
                "completed": completed,
                "ops_total": ops_total,
                "errors": errors,
                "retained": retained,
                "took_op_rolling": self.took_op.summary(),
                "took_bulk_rolling": self.took_bulk.summary(),
                "events": INGEST_EVENTS.stats()}
