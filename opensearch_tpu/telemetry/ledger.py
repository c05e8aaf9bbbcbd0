"""Transfer ledger + device-memory accounting for the TPU query path.

A batch's collect wall is one opaque `device_get` number until something
says WHICH bytes cross the host↔device link and how many synchronizations
carry them; the wave scheduler needs the live tail of the same numbers.
This module is that accounting contract:

- `TransferLedger` attributes every host↔device transfer on the query
  path to a named channel (`topk_ids`, `scores`, `sort_keys`,
  `docvalues`, `agg_buffers`, `result_page` — the single-round-trip
  fused page when `search.result_page.enabled` is on —
  `upload.literals`, `upload.corpus`, `upload.agg_constants`,
  `padding`, ...) with direction, bytes (from
  array `nbytes` / shape·dtype — never an extra device sync), wave id
  and round-trip participation. Aggregates serve
  `GET /_telemetry/transfers` and the `telemetry` section of
  `_nodes/stats`; per-request `LedgerScope` objects feed the Profile
  API's `transfers[]` and the slow log's `bytes_fetched`/
  `device_get_ms` fields.

- `DeviceMemoryAccounting` is the HBM analog of the reference's JVM mem
  stats: live-bytes gauges per channel class (corpus columns, interned
  plan bundles, in-flight wave buffers, agg executable constants,
  compiled-executable counts) fed by registration at the owning layer,
  plus raw `jax.local_devices()[0].memory_stats()` where the backend
  provides it.

No-op discipline (same contract as the PR 4 tracer and the PR 6 fault
injector, checked by tools/lint): the ledger is OFF by default and the
hot-path guard is `LEDGER.scope(trace)` returning None — one attribute
load and a branch, nothing else runs. Per-channel `round_trips` counts
the transfer rounds a channel RODE (channels sharing one fused
`device_get` each count that round); the true global round-trip count is
`device_get.calls` in the snapshot.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from opensearch_tpu.telemetry.rolling import RollingEstimator

H2D = "h2d"
D2H = "d2h"

# the host loop and envelope path talk to exactly one device; their
# transfers attribute to it so the per-device table always conserves
# against the channel totals (ISSUE 14's pinned invariant)
DEFAULT_DEVICE = 0

# a query only NAMES a straggler when its per-chip skew clears this
# floor: the per-device walls are measured by blocking replicas in
# device order, so sub-millisecond "skew" is block-ordering noise that
# would otherwise pin every straggler_hit on the last-blocked chip
# (tools/bench_compare.py's skew gate uses the same 1 ms floor)
STRAGGLER_FLOOR_MS = 1.0


class DeviceScope:
    """Per-query per-device accumulator for the SPMD serving path
    (ISSUE 14): the phase breakdown FLASH-MAXSIM's IO-aware framing
    asks for — where and when the bytes moved, per chip.

    Filled by DistributedSearcher.search_resident on the request
    thread:
      - `upload_ms` / `upload_bytes`: the per-query flat-input upload
        (h2d wall measured on host; bytes split per device);
      - `partials`: [(device_id, wall_ms)] — per-chip dispatch→done
        wall, measured by blocking on each device's replica of the
        merged output in device order. The collective aligns chips at
        the merge, so these walls bound each chip's partial top-k
        compute + its wait at the gather; the SKEW (max − median) is
        the straggler signal even when the absolute walls overlap;
      - `merge_*`: the analytic collective-merge accounting — payload
        gathered per device and total ICI bytes (k_local × 3 channels
        × 4 B over the mesh), computed from program statics, never a
        device sync;
      - `pull_ms` / `pull_bytes` / `pull_device`: the result-page
        fetch (the np.asarray d2h sync)."""

    __slots__ = ("devices", "rows", "upload_ms", "upload_bytes",
                 "partials", "merge_payload_bytes", "merge_ici_bytes",
                 "pull_ms", "pull_bytes", "pull_device")

    def __init__(self):
        self.devices = 0
        self.rows = 0
        self.upload_ms = 0.0
        self.upload_bytes = 0
        self.partials: List[Tuple[int, float]] = []
        self.merge_payload_bytes = 0
        self.merge_ici_bytes = 0
        self.pull_ms = 0.0
        self.pull_bytes = 0
        self.pull_device = DEFAULT_DEVICE

    def skew_ms(self) -> float:
        """Straggler skew: max − median per-chip wall for this query
        (0 for a single-chip mesh — there is nobody to straggle
        behind). LOWER median for even chip counts: the upper median
        of two walls IS the max, which would make skew identically 0
        on a 2-chip mesh and structurally blind its straggler gate."""
        if len(self.partials) < 2:
            return 0.0
        walls = sorted(w for _, w in self.partials)
        return walls[-1] - walls[(len(walls) - 1) // 2]

    def straggler(self) -> Optional[int]:
        """The device id with the max per-chip wall — None when fewer
        than two chips reported OR the skew sits under
        STRAGGLER_FLOOR_MS (naming a straggler out of block-ordering
        noise would pin every hit on the last-blocked chip)."""
        if len(self.partials) < 2 \
                or self.skew_ms() < STRAGGLER_FLOOR_MS:
            return None
        return max(self.partials, key=lambda p: p[1])[0]

    def to_dict(self) -> dict:
        """JSON-able phase breakdown — the shape the Profile API's
        SPMD shard entry, the timeline `merge` event and the scaling
        bench all read."""
        return {
            "devices": self.devices,
            "rows": self.rows,
            "upload_ms": round(self.upload_ms, 3),
            "upload_bytes": self.upload_bytes,
            "partials": [{"device": d, "wall_ms": round(w, 3)}
                         for d, w in self.partials],
            "straggler_skew_ms": round(self.skew_ms(), 3),
            "straggler": self.straggler(),
            "collective": {
                "payload_bytes_per_device": self.merge_payload_bytes
                // max(self.devices, 1),
                "payload_bytes": self.merge_payload_bytes,
                "ici_bytes": self.merge_ici_bytes,
            },
            "pull_ms": round(self.pull_ms, 3),
            "pull_bytes": self.pull_bytes,
            "pull_device": self.pull_device,
        }


class DeviceLedger:
    """Per-device attribution for sharded serving (ISSUE 14): the
    `device` dimension on transfer records, the per-chip SPMD phase
    aggregates, and the straggler-skew rolling estimator — the
    measurement layer ROADMAP item 4's multi-chip scale-out is judged
    against, surfaced as `telemetry.devices` on `_nodes/stats`.

    No-op discipline (tracer/ledger/faults contract, gate-lint registry
    row): OFF by default, the per-query gate is
    `scope()` returning None — the disabled SPMD path costs one
    attribute load and a branch, and the disabled TransferLedger.record
    path never touches the per-device table.

    Conservation invariant (pinned by tests/test_device_ledger.py):
    for every channel, the sum of per-device bytes equals the channel
    total in TransferLedger — transfers without an explicit device
    split attribute to DEFAULT_DEVICE (the only device the host loop
    talks to), so nothing ever leaks out of the table."""

    def __init__(self):
        self.enabled = False
        self._lock = threading.Lock()
        # device id -> {channel: {"h2d": bytes, "d2h": bytes}}
        self._transfers: Dict[int, Dict[str, Dict[str, int]]] = {}
        # device id -> per-chip phase aggregates
        self._phases: Dict[int, Dict[str, float]] = {}
        self.queries = 0
        self.collective_payload_bytes = 0
        self.collective_ici_bytes = 0
        self.skew = RollingEstimator()
        self.partial_wall = RollingEstimator()
        self._tls = threading.local()

    # ------------------------------------------------------------- hot path

    def scope(self) -> Optional[DeviceScope]:
        """The per-query gate: a DeviceScope when per-device
        attribution is on, else None — search/spmd.py guards its whole
        capture block with `if scope is not None`."""
        if not self.enabled:
            return None
        return DeviceScope()

    def note_transfer(self, channel: str, direction: str,
                      splits: List[Tuple[int, int]]) -> None:
        """Per-device byte rows for one transfer; `splits` must sum to
        the transfer's channel-recorded bytes (the conservation
        invariant). Called by TransferLedger.record under the enabled
        guard."""
        with self._lock:
            for dev, nbytes in splits:
                chans = self._transfers.setdefault(int(dev), {})
                ent = chans.get(channel)
                if ent is None:
                    ent = chans[channel] = {H2D: 0, D2H: 0}
                ent[direction] += int(nbytes)

    def note_query(self, scope: DeviceScope) -> None:
        """Fold one query's DeviceScope into the node-wide per-chip
        aggregates + the straggler estimators, and stash it as the
        thread's `last` for the Profile API (the SPMD query phase and
        the profile assembly run on the same request thread)."""
        skew = scope.skew_ms()
        straggler = scope.straggler()
        with self._lock:
            self.queries += 1
            self.collective_payload_bytes += scope.merge_payload_bytes
            self.collective_ici_bytes += scope.merge_ici_bytes
            for dev, wall in scope.partials:
                ph = self._phases.get(dev)
                if ph is None:
                    ph = self._phases[dev] = {
                        "queries": 0, "partial_ms": 0.0,
                        "straggler_hits": 0}
                ph["queries"] += 1
                ph["partial_ms"] += wall
                if dev == straggler:
                    ph["straggler_hits"] += 1
        self.skew.observe(skew)
        for _, wall in scope.partials:
            self.partial_wall.observe(wall)
        self._tls.last = scope

    def take_last(self) -> Optional[DeviceScope]:
        """Pop the thread's most recent query scope (profile assembly
        reads it once; popping keeps a later request on this thread
        from inheriting a stale breakdown)."""
        last = getattr(self._tls, "last", None)
        self._tls.last = None
        return last

    # --------------------------------------------------------------- reading

    def snapshot(self) -> dict:
        with self._lock:
            devices = {}
            for dev in sorted(set(self._transfers) | set(self._phases)):
                ent: Dict[str, Any] = {}
                chans = self._transfers.get(dev)
                if chans:
                    ent["transfer_bytes"] = {
                        c: dict(d) for c, d in sorted(chans.items())}
                    ent["h2d_bytes"] = sum(d[H2D] for d in chans.values())
                    ent["d2h_bytes"] = sum(d[D2H] for d in chans.values())
                ph = self._phases.get(dev)
                if ph:
                    ent.update({"queries": int(ph["queries"]),
                                "partial_ms":
                                    round(ph["partial_ms"], 3),
                                "straggler_hits":
                                    int(ph["straggler_hits"])})
                devices[str(dev)] = ent
            queries = self.queries
            payload = self.collective_payload_bytes
            ici = self.collective_ici_bytes
        return {
            "enabled": self.enabled,
            "queries": queries,
            "devices": devices,
            "collective": {
                "payload_bytes_total": payload,
                "ici_bytes_total": ici,
                "ici_bytes_per_query":
                    round(ici / queries, 1) if queries else 0.0,
            },
            "rolling": {"straggler_skew_ms": self.skew.summary(),
                        "partial_wall_ms": self.partial_wall.summary()},
        }

    def device_bytes(self) -> Dict[int, Dict[str, Dict[str, int]]]:
        """{device: {channel: {h2d, d2h}}} — the conservation test's
        read side."""
        with self._lock:
            return {dev: {c: dict(d) for c, d in chans.items()}
                    for dev, chans in self._transfers.items()}

    def reset(self) -> None:
        with self._lock:
            self._transfers.clear()
            self._phases.clear()
            self.queries = 0
            self.collective_payload_bytes = 0
            self.collective_ici_bytes = 0
        self.skew.reset()
        self.partial_wall.reset()


class LedgerScope:
    """Per-request transfer accumulator (explicit context, like spans:
    the msearch envelope runs B requests on one thread, so ambient
    context would misattribute). Entries are (channel, direction,
    bytes, round_trips, wave) tuples."""

    __slots__ = ("entries", "h2d_bytes", "d2h_bytes", "device_get_ms",
                 "round_trips", "waves", "overlap_ms")

    def __init__(self):
        self.entries: List[Tuple[str, str, int, int, Optional[int]]] = []
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.device_get_ms = 0.0
        self.round_trips = 0
        # wave-pipeline attribution: how many device waves served this
        # request and how much of their dispatch work ran WHILE an
        # earlier wave's device_get was in flight (the overlap win)
        self.waves = 0
        self.overlap_ms = 0.0

    def absorb(self, other: "LedgerScope") -> None:
        self.entries.extend(other.entries)
        self.h2d_bytes += other.h2d_bytes
        self.d2h_bytes += other.d2h_bytes
        self.device_get_ms += other.device_get_ms
        self.round_trips += other.round_trips
        self.waves += other.waves
        self.overlap_ms += other.overlap_ms

    def to_list(self) -> List[dict]:
        """JSON-able per-transfer records for the Profile API."""
        return [{"channel": c, "direction": d, "bytes": b,
                 "round_trips": r, **({"wave": w} if w is not None else {})}
                for c, d, b, r, w in self.entries]

    def publish(self, span=None, phase_times=None) -> None:
        """The one publication contract for a request's attribution:
        span attributes (bytes_to_device / bytes_fetched / transfers[])
        when the span records, and the phase_times fields the slow log
        reads. Both the controller and the msearch envelope call THIS so
        the two surfaces can never drift."""
        if span is not None and getattr(span, "recording", False):
            span.set_attribute("bytes_to_device", self.h2d_bytes)
            span.set_attribute("bytes_fetched", self.d2h_bytes)
            span.set_attribute("transfers", self.to_list())
            if self.waves:
                span.set_attribute("waves", self.waves)
                span.set_attribute("overlap_ms", round(self.overlap_ms, 3))
        if phase_times is not None:
            phase_times["device_get"] = self.device_get_ms
            phase_times["bytes_fetched"] = self.d2h_bytes
            phase_times["bytes_to_device"] = self.h2d_bytes
            if self.waves:
                phase_times["waves"] = self.waves
                phase_times["overlap_ms"] = self.overlap_ms


class TransferLedger:
    """Node-wide per-channel transfer aggregates + wave accounting."""

    def __init__(self):
        self.enabled = False
        # per-device attribution (ISSUE 14): its own gate — a node can
        # run channel accounting without paying the per-device table,
        # and vice versa the device ledger implies nothing about the
        # channel aggregates' enabled state
        self.devices = DeviceLedger()
        self._lock = threading.Lock()
        # (channel, direction) -> [transfers, round_trips, bytes]
        self._channels: Dict[Tuple[str, str], List[int]] = {}
        self._wave_seq = 0
        self._device_get_calls = 0
        self._device_get_ms = 0.0
        # wave-pipeline gauges: waves dispatched but not yet collected
        # (live like the device-memory classes, not ledger-gated — the
        # update is one lock acquire per WAVE, not per item) plus the
        # measured dispatch/collect overlap the pipeline actually won
        self._inflight_waves = 0
        self._max_inflight_waves = 0
        self._overlap_events = 0
        self._overlap_ms = 0.0
        # live views for the wave scheduler: bytes fetched per wave and
        # device_get wall per wave (rolling.py — O(1) reads)
        self.wave_bytes = RollingEstimator()
        self.wave_ms = RollingEstimator()
        self.wave_overlap_ms = RollingEstimator()
        self._tls = threading.local()

    # ------------------------------------------------------------- hot path

    def scope(self, trace=None) -> Optional[LedgerScope]:
        """The per-request accounting gate: a LedgerScope when either the
        ledger is enabled or the request's trace records (profile /
        tracing), else None — callers guard every accounting block with
        `if scope is not None`, so the disabled path costs one attribute
        load and a branch."""
        if self.enabled or (trace is not None
                            and getattr(trace, "recording", False)):
            return LedgerScope()
        return None

    def new_wave(self) -> Optional[int]:
        """Next global wave id — None when the ledger is disabled (a
        traced-only request still accounts per-request, but must not
        advance the node-wide sequence: snapshot()'s `waves` has to stay
        consistent with its device_get/channel counts)."""
        if not self.enabled:
            return None
        with self._lock:
            self._wave_seq += 1
            return self._wave_seq

    def record(self, channel: str, direction: str, nbytes: int,
               round_trips: int = 1, wave: Optional[int] = None,
               scope: Optional[LedgerScope] = None,
               devices: Optional[List[Tuple[int, int]]] = None) -> None:
        """`devices`: optional per-device byte split [(device_id,
        nbytes), ...] for transfers sharded over a mesh; splits must
        sum to `nbytes` (conservation). None attributes the whole
        transfer to DEFAULT_DEVICE when the device ledger is on — the
        host loop and envelope path talk to exactly one device."""
        nbytes = int(nbytes)
        if scope is not None:
            scope.entries.append((channel, direction, nbytes, round_trips,
                                  wave))
            if direction == H2D:
                scope.h2d_bytes += nbytes
            else:
                scope.d2h_bytes += nbytes
        if not self.enabled:
            return
        tag = getattr(self._tls, "tag", None)
        if tag is not None:
            channel = f"{tag}.{channel}"
        if self.devices.enabled:
            self.devices.note_transfer(
                channel, direction,
                devices if devices is not None
                else [(DEFAULT_DEVICE, nbytes)])
        key = (channel, direction)
        with self._lock:
            ent = self._channels.get(key)
            if ent is None:
                ent = self._channels[key] = [0, 0, 0]
            ent[0] += 1
            ent[1] += round_trips
            ent[2] += nbytes

    def note_device_get(self, ms: float, nbytes: Optional[int] = None,
                        scope: Optional[LedgerScope] = None,
                        round_trips: int = 1) -> None:
        """One collect: wall time + fetched bytes. `round_trips` > 1 when
        the collect degraded to per-program gathers (the msearch
        fallback fetch) — `device_get.calls` stays the TRUE global
        round-trip count, consistent with the channel records."""
        if scope is not None:
            scope.device_get_ms += ms
            scope.round_trips += round_trips
        if not self.enabled:
            return
        with self._lock:
            self._device_get_calls += round_trips
            self._device_get_ms += ms
        self.wave_ms.observe(ms)
        if nbytes:
            self.wave_bytes.observe(float(nbytes))

    def note_round_trip(self, channel: str, ms: float = 0.0,
                        scope: Optional[LedgerScope] = None,
                        wave: Optional[int] = None) -> None:
        """One device round trip that moved no accountable wire bytes on
        THIS backend: the host-mirror stand-in for a device-resident
        column read (the legacy sort-key re-key, fetch.py's per-leaf
        docvalue scans). Records a zero-byte channel entry — byte
        conservation against measured `device_get` nbytes stays exact —
        while `round_trips` and `device_get.calls` count the
        synchronization a device-resident read would pay, which is the
        wall the result page removes (ISSUE 17 satellite 1)."""
        self.record(channel, D2H, 0, round_trips=1, wave=wave,
                    scope=scope)
        if scope is not None:
            scope.device_get_ms += ms
            scope.round_trips += 1
        if not self.enabled:
            return
        with self._lock:
            self._device_get_calls += 1
            self._device_get_ms += ms

    def note_wave_inflight(self, delta: int) -> None:
        """In-flight wave gauge: +1 at dispatch, -1 when the wave's
        collect completes. Live regardless of `enabled` (same contract
        as the device-memory gauges): a `_nodes/stats` poll must see the
        pipeline depth even when per-channel accounting is off."""
        with self._lock:
            self._inflight_waves = max(self._inflight_waves + delta, 0)
            if self._inflight_waves > self._max_inflight_waves:
                self._max_inflight_waves = self._inflight_waves

    def inflight_waves(self) -> int:
        with self._lock:
            return self._inflight_waves

    def note_overlap(self, ms: float,
                     scope: Optional[LedgerScope] = None) -> None:
        """One wave's measured overlap: how long its host prepare +
        async dispatch ran while an earlier wave's device_get was in
        flight on the collector thread — the pipeline's win as a
        first-class number, not a wall-clock inference."""
        if scope is not None:
            scope.overlap_ms += ms
        if not self.enabled:
            return
        with self._lock:
            self._overlap_events += 1
            self._overlap_ms += ms
        self.wave_overlap_ms.observe(ms)

    @contextmanager
    def tagged(self, tag: str):
        """Prefix this thread's channel names (warmup replays record as
        `warmup.upload.literals` etc. so replay traffic never pollutes
        the serving channels). A tagged region is attribution-marked:
        replay syncs are ledger-owned by construction."""
        prev = getattr(self._tls, "tag", None)
        self._tls.tag = tag if prev is None else f"{prev}.{tag}"
        self._tls.attr_depth = getattr(self._tls, "attr_depth", 0) + 1
        try:
            yield
        finally:
            self._tls.tag = prev
            self._tls.attr_depth -= 1

    @contextmanager
    def ambient(self, scope: Optional[LedgerScope]):
        """Bind a request's scope to this thread for call sites too deep
        to plumb it into (the fetch phase's inner-hit gathers). Safe
        ONLY around single-request phases — the msearch envelope must
        keep passing scopes explicitly (B requests share one thread)."""
        prev = getattr(self._tls, "scope", None)
        self._tls.scope = scope
        self._tls.attr_depth = getattr(self._tls, "attr_depth", 0) + 1
        try:
            yield
        finally:
            self._tls.scope = prev
            self._tls.attr_depth -= 1

    @contextmanager
    def attributed(self, scope: Optional[LedgerScope] = None):
        """Mark this thread as inside a ledger-attributed region — the
        contract the sync sanitizer (common/sanitize.py) enforces: every
        query-path `device_get` must execute under one of `attributed`/
        `ambient`/`tagged`, i.e. inside code whose transfers the ledger
        can explain. Unlike `ambient`, a None scope does NOT unbind an
        outer ambient scope (the region is attributed even when this
        request's accounting gate returned None)."""
        tls = self._tls
        prev = getattr(tls, "scope", None)
        if scope is not None:
            tls.scope = scope
        tls.attr_depth = getattr(tls, "attr_depth", 0) + 1
        try:
            yield
        finally:
            tls.scope = prev
            tls.attr_depth -= 1

    def attribution_depth(self) -> int:
        """How many attributed regions are active on this thread (0 =
        a sync here is unattributed — the sanitizer's trip condition)."""
        return getattr(self._tls, "attr_depth", 0)

    def current(self) -> Optional[LedgerScope]:
        """The thread's ambient per-request scope, if a phase bound one."""
        return getattr(self._tls, "scope", None)

    # --------------------------------------------------------------- reading

    def snapshot(self) -> dict:
        with self._lock:
            chans = {d: {} for d in (H2D, D2H)}
            totals = {H2D: 0, D2H: 0}
            for (channel, direction), (n, rt, b) in sorted(
                    self._channels.items()):
                chans[direction][channel] = {
                    "transfers": n, "round_trips": rt, "bytes": b}
                totals[direction] += b
            calls, total_ms = self._device_get_calls, self._device_get_ms
            waves = self._wave_seq
            pipeline = {
                "inflight_waves": self._inflight_waves,
                "max_inflight_waves": self._max_inflight_waves,
                "overlap_events": self._overlap_events,
                "overlap_ms": round(self._overlap_ms, 3),
            }
        return {
            "enabled": self.enabled,
            "waves": waves,
            "pipeline": pipeline,
            "device_get": {"calls": calls,
                           "total_ms": round(total_ms, 3)},
            "bytes_total": dict(totals),
            "channels": chans,
            "rolling": {"wave_bytes": self.wave_bytes.summary(),
                        "wave_device_get_ms": self.wave_ms.summary(),
                        "wave_overlap_ms":
                            self.wave_overlap_ms.summary()},
        }

    def reset(self) -> None:
        with self._lock:
            self._channels.clear()
            self._wave_seq = 0
            self._device_get_calls = 0
            self._device_get_ms = 0.0
            # the inflight gauge itself is NOT reset: waves still in
            # flight at reset time must drain to zero, not go negative
            self._max_inflight_waves = self._inflight_waves
            self._overlap_events = 0
            self._overlap_ms = 0.0
        self.wave_bytes.reset()
        self.wave_ms.reset()
        self.wave_overlap_ms.reset()


class ChurnScope:
    """Per-event (one refresh / one merge) accumulator for the device-
    side consequence of a write-path event: which segment images shipped
    (`upload.corpus` bytes), whether each new segment's device shape
    bucket had been seen before (executable reuse) or is novel (the next
    query over it pays an XLA compile), and live-mask-only re-uploads.
    Filled by ShardReader while bound ambient on the refreshing thread
    (write events run start-to-finish on one thread)."""

    __slots__ = ("uploads", "upload_bytes", "live_mask_bytes")

    def __init__(self):
        # (seg_id, nbytes, shape_known)
        self.uploads: List[Tuple[str, int, bool]] = []
        self.upload_bytes = 0
        self.live_mask_bytes = 0

    def note_upload(self, seg_id: str, nbytes: int,
                    shape_known: bool) -> None:
        self.uploads.append((seg_id, int(nbytes), bool(shape_known)))
        self.upload_bytes += int(nbytes)

    def note_live_mask(self, nbytes: int) -> None:
        self.live_mask_bytes += int(nbytes)


# cap on the seen-shape-bucket set: device shapes are power-of-two
# bucketed (ops/device_segment.py), so a real node sees tens of
# buckets; the cap bounds pathological shape churn (randomized tests)
_MAX_SEEN_SHAPES = 4096


class ChurnLedger:
    """Segment-churn ledger (ISSUE 13): one `churn` record per
    refresh/merge event, attributing the *device-side* marginal cost of
    the write path — the measurement ROADMAP item 5's incremental
    segment publish will be judged against.

    Per record: the `upload.corpus` bytes the event re-shipped, a
    recompile/warmup-hit verdict per new segment (did its device shape
    bucket land in an already-compiled (plan-struct, shape-bucket)
    family, or will the first query over it pay a fresh XLA compile),
    and how many interned RotatingMemo entries the event invalidated —
    both the wholesale ShardStats-memo drop a segment-list change
    causes (every skeleton + bundle recompiles on the host) and the
    subset keyed to the removed (segment-uid, mapper-version) pairs.

    No-op discipline (tracer/ledger/faults contract, gate-lint row):
    OFF by default, `scope()` returns None when
    disabled. `observe_shape` alone is live regardless (the
    inflight-wave-gauge contract): it is one lock + set-add per SEGMENT
    UPLOAD, never per query, and the verdict is only honest if the
    seen-set covers uploads from before the ledger was enabled."""

    def __init__(self, ring_size: int = 128):
        self.enabled = False
        self._lock = threading.Lock()
        self._ring: List[dict] = []
        self._ring_size = ring_size
        self._seq = 0
        self._shapes_seen: set = set()
        self._tls = threading.local()
        self.totals = {"events": 0, "refresh": 0, "merge": 0,
                       "recompile_segments": 0, "warm_hit_segments": 0,
                       "upload_bytes": 0, "live_mask_bytes": 0,
                       "memo_entries_dropped": 0,
                       "memo_entries_keyed": 0,
                       "memo_invalidations": 0,
                       "memo_entries_kept": 0,
                       "precompiled": 0,
                       "recompile_on_serve": 0}

    # ------------------------------------------------------------- hot path

    def scope(self) -> Optional[ChurnScope]:
        """The per-event accounting gate: a ChurnScope when the ledger
        is enabled, else None — IndexShard guards its whole attribution
        block with `if scope is not None`, so the disabled refresh path
        costs one attribute load and a branch."""
        if not self.enabled:
            return None
        return ChurnScope()

    def current(self) -> Optional[ChurnScope]:
        """The thread's bound churn scope (ShardReader's read). Tests
        the flag first: the disabled segment-upload path never touches
        thread-local state."""
        if not self.enabled:
            return None
        return getattr(self._tls, "scope", None)

    @contextmanager
    def bound(self, scope: Optional[ChurnScope]):
        prev = getattr(self._tls, "scope", None)
        self._tls.scope = scope
        try:
            yield scope
        finally:
            self._tls.scope = prev

    def observe_shape(self, shape_sig: str) -> bool:
        """Record a segment's device shape-bucket signature; returns
        whether it was already known. Known = some segment with
        byte-identical device array shapes was uploaded before, i.e.
        every executable compiled against that shape family is reusable
        for the new segment (XLA caches per plan signature, and plan
        signatures embed input shapes). Live regardless of `enabled`."""
        with self._lock:
            known = shape_sig in self._shapes_seen
            if not known:
                if len(self._shapes_seen) >= _MAX_SEEN_SHAPES:
                    self._shapes_seen.clear()
                self._shapes_seen.add(shape_sig)
        return known

    def publish(self, scope: ChurnScope, kind: str,
                segments_before: int, segments_after: int,
                docs: int, wall_ms: float,
                memo_entries_dropped: int = 0,
                memo_entries_keyed: int = 0,
                removed_seg_ids: Optional[List[str]] = None,
                event_id: Optional[int] = None,
                shard: Optional[str] = None,
                warmup_registered: Optional[int] = None,
                memo_invalidations: Optional[int] = None,
                memo_entries_kept: Optional[int] = None) -> dict:
        """Close one refresh/merge event's attribution into a churn
        record. The verdict is per NEW segment: `recompile` when its
        shape bucket was unseen at upload time, `warmup_hit` when an
        already-compiled shape family absorbs it."""
        recompiles = sum(1 for _, _, known in scope.uploads if not known)
        warm_hits = sum(1 for _, _, known in scope.uploads if known)
        rec = {
            "kind": kind,
            "shard": shard,
            "segments": {"before": int(segments_before),
                         "after": int(segments_after)},
            "docs": int(docs),
            "wall_ms": round(wall_ms, 3),
            "uploads": [{"seg_id": sid, "bytes": nb,
                         "verdict": "warmup_hit" if known
                         else "recompile"}
                        for sid, nb, known in scope.uploads],
            "upload_bytes": scope.upload_bytes,
            "live_mask_bytes": scope.live_mask_bytes,
            "verdict": ("warmup_hit" if scope.uploads and recompiles == 0
                        else ("recompile" if recompiles else "none")),
            "memo_entries_dropped": int(memo_entries_dropped),
            "memo_entries_keyed": int(memo_entries_keyed),
            # entries actually evicted: with segment-keyed carry on this
            # is the uid-touched subset; without it, the wholesale drop
            "memo_invalidations": int(
                memo_invalidations if memo_invalidations is not None
                else memo_entries_dropped),
        }
        if memo_entries_kept is not None:
            rec["memo_entries_kept"] = int(memo_entries_kept)
        if removed_seg_ids:
            rec["removed_segments"] = list(removed_seg_ids)
        if event_id is not None:
            rec["event_id"] = event_id
        if warmup_registered is not None:
            rec["warmup_registered"] = int(warmup_registered)
        with self._lock:
            self._seq += 1
            rec["churn_id"] = self._seq
            self._ring.append(rec)
            if len(self._ring) > self._ring_size:
                del self._ring[:len(self._ring) - self._ring_size]
            t = self.totals
            t["events"] += 1
            t[kind] = t.get(kind, 0) + 1
            t["recompile_segments"] += recompiles
            t["warm_hit_segments"] += warm_hits
            t["upload_bytes"] += scope.upload_bytes
            t["live_mask_bytes"] += scope.live_mask_bytes
            t["memo_entries_dropped"] += int(memo_entries_dropped)
            t["memo_entries_keyed"] += int(memo_entries_keyed)
            t["memo_invalidations"] += rec["memo_invalidations"]
            if memo_entries_kept is not None:
                t["memo_entries_kept"] += int(memo_entries_kept)
        return rec

    # ---------------------------------------------- verdict lifecycle
    # (ISSUE 16): a `recompile` verdict is provisional — the shape was
    # novel at upload, but WHO pays the compile is decided later. The
    # off-path precompiler flips pending records to `precompiled`; the
    # first serving-thread compile flips them to `recompile-on-serve`
    # (the failure mode the acceptance criterion pins to zero).

    def mark_precompiled(self, churn_ids, took_ms: float,
                         by: str = "precompiler") -> int:
        """Resolve pending `recompile` records for the given churn ids:
        the precompiler absorbed their compiles off-path."""
        if not self.enabled:
            return 0
        ids = set(churn_ids)
        n = 0
        with self._lock:
            for rec in self._ring:
                if rec.get("churn_id") in ids and \
                        rec.get("verdict") == "recompile":
                    rec["verdict"] = "precompiled"
                    rec["precompile_ms"] = round(float(took_ms), 3)
                    rec["precompiled_by"] = by
                    n += 1
            self.totals["precompiled"] += n
        return n

    def note_serve_compile(self) -> int:
        """A serving thread just paid an XLA compile: every still-pending
        `recompile` record escalates to `recompile-on-serve` — the write
        path published a shape the precompiler did not cover in time."""
        if not self.enabled:
            return 0
        n = 0
        with self._lock:
            for rec in self._ring:
                if rec.get("verdict") == "recompile":
                    rec["verdict"] = "recompile-on-serve"
                    n += 1
            self.totals["recompile_on_serve"] += n
        return n

    # --------------------------------------------------------------- reading

    def records(self, size: Optional[int] = None) -> List[dict]:
        """Most-recent-first churn records."""
        with self._lock:
            out = list(self._ring)
        out.reverse()
        return out[:size] if size is not None else out

    def snapshot(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled,
                    "totals": dict(self.totals),
                    "shapes_seen": len(self._shapes_seen),
                    "retained": len(self._ring)}

    def reset(self) -> None:
        """Clear records + totals; the seen-shape set SURVIVES (clearing
        it would turn every post-reset upload into a false `recompile`
        verdict — shapes compiled before the reset stay compiled)."""
        with self._lock:
            self._ring = []
            self._seq = 0
            self.totals = {k: 0 for k in self.totals}


class DeviceMemoryAccounting:
    """Live-bytes gauges per device-memory class.

    Two feeding styles:
      - register/release/adjust: the owning layer reports exact bytes
        (in-flight wave buffers, agg executable constants);
      - providers: a callable sampled at stats() time over live objects
        (corpus columns via the executor's ShardReader weak-set, interned
        bundle memos, compiled-executable counts) — nothing to release,
        dead owners just stop being summed.

    `stats()` also samples `jax.local_devices()[0].memory_stats()` where
    the backend exposes it (TPU runtimes do; CPU returns nothing) — the
    HBM analog of `_nodes/stats`' JVM mem block.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # cls -> {key: (nbytes, per-device split or None)}
        self._registered: Dict[str, Dict[Any, Tuple[int, Any]]] = {}
        self._gauges: Dict[str, int] = {}
        self._providers: Dict[str, Any] = {}

    def register(self, cls: str, key: Any, nbytes: int,
                 devices: Optional[List[Tuple[int, int]]] = None) -> None:
        """`devices`: optional per-device byte split [(device_id,
        nbytes), ...] for allocations sharded over a mesh (ISSUE 14 —
        the HbmShardSet's stacked image); stats() folds the splits into
        a per-class `by_device` breakdown."""
        with self._lock:
            self._registered.setdefault(cls, {})[key] = (
                int(nbytes),
                [(int(d), int(b)) for d, b in devices]
                if devices is not None else None)

    def release(self, cls: str, key: Any) -> None:
        with self._lock:
            self._registered.get(cls, {}).pop(key, None)

    def adjust(self, cls: str, delta: int) -> None:
        """Plain up/down gauge for churny classes (in-flight buffers)."""
        with self._lock:
            self._gauges[cls] = max(self._gauges.get(cls, 0) + int(delta),
                                    0)

    def add_provider(self, name: str, fn) -> None:
        """Idempotent by name: module re-imports keep the latest."""
        with self._lock:
            self._providers[name] = fn

    def live_bytes(self, cls: str) -> int:
        with self._lock:
            if cls in self._gauges:
                return self._gauges[cls]
            return sum(nb for nb, _ in
                       self._registered.get(cls, {}).values())

    def stats(self) -> dict:
        classes: Dict[str, dict] = {}
        with self._lock:
            for cls, entries in self._registered.items():
                ent: Dict[str, Any] = {
                    "live_bytes": sum(nb for nb, _ in entries.values()),
                    "entries": len(entries)}
                by_device: Dict[str, int] = {}
                for nb, split in entries.values():
                    if split:
                        for dev, b in split:
                            by_device[str(dev)] = \
                                by_device.get(str(dev), 0) + b
                if by_device:
                    ent["by_device"] = dict(sorted(by_device.items()))
                classes[cls] = ent
            for cls, v in self._gauges.items():
                classes[cls] = {"live_bytes": v}
            providers = list(self._providers.items())
        for name, fn in providers:
            try:
                classes[name] = dict(fn())
            except Exception:   # except-ok: third-party provider callables; a stats poll must never 500 the node
                classes[name] = {"error": "provider failed"}
        return {"classes": classes, "hbm": _hbm_stats()}

    def reset(self) -> None:
        with self._lock:
            self._registered.clear()
            self._gauges.clear()


def _hbm_stats() -> Optional[dict]:
    """Raw backend memory stats of the first local device: the TPU
    runtime reports bytes_in_use / peak_bytes_in_use / bytes_limit etc.;
    the CPU backend reports nothing (None)."""
    import jax
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return None
    return {k: v for k, v in stats.items() if isinstance(v, (int, float))}
