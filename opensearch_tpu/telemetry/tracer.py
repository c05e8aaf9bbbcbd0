"""Request-scoped tracing: explicit-context spans over the search path.

Re-design of the reference telemetry tracing layer (libs/telemetry
TracerFactory + the spans the REST/transport interceptors open). Two
deliberate departures, both forced by this build's execution model:

- Context is a plain object passed DOWN the call chain (`trace=` params),
  never a thread-local: the msearch envelope executes B requests inside
  one device program on one thread, so ambient context would attribute
  every sub-request's device work to whichever request happened to be
  "current".
- Spans time with `time.monotonic_ns()` and close via context manager
  (`with span.child("phase"):`), so failure paths — exceptions,
  backpressure rejections — still close every opened span.

When tracing is disabled (the default), `start_trace` returns a shared
NOOP span whose every method is a constant-time no-op — the query path
pays a couple of attribute loads, nothing else.

Beside the verbose tree sits the ALWAYS-ON span ring (`SpanRing`,
`Tracer.spans`): flat completed records `(trace_id, span_id, parent_id,
name, start_ns, end_ns, attributes)`. A request's row holds the layer
boundaries of the served path (http.request -> rest.search -> envelope
-> envelope.parse / compile_group / pack, dispatch, device_wait,
respond; on the SPMD route rest.search -> spmd.plan, dispatch,
device_wait, spmd.reduce x 2, respond), each fed from the clock reads
the always-on histograms already make, and BELOW the four boundaries
that are wide on the host their children: `compile.bundle` >
`compile.text_clause` and `compile.scan_note` under
`envelope.compile_group`; `respond.unpack` / `.decode_aggs` /
`.reduce_aggs` / `.render` under the envelope's `respond`;
`spmd.plan.compile_rows` / `.align` / `.stack` under `spmd.plan`;
`spmd.reduce.scan_note` / `.candidates` / `.decode_aggs` /
`.reduce_aggs` under the two `spmd.reduce`. No child lies directly
under `http.request`, `rest.*` or `envelope`: their SELF time is what
the benchmark reads of them. A warm B=1 BM25 request is sixteen
records, an aggregating one and an SPMD dashboard request seventeen
(two fewer where the bundle memo holds the body): a clock read and a
tuple append each, one row in the ring and no lock. A wave keeps the
first `COMPILE_SPANS_A_WAVE` (search/compile.py) of its compile spans,
so a cold `_msearch` of hundreds of bodies stays a short row.

What no request owns lies on the ring's PROCESS TRACK
(`SpanRing.process`, `trace_id` 0, the same clock): the interpreter's
collections (`gc.collect`, from `gc.callbacks`: `GcSpans`) and what is
built once an index (`install.upload_segment` > `install.host_pad`,
`install.device_put`; `install.shard_set` > `.host_images`, `.stack`,
`.device_put`). `GET /_telemetry/spans`
serves both, under `"spans"` and `"process"`, and the benchmark's
per-layer metrics read them. Both forms are on `time.monotonic_ns()`,
the clock of `time.monotonic()` on Linux, so a span lies on a client's
samples without conversion; the export carries one `(monotonic_ns,
time_ns)` pair for wall time. `telemetry.tracing.enabled` gates only
the verbose tree.

Completed root spans land in a bounded in-memory ring buffer served by
`GET /_telemetry/traces` and, when configured with a data dir, are
appended as JSONL under `_state/traces.jsonl` for offline analysis
(tools/trace_report.py).
"""

from __future__ import annotations

import gc
import itertools
import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

DEFAULT_RING_SIZE = 256
# requests kept. A served request is eleven boundary spans
# (http.request and its two halves, rest.*, envelope and its parse, and
# five a wave) and four to seven children below them (sixteen records a
# B=1 BM25 request, seventeen an aggregating or an SPMD one; a wave of
# an `_msearch` at most search/compile.py's `COMPILE_SPANS_A_WAVE` more),
# so the ring holds 131,072 spans and more; the 30 s window of the
# busiest queued benchmark cell is 140 requests/s = 4,200 requests
SPAN_RING_SIZE = 8192
# process-track spans kept: three a segment installed, four a shard set,
# every full collection of the heap and a younger one that took 1 ms or
# more. A 30 s window of the k-NN cell (3,000 requests) adds ten to
# twenty (two or three full collections, eight to twelve younger ones
# of 1.0-1.7 ms: PERF.md, section 6), so the install spans outlive
# hundreds of windows
PROCESS_RING_SIZE = 4096
# a collection of generation 0 or 1 is kept as a span only from here
# up; every one is counted
GC_SPAN_MIN_NS = 1_000_000


class Trace:
    """One request's timeline while it is served: the id its spans
    share, the id of the span open on the serving thread (`top`: the
    parent of what starts next), and the completed spans, each
    `(span_id, parent_id, name, start, end, attributes)`."""

    __slots__ = ("trace_id", "top", "spans")

    def __init__(self, trace_id: int):
        self.trace_id = self.top = trace_id
        self.spans: List[tuple] = []


class SpanRing:
    """Bounded ring of completed requests' flat span records, always on.

    A request costs ONE row: the outermost span that starts on a thread
    (`enter`: `http.request`; `rest.search` or `envelope` for a caller
    that came in below HTTP) makes a `Trace`, every layer below appends
    its completed spans to `trace.spans` from clock reads it already
    made for a histogram, and the outermost span's `leave` puts the
    trace into the ring: a list append a span, a deque append a
    request, both atomic under the interpreter lock. No lock is taken
    and none is held across a device call. A request still being served
    is not in the ring yet.

    A span is `(span_id, parent_id, name, start, end, attributes)`. A
    time is `time.monotonic_ns()` as read, or `time.monotonic()` seconds
    as read; `attributes` is None, a dict, or `(build, *arguments)` with
    `build(*arguments)` the dict: the export converts and builds, off
    the serving path. The ring drops its oldest request when full and
    `dropped` says how many (the count's `+= 1` is not atomic: racing
    writers can count one for two, so it is exact when writers do not
    overlap and a close lower bound otherwise).

    The trace reaches the layers below as a thread-local binding
    (`current`), the way the lifecycle timeline does: one HTTP request
    is served on one thread. Where the work changes thread (the wave
    collector, the wave scheduler) the trace rides the wave or the
    queued item. The items of a batch get no spans of their own, so the
    objection to ambient context in the module docstring (B
    sub-requests in one program) does not apply.

    Ids come eight apart (`ids`): a span that closes with leaf children
    names them `its id + 1 .. + 7` without another draw.

    Beside the requests sits the process track (`process`): completed
    spans of what no request owns, `trace_id` 0, in a bounded deque of
    their own that drops its oldest; the same clock, ids from the same
    counter."""

    def __init__(self, size: int = SPAN_RING_SIZE,
                 process_size: int = PROCESS_RING_SIZE):
        self._ring: "deque[Trace]" = deque(maxlen=size)
        self._process: "deque[tuple]" = deque(maxlen=process_size)
        self.ids = itertools.count(8, 8)
        self._put = 0               # requests completed since `clear`
        self._local = threading.local()

    # ------------------------------------------------------------- writing

    def current(self) -> Optional[Trace]:
        """The request being served on this thread."""
        return getattr(self._local, "trace", None)

    def enter(self):
        """A span that has children starts on this thread: `(the
        request's trace, the span's id, its parent's id)`. With no
        request open it is the root (parent 0) of a new trace. The
        caller appends the span to `trace.spans` when it ends, then
        calls `leave`."""
        trace = getattr(self._local, "trace", None)
        span_id = next(self.ids)
        if trace is None:
            self._local.trace = trace = Trace(span_id)
            return trace, span_id, 0
        parent_id = trace.top
        trace.top = span_id
        return trace, span_id, parent_id

    def leave(self, trace: Trace, parent_id: int) -> None:
        """The span `enter` began has ended: its parent is the open span
        again, and the root's end puts the request into the ring."""
        if parent_id:
            trace.top = parent_id
        else:
            self._local.trace = None
            self._put += 1
            self._ring.append(trace)

    def child(self, name: str, start, end, attributes=None,
              parent_id: int = 0) -> int:
        """A completed span under the span open on this thread, or under
        `parent_id` (a span of this request recorded after the fact,
        whose id an earlier `child` returned); returns its id. Nothing,
        and 0, when no request is open (a direct library caller)."""
        trace = getattr(self._local, "trace", None)
        if trace is None:
            return 0
        span_id = next(self.ids)
        trace.spans.append((span_id, parent_id or trace.top, name, start,
                            end, attributes))
        return span_id

    def process(self, name: str, start, end, attributes=None,
                parent_id: int = 0) -> int:
        """A completed span of the process track: what no request owns
        (a collection of the heap, an index's install). Times and
        attributes as a request's spans; `parent_id` is a process span
        recorded before (0: none). One deque append, no lock: callable
        from a `gc` callback. Returns the span's id."""
        span_id = next(self.ids)
        self._process.append((span_id, parent_id, name, start, end,
                              attributes))
        return span_id

    # ------------------------------------------------------------- reading

    @property
    def dropped(self) -> int:
        """Requests the ring pushed out since `clear`."""
        return max(self._put - len(self._ring), 0)

    def export(self, since_ns: Optional[int] = None,
               until_ns: Optional[int] = None) -> dict:
        """The `GET /_telemetry/spans` body: under `spans` every span of
        a completed request, under `process` every span of the process
        track, that ends at or after `since_ns` and starts at or before
        `until_ns`."""
        traces = self._ring.copy()  # one C call: atomic under the GIL
        out = []
        for trace in traces:
            _rows(out, trace.trace_id, trace.spans.copy(), since_ns,
                  until_ns)
        process = []
        _rows(process, 0, self._process.copy(), since_ns, until_ns)
        return {"clock": "monotonic_ns",
                "anchor": {"monotonic_ns": time.monotonic_ns(),
                           "time_ns": time.time_ns()},
                "dropped": self.dropped, "spans": out,
                "process": process}

    def clear(self) -> None:
        """Forget the requests. The process track stays: what it holds
        of an index's install is recorded once."""
        self._ring.clear()
        self._put = 0

    def stats(self) -> dict:
        retained = len(self._ring)
        return {"size": self._ring.maxlen, "retained": retained,
                "recorded": max(self._put, retained),
                "dropped": self.dropped, "process": len(self._process)}


def _rows(out: list, trace_id: int, spans, since_ns, until_ns) -> None:
    """The export's rows of one request's (or the process track's)
    span tuples: times to integer nanoseconds, attributes built."""
    for span_id, parent_id, name, t0, t1, attrs in spans:
        if type(t0) is not int:
            t0 = int(t0 * 1e9)
        if type(t1) is not int:
            t1 = int(t1 * 1e9)
        if (since_ns is not None and t1 < since_ns) \
                or (until_ns is not None and t0 > until_ns):
            continue
        row = {"trace_id": trace_id, "span_id": span_id,
               "parent_id": parent_id, "name": name,
               "start_ns": t0, "end_ns": t1}
        if type(attrs) is tuple:
            attrs = attrs[0](*attrs[1:])
        if attrs:
            row["attributes"] = attrs
        out.append(row)


def _gc_attrs(generation: int, collected: int, uncollectable: int) -> dict:
    return {"generation": generation, "collected": collected,
            "uncollectable": uncollectable}


class GcSpans:
    """The interpreter's collections on the ring's process track: a
    `gc.callbacks` entry (`install`, where the node's telemetry starts)
    that reads the ring's clock when a collection starts and when it
    stops. Every collection of generation 2 becomes a `gc.collect` span
    (`generation`, `collected`, `uncollectable`), a younger one only if
    it took `GC_SPAN_MIN_NS` or more; the full ones are counted
    (`full`), and the time all of them took (`pause_ns`).

    The callback runs inside whatever allocation set the collection off,
    on that thread, with any lock that thread holds: it takes none
    (plain ints, one deque append; one collection runs at a time, so
    the ints have one writer) and raises nothing. The counts reach
    `_nodes/stats` as counters read when the stats are
    (`MetricsRegistry.publish`)."""

    def __init__(self, ring: SpanRing):
        self.ring = ring
        self.full = 0           # collections of generation 2
        self.pause_ns = 0       # every generation
        self._start = 0

    def __call__(self, phase: str, info: dict) -> None:
        now = time.monotonic_ns()
        if phase == "start":
            self._start = now
            return
        start, self._start = self._start, 0
        if not start:
            return      # installed while this collection was running
        generation = info.get("generation", 2)
        self.full += generation == 2
        self.pause_ns += now - start
        if generation == 2 or now - start >= GC_SPAN_MIN_NS:
            self.ring.process(
                "gc.collect", start, now,
                (_gc_attrs, generation, info.get("collected", 0),
                 info.get("uncollectable", 0)))

    def install(self) -> None:
        if self not in gc.callbacks:
            gc.callbacks.append(self)


class Span:
    """One timed operation. `children` nest; attributes are flat K/V."""

    __slots__ = ("name", "attributes", "children", "start_ns", "end_ns",
                 "status", "error", "trace_id")

    recording = True

    def __init__(self, name: str, attributes: Optional[dict] = None,
                 trace_id: Optional[int] = None):
        self.name = name
        self.attributes: Dict[str, Any] = dict(attributes) \
            if attributes else {}
        self.children: List["Span"] = []
        # the ring's trace id where the request has one, so the tree and
        # the flat records of one request can be laid side by side
        self.trace_id = trace_id
        self.start_ns = time.monotonic_ns()
        self.end_ns: Optional[int] = None
        self.status = "ok"
        self.error: Optional[str] = None

    # ------------------------------------------------------------- lifecycle

    def child(self, name: str, **attributes) -> "Span":
        s = Span(name, attributes, trace_id=self.trace_id)
        self.children.append(s)
        return s

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def end(self, status: Optional[str] = None,
            error: Optional[BaseException] = None) -> None:
        if self.end_ns is None:
            self.end_ns = time.monotonic_ns()
        if error is not None:
            self.status = "error"
            self.error = f"{type(error).__name__}: {error}"
        if status is not None:
            self.status = status

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end(error=exc if exc_type is not None else None)
        return False

    # --------------------------------------------------------------- reading

    def duration_ns(self) -> int:
        end = self.end_ns if self.end_ns is not None \
            else time.monotonic_ns()
        return end - self.start_ns

    def to_dict(self) -> dict:
        out: Dict[str, Any] = {
            "name": self.name,
            "duration_ms": round(self.duration_ns() / 1e6, 3),
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "status": self.status,
        }
        if self.trace_id is not None:
            out["trace_id"] = self.trace_id
        if self.error is not None:
            out["error"] = self.error
        if self.attributes:
            out["attributes"] = self.attributes
        if self.children:
            out["children"] = [c.to_dict() for c in self.children]
        return out


class _NoopSpan:
    """Shared constant returned when tracing is off: absorbs the whole
    Span API in O(1) with no allocation."""

    __slots__ = ()
    recording = False
    children: List[Any] = []
    attributes: Dict[str, Any] = {}
    status = "ok"

    def child(self, name: str, **attributes) -> "_NoopSpan":
        return self

    def set_attribute(self, key: str, value: Any) -> None:
        pass

    def end(self, status=None, error=None) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def duration_ns(self) -> int:
        return 0

    def to_dict(self) -> dict:
        return {}


NOOP_SPAN = _NoopSpan()


class Tracer:
    """Node-wide tracer: opens root spans, retains completed traces."""

    def __init__(self, ring_size: int = DEFAULT_RING_SIZE):
        self.enabled = False
        # the always-on flat span ring (not gated by `enabled`), and the
        # collections of the heap on its process track
        self.spans = SpanRing()
        self.gc = GcSpans(self.spans)
        self._ring: "deque[dict]" = deque(maxlen=ring_size)
        self._lock = threading.Lock()
        # separate lock for file appends: a slow disk must not block
        # other threads' ring appends
        self._io_lock = threading.Lock()
        self.jsonl_path: Optional[str] = None
        self.started = 0
        self.finished = 0
        self.export_errors = 0

    # ------------------------------------------------------------- lifecycle

    def start_trace(self, name: str, force: bool = False, **attributes):
        """Root span for one request. `force=True` returns a real span
        even when tracing is disabled (the profile API builds its
        response from request-scoped spans regardless of node-wide
        tracing) — forced traces are NOT retained in the ring unless the
        tracer is enabled."""
        if not self.enabled and not force:
            return NOOP_SPAN
        if self.enabled and not force:
            # forced (profile-only) spans are request-local and never
            # reach finish(); counting them would make started/finished
            # read as leaked spans
            self.started += 1
        ctx = self.spans.current()
        return Span(name, attributes,
                    trace_id=ctx.trace_id if ctx is not None else None)

    def finish(self, span) -> None:
        """Close a root span and retain it (ring + optional JSONL).
        Spans for failed/rejected requests close here too — the caller
        sets status before finishing. No-op for NOOP spans and, when the
        tracer is disabled, for forced (profile-only) spans."""
        if not getattr(span, "recording", False):
            return
        span.end()
        # count the finish even if tracing was disabled mid-request: the
        # span was counted started, and started != finished is this API's
        # leaked-span signal — it must not fire on a runtime toggle
        with self._lock:
            self.finished += 1
        if not self.enabled:
            return
        rec = {"trace": span.to_dict(), "ts_ms": int(time.time() * 1000)}
        with self._lock:
            self._ring.append(rec)
        path = self.jsonl_path
        if path is not None:
            line = json.dumps(rec, default=str) + "\n"
            try:
                # serialized append: concurrent finishers must not
                # interleave partial lines (one json line can span
                # multiple write() syscalls)
                with self._io_lock, open(path, "a") as f:
                    f.write(line)
            except OSError:
                self.export_errors += 1

    # --------------------------------------------------------------- reading

    def traces(self, size: Optional[int] = None) -> List[dict]:
        """Most-recent-first dump of the ring buffer."""
        with self._lock:
            out = list(self._ring)
        out.reverse()
        return out[:size] if size is not None else out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def resize(self, ring_size: int) -> None:
        with self._lock:
            self._ring = deque(self._ring, maxlen=max(int(ring_size), 1))

    def stats(self) -> dict:
        with self._lock:
            retained = len(self._ring)
            maxlen = self._ring.maxlen
        return {"enabled": self.enabled, "started": self.started,
                "finished": self.finished, "retained": retained,
                "ring_size": maxlen, "jsonl_path": self.jsonl_path,
                "export_errors": self.export_errors,
                "spans": self.spans.stats()}
