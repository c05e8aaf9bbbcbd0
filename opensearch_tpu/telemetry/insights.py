"""Query Insights: per-shape cost attribution + the heavy-query top-N
registry (ISSUE 15).

Every observability layer so far answers "where did the time go" —
phases (PR 4), transfers (PR 7), lifecycle (PR 10), ingest events
(PR 13), devices and scanned bytes (PR 14) — but none answers "WHICH
queries cost what". The reference OpenSearch ships a Query Insights
subsystem (top-N queries by latency/cpu/memory behind
`/_insights/top_queries`); this module is its analog, built on the
grouping key the repo already interns: PR 5's template signatures.

The join: every completed search / msearch sub-request is attributed to
its **shape class** — the interned `dsl.QueryTemplate.sig` (the query's
structure with literals stripped: `match:3fa2bc01`), falling back to a
structural hash for bodies the interner declines (`~match_phrase:ab12`,
`~hybrid:…`). Per shape class the recorder maintains

  - rolling p50/p99 latency and per-request device milliseconds (the
    wave's `device_get` wall split across co-batched owners exactly as
    PR 14's `device_share_ms` splits the scheduler's shared waves),
  - scanned bytes (telemetry/scan.py's per-query posting/dense counters,
    joined per request — byte-exact against the global heat map),
  - transfer-ledger bytes and round trips (when the ledger is on),
  - co-batch ratio (what fraction of this shape's requests rode a
    shared wave, and with how many companions),
  - compile / bundle-warm-hit counts and the request-cache hit count,
  - a bounded per-tenant count breakdown,

plus three bounded **top-N rings** (latency, device_ms, scan_bytes)
holding full capture records like the flight recorder's — the
"top_queries" face.

Why it matters (ROADMAP items 3/4): the block-max go/no-go trigger is a
global scanned-bytes heat map today, but BM25S-style posting pruning
(arxiv 2407.03618) pays off per query CLASS — head-term dense-kernel
queries and candidate-kernel queries have ~10× different scan profiles
— and the MaxSim rerank tier's multi-stage cost budget (arxiv
1707.08275) needs per-stage per-class attribution from day one. This
recorder is that input, live.

No-op discipline (the tracer/ledger/faults/flight contract, gate-lint
registry row): OFF by default, `gate()` returns None — the disabled
query path costs one attribute load and a branch per sub-request.
Enabled cost is one lock + dict adds per completed sub-request (no
per-hit or per-lane work).

The same shape vocabulary also prices admission: the shape-aware
`DeadlineShedder` pricing (common/admission.py, its own OFF-by-default
`shape_gate()`) replaces the global service median with the arriving
shape's rolling median once that shape has enough samples — a cheap
`match_all` no longer prices a heavy aggs arrival.
"""

from __future__ import annotations

import hashlib
import heapq
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from opensearch_tpu.telemetry.rolling import RollingEstimator

# the three top-N registries (the reference's top_queries metric axes,
# mapped to what THIS node measures: wall, device wall, scanned bytes)
TOP_METRICS = ("latency", "device_ms", "scan_bytes")

DEFAULT_TOP_N = 8

# bound on distinct tracked shape classes: the shape key derives from
# client-supplied bodies, so an unbounded dict would be a memory-DoS
# vector inside the observability layer itself (the TenantQuotas /
# scan-heat-map bounding pattern). Past the cap, new shapes fold into
# the overflow row.
MAX_TRACKED_SHAPES = 256
OVERFLOW_SHAPE = "_other"
# per-shape tenant breakdown bound (tenant ids are client-supplied too)
MAX_TENANTS_PER_SHAPE = 16


def _h8(obj: Any) -> str:
    """Stable 8-hex digest of a structure. md5 over repr: reprs of
    nested tuples/strings/numbers are deterministic across processes
    (unlike hash(), which PYTHONHASHSEED salts), so shape ids compare
    equal across bench rounds — the bench_compare equal-shape-key
    contract."""
    return hashlib.md5(repr(obj).encode()).hexdigest()[:8]


def _skeleton(q: Any) -> Any:
    """Structure-only skeleton of a raw query body: dict keys and
    nesting survive, scalar literals collapse to their type name — the
    fallback grouping key for bodies `dsl.intern_query` declines
    (match_phrase, hybrid, spans, joins, now-math, …). Two bodies with
    the same clause tree and different literals hash equal."""
    if isinstance(q, dict):
        return ("d", tuple((k, _skeleton(v)) for k in sorted(q)
                           for v in (q[k],)))
    if isinstance(q, (list, tuple)):
        return ("l", tuple(_skeleton(v) for v in q))
    return type(q).__name__


# label memo: the envelope renders a label per ITEM when insights or
# the flight recorder is on, and a B=1024 batch of repeated templates
# would otherwise pay 1024 repr+md5 walks per wave — a dict hit is the
# warm cost. Bounded by wholesale clear (shape cardinality is tiny).
_LABEL_MEMO: Dict[tuple, str] = {}


def template_shape(sig: tuple) -> str:
    """Shape id of an interned template signature (dsl.QueryTemplate
    .sig): `<top-clause>:<h8>`, e.g. `match:3fa2bc01`."""
    label = _LABEL_MEMO.get(sig)
    if label is None:
        if len(_LABEL_MEMO) >= 4096:
            _LABEL_MEMO.clear()
        label = f"{sig[0]}:{_h8(sig)}"
        _LABEL_MEMO[sig] = label  # shared-state-ok: benign double-render race; dict slot write is GIL-atomic
    return label


def structural_shape(q: Any) -> str:
    """Fallback shape id for a non-internable body: `~<top>:<h8>` over
    the structural skeleton. The `~` marks the hash family so a report
    reader knows the group key is structural, not an interned
    template."""
    top = "q"
    if isinstance(q, dict) and len(q) == 1:
        top = next(iter(q))
    elif q is None:
        top = "match_all"
    return f"~{top}:{_h8(_skeleton(q))}"


def query_shape(q: Any) -> Tuple[str, str]:
    """(shape id, kind) for a raw query body — THE public join helper
    (the REST shed-pricing hook and the controller both call this).
    kind ∈ {"template", "hash"}."""
    from opensearch_tpu.search import dsl
    tpl = dsl.intern_query(q)
    if tpl is not None:
        return template_shape(tpl.sig), "template"
    return structural_shape(q), "hash"


class _TopN:
    """Bounded top-N ring over one metric, holding full capture
    records. A min-heap keyed (value, seq): the retained set is exactly
    the N largest values ever offered — deterministic regardless of
    offer interleaving (equal values tie-break on arrival seq, which
    the owner assigns under its lock). `records()` renders
    largest-first."""

    __slots__ = ("n", "_heap")

    def __init__(self, n: int = DEFAULT_TOP_N):
        self.n = max(int(n), 1)
        self._heap: List[Tuple[float, int, dict]] = []

    def offer(self, value: float, seq: int, record: dict) -> None:
        if len(self._heap) < self.n:
            heapq.heappush(self._heap, (value, seq, record))
        elif (value, seq) > self._heap[0][:2]:
            heapq.heapreplace(self._heap, (value, seq, record))

    def records(self, size: Optional[int] = None) -> List[dict]:
        out = [rec for _v, _s, rec in
               sorted(self._heap, key=lambda e: e[:2], reverse=True)]
        return out[:size] if size is not None else out

    def clear(self) -> None:
        self._heap = []


def _new_row(kind: str) -> dict:
    return {"kind": kind, "count": 0, "errors": 0, "cached": 0,
            "took_total_ms": 0.0, "device_ms_total": 0.0,
            "posting_bytes": 0, "dense_bytes": 0, "pruned_bytes": 0,
            "h2d_bytes": 0, "d2h_bytes": 0, "round_trips": 0,
            "co_batched_sum": 0, "co_batched_max": 0, "coalesced": 0,
            "compiled": 0, "warm_hits": 0,
            "tenants": {},
            "took": RollingEstimator(), "device": RollingEstimator()}


class QueryInsights:
    """Node-wide per-shape cost recorder + the heavy-query top-N rings.

    Thread model: `note()` takes one lock for the row/total/ring
    updates (the rolling estimators carry their own locks and observe
    outside it). The tenant binding and the scan join are thread-local
    — a write-ahead channel the executor/controller read back on the
    SAME thread, never across the wave-collector boundary."""

    def __init__(self, top_n: int = DEFAULT_TOP_N):
        self.enabled = False
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._seq = 0
        self._shapes: Dict[str, dict] = {}
        self.top = {m: _TopN(top_n) for m in TOP_METRICS}
        # global conservation totals, updated ATOMICALLY with the rows:
        # sum-over-shapes == these, and these == the window deltas of
        # the global counters (SCAN byte-exact, ledger byte-exact,
        # msearch.bodies ±1) — the acceptance's conservation contract
        self.totals = {"queries": 0, "errors": 0, "cached": 0,
                       "took_total_ms": 0.0, "device_ms_total": 0.0,
                       "posting_bytes": 0, "dense_bytes": 0,
                       "pruned_bytes": 0,
                       "h2d_bytes": 0, "d2h_bytes": 0, "round_trips": 0}

    # ------------------------------------------------------------- gating

    def gate(self) -> Optional["QueryInsights"]:
        """The per-request gate: None when insights is disabled —
        callers fall straight through (one attribute load + branch)."""
        if not self.enabled:
            return None
        return self

    # ------------------------------------------- thread-local join channels

    def bind_tenant(self, tenant: Optional[str]) -> Optional[str]:
        """Bind the request's tenant to this thread (the REST layer
        owns the request; the executor's note reads it back). Returns
        the previous binding for unbind — only reached when enabled."""
        prev = getattr(self._tls, "tenant", None)
        self._tls.tenant = tenant
        return prev

    def unbind_tenant(self, prev: Optional[str]) -> None:
        self._tls.tenant = prev

    def current_tenant(self) -> Optional[str]:
        return getattr(self._tls, "tenant", None)

    def add_scan(self, posting_bytes: int, dense_bytes: int,
                 pruned_bytes: int = 0) -> None:
        """Accumulate one query-phase execution's scan bytes for the
        CURRENT request (general host loop / SPMD path — the same
        numbers those paths feed telemetry.scan, so the per-shape join
        stays byte-exact). `pruned_bytes`: posting bytes the block-max
        phase-B mask kept out of the gathers (0 with the gate off).
        Read-and-reset by `take_scan` at the request's note point, same
        thread."""
        t = self._tls
        t.scan_p = getattr(t, "scan_p", 0) + int(posting_bytes)
        t.scan_d = getattr(t, "scan_d", 0) + int(dense_bytes)
        if pruned_bytes:
            t.scan_pr = getattr(t, "scan_pr", 0) + int(pruned_bytes)

    def take_scan(self) -> Tuple[int, int, int]:
        t = self._tls
        out = (getattr(t, "scan_p", 0), getattr(t, "scan_d", 0),
               getattr(t, "scan_pr", 0))
        t.scan_p = 0
        t.scan_d = 0
        t.scan_pr = 0
        return out

    # ------------------------------------------------------------- hot path

    def note(self, shape: str, kind: str = "template",
             took_ms: float = 0.0, device_ms: float = 0.0,
             posting_bytes: int = 0, dense_bytes: int = 0,
             pruned_bytes: int = 0,
             h2d_bytes: int = 0, d2h_bytes: int = 0,
             round_trips: int = 0, co_batched: int = 1,
             compiled: bool = False, warm_hit: bool = False,
             cached: bool = False, tenant: Optional[str] = None,
             status: str = "ok") -> None:
        """Attribute one COMPLETED sub-request to its shape class. One
        lock acquire + dict adds; the two rolling estimators observe
        outside the lock (they carry their own)."""
        scan_bytes = int(posting_bytes) + int(dense_bytes)
        with self._lock:
            row = self._shapes.get(shape)
            if row is None:
                if len(self._shapes) >= MAX_TRACKED_SHAPES \
                        and shape != OVERFLOW_SHAPE:
                    shape = OVERFLOW_SHAPE
                    row = self._shapes.get(shape)
                if row is None:
                    row = self._shapes[shape] = _new_row(kind)
            row["count"] += 1
            self.totals["queries"] += 1
            if status != "ok":
                row["errors"] += 1
                self.totals["errors"] += 1
            if cached:
                row["cached"] += 1
                self.totals["cached"] += 1
            row["took_total_ms"] += float(took_ms)
            row["device_ms_total"] += float(device_ms)
            row["posting_bytes"] += int(posting_bytes)
            row["dense_bytes"] += int(dense_bytes)
            row["pruned_bytes"] = \
                row.get("pruned_bytes", 0) + int(pruned_bytes)
            row["h2d_bytes"] += int(h2d_bytes)
            row["d2h_bytes"] += int(d2h_bytes)
            row["round_trips"] += int(round_trips)
            row["co_batched_sum"] += int(co_batched)
            if co_batched > row["co_batched_max"]:
                row["co_batched_max"] = int(co_batched)
            if co_batched > 1:
                row["coalesced"] += 1
            if compiled:
                row["compiled"] += 1
            if warm_hit:
                row["warm_hits"] += 1
            t = tenant or "_default"
            tenants = row["tenants"]
            if t not in tenants and len(tenants) >= MAX_TENANTS_PER_SHAPE:
                t = OVERFLOW_SHAPE
            tenants[t] = tenants.get(t, 0) + 1
            self.totals["took_total_ms"] += float(took_ms)
            self.totals["device_ms_total"] += float(device_ms)
            self.totals["posting_bytes"] += int(posting_bytes)
            self.totals["dense_bytes"] += int(dense_bytes)
            self.totals["pruned_bytes"] = \
                self.totals.get("pruned_bytes", 0) + int(pruned_bytes)
            self.totals["h2d_bytes"] += int(h2d_bytes)
            self.totals["d2h_bytes"] += int(d2h_bytes)
            self.totals["round_trips"] += int(round_trips)
            self._seq += 1
            seq = self._seq
            # the heavy-query registries: full capture records like the
            # flight recorder's, bounded, deterministic eviction (the
            # retained set is the N largest per metric)
            rec = {"shape": shape, "kind": kind, "seq": seq,
                   "ts_ms": int(time.time() * 1000),
                   "took_ms": round(float(took_ms), 3),
                   "device_ms": round(float(device_ms), 3),
                   "scan_bytes": scan_bytes,
                   "posting_bytes": int(posting_bytes),
                   "dense_bytes": int(dense_bytes),
                   "transfer_bytes": int(h2d_bytes) + int(d2h_bytes),
                   "co_batched": int(co_batched),
                   "tenant": t, "cached": bool(cached),
                   "status": status}
            self.top["latency"].offer(float(took_ms), seq, rec)
            self.top["device_ms"].offer(float(device_ms), seq, rec)
            self.top["scan_bytes"].offer(float(scan_bytes), seq, rec)
        row["took"].observe(float(took_ms))
        if device_ms:
            row["device"].observe(float(device_ms))

    # --------------------------------------------------------------- reading

    def _render_row(self, row: dict) -> dict:
        count = row["count"]
        took = row["took"].summary()
        dev = row["device"].summary()
        return {
            "kind": row["kind"],
            "count": count,
            "errors": row["errors"],
            "cached": row["cached"],
            "took_total_ms": round(row["took_total_ms"], 3),
            "p50_ms": took["p50"],
            "p99_ms": took["p99"],
            "max_ms": took["max"],
            "device_ms_total": round(row["device_ms_total"], 3),
            "device_p50_ms": dev["p50"],
            "device_p99_ms": dev["p99"],
            "posting_bytes": row["posting_bytes"],
            # effective = static posting minus block-max pruned bytes;
            # equal to posting_bytes whenever the gate is off
            "pruned_bytes": row.get("pruned_bytes", 0),
            "effective_posting_bytes":
                row["posting_bytes"] - row.get("pruned_bytes", 0),
            "dense_bytes": row["dense_bytes"],
            "h2d_bytes": row["h2d_bytes"],
            "d2h_bytes": row["d2h_bytes"],
            "round_trips": row["round_trips"],
            "co_batch_ratio": round(row["coalesced"] / count, 3)
            if count else 0.0,
            "co_batched_mean": round(row["co_batched_sum"] / count, 2)
            if count else 0.0,
            "co_batched_max": row["co_batched_max"],
            "compiled": row["compiled"],
            "warm_hits": row["warm_hits"],
            "tenants": dict(sorted(row["tenants"].items())),
        }

    def snapshot(self, top: bool = False) -> dict:
        """The `insights` block: per-shape rows (device-ms-hottest
        first) + conservation totals; `top=True` adds the three top-N
        registries (the `/_insights` face — `_nodes/stats` keeps the
        lighter shape)."""
        with self._lock:
            shapes = {shape: self._render_row(row)
                      for shape, row in self._shapes.items()}
            totals = dict(self.totals)
            totals["took_total_ms"] = round(totals["took_total_ms"], 3)
            totals["device_ms_total"] = round(
                totals["device_ms_total"], 3)
            out = {
                "enabled": self.enabled,
                "shapes_tracked": len(self._shapes),
                "totals": totals,
                "shapes": dict(sorted(
                    shapes.items(),
                    key=lambda kv: -kv[1]["device_ms_total"])),
            }
            if top:
                out["top"] = {m: ring.records()
                              for m, ring in self.top.items()}
        return out

    def top_queries(self, metric: str,
                    size: Optional[int] = None) -> List[dict]:
        """The reference's `GET /_insights/top_queries?metric=…` face:
        the bounded registry for one metric, heaviest first."""
        ring = self.top.get(metric)
        if ring is None:
            raise KeyError(metric)
        with self._lock:
            return ring.records(size)

    def stats(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled,
                    "shapes_tracked": len(self._shapes),
                    "queries": self.totals["queries"],
                    "errors": self.totals["errors"]}

    def clear(self) -> None:
        with self._lock:
            self._shapes.clear()
            self._seq = 0
            for ring in self.top.values():
                ring.clear()
            for k in self.totals:
                self.totals[k] = 0.0 if k.endswith("_ms") else 0


# process-wide singleton (the SCAN / INGEST_EVENTS pattern: deep call
# sites — executor wave merge, controller epilogue — need no service
# plumbing); TELEMETRY.insights is this instance
INSIGHTS = QueryInsights()
