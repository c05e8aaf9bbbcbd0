"""Always-on scanned-bytes accounting for the query kernels (ISSUE 14).

ROADMAP item 4 defers block-max (WAND) pruning behind a measured
trigger: "add on-device block-max skipping once scanned-bytes/query
starts dominating" (BM25S, arxiv 2407.03618). This module is that
number LIVE: per-query counters for the bytes each kernel class touches,
aggregated into a per-shard/per-segment heat map on `_nodes/stats`
(`telemetry.scan`), so the go/no-go trigger is a standing dashboard
number instead of an archaeology exercise.

Two byte classes, each computed from term metadata and plan statics
(tests/test_device_ledger.py holds the live count to the formula):

- **posting bytes** (candidate-buffer kernel): the query terms' posting
  blocks — `blocks × 128 lanes × 8 B` (docs int32 + tf f32). Counted
  from `Plan.scan_blocks`, a static the compiler records at
  plan build; per query this is one attribute read per plan node —
  no per-lane work, no device sync.
- **dense-lane bytes** (dense kernel): `d_pad × 9 B` per clause
  evaluation — score f32 + hit i32 + live bool per doc lane, the
  "~9 bytes/doc-lane" O(d_pad) HBM traffic of a dense scan.

Always-on discipline: this is NOT a gated subsystem — the counters are
the trigger metric for a capacity decision, so they must be live on
every node like the inflight-wave gauge and the engine event log. The
budget that buys: O(plan nodes) integer adds per (query, segment) on
the host, one dict update per segment and one rolling observe per
query. Nothing allocates per lane, nothing syncs the device.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from opensearch_tpu.telemetry.rolling import RollingEstimator

# posting block geometry (ops/device_segment.py): 128 lanes per block,
# docs int32 + tf f32 = 8 bytes per lane
POSTING_BLOCK_BYTES = 128 * 8
# dense kernel per-lane traffic: score f32 + hit i32 + live bool
DENSE_LANE_BYTES = 9

# bound on distinct tracked (index, shard) rows and per-shard segment
# rows: corpus/segment churn must not grow the map without bound — past
# the cap, new keys fold into the overflow row
_MAX_SHARDS = 128
_MAX_SEGMENTS_PER_SHARD = 16
_OVERFLOW = "_other"


def plan_scan_blocks(plan) -> int:
    """Total posting blocks a compiled plan tree gathers — the sum of
    each text node's `scan_blocks` static (compile.py records it at
    plan build). Memoized on the root plan object: plans are immutable
    and memo-shared, so the warm path is one attribute read."""
    cached = getattr(plan, "_scan_blocks_total", None)
    if cached is None:
        cached = plan.scan_blocks + sum(
            plan_scan_blocks(c) for c in plan.children)
        try:
            plan._scan_blocks_total = cached
        except AttributeError:      # frozen/slotted plan variants
            pass
    return cached


def plan_scan_extra(plan) -> int:
    """Total extra-class bytes a compiled plan tree scans — the sum of
    each node's `scan_extra` static (rank_vectors token-matrix / PQ-code
    bytes the maxsim kernels walk, recorded by compile.py). Memoized
    like plan_scan_blocks; plans without the field cost one getattr."""
    cached = getattr(plan, "_scan_extra_total", None)
    if cached is None:
        cached = getattr(plan, "scan_extra", 0) + sum(
            plan_scan_extra(c) for c in plan.children)
        try:
            plan._scan_extra_total = cached
        except AttributeError:      # frozen/slotted plan variants
            pass
    return cached


class ScanAccounting:
    """Node-wide scanned-bytes aggregates + the per-shard heat map."""

    def __init__(self):
        self._lock = threading.Lock()
        self.queries = 0
        self.posting_bytes_total = 0
        self.dense_bytes_total = 0
        # block-max pruning overlay (ISSUE 20): bytes the phase-B mask
        # kept OUT of the posting gathers. Static accounting above is
        # untouched (Plan.scan_blocks stays the ceiling); effective
        # bytes derive as posting - pruned at read time, so with the
        # gate off (no note_pruned_* calls) effective == static exactly.
        self.pruned_bytes_total = 0
        self.pruned_queries = 0
        # per-query posting-bytes distribution — THE trigger metric
        # (the offline scanned-bytes/query column, live)
        self.per_query_posting = RollingEstimator()
        self.per_query_dense = RollingEstimator()
        # per-query EFFECTIVE posting bytes (static - pruned), fed only
        # by waves that ran a pruning-admitted program
        self.per_query_effective = RollingEstimator()
        # (index, shard) -> heat-map row
        self._shards: Dict[Tuple[str, str], dict] = {}

    # ------------------------------------------------------------- hot path

    def note_segment(self, index: str, shard: str, seg_id: str,
                     posting_bytes: int, dense_bytes: int,
                     kernel: str) -> None:
        """One (query, segment) execution's scan attribution. `kernel`
        names the program class that ran: `candidate` (candidate-buffer
        kernel), `dense` (per-doc dense vector), `spmd` (the
        distributed program — dense per row), `hybrid`."""
        key = (str(index), str(shard))
        with self._lock:
            row = self._shards.get(key)
            if row is None:
                if len(self._shards) >= _MAX_SHARDS:
                    key = (_OVERFLOW, _OVERFLOW)
                    row = self._shards.get(key)
                if row is None:
                    row = self._shards[key] = {
                        "queries": 0, "posting_bytes": 0,
                        "dense_bytes": 0, "kernels": {}, "segments": {}}
            row["queries"] += 1
            row["posting_bytes"] += int(posting_bytes)
            row["dense_bytes"] += int(dense_bytes)
            row["kernels"][kernel] = row["kernels"].get(kernel, 0) + 1
            segs = row["segments"]
            seg = segs.get(seg_id)
            if seg is None:
                if len(segs) >= _MAX_SEGMENTS_PER_SHARD:
                    seg_id = _OVERFLOW
                    seg = segs.get(seg_id)
                if seg is None:
                    seg = segs[seg_id] = {
                        "queries": 0, "posting_bytes": 0,
                        "dense_bytes": 0}
            seg["queries"] += 1
            seg["posting_bytes"] += int(posting_bytes)
            seg["dense_bytes"] += int(dense_bytes)

    def note_query(self, posting_bytes: int, dense_bytes: int) -> None:
        """One request's total scan bytes across every segment it
        touched — feeds the per-query distribution the block-max
        trigger reads."""
        with self._lock:
            self.queries += 1
            self.posting_bytes_total += int(posting_bytes)
            self.dense_bytes_total += int(dense_bytes)
        self.per_query_posting.observe(float(posting_bytes))
        if dense_bytes:
            self.per_query_dense.observe(float(dense_bytes))

    def note_batch(self, index: str, shard: str, seg_rows: Dict,
                   per_query: List[Tuple[int, int]]) -> None:
        """One msearch wave's scan attribution in a single flush: the
        envelope path accumulates per-(segment, kernel) rows and
        per-item (posting, dense) totals LOCALLY while packing (plain
        dict adds, no lock), then lands everything here — one lock
        acquire per WAVE instead of two per query, which is what keeps
        the always-on counters inside the <2% analytic overhead gate
        at B=1024. `seg_rows`: {seg_id: [queries, posting_bytes,
        dense_bytes, {kernel: count}]}."""
        if not per_query:
            return
        key = (str(index), str(shard))
        agg_posting = sum(p for p, _ in per_query)
        agg_dense = sum(d for _, d in per_query)
        with self._lock:
            row = self._shards.get(key)
            if row is None:
                if len(self._shards) >= _MAX_SHARDS:
                    key = (_OVERFLOW, _OVERFLOW)
                    row = self._shards.get(key)
                if row is None:
                    row = self._shards[key] = {
                        "queries": 0, "posting_bytes": 0,
                        "dense_bytes": 0, "kernels": {}, "segments": {}}
            row["queries"] += len(per_query)
            row["posting_bytes"] += agg_posting
            row["dense_bytes"] += agg_dense
            segs = row["segments"]
            for seg_id, (n, posting, dense, kernels) in seg_rows.items():
                for kernel, cnt in kernels.items():
                    row["kernels"][kernel] = \
                        row["kernels"].get(kernel, 0) + cnt
                seg = segs.get(seg_id)
                if seg is None:
                    if len(segs) >= _MAX_SEGMENTS_PER_SHARD:
                        seg_id = _OVERFLOW
                        seg = segs.get(seg_id)
                    if seg is None:
                        seg = segs[seg_id] = {
                            "queries": 0, "posting_bytes": 0,
                            "dense_bytes": 0}
                seg["queries"] += n
                seg["posting_bytes"] += posting
                seg["dense_bytes"] += dense
            self.queries += len(per_query)
            self.posting_bytes_total += agg_posting
            self.dense_bytes_total += agg_dense
        for posting, dense in per_query:
            self.per_query_posting.observe(float(posting))
            if dense:
                self.per_query_dense.observe(float(dense))

    def note_pruned_batch(self, index: str, shard: str,
                          seg_pruned: Dict[str, int],
                          per_query: List[Tuple[int, int]]) -> None:
        """Block-max pruning overlay for one msearch wave (ISSUE 20),
        flushed at FINISH time (the pruned counts ride the existing
        result page — phase-A popcounts fetched with the top-k rows, no
        extra round trip). The static note_batch accounting for the same
        wave already landed at prepare; this call only adds the pruned
        deltas, so effective = posting - pruned stays conservative
        (effective <= static always, == when the gate is off).

        seg_pruned: {seg_id: pruned_bytes}; per_query: [(static_posting
        _bytes, pruned_bytes)] for every query in the wave's
        pruning-admitted groups (pruned may be 0 — those still feed the
        effective distribution so pruned/unpruned p50s compare like for
        like). The shard row's pruned bytes derive from seg_pruned, not
        per_query: the SPMD path spans shards in one query and calls
        once per shard, with the single per_query entry on the first
        call only."""
        if not per_query and not seg_pruned:
            return
        key = (str(index), str(shard))
        agg_pruned = sum(int(p) for p in seg_pruned.values())
        with self._lock:
            row = self._shards.get(key)
            if row is None:
                if len(self._shards) >= _MAX_SHARDS:
                    key = (_OVERFLOW, _OVERFLOW)
                    row = self._shards.get(key)
                if row is None:
                    row = self._shards[key] = {
                        "queries": 0, "posting_bytes": 0,
                        "dense_bytes": 0, "kernels": {}, "segments": {}}
            row["pruned_bytes"] = row.get("pruned_bytes", 0) + agg_pruned
            segs = row["segments"]
            for seg_id, pruned in seg_pruned.items():
                seg = segs.get(seg_id)
                if seg is None:
                    seg_id = _OVERFLOW
                    seg = segs.get(seg_id)
                if seg is not None:
                    seg["pruned_bytes"] = \
                        seg.get("pruned_bytes", 0) + int(pruned)
            self.pruned_bytes_total += agg_pruned
            self.pruned_queries += len(per_query)
        for posting, pruned in per_query:
            self.per_query_effective.observe(float(posting - pruned))

    # --------------------------------------------------------------- reading

    def stats(self) -> dict:
        with self._lock:
            shards = {}
            for (index, shard), row in sorted(self._shards.items()):
                pruned = row.get("pruned_bytes", 0)
                segments = {}
                for sid, seg in sorted(row["segments"].items()):
                    s = dict(seg)
                    sp = s.pop("pruned_bytes", 0)
                    s["pruned_bytes"] = sp
                    s["effective_posting_bytes"] = s["posting_bytes"] - sp
                    segments[sid] = s
                shards[f"{index}[{shard}]"] = {
                    "queries": row["queries"],
                    "posting_bytes": row["posting_bytes"],
                    # effective = static ceiling minus phase-B pruned
                    # bytes; identical to posting_bytes when the
                    # blockmax gate is off (conservation contract)
                    "pruned_bytes": pruned,
                    "effective_posting_bytes": row["posting_bytes"] - pruned,
                    "dense_bytes": row["dense_bytes"],
                    "kernels": dict(sorted(row["kernels"].items())),
                    "segments": segments,
                }
            queries = self.queries
            posting = self.posting_bytes_total
            dense = self.dense_bytes_total
            pruned_total = self.pruned_bytes_total
            pruned_queries = self.pruned_queries
        # with no pruning-admitted traffic the effective distribution has
        # no observations of its own: report the static distribution so
        # effective == static holds byte-exactly, not vacuously
        effective = self.per_query_effective.summary() if pruned_queries \
            else self.per_query_posting.summary()
        return {
            "queries": queries,
            "posting_bytes_total": posting,
            "pruned_bytes_total": pruned_total,
            "effective_posting_bytes_total": posting - pruned_total,
            "dense_bytes_total": dense,
            "per_query": {
                "posting_bytes": self.per_query_posting.summary(),
                "effective_posting_bytes": effective,
                "dense_bytes": self.per_query_dense.summary(),
            },
            "shards": shards,
        }

    def reset(self) -> None:
        with self._lock:
            self.queries = 0
            self.posting_bytes_total = 0
            self.dense_bytes_total = 0
            self.pruned_bytes_total = 0
            self.pruned_queries = 0
            self._shards.clear()
        self.per_query_posting.reset()
        self.per_query_dense.reset()
        self.per_query_effective.reset()


# process-wide singleton (the TELEMETRY.scan face; module-level like
# INGEST_EVENTS so deep call sites need no service plumbing)
SCAN = ScanAccounting()
