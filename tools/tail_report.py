#!/usr/bin/env python
""""Where did p99 go" — attribute captured slow requests' wall to named
lifecycle phases.

Input (auto-detected), any of:
  - the flight recorder's JSONL export (`<data>/_state/tail.jsonl`) —
    one capture record per line;
  - a saved `GET /_telemetry/tail` response ({"captured": [...]});
  - a bare JSON array of capture records.

Each record is one request's lifecycle timeline (telemetry/lifecycle.py)
with its ledger-fed phase decomposition. The report attributes each
capture's `took_ms` to: `queue` (queue_wait), the request's disjoint
phase set, and an `other` remainder — and prints `attr_pct`, the share
of the wall the named phases explain. The disjointness rule: when a
record carries a controller-path `query` phase, `device_get` is the
transfer ledger's SUB-attribution of `query` (shown in its own column,
not summed); on the msearch-envelope path `device_get` is its own
disjoint phase and counts.

    python tools/tail_report.py data/_state/tail.jsonl
    curl -s localhost:9200/_telemetry/tail | python tools/tail_report.py -
    python tools/tail_report.py --assert-attribution 90 tail.jsonl
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from trace_report import _render  # noqa: E402  (shared table renderer)

# fields riding a phase map that are not durations, plus overlap_ms
# (a measured concurrency win, not a wall slice)
NON_TIME_PHASES = frozenset({"bytes_fetched", "bytes_to_device", "waves",
                             "overlap_ms"})

# the fixed report columns; every other attributed phase folds into
# `other` so envelope- and controller-path captures share one table
COLUMNS = ("queue", "compile", "device_get", "respond", "other")

# phases bucketed as "compile" / "respond" in the fixed columns
# (`handoff` = measured response-ready → request-completed interval —
# respond-path glue + scheduler starvation under contention)
_COMPILE_PHASES = frozenset({"compile_group"})
_RESPOND_PHASES = frozenset({"respond", "render", "handoff"})


def load_records(path: str) -> List[dict]:
    """Parse a tail dump ('-' = stdin) into capture-record dicts."""
    text = sys.stdin.read() if path == "-" else open(path).read()
    text = text.strip()
    if not text:
        return []
    records: List[Any] = []
    if text[0] == "{" and "\n" in text:
        parsed, bad = [], 0
        for line in text.splitlines():
            if not line.strip():
                continue
            try:
                parsed.append(json.loads(line))
            except json.JSONDecodeError:
                bad += 1
        if parsed and (len(parsed) > 1 or bad):
            if bad:
                print(f"warning: skipped {bad} unparseable line(s)",
                      file=sys.stderr)
            records = parsed
    if not records:
        data = json.loads(text)
        if isinstance(data, dict):
            records = data.get("captured", [data])
        else:
            records = list(data)
    return [r for r in records
            if isinstance(r, dict) and "took_ms" in r]


def attribution(rec: dict) -> dict:
    """One capture's wall decomposition: per-bucket ms + attr_pct."""
    took = float(rec.get("took_ms") or 0.0)
    phases: Dict[str, float] = dict(rec.get("phases") or {})
    queue = float(rec.get("queue_wait_ms") or 0.0)
    nested_device_get = "query" in phases   # controller path: device_get
    # is the ledger's sub-attribution of the query phase
    buckets = {c: 0.0 for c in COLUMNS}
    buckets["queue"] = queue
    attributed = queue
    device_get_sub = 0.0
    for name, ms in phases.items():
        if name in NON_TIME_PHASES:
            continue
        ms = float(ms)
        if name == "device_get":
            if nested_device_get:
                device_get_sub = ms
                continue
            buckets["device_get"] += ms
        elif name in _COMPILE_PHASES:
            buckets["compile"] += ms
        elif name in _RESPOND_PHASES:
            buckets["respond"] += ms
        else:
            buckets["other"] += ms
        attributed += ms
    if nested_device_get:
        buckets["device_get"] = device_get_sub   # shown, not summed
    pct = 100.0 * attributed / took if took > 0 else 100.0
    return {
        "took_ms": round(took, 3),
        "status": rec.get("status", "?"),
        "trigger": rec.get("trigger", "?"),
        "attributed_ms": round(attributed, 3),
        "attr_pct": round(min(pct, 100.0), 1),
        "buckets": {c: round(v, 3) for c, v in buckets.items()},
        "device_get_nested": nested_device_get,
    }


def report_rows(records: List[dict]) -> List[dict]:
    rows = []
    for i, rec in enumerate(records):
        att = attribution(rec)
        row = {"capture": i, "trigger": att["trigger"],
               "took_ms": att["took_ms"]}
        for col in COLUMNS:
            v = att["buckets"][col]
            cell = f"{v:g}"
            if col == "device_get" and att["device_get_nested"]:
                cell += "*"          # sub-attribution of the query phase
            row[col] = cell
        row["attr_pct"] = att["attr_pct"]
        rows.append(row)
    return rows


def render_table(rows: List[dict]) -> str:
    return _render(rows, ["capture", "trigger", "took_ms", *COLUMNS,
                          "attr_pct"])


def coalesce_groups(records: List[dict]) -> Dict[str, dict]:
    """Group tail captures by coalesce state (ISSUE 12): a capture
    whose timeline carries any `coalesce` event with co_batched > 1
    rode a SHARED wave (cross-request companions from the scheduler, or
    envelope siblings); co_batched == 1 throughout is a solo dispatch.
    The split answers the scheduler's core tail question — are the
    slow requests the coalesced ones (window cost) or the solo ones
    (missed coalescing)? `window_wait` is the mean queue_wait of the
    group: the price the window charged its captures."""
    groups: Dict[str, dict] = {}
    for rec in records:
        cb_max = 0
        saw_wave = False
        for ev in rec.get("events") or []:
            if ev.get("event") == "coalesce":
                saw_wave = True
                cb_max = max(cb_max, int(ev.get("co_batched", 0) or 0))
        if not saw_wave:
            continue
        key = "coalesced" if cb_max > 1 else "solo"
        g = groups.setdefault(key, {
            "captures": 0, "co_batched_max": 0, "took_ms": [],
            "queue_wait_ms": []})
        g["captures"] += 1
        g["co_batched_max"] = max(g["co_batched_max"], cb_max)
        g["took_ms"].append(float(rec.get("took_ms") or 0.0))
        g["queue_wait_ms"].append(float(rec.get("queue_wait_ms") or 0.0))
    out: Dict[str, dict] = {}
    for key, g in groups.items():
        took = sorted(g["took_ms"])
        out[key] = {
            "captures": g["captures"],
            "co_batched_max": g["co_batched_max"],
            "took_p50_ms": round(took[len(took) // 2], 3),
            "took_max_ms": round(took[-1], 3),
            "window_wait_ms": round(
                sum(g["queue_wait_ms"]) / len(g["queue_wait_ms"]), 3),
        }
    return out


def render_coalesce(groups: Dict[str, dict]) -> str:
    rows = [{"state": k, **v} for k, v in sorted(groups.items())]
    return _render(rows, ["state", "captures", "co_batched_max",
                          "took_p50_ms", "took_max_ms",
                          "window_wait_ms"])


def shape_groups(records: List[dict]) -> Dict[str, dict]:
    """Group tail captures by shape class (ISSUE 15): each capture's
    `shape` annotation (the interned-template / structural-hash id the
    executor/controller stamped, the same key telemetry/insights.py
    groups costs by) answers "which shape owns the p99" the way
    `ingest_events` answers "did a merge cause it". Captures without
    the annotation (pre-ISSUE-15 dumps, rejected requests) fold into
    `_unshaped` so old files still render."""
    groups: Dict[str, dict] = {}
    annotated = False
    for rec in records:
        shape = rec.get("shape")
        if shape is not None:
            annotated = True
        key = shape if shape is not None else "_unshaped"
        g = groups.setdefault(key, {"captures": 0, "took_ms": [],
                                    "queue_wait_ms": []})
        g["captures"] += 1
        g["took_ms"].append(float(rec.get("took_ms") or 0.0))
        g["queue_wait_ms"].append(float(rec.get("queue_wait_ms") or 0.0))
    if not annotated:
        return {}
    out: Dict[str, dict] = {}
    for key, g in groups.items():
        took = sorted(g["took_ms"])
        out[key] = {
            "captures": g["captures"],
            "took_p50_ms": round(took[len(took) // 2], 3),
            "took_max_ms": round(took[-1], 3),
            "queue_wait_mean_ms": round(
                sum(g["queue_wait_ms"]) / len(g["queue_wait_ms"]), 3),
        }
    return out


def render_shapes(groups: Dict[str, dict]) -> str:
    rows = [{"shape": k, **v} for k, v in sorted(
        groups.items(), key=lambda kv: -kv[1]["took_max_ms"])]
    return _render(rows, ["shape", "captures", "took_p50_ms",
                          "took_max_ms", "queue_wait_mean_ms"])


def ingest_groups(records: List[dict]) -> Dict[str, dict]:
    """Group tail captures by the write-path events that overlapped
    their window (ISSUE 13): each capture's `ingest_events` annotation
    (attached by the flight recorder from the engine event log) names
    the refresh/merge/flush events in flight while the request ran. The
    split answers "did a merge cause this p99" — a `merge` group with a
    far higher took_p50 than `quiet` is the smoking gun, and
    `events_per_capture` says how churny the overlap was."""
    groups: Dict[str, dict] = {}
    annotated = False
    for rec in records:
        evs = rec.get("ingest_events")
        if evs is None:
            continue            # pre-ISSUE-13 capture: no annotation
        annotated = True
        kinds = sorted({e.get("kind", "?") for e in evs})
        key = "+".join(kinds) if kinds else "quiet"
        g = groups.setdefault(key, {"captures": 0, "events": 0,
                                    "took_ms": []})
        g["captures"] += 1
        g["events"] += len(evs)
        g["took_ms"].append(float(rec.get("took_ms") or 0.0))
    if not annotated:
        return {}
    out: Dict[str, dict] = {}
    for key, g in groups.items():
        took = sorted(g["took_ms"])
        out[key] = {
            "captures": g["captures"],
            "events_per_capture": round(g["events"]
                                        / max(g["captures"], 1), 2),
            "took_p50_ms": round(took[len(took) // 2], 3),
            "took_max_ms": round(took[-1], 3),
        }
    return out


def render_ingest(groups: Dict[str, dict]) -> str:
    rows = [{"ingest_overlap": k, **v} for k, v in sorted(groups.items())]
    return _render(rows, ["ingest_overlap", "captures",
                          "events_per_capture", "took_p50_ms",
                          "took_max_ms"])


def device_groups(records: List[dict]) -> Dict[str, dict]:
    """Group SPMD collective-phase events by device (ISSUE 14): a
    capture whose timeline carries `partial` events was served by the
    shard_map program with the SPMD timeline on — per device, the
    partial-wall distribution and how often the `merge` event named it
    the straggler. The split answers the sharded-serving tail question
    the way coalesce_groups answers the scheduler's: is the p99 one
    lame chip (one device owns the straggler column) or uniform load
    (straggler hits spread evenly)?"""
    groups: Dict[str, dict] = {}
    skews: List[float] = []
    for rec in records:
        for ev in rec.get("events") or []:
            if ev.get("event") == "partial":
                dev = str(ev.get("device", "?"))
                g = groups.setdefault(dev, {
                    "partials": 0, "wall_ms": [], "straggler_hits": 0})
                g["partials"] += 1
                g["wall_ms"].append(float(ev.get("ms", 0.0) or 0.0))
            elif ev.get("event") == "merge":
                skews.append(float(ev.get("skew_ms", 0.0) or 0.0))
                straggler = ev.get("straggler")
                if straggler is not None:
                    g = groups.setdefault(str(straggler), {
                        "partials": 0, "wall_ms": [],
                        "straggler_hits": 0})
                    g["straggler_hits"] += 1
    out: Dict[str, dict] = {}
    for dev, g in groups.items():
        walls = sorted(g["wall_ms"]) or [0.0]
        out[dev] = {
            "partials": g["partials"],
            "wall_p50_ms": round(walls[len(walls) // 2], 3),
            "wall_max_ms": round(walls[-1], 3),
            "straggler_hits": g["straggler_hits"],
        }
    if out and skews:
        skews.sort()
        out["_skew"] = {"partials": len(skews),
                        "wall_p50_ms": round(skews[len(skews) // 2], 3),
                        "wall_max_ms": round(skews[-1], 3),
                        "straggler_hits": "-"}
    return out


def render_devices(groups: Dict[str, dict]) -> str:
    rows = [{"device": k, **v} for k, v in sorted(
        groups.items(), key=lambda kv: (kv[0] == "_skew", kv[0]))]
    return _render(rows, ["device", "partials", "wall_p50_ms",
                          "wall_max_ms", "straggler_hits"])


def rejection_groups(records: List[dict]) -> Dict[str, dict]:
    """Group captures that carry a `reject` lifecycle event by the
    structured reason + tenant the admission controller stamped
    (`deadline_shed` | `tenant_quota` | `breaker:<name>` |
    `backpressure`, ISSUE 11). `items` sums per-item msearch rejects
    (the event's `items` field, 1 for the single-search path);
    `reject_ms` tracks how fast the node turned the rejections around —
    the <5 ms shed-latency contract, eyeballable per group."""
    groups: Dict[str, dict] = {}
    for rec in records:
        for ev in rec.get("events") or []:
            if ev.get("event") != "reject":
                continue
            key = f"{ev.get('reason', '?')}" \
                  f"[{ev.get('tenant', '_default')}]"
            g = groups.setdefault(
                key, {"captures": 0, "items": 0, "max_took_ms": 0.0})
            g["captures"] += 1
            g["items"] += int(ev.get("items", 1))
            g["max_took_ms"] = max(g["max_took_ms"],
                                   float(rec.get("took_ms") or 0.0))
    return groups


def render_rejections(groups: Dict[str, dict]) -> str:
    rows = [{"reason": k, **{kk: f"{vv:g}" if kk == "max_took_ms"
                             else vv for kk, vv in v.items()}}
            for k, v in sorted(groups.items())]
    return _render(rows, ["reason", "captures", "items", "max_took_ms"])


def main(argv: List[str]) -> int:
    min_attr = None
    args: List[str] = []
    rest = list(argv[1:])
    while rest:
        a = rest.pop(0)
        if a.startswith("--assert-attribution"):
            min_attr = float(a.split("=", 1)[1]) if "=" in a \
                else float(rest.pop(0))
        else:
            args.append(a)
    path = args[0] if args else "-"
    records = load_records(path)
    if not records:
        print("no tail captures found (enable the flight recorder: "
              "POST /_telemetry/tail/_enable, then re-run traffic)")
        return 1
    rows = report_rows(records)
    print(f"{len(records)} captured slow request(s)   "
          f"(* = device_get nested inside query, not summed)")
    print(render_table(rows))
    co = coalesce_groups(records)
    if co:
        print("\ntail by coalesce state (co_batched > 1 = shared wave):")
        print(render_coalesce(co))
    sg = shape_groups(records)
    if sg:
        print("\ntail by shape class (which shape owns the p99):")
        print(render_shapes(sg))
    ig = ingest_groups(records)
    if ig:
        print("\ntail by ingest overlap (write-path events in flight "
              "during the capture window):")
        print(render_ingest(ig))
    dg = device_groups(records)
    if dg:
        print("\ntail by device (SPMD partial walls + straggler "
              "attribution; _skew = per-query max-median):")
        print(render_devices(dg))
    groups = rejection_groups(records)
    if groups:
        print(f"\nrejections by reason "
              f"({sum(g['items'] for g in groups.values())} item(s) "
              f"across {sum(g['captures'] for g in groups.values())} "
              f"capture(s)):")
        print(render_rejections(groups))
    attrs = [r["attr_pct"] for r in rows]
    print(f"\nattribution: min {min(attrs):.1f}%  "
          f"mean {sum(attrs) / len(attrs):.1f}%")
    if min_attr is not None:
        under = [r for r in rows if r["attr_pct"] < min_attr]
        if under:
            print(f"FAIL: {len(under)} capture(s) under "
                  f"{min_attr:g}% attribution")
            return 1
        print(f"OK: every capture >= {min_attr:g}% attributed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
