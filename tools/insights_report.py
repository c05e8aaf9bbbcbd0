#!/usr/bin/env python
"""Top shapes by device-ms — render a query-insights dump as a table.

Input (auto-detected), any of:
  - a JSONL file, one record per line, the insights block under
    "insights";
  - a saved `GET /_insights` response ({"insights": {...}});
  - a bare insights snapshot ({"shapes": {...}, "totals": {...}}).

The report answers the per-class questions ROADMAP items 3/4 need
(block-max pays per query class; the MaxSim tier's stage budget needs
per-class cost): which shape classes own the device wall, what they
scan, how well they coalesce, and who sends them.

    python tools/insights_report.py insights.json
    curl -s localhost:9200/_insights | python tools/insights_report.py -
    python tools/insights_report.py --metric scan insights.json
    python tools/insights_report.py --assert-shapes 3 insights.json
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from trace_report import _render  # noqa: E402  (shared table renderer)

# --metric choices -> the shape-row key the table sorts by
SORT_KEYS = {"device": "device_ms_total", "latency": "took_total_ms",
             "scan": "_scan_bytes", "count": "count"}


def load_insights(path: str) -> Optional[dict]:
    """Parse any supported dump shape into the insights snapshot dict
    ({"shapes": ..., "totals": ...}). '-' reads stdin."""
    text = sys.stdin.read() if path == "-" else open(path).read()
    text = text.strip()
    if not text:
        return None
    candidates: List[dict] = []
    if text[0] == "[":
        candidates = [r for r in json.loads(text) if isinstance(r, dict)]
    else:
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict):
                candidates.append(obj)
    for rec in candidates:
        for block in (rec.get("insights"), rec):
            if isinstance(block, dict) and \
                    isinstance(block.get("shapes"), dict):
                return block
    return None


def shape_rows(ins: dict, sort_key: str = "device_ms_total") \
        -> List[dict]:
    """Flatten the per-shape block into report rows, heaviest first by
    `sort_key`. Scan/transfer render in KB; co-batch as the ratio of
    requests that rode a shared wave."""
    rows = []
    for shape, r in ins.get("shapes", {}).items():
        scan = int(r.get("posting_bytes", 0)) + int(r.get("dense_bytes",
                                                          0))
        transfer = int(r.get("h2d_bytes", 0)) + int(r.get("d2h_bytes", 0))
        rows.append({
            "shape": shape,
            "kind": r.get("kind", "?"),
            "count": r.get("count", 0),
            "p50_ms": r.get("p50_ms"),
            "p99_ms": r.get("p99_ms"),
            "device_ms": round(float(r.get("device_ms_total", 0)), 1),
            "scan_kb": round(scan / 1024, 1),
            "transfer_kb": round(transfer / 1024, 1),
            "co_batch": r.get("co_batch_ratio", 0.0),
            "warm": r.get("warm_hits", 0),
            "compiled": r.get("compiled", 0),
            "cached": r.get("cached", 0),
            "_scan_bytes": scan,
            "took_total_ms": round(float(r.get("took_total_ms", 0)), 1),
            "device_ms_total": float(r.get("device_ms_total", 0)),
        })
    rows.sort(key=lambda r: (-float(r.get(sort_key, 0) or 0),
                             r["shape"]))
    return rows


def render_shapes(rows: List[dict]) -> str:
    cols = ["shape", "kind", "count", "p50_ms", "p99_ms", "device_ms",
            "scan_kb", "transfer_kb", "co_batch", "warm", "compiled",
            "cached"]
    return _render([{c: r.get(c) for c in cols} for r in rows], cols)


def render_top(ins: dict, size: int = 3) -> str:
    """The heavy-query registries: the top few capture records per
    metric, one compact line each."""
    out = []
    for metric, recs in (ins.get("top") or {}).items():
        out.append(f"top[{metric}]:")
        for rec in recs[:size]:
            out.append(
                f"  {rec.get('shape')}  took={rec.get('took_ms')}ms  "
                f"device={rec.get('device_ms')}ms  "
                f"scan={rec.get('scan_bytes')}B  "
                f"co_batched={rec.get('co_batched')}  "
                f"tenant={rec.get('tenant')}")
    return "\n".join(out)


def render_tenants(ins: dict) -> str:
    """Per-tenant request counts summed over shapes (who sends what)."""
    tenants: Dict[str, int] = {}
    for r in ins.get("shapes", {}).values():
        for t, n in (r.get("tenants") or {}).items():
            tenants[t] = tenants.get(t, 0) + int(n)
    rows = [{"tenant": t, "requests": n}
            for t, n in sorted(tenants.items(), key=lambda kv: -kv[1])]
    return _render(rows, ["tenant", "requests"]) if rows else ""


def main(argv: List[str]) -> int:
    metric = "device"
    min_shapes = None
    args: List[str] = []
    rest = list(argv[1:])
    while rest:
        a = rest.pop(0)
        if a.startswith("--metric"):
            metric = a.split("=", 1)[1] if "=" in a else rest.pop(0)
        elif a.startswith("--assert-shapes"):
            min_shapes = int(a.split("=", 1)[1]) if "=" in a \
                else int(rest.pop(0))
        else:
            args.append(a)
    if metric not in SORT_KEYS:
        print(f"unknown --metric {metric!r} "
              f"(one of {', '.join(sorted(SORT_KEYS))})")
        return 2
    path = args[0] if args else "-"
    ins = load_insights(path)
    if ins is None:
        print("no insights block found (enable the recorder: "
              "POST /_insights/_enable, then re-run traffic)")
        return 1
    rows = shape_rows(ins, SORT_KEYS[metric])
    totals = ins.get("totals", {})
    print(f"{len(rows)} shape class(es), "
          f"{totals.get('queries', '?')} request(s) attributed "
          f"(sorted by {metric})")
    print(render_shapes(rows))
    top = render_top(ins)
    if top:
        print("\nheavy-query registries (top captures per metric):")
        print(top)
    tns = render_tenants(ins)
    if tns:
        print("\nrequests by tenant:")
        print(tns)
    if min_shapes is not None and len(rows) < min_shapes:
        print(f"\nFAIL: {len(rows)} shape class(es) < {min_shapes}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
