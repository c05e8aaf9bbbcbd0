#!/usr/bin/env python
"""Render the segment-churn ledger as a per-event table + verdict mix.

Input: any JSON/JSONL artifact that carries churn records — a saved
`GET /_telemetry/ingest` response ({"churn": {"records": [...]}}), a
bare list of churn records, or JSONL records embedding a
"churn_records" list. The table is the ISSUE 16
acceptance surface in one place: per refresh/merge, how many bytes the
event actually shipped (delta publish), how many interned memo entries
it invalidated vs kept (segment-keyed carry), and where each event's
recompile verdict LANDED (warm hit / precompiled off-path / paid on a
serving thread).

    python tools/churn_report.py ingest_dump.json
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List

COLUMNS = ("churn_id", "kind", "docs", "upload_bytes",
           "live_mask_bytes", "memo_invalidations", "memo_entries_kept",
           "verdict", "precompile_ms")


def extract_records(obj) -> List[dict]:
    """Pull churn records out of any of the accepted shapes."""
    if isinstance(obj, list):
        out: List[dict] = []
        for item in obj:
            out.extend(extract_records(item))
        return out
    if not isinstance(obj, dict):
        return []
    if "verdict" in obj and ("upload_bytes" in obj or "kind" in obj):
        return [obj]
    out = []
    for key in ("churn_records", "records"):
        if isinstance(obj.get(key), list):
            out.extend(extract_records(obj[key]))
    if isinstance(obj.get("churn"), dict):
        out.extend(extract_records(obj["churn"]))
    return out


def load(path: str) -> List[dict]:
    """JSON file or JSONL file → churn records."""
    text = open(path).read().strip()
    if not text:
        return []
    try:
        return extract_records(json.loads(text))
    except json.JSONDecodeError:
        pass
    records: List[dict] = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            records.extend(extract_records(json.loads(line)))
        except json.JSONDecodeError:
            continue
    return records


def verdict_mix(records: List[dict]) -> Dict[str, int]:
    mix: Dict[str, int] = {}
    for rec in records:
        v = str(rec.get("verdict", "none"))
        mix[v] = mix.get(v, 0) + 1
    return mix


def render(records: List[dict]) -> str:
    """The per-event table + totals footer."""
    table = [list(COLUMNS)]
    for rec in records:
        table.append([str(rec.get(c, "-")) for c in COLUMNS])
    widths = [max(len(row[i]) for row in table)
              for i in range(len(COLUMNS))]
    lines = ["  ".join(cell.ljust(w) for cell, w in zip(row, widths))
             .rstrip() for row in table]
    upload = sum(int(r.get("upload_bytes", 0) or 0) for r in records)
    inval = sum(int(r.get("memo_invalidations",
                          r.get("memo_entries_dropped", 0)) or 0)
                for r in records)
    kept = sum(int(r.get("memo_entries_kept", 0) or 0) for r in records)
    mix = verdict_mix(records)
    lines.append("")
    lines.append(f"events: {len(records)}  upload_bytes: {upload}  "
                 f"memo_invalidations: {inval}  memo_entries_kept: "
                 f"{kept}")
    lines.append("verdict mix: " + ", ".join(
        f"{k}={v}" for k, v in sorted(mix.items())))
    return "\n".join(lines)


def main(argv: List[str]) -> int:
    if len(argv) != 2:
        print("usage: churn_report.py INGEST_DUMP.json")
        return 2
    records = load(argv[1])
    if not records:
        print(f"no churn records in {argv[1]}")
        return 2
    print(render(records))
    # the acceptance tripwire reads straight off the footer: any event
    # whose compile landed on a serving thread is called out loudly
    on_serve = verdict_mix(records).get("recompile-on-serve", 0)
    if on_serve:
        print(f"\nWARNING: {on_serve} event(s) paid an XLA compile on "
              f"a serving thread (recompile-on-serve)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
