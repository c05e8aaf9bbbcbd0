#!/usr/bin/env python
"""Render a multi-chip scaling-efficiency record (ISSUE 14): JSONL, one
point per device count.

One row per device count D: serving QPS on the real segment-sharded
SPMD path, per-chip scaling efficiency QPS(D)/(D·QPS(1)), straggler
skew (max−median per-chip wall), analytic collective bytes/query over
the ICI, and the live scanned-bytes counter (the block-max trigger
metric). A per-device section
breaks each point down by chip: partial wall, straggler hits, h2d
bytes.

    python tools/scaling_report.py points.jsonl
    python tools/scaling_report.py --assert-efficiency 0.5 points.jsonl

--assert-efficiency F: exit 1 unless every multi-chip point (D > 1)
holds per-chip efficiency >= F — the harness's own floor check, next
to tools/bench_compare.py's cross-round 15% regression gate.
"""

from __future__ import annotations

import json
import os
import sys
from typing import List

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from trace_report import _render  # noqa: E402  (shared table renderer)


def load_records(path: str) -> List[dict]:
    """One JSON object per line (or one array) → scaling point dicts,
    sorted by device count; error points kept (reported, never
    silently dropped)."""
    text = (sys.stdin.read() if path == "-" else open(path).read()).strip()
    if not text:
        return []
    records: List[dict] = []
    if text[0] == "[":
        records = [r for r in json.loads(text) if isinstance(r, dict)]
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
                records.append(obj)
    records = [r for r in records if "devices" in r]
    records.sort(key=lambda r: r["devices"])
    return records


def report_rows(records: List[dict]) -> List[dict]:
    rows = []
    for rec in records:
        if "error" in rec:
            rows.append({"devices": rec["devices"],
                         "mode": rec.get("mode", "-"),
                         "qps": "ERROR",
                         "efficiency": "-", "skew_p50_ms": "-",
                         "ici_bytes_q": "-", "scan_bytes_q": "-",
                         "eff_bytes_q": "-", "pruned_frac": "-"})
            continue
        rows.append({
            "devices": rec["devices"],
            "mode": rec.get("mode", "-"),
            "qps": f"{rec.get('value', 0):g}",
            "efficiency": f"{rec['per_chip_efficiency']:g}"
            if rec.get("per_chip_efficiency") is not None else "-",
            "skew_p50_ms": f"{rec['straggler_skew_p50_ms']:g}"
            if rec.get("straggler_skew_p50_ms") is not None else "-",
            "ici_bytes_q": f"{rec.get('collective_ici_bytes_per_query', 0):g}",
            "scan_bytes_q":
                f"{rec['scanned_bytes_per_query_p50']:.0f}"
                if rec.get("scanned_bytes_per_query_p50") else "-",
            # block-max overlay (ISSUE 20): the effective (post-pruning)
            # per-query posting bytes and the pruned share — the pruned
            # arm's payoff next to the static trigger column; unpruned
            # rows show effective == static (the scan conservation law)
            "eff_bytes_q":
                f"{rec['effective_bytes_per_query_p50']:.0f}"
                if rec.get("effective_bytes_per_query_p50") else "-",
            "pruned_frac": f"{rec['pruned_fraction']:g}"
            if rec.get("pruned_fraction") is not None else "-",
        })
    return rows


def device_rows(records: List[dict]) -> List[dict]:
    """Per-chip breakdown across every point: who straggled, who moved
    the bytes."""
    rows = []
    for rec in records:
        per_dev = rec.get("per_device") or {}
        for dev, ent in sorted(per_dev.items(), key=lambda kv: int(kv[0])):
            q = max(ent.get("queries", 0), 1)
            rows.append({
                "D": rec["devices"],
                "device": dev,
                "queries": ent.get("queries", 0),
                "partial_ms_per_q":
                    f"{ent.get('partial_ms', 0.0) / q:.3f}",
                "straggler_hits": ent.get("straggler_hits", 0),
                "h2d_bytes": ent.get("h2d_bytes", 0),
            })
    return rows


def main(argv: List[str]) -> int:
    min_eff = None
    args: List[str] = []
    rest = list(argv[1:])
    while rest:
        a = rest.pop(0)
        if a.startswith("--assert-efficiency"):
            min_eff = float(a.split("=", 1)[1]) if "=" in a \
                else float(rest.pop(0))
        else:
            args.append(a)
    path = args[0] if args else "-"
    records = load_records(path)
    if not records:
        print(f"no scaling points found in {path}")
        return 1
    print(f"multi-chip scaling ({path}): QPS(D) on the real SPMD "
          f"serving path, efficiency = QPS(D)/(D*QPS(1))")
    print(_render(report_rows(records),
                  ["devices", "mode", "qps", "efficiency", "skew_p50_ms",
                   "ici_bytes_q", "scan_bytes_q", "eff_bytes_q",
                   "pruned_frac"]))
    dev = device_rows(records)
    if dev:
        print("\nper-chip breakdown (partial wall per query, "
              "straggler hits, upload bytes):")
        print(_render(dev, ["D", "device", "queries", "partial_ms_per_q",
                            "straggler_hits", "h2d_bytes"]))
    if min_eff is not None:
        bad = [r for r in records
               if "error" not in r and r["devices"] > 1
               and (r.get("per_chip_efficiency") or 0) < min_eff]
        errors = [r for r in records if "error" in r]
        if bad or errors:
            for r in bad:
                print(f"FAIL: D={r['devices']} efficiency "
                      f"{r.get('per_chip_efficiency')} < {min_eff:g}")
            for r in errors:
                print(f"FAIL: D={r['devices']} errored: "
                      f"{r['error'][:120]}")
            return 1
        print(f"OK: every multi-chip point >= {min_eff:g} per-chip "
              f"efficiency")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
