#!/usr/bin/env python
"""Per-channel transfer report from a ledger dump.

The transfer ledger (opensearch_tpu/telemetry/ledger.py) attributes every
host↔device transfer on the query path to a named channel; this tool
renders a dump of it as the table ROADMAP's speed items
work from: bytes / transfers / round-trips per channel and direction,
the device_get wall decomposition, and the implied link bandwidth
(d2h bytes over device_get wall — the number on-device top-k/gather has
to beat by shrinking the numerator).

Input (auto-detected), any of:
  - a saved `GET /_telemetry/transfers` response
    ({"transfers": {...}, "device_memory": {...}});
  - a bare ledger snapshot ({"channels": ..., "device_get": ...});
  - a JSONL file of records that carry the snapshot at
    telemetry.transfers (the first line carrying a ledger is reported).

    python tools/transfer_report.py transfers.json
    curl -s localhost:9200/_telemetry/transfers | python tools/transfer_report.py -
"""

from __future__ import annotations

import json
import sys
from typing import Any, List, Optional


def _find_snapshot(obj: Any) -> Optional[dict]:
    """Dig the ledger snapshot out of whichever wrapper it arrived in."""
    if not isinstance(obj, dict):
        return None
    if "channels" in obj and "device_get" in obj:
        return obj
    for key in ("transfers", "telemetry"):
        found = _find_snapshot(obj.get(key))
        if found is not None:
            return found
    return None


def load_snapshot(path: str) -> Optional[dict]:
    """Parse a dump file ('-' = stdin); JSONL files report the first
    line that carries a ledger snapshot."""
    text = sys.stdin.read() if path == "-" else open(path).read()
    text = text.strip()
    if not text:
        return None
    candidates: List[Any] = []
    if text[0] == "{" and "\n" in text:
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                candidates.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    if not candidates:
        try:
            candidates = [json.loads(text)]
        except json.JSONDecodeError:
            return None
    for obj in candidates:
        snap = _find_snapshot(obj)
        if snap is not None:
            return snap
    return None


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024.0 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GB"


def channel_rows(snap: dict) -> List[dict]:
    rows = []
    totals = snap.get("bytes_total", {})
    for direction in ("h2d", "d2h"):
        chans = snap.get("channels", {}).get(direction, {})
        dir_total = totals.get(direction, 0) or \
            sum(e.get("bytes", 0) for e in chans.values())
        for name in sorted(chans,
                           key=lambda c: -chans[c].get("bytes", 0)):
            ent = chans[name]
            rows.append({
                "channel": name,
                "dir": direction,
                "transfers": ent.get("transfers", 0),
                "round_trips": ent.get("round_trips", 0),
                "bytes": _fmt_bytes(ent.get("bytes", 0)),
                "pct_of_dir": round(
                    100.0 * ent.get("bytes", 0) / max(dir_total, 1), 1),
            })
    return rows


def render_table(rows: List[dict]) -> str:
    headers = ["channel", "dir", "transfers", "round_trips", "bytes",
               "pct_of_dir"]
    table = [headers] + [[str(r[h]) for h in headers] for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def summary_lines(snap: dict) -> List[str]:
    get = snap.get("device_get", {})
    totals = snap.get("bytes_total", {})
    calls = get.get("calls", 0)
    total_ms = float(get.get("total_ms", 0.0))
    d2h = totals.get("d2h", 0)
    lines = [
        f"waves: {snap.get('waves', 0)}  device_get calls: {calls}  "
        f"device_get wall: {total_ms:.1f}ms",
        f"bytes h2d: {_fmt_bytes(totals.get('h2d', 0))}  "
        f"d2h: {_fmt_bytes(d2h)}",
    ]
    if total_ms > 0 and d2h:
        mbps = (d2h / 1e6) / (total_ms / 1e3)
        lines.append(f"implied d2h bandwidth: {mbps:.1f} MB/s "
                     f"({_fmt_bytes(d2h / max(calls, 1))}/round-trip)")
    pipeline = snap.get("pipeline") or {}
    if pipeline:
        lines.append(
            f"pipeline: inflight_waves={pipeline.get('inflight_waves', 0)}"
            f" max_inflight={pipeline.get('max_inflight_waves', 0)}"
            f" overlap={pipeline.get('overlap_ms', 0.0):.1f}ms over "
            f"{pipeline.get('overlap_events', 0)} wave(s)")
    rolling = snap.get("rolling") or {}
    for key, label in (("wave_bytes", "bytes/wave"),
                       ("wave_device_get_ms", "device_get ms/wave"),
                       ("wave_overlap_ms", "overlap ms/wave")):
        s = rolling.get(key)
        if s and s.get("count"):
            lines.append(
                f"rolling {label}: p50={s.get('p50')} p95={s.get('p95')} "
                f"p99={s.get('p99')} max={s.get('max')}")
    return lines


def main(argv: List[str]) -> int:
    path = argv[1] if len(argv) > 1 else "-"
    snap = load_snapshot(path)
    if snap is None:
        print("no transfer ledger found (enable it: "
              "POST /_telemetry/transfers/_enable, then re-run "
              "traffic and dump "
              "GET /_telemetry/transfers)")
        return 1
    for line in summary_lines(snap):
        print(line)
    rows = channel_rows(snap)
    if rows:
        print(render_table(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
