#!/usr/bin/env python
"""Per-phase latency report from a telemetry trace dump.

The successor to ad-hoc profiling runs: instead of
hand-instrumented one-off scripts, point this at the tracer's output and
get the per-phase latency distribution of real traffic.

Input (auto-detected), any of:
  - the JSONL export the node appends under `<data>/_state/traces.jsonl`
    (one {"trace": {...}, "ts_ms": N} object per line);
  - a saved `GET /_telemetry/traces` response ({"traces": [...]});
  - a bare JSON array of trace records.

Output: one fixed-width table — per phase (root spans' direct children,
grouped by span name) count, p50/p99/max milliseconds and share of total
root time — plus the root-span latency line. Pure stdlib; no server
required.

    python tools/trace_report.py data/_state/traces.jsonl
    curl -s localhost:9200/_telemetry/traces | python tools/trace_report.py -
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List


def _extract_trace(obj: Any) -> Any:
    """A record may be the span dict itself or wrapped as {"trace": ...}."""
    if isinstance(obj, dict) and "trace" in obj and "name" not in obj:
        return obj["trace"]
    return obj


def load_traces(path: str) -> List[dict]:
    """Parse a trace dump file ('-' = stdin) into root-span dicts."""
    text = sys.stdin.read() if path == "-" else open(path).read()
    text = text.strip()
    if not text:
        return []
    traces: List[Any] = []
    if text[0] == "{" and "\n" in text:
        # try JSONL first — skipping corrupt/truncated lines (a node
        # killed mid-append leaves one): the valid traces still report
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
            traces = parsed
    if not traces:
        data = json.loads(text)
        if isinstance(data, dict):
            traces = data.get("traces", [data])
        else:
            traces = list(data)
    out = []
    for rec in traces:
        trace = _extract_trace(rec)
        if isinstance(trace, dict) and "name" in trace:
            out.append(trace)
    return out


def _pct(sorted_vals: List[float], p: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(len(sorted_vals) * p))
    return sorted_vals[i]


def phase_rows(traces: List[dict]) -> List[dict]:
    """Group root spans' direct children by name; one stats row each."""
    per_phase: Dict[str, List[float]] = {}
    roots: List[float] = []
    for trace in traces:
        roots.append(float(trace.get("duration_ms", 0.0)))
        for child in trace.get("children") or []:
            per_phase.setdefault(child.get("name", "?"), []).append(
                float(child.get("duration_ms", 0.0)))
    total_root = sum(roots) or 1.0
    rows = []
    for name in sorted(per_phase):
        vals = sorted(per_phase[name])
        rows.append({
            "phase": name,
            "count": len(vals),
            "p50_ms": round(_pct(vals, 0.5), 3),
            "p99_ms": round(_pct(vals, 0.99), 3),
            "max_ms": round(vals[-1], 3),
            "total_ms": round(sum(vals), 3),
            "pct_of_root": round(100.0 * sum(vals) / total_root, 1),
        })
    roots.sort()
    rows.append({
        "phase": "(root)",
        "count": len(roots),
        "p50_ms": round(_pct(roots, 0.5), 3),
        "p99_ms": round(_pct(roots, 0.99), 3),
        "max_ms": round(roots[-1], 3) if roots else 0.0,
        "total_ms": round(sum(roots), 3),
        "pct_of_root": 100.0,
    })
    return rows


def pipeline_rows(traces: List[dict]) -> List[dict]:
    """Per-wave pipeline attribution (the PR 9 `pipeline` fields): one
    row per (trace, wave) from the span's `lifecycle` attribute
    (telemetry/lifecycle.py — coalesce/dispatch/collect/overlap events
    carry co_batched, inflight pipeline depth, per-wave overlap_ms),
    falling back to the span-level `waves`/`overlap_ms` attributes
    (LedgerScope.publish) as a single `(all)` row when no lifecycle
    rides the trace."""
    rows: List[dict] = []
    for ti, trace in enumerate(traces):
        attrs = trace.get("attributes") or {}
        lc = attrs.get("lifecycle") or {}
        # window_wait: the request's measured scheduler-queue delay
        # (lifecycle queue_wait_ms — the coalesce window's price,
        # ISSUE 12), shown on each of its wave rows
        wait = lc.get("queue_wait_ms")
        wait = wait if isinstance(wait, (int, float)) and wait > 0 \
            else "-"
        waves: Dict[Any, dict] = {}
        for ev in lc.get("events") or []:
            w = ev.get("wave")
            if w is None:
                continue
            row = waves.setdefault(w, {
                "trace": ti, "wave": w, "window_wait_ms": wait,
                "co_batched": "-", "inflight_waves": "-",
                "overlap_ms": "-", "collect_ms": "-"})
            name = ev.get("event")
            if name == "coalesce":
                row["co_batched"] = ev.get("co_batched", "-")
            elif name == "dispatch":
                row["inflight_waves"] = ev.get("inflight", "-")
            elif name == "collect":
                row["collect_ms"] = ev.get("ms", "-")
            elif name == "overlap":
                row["overlap_ms"] = ev.get("ms", "-")
        if waves:
            rows.extend(waves[w] for w in sorted(waves))
        elif "waves" in attrs or "overlap_ms" in attrs:
            rows.append({"trace": ti, "wave": "(all)",
                         "window_wait_ms": wait,
                         "co_batched": "-", "inflight_waves": "-",
                         "overlap_ms": attrs.get("overlap_ms", "-"),
                         "collect_ms": "-",
                         **({"waves": attrs["waves"]}
                            if "waves" in attrs else {})})
    return rows


def _render(rows: List[dict], headers: List[str]) -> str:
    table = [headers] + [[str(r.get(h, "-")) for h in headers]
                         for r in rows]
    widths = [max(len(row[i]) for row in table) for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def render_table(rows: List[dict]) -> str:
    return _render(rows, ["phase", "count", "p50_ms", "p99_ms", "max_ms",
                          "total_ms", "pct_of_root"])


def render_pipeline_table(rows: List[dict]) -> str:
    return _render(rows, ["trace", "wave", "window_wait_ms",
                          "co_batched", "inflight_waves",
                          "overlap_ms", "collect_ms"])


def main(argv: List[str]) -> int:
    path = argv[1] if len(argv) > 1 else "-"
    traces = load_traces(path)
    if not traces:
        print("no traces found (enable tracing: "
              "POST /_telemetry/_enable, then re-run traffic)")
        return 1
    print(f"{len(traces)} trace(s)")
    print(render_table(phase_rows(traces)))
    pipe = pipeline_rows(traces)
    if pipe:
        print("\nwave pipeline (per-wave overlap / in-flight depth):")
        print(render_pipeline_table(pipe))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
