#!/usr/bin/env python
"""Kernel-level device-compute breakdown — render a kernel-profiler
snapshot as tables (ISSUE 19).

Input (auto-detected), any of:
  - a saved `GET /_telemetry/kernels` response ({"kernels": {...}});
  - a bare profiler snapshot ({"families": {...}, "census": {...}});
  - a `GET /_nodes/stats` dump (the nested telemetry.kernels block);
  - a BENCH_KERNELS_r*.json dump (per-(bench, family) rows from
    bench.py --kernels, one JSON record per line).

The report answers the question the five earlier observability layers
could not: WHICH executables own the device wall. Families rank by
estimated device-ms (timed rounds) falling back to compile-ms
(census-only snapshots); the roofline table marks each family compute-
vs memory-bound against the configured peak_flops/peak_bw ridge; the
census dump lists individual executables heaviest-compile first.

    python tools/kernel_report.py KERNELS.json
    curl -s localhost:9200/_telemetry/kernels | \\
        python tools/kernel_report.py -
    python tools/kernel_report.py --top 5 BENCH_KERNELS_r01.json
    python tools/kernel_report.py --assert-families 3 KERNELS.json
"""

from __future__ import annotations

import json
import os
import sys
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from trace_report import _render  # noqa: E402  (shared table renderer)


def load_snapshot(path: str) -> Optional[dict]:
    """Parse any supported dump into the profiler snapshot dict
    ({"families": ..., "census": ...}). '-' reads stdin. BENCH_KERNELS
    row dumps are up-converted into the same shape (one synthetic
    family per bench+family row, census-less)."""
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
    bench_rows = []
    for rec in candidates:
        for block in (rec.get("kernels"),
                      (rec.get("telemetry") or {}).get("kernels")
                      if isinstance(rec.get("telemetry"), dict) else None,
                      rec):
            if isinstance(block, dict) and \
                    isinstance(block.get("families"), dict):
                return block
        if isinstance(rec.get("family"), str) and "device_ms" in rec:
            bench_rows.append(rec)
    if bench_rows:
        families = {}
        for r in bench_rows:
            name = f"{r.get('bench', '?')}/{r['family']}"
            families[name] = {
                "calls": r.get("calls", 0),
                "device_ms_est": r.get("device_ms", 0.0),
                "p50_ms": r.get("p50_ms"), "p99_ms": r.get("p99_ms"),
                "compiles": r.get("compiles", 0),
                "compile_ms": r.get("compile_ms", 0.0),
                "flops": r.get("flops"), "bytes": r.get("bytes"),
                "arithmetic_intensity": r.get("arithmetic_intensity"),
                "bound": r.get("bound") or "unclassified",
            }
        return {"families": families, "census": {}}
    return None


def family_rows(snap: dict) -> List[dict]:
    """Flatten the per-family block into report rows, heaviest first by
    estimated device-ms (compile-ms breaks the tie for census-only
    families that never dispatched in the measured window)."""
    rows = []
    for fam, r in snap.get("families", {}).items():
        rows.append({
            "family": fam,
            "calls": r.get("calls", 0),
            "device_ms": r.get("device_ms_est", 0.0),
            "p50_ms": r.get("p50_ms"),
            "p99_ms": r.get("p99_ms"),
            "compiles": r.get("compiles", 0),
            "compile_ms": r.get("compile_ms", 0.0),
            "bound": r.get("bound") or "unclassified",
        })
    rows.sort(key=lambda r: (-float(r["device_ms"] or 0.0),
                             -float(r["compile_ms"] or 0.0),
                             r["family"]))
    return rows


def render_families(rows: List[dict]) -> str:
    cols = ["family", "calls", "device_ms", "p50_ms", "p99_ms",
            "compiles", "compile_ms", "bound"]
    return _render([{c: r.get(c) for c in cols} for r in rows], cols)


def roofline_rows(snap: dict) -> List[dict]:
    """The roofline table: arithmetic intensity vs the configured ridge
    point, one row per family with known static cost."""
    rows = []
    for fam, r in snap.get("families", {}).items():
        ai = r.get("arithmetic_intensity")
        if ai is None:
            continue
        rows.append({
            "family": fam,
            "flops": r.get("flops"),
            "bytes": r.get("bytes"),
            "intensity": ai,
            "bound": r.get("bound") or "unclassified",
        })
    rows.sort(key=lambda r: (-float(r["intensity"] or 0.0), r["family"]))
    return rows


def render_roofline(rows: List[dict], ridge: Optional[float]) -> str:
    cols = ["family", "flops", "bytes", "intensity", "bound"]
    table = _render([{c: r.get(c) for c in cols} for r in rows], cols)
    if ridge is not None:
        table += f"\nridge intensity (peak_flops/peak_bw): {ridge}"
    return table


def census_rows(snap: dict, top: int = 10) -> List[dict]:
    """Top individual executables from the census dump, heaviest
    compile first (the compile-cliff registry a warmup config reads)."""
    execs = (snap.get("census") or {}).get("executables") or []
    rows = [{
        "family": e.get("family"),
        "shape": e.get("shape"),
        "fingerprint": e.get("fingerprint"),
        "compile_ms": e.get("compile_ms"),
        "flops": e.get("flops"),
        "bytes": e.get("bytes"),
        "cost_source": e.get("cost_source"),
    } for e in execs]
    rows.sort(key=lambda r: -float(r["compile_ms"] or 0.0))
    return rows[:top]


def render_census(rows: List[dict]) -> str:
    cols = ["family", "shape", "fingerprint", "compile_ms", "flops",
            "bytes", "cost_source"]
    return _render([{c: r.get(c) for c in cols} for r in rows], cols)


def main(argv: List[str]) -> int:
    top = 10
    min_families = None
    args: List[str] = []
    rest = list(argv[1:])
    while rest:
        a = rest.pop(0)
        if a.startswith("--top"):
            top = int(a.split("=", 1)[1]) if "=" in a \
                else int(rest.pop(0))
        elif a.startswith("--assert-families"):
            min_families = int(a.split("=", 1)[1]) if "=" in a \
                else int(rest.pop(0))
        else:
            args.append(a)
    path = args[0] if args else "-"
    snap = load_snapshot(path)
    if snap is None:
        print("no kernel-profiler block found (the census is always-on "
              "after the first compile; for timed rows enable the "
              "profiler: POST /_telemetry/kernels/_enable, re-run "
              "traffic, or run bench.py --kernels)")
        return 1
    rows = family_rows(snap)
    census = snap.get("census") or {}
    print(f"{len(rows)} kernel famil{'y' if len(rows) == 1 else 'ies'}, "
          f"{census.get('entries', '?')} census executable(s), "
          f"compile total {census.get('compile_ms_total', '?')} ms "
          f"(sorted by device-ms, then compile-ms)")
    print(render_families(rows))
    rf = roofline_rows(snap)
    if rf:
        print("\nroofline (arithmetic intensity vs ridge):")
        print(render_roofline(rf, snap.get("ridge_intensity")))
    cr = census_rows(snap, top)
    if cr:
        print(f"\nexecutable census (top {len(cr)} by compile-ms):")
        print(render_census(cr))
    if min_families is not None and len(rows) < min_families:
        print(f"\nFAIL: {len(rows)} famil"
              f"{'y' if len(rows) == 1 else 'ies'} < {min_families}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
