#!/usr/bin/env python
"""Diff two bench dumps; fail on warm-latency regression.

Input: two JSONL dumps, one JSON object per line, each carrying
"metric" or "mode" plus latency fields (nothing in the tree writes them
since PR 31: ROADMAP D5b). Configs are
matched by "mode" when present, else by the "metric" name with the
trailing platform/shape suffix kept (the same config always renders the
same metric string).

The gate: any config whose warm p50 ("warm_p50_ms", falling back to
"p50_ms" for configs without a warmup pass) OR warm p99 regresses by
more than --threshold (default 10%) fails the run with exit code 1 —
the CI tripwire for "this PR made warm serving slower". The p99 side is
what the open-loop concurrent-clients records exist for: a scheduler
change can hold p50 while destroying the tail, and a p50-only gate
would wave it through. Warm
p99 comes from "warm_p99_ms"; open-loop records (identified by their
"clients" field) are warm by construction, so their "p99_ms" counts.
Configs present in only one file are reported but never fail (bench
sets grow PR over PR); configs without a p99 field skip the p99 gate.

    python tools/bench_compare.py old.json new.json
    python tools/bench_compare.py --threshold 15 old.json new.json
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Tuple

WARM_KEYS = ("warm_p50_ms", "p50_ms")

# the overload-sweep gate (ISSUE 11): goodput past the knee may not
# collapse by more than this between two curves — the "degrades
# gracefully" contract, distinct from the warm-latency threshold
OVERLOAD_COLLAPSE_PCT = 15.0

# the interference gate (ISSUE 13): at the SAME ingest rate, search p99
# may not degrade by more than this between two rounds, and ingest
# throughput may not drop by more than this — "serving under writes got
# slower" and "writes under serving got slower" both fail the run
INTERFERENCE_P99_PCT = 15.0

# the multi-chip scaling gate (ISSUE 14): at EQUAL device count D,
# per-chip scaling efficiency QPS(D)/(D·QPS(1)) may not drop by more
# than this between two scaling rounds — "adding chips stopped
# paying" fails the run even when absolute QPS moved with box state
EFFICIENCY_DROP_PCT = 15.0

# the insights gate (ISSUE 15): at EQUAL shape key, a shape class's
# warm p99 may not degrade by more than this between two INSIGHTS
# rounds — "this query class got slower" fails the run even when the
# overall mix shifted. Shapes need a minimal sample count on both
# sides: a 3-request shape's p99 is one unlucky request, not a class.
INSIGHTS_P99_PCT = 15.0
INSIGHTS_MIN_COUNT = 20

# the late-interaction gate (ISSUE 18): at EQUAL config key, MaxSim
# recall@10 may not drop by more than this (absolute) between rounds,
# and the PQ arm's recall-vs-exact must clear the committed floor on
# the new side unconditionally (ISSUE 18's acceptance)
MAXSIM_RECALL_DROP = 0.02
MAXSIM_PQ_RECALL_FLOOR = 0.95

# the block-max gate (ISSUE 20): within the NEW round, the pruned arm
# of a blockmax A/B (mode `X_bmx` next to its unpruned `X`) must carry
# a top-k page digest IDENTICAL to the unpruned arm's — rank-exactness
# is the pruning kernel's contract, checked in CI, never assumed — and
# at ≤1M docs its warm p50 may not exceed the unpruned arm's by more
# than this: below the trigger scale pruning pays little back, so the
# A/B pins the price of serving with the gate on
BLOCKMAX_P50_PCT = 15.0
BLOCKMAX_P50_MAX_DOCS = 1_000_000


def load_records(path: str) -> Dict[str, dict]:
    """file of JSON lines (or one JSON array) → {config key: record}."""
    text = open(path).read().strip()
    if not text:
        return {}
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
    out: Dict[str, dict] = {}
    for rec in records:
        key = rec.get("mode") or rec.get("metric")
        if key and "error" not in rec:
            out[str(key)] = rec      # latest record per config wins
    return out


def warm_p50(rec: dict) -> Optional[float]:
    for key in WARM_KEYS:
        v = rec.get(key)
        if isinstance(v, (int, float)) and v > 0:
            return float(v)
    return None


def warm_p99(rec: dict) -> Optional[float]:
    """Warm tail latency: explicit "warm_p99_ms", or bare "p99_ms" for
    open-loop concurrent-mode records (their measured window is warm by
    construction — the run warms before the arrival schedule starts).
    Cold-inclusive p99_ms on other configs deliberately does NOT count:
    its compile cliff is box-state noise, not a serving regression."""
    v = rec.get("warm_p99_ms")
    if isinstance(v, (int, float)) and v > 0:
        return float(v)
    if "clients" in rec or "arrival_rate" in rec:
        v = rec.get("p99_ms")
        if isinstance(v, (int, float)) and v > 0:
            return float(v)
    return None


def compare(old: Dict[str, dict], new: Dict[str, dict],
            threshold_pct: float) -> Tuple[List[dict], List[str]]:
    """→ (rows, failures). A row per config in either file."""
    rows, failures = [], []
    for key in sorted(set(old) | set(new)):
        o, n = old.get(key), new.get(key)
        if any(r is not None and "offered_rate" in r
               and "goodput_qps" in r for r in (o, n)):
            # BENCH_OVERLOAD ramp points have their own gate
            # (compare_overload): their bare p50/p99 are open-loop
            # intended-arrival latencies that grow without bound past
            # saturation BY CONSTRUCTION and scale with each round's
            # independently measured saturation reference — gating
            # them as warm latency would fail identical builds
            continue
        if any(r is not None and "ingest_rate" in r for r in (o, n)):
            # BENCH_INTERFERENCE points have their own gate
            # (compare_interference, 15% at equal ingest rate): their
            # p99 under concurrent ingest includes churn-induced
            # compile stalls the generic warm gate would misread
            continue
        if any(r is not None and "devices" in r
               and "per_chip_efficiency" in r for r in (o, n)):
            # multi-chip points have their own gate (compare_scaling):
            # per-chip EFFICIENCY is round-normalized (divided by the
            # same round's QPS(1)), where absolute warm latency on the
            # virtual-chip CPU box moves with box state
            continue
        if any(r is not None and isinstance(r.get("insights"), dict)
               and "shapes" in r["insights"] for r in (o, n)):
            # INSIGHTS records have their own gate (compare_insights,
            # per-shape warm p99 at equal shape key): their aggregate
            # p99 moves with the shape MIX, which shifts legitimately
            # round over round
            continue
        row = {"config": key}
        if o is None or n is None:
            row["status"] = "old-only" if n is None else "new-only"
            rows.append(row)
            continue
        ov, nv = warm_p50(o), warm_p50(n)
        row["old_warm_p50_ms"] = ov
        row["new_warm_p50_ms"] = nv
        if ov is None or nv is None:
            row["status"] = "no-latency-field"
            rows.append(row)
            continue
        delta_pct = 100.0 * (nv - ov) / ov
        row["delta_pct"] = round(delta_pct, 1)
        status = "ok"
        if delta_pct > threshold_pct:
            status = "REGRESSION"
            failures.append(
                f"{key}: warm p50 {ov}ms -> {nv}ms "
                f"(+{delta_pct:.1f}% > {threshold_pct:g}%)")
        # the tail gate: both sides must carry a warm p99 (configs
        # without one skip — the p50 verdict stands alone)
        o99, n99 = warm_p99(o), warm_p99(n)
        if o99 is not None and n99 is not None:
            row["old_warm_p99_ms"] = o99
            row["new_warm_p99_ms"] = n99
            d99 = 100.0 * (n99 - o99) / o99
            row["p99_delta_pct"] = round(d99, 1)
            if d99 > threshold_pct:
                status = "REGRESSION"
                failures.append(
                    f"{key}: warm p99 {o99}ms -> {n99}ms "
                    f"(+{d99:.1f}% > {threshold_pct:g}%)")
        # open-loop concurrency records (BENCH_CONC shape, ISSUE 12):
        # gate THROUGHPUT too — a scheduler change must not trade
        # open-loop QPS away under the same offered load (the p99 gate
        # above already covers the admitted tail: conc records' p99 is
        # warm by construction) — and when the new record ran with the
        # wave scheduler enabled, demand OBSERVED cross-request
        # coalescing: a captured timeline with co_batched > 1, not a
        # config flag
        if "clients" in o or "clients" in n:
            oq, nq = o.get("value"), n.get("value")
            if isinstance(oq, (int, float)) and \
                    isinstance(nq, (int, float)) and oq > 0:
                dq = 100.0 * (nq - oq) / oq
                row["qps_delta_pct"] = round(dq, 1)
                if dq < -threshold_pct:
                    status = "REGRESSION"
                    failures.append(
                        f"{key}: open-loop QPS {oq} -> {nq} "
                        f"({dq:.1f}% < -{threshold_pct:g}%)")
        n_sched = n.get("scheduler")
        if isinstance(n_sched, dict) and n_sched.get("enabled"):
            cb = max(int(n_sched.get("tail_co_batched_max", 0) or 0),
                     int((n_sched.get("co_batched") or {})
                         .get("max", 0) or 0))
            row["co_batched_max"] = cb
            if cb <= 1:
                status = "NO-COALESCE"
                failures.append(
                    f"{key}: scheduler enabled but no captured "
                    f"timeline shows co_batched > 1 (max {cb})")
        row["status"] = status
        rows.append(row)
    return rows, failures


def _overload_records(recs: Dict[str, dict]) -> Dict[str, dict]:
    """The BENCH_OVERLOAD shape: offered-load ramp points carrying
    `offered_rate` + `goodput_qps`."""
    return {k: r for k, r in recs.items()
            if isinstance(r.get("offered_rate"), (int, float))
            and isinstance(r.get("goodput_qps"), (int, float))}


def _knee_rate(recs: Dict[str, dict]) -> float:
    """The curve's knee: the offered rate of the max-goodput point —
    past it, added offered load buys nothing and the only question is
    whether goodput HOLDS (plateau) or collapses."""
    best = max(recs.values(), key=lambda r: r["goodput_qps"])
    return float(best["offered_rate"])


def compare_overload(old: Dict[str, dict], new: Dict[str, dict],
                     threshold_pct: float) -> Tuple[List[dict], List[str]]:
    """Gate two goodput-vs-offered-load curves: fail on goodput
    collapse (> OVERLOAD_COLLAPSE_PCT drop at-or-past the OLD curve's
    knee) or admitted-p99 breach (new p99 over the record's own SLO
    setting, or over old p99 by more than --threshold). Pre-knee
    goodput moves with box state and never fails; points present in
    only one curve report but never fail (ramps grow round over
    round)."""
    o_recs, n_recs = _overload_records(old), _overload_records(new)
    rows, failures = [], []
    if not o_recs or not n_recs:
        return rows, failures
    knee = _knee_rate(o_recs)
    for key in sorted(set(o_recs) | set(n_recs),
                      key=lambda k: (o_recs.get(k) or n_recs.get(k))
                      ["offered_rate"]):
        o, n = o_recs.get(key), n_recs.get(key)
        row = {"config": key,
               "offered_rate": (o or n)["offered_rate"]}
        if o is None or n is None:
            row["status"] = "old-only" if n is None else "new-only"
            rows.append(row)
            continue
        row["old_goodput"] = o["goodput_qps"]
        row["new_goodput"] = n["goodput_qps"]
        status = "ok"
        delta = 100.0 * (n["goodput_qps"] - o["goodput_qps"]) \
            / max(o["goodput_qps"], 1e-9)
        row["goodput_delta_pct"] = round(delta, 1)
        past_knee = float(o["offered_rate"]) >= knee
        row["past_knee"] = past_knee
        if past_knee and delta < -OVERLOAD_COLLAPSE_PCT:
            status = "COLLAPSE"
            failures.append(
                f"{key}: goodput {o['goodput_qps']} -> "
                f"{n['goodput_qps']} ({delta:+.1f}% past the knee, "
                f"limit -{OVERLOAD_COLLAPSE_PCT:g}%)")
        o99, n99 = o.get("admitted_p99_ms"), n.get("admitted_p99_ms")
        if isinstance(o99, (int, float)) and isinstance(n99, (int, float)):
            row["old_admitted_p99_ms"] = o99
            row["new_admitted_p99_ms"] = n99
            slo = n.get("slo_ms")
            if isinstance(slo, (int, float)) and n99 > slo:
                status = "P99-BREACH"
                failures.append(
                    f"{key}: admitted p99 {n99}ms over the SLO "
                    f"setting [{slo}ms]")
            elif o99 > 0 and 100.0 * (n99 - o99) / o99 > threshold_pct:
                status = "P99-BREACH"
                failures.append(
                    f"{key}: admitted p99 {o99}ms -> {n99}ms "
                    f"(+{100.0 * (n99 - o99) / o99:.1f}% > "
                    f"{threshold_pct:g}%)")
        row["status"] = status
        rows.append(row)
    return rows, failures


def _interference_records(recs: Dict[str, dict]) -> Dict[str, dict]:
    """The BENCH_INTERFERENCE shape: points carrying `ingest_rate` next
    to search latency fields."""
    return {k: r for k, r in recs.items()
            if isinstance(r.get("ingest_rate"), (int, float))
            and isinstance(r.get("p99_ms"), (int, float))}


def compare_interference(old: Dict[str, dict], new: Dict[str, dict],
                         threshold_pct: float
                         ) -> Tuple[List[dict], List[str]]:
    """Gate two interference sweeps point-by-point at EQUAL ingest
    rate: fail when search p99 degrades more than INTERFERENCE_P99_PCT
    (serving under writes got slower), or when achieved ingest
    throughput (`ingest_dps`) drops more than --threshold (writes under
    serving got slower). Points present in only one round report but
    never fail (rate grids grow round over round); the ingest-off
    control gates like any other point (its ingest_dps is 0 on both
    sides and skips the throughput gate)."""
    o_recs = _interference_records(old)
    n_recs = _interference_records(new)
    rows, failures = [], []
    if not o_recs or not n_recs:
        return rows, failures
    for key in sorted(set(o_recs) | set(n_recs),
                      key=lambda k: (o_recs.get(k) or n_recs.get(k))
                      ["ingest_rate"]):
        o, n = o_recs.get(key), n_recs.get(key)
        row = {"config": key,
               "ingest_rate": (o or n)["ingest_rate"]}
        if o is None or n is None:
            row["status"] = "old-only" if n is None else "new-only"
            rows.append(row)
            continue
        status = "ok"
        o99, n99 = float(o["p99_ms"]), float(n["p99_ms"])
        row["old_p99_ms"] = o99
        row["new_p99_ms"] = n99
        # equal OFFERED rate is the join key, but the rounds only truly
        # compare at equal ACHIEVED pressure — annotate the ratio of
        # achieved docs/s so an "improvement" bought by a slower ingest
        # client is visible in the row (and in any failure message)
        od_, nd_ = o.get("ingest_dps"), n.get("ingest_dps")
        pressure = ""
        if isinstance(od_, (int, float)) and \
                isinstance(nd_, (int, float)) and od_ > 0:
            row["achieved_ratio"] = round(nd_ / od_, 3)
            pressure = (f"; achieved ingest {od_:g} -> {nd_:g} docs/s "
                        f"(x{row['achieved_ratio']:g})")
        if o99 > 0:
            d99 = 100.0 * (n99 - o99) / o99
            row["p99_delta_pct"] = round(d99, 1)
            if d99 > INTERFERENCE_P99_PCT:
                status = "P99-REGRESSION"
                failures.append(
                    f"{key}: search p99 under ingest {o99}ms -> "
                    f"{n99}ms (+{d99:.1f}% > "
                    f"{INTERFERENCE_P99_PCT:g}% at equal ingest rate"
                    f"{pressure})")
        od = o.get("ingest_dps")
        nd = n.get("ingest_dps")
        if isinstance(od, (int, float)) and isinstance(nd, (int, float)) \
                and od > 0:
            row["old_ingest_dps"] = od
            row["new_ingest_dps"] = nd
            dd = 100.0 * (nd - od) / od
            row["ingest_delta_pct"] = round(dd, 1)
            if dd < -threshold_pct:
                status = "INGEST-REGRESSION"
                failures.append(
                    f"{key}: ingest throughput {od} -> {nd} docs/s "
                    f"({dd:.1f}% < -{threshold_pct:g}%)")
        row["status"] = status
        rows.append(row)
    return rows, failures


def _scaling_records(recs: Dict[str, dict]) -> Dict[str, dict]:
    """The multi-chip scaling shape: points carrying `devices` next
    to a QPS `value`."""
    return {k: r for k, r in recs.items()
            if isinstance(r.get("devices"), (int, float))
            and isinstance(r.get("value"), (int, float))}


def compare_scaling(old: Dict[str, dict], new: Dict[str, dict],
                    threshold_pct: float) -> Tuple[List[dict], List[str]]:
    """Gate two multi-chip scaling curves point-by-point at EQUAL
    device count: fail when per-chip efficiency QPS(D)/(D·QPS(1))
    drops by more than EFFICIENCY_DROP_PCT (the chips stopped
    pulling their weight), or when straggler skew more than doubles
    past --threshold over a 1 ms floor (a chip went quietly lame).
    Single-chip points (D=1, efficiency 1.0 by construction) gate only
    through the generic warm-latency rows; points present in only one
    round report but never fail (device grids grow round over
    round)."""
    o_recs, n_recs = _scaling_records(old), _scaling_records(new)
    rows, failures = [], []
    if not o_recs or not n_recs:
        return rows, failures
    for key in sorted(set(o_recs) | set(n_recs),
                      key=lambda k: (o_recs.get(k) or n_recs.get(k))
                      ["devices"]):
        o, n = o_recs.get(key), n_recs.get(key)
        row = {"config": key, "devices": (o or n)["devices"]}
        if o is None or n is None:
            row["status"] = "old-only" if n is None else "new-only"
            rows.append(row)
            continue
        status = "ok"
        oe, ne = o.get("per_chip_efficiency"), n.get("per_chip_efficiency")
        if isinstance(oe, (int, float)) and isinstance(ne, (int, float)) \
                and oe > 0:
            row["old_efficiency"] = oe
            row["new_efficiency"] = ne
            de = 100.0 * (ne - oe) / oe
            row["efficiency_delta_pct"] = round(de, 1)
            if de < -EFFICIENCY_DROP_PCT:
                status = "EFFICIENCY-REGRESSION"
                failures.append(
                    f"{key}: per-chip efficiency {oe} -> {ne} "
                    f"({de:.1f}% < -{EFFICIENCY_DROP_PCT:g}% at "
                    f"equal D)")
        os_, ns = o.get("straggler_skew_p50_ms"), \
            n.get("straggler_skew_p50_ms")
        if isinstance(os_, (int, float)) and isinstance(ns, (int, float)):
            row["old_skew_p50_ms"] = os_
            row["new_skew_p50_ms"] = ns
            # floor at 1ms: sub-millisecond skews on the virtual-chip
            # box are scheduler noise, not a lame chip
            if ns > max(os_ * 2, 1.0) and \
                    100.0 * (ns - os_) / max(os_, 1e-9) > threshold_pct:
                status = "SKEW-REGRESSION"
                failures.append(
                    f"{key}: straggler skew p50 {os_}ms -> {ns}ms "
                    f"(more than doubled past the 1ms floor)")
        row["status"] = status
        rows.append(row)
    return rows, failures


def _page_records(recs: Dict[str, dict]) -> Dict[str, dict]:
    """The result-page A/B shape: arm records carrying the
    `result_page` arm marker."""
    return {k: r for k, r in recs.items() if "result_page" in r}


def compare_page(old: Dict[str, dict], new: Dict[str, dict],
                 threshold_pct: float) -> Tuple[List[dict], List[str]]:
    """Gate the single-round-trip result page (ISSUE 17): any NEW-side
    arm that ran with the page gate on must have read its whole
    response — merged top-k, sort keys, docvalue lanes, totals, aggs —
    in EXACTLY one device round trip per wave, or the run fails
    (PAGE-MULTI-TRIP). The row also reports the page-bytes vs
    legacy-bytes d2h ratio at equal config key, next to the transfer
    gates: the page pays for its one trip by shipping every merged
    lane as wire bytes, where the legacy tail's extra trips read
    zero-byte host mirrors — the ratio is the measured wire price of
    the single round trip (a few extra KB per wave), reported so a
    future layout change that silently blows the page up is visible,
    not gated (the warm-p50 gate is the arbiter of whether the trade
    still pays). The warm-p50
    side of the A/B rides the generic gate above (the two arms share a
    config key, so the page arm is gated against the legacy arm at
    --threshold like any round-over-round pair). Arms measured without
    --telemetry carry no ledger fields and only report (no-ledger)."""
    del threshold_pct
    o_recs, n_recs = _page_records(old), _page_records(new)
    rows, failures = [], []
    if not n_recs:
        return rows, failures
    for key in sorted(n_recs):
        o, n = o_recs.get(key), n_recs[key]
        row = {"config": key, "result_page": bool(n.get("result_page"))}
        status = "ok"
        rt = n.get("round_trips_per_wave")
        row["round_trips_per_wave"] = rt
        if n.get("result_page"):
            if not isinstance(rt, (int, float)):
                status = "no-ledger"
            elif rt != 1:
                status = "PAGE-MULTI-TRIP"
                failures.append(
                    f"{key}: page arm read {rt} device round trips per "
                    f"wave (the result-page contract is exactly 1)")
        ob = o.get("d2h_bytes_per_wave") if o is not None else None
        nb = n.get("d2h_bytes_per_wave")
        if isinstance(ob, (int, float)) and ob > 0 and \
                isinstance(nb, (int, float)):
            row["old_d2h_bytes_per_wave"] = ob
            row["new_d2h_bytes_per_wave"] = nb
            row["bytes_ratio"] = round(nb / ob, 3)
        row["status"] = status
        rows.append(row)
    return rows, failures


def _insights_records(recs: Dict[str, dict]) -> Dict[str, dict]:
    """The INSIGHTS shape: records carrying an `insights` block with
    per-shape rows."""
    return {k: r for k, r in recs.items()
            if isinstance(r.get("insights"), dict)
            and isinstance(r["insights"].get("shapes"), dict)}


def compare_insights(old: Dict[str, dict], new: Dict[str, dict],
                     threshold_pct: float) -> Tuple[List[dict], List[str]]:
    """Gate two insights records shape-by-shape at EQUAL shape key:
    fail when a shape class's warm p99 regresses by more than
    INSIGHTS_P99_PCT. The shape id is structural (interned-template /
    skeleton hash), so it compares stably across rounds; shapes present
    in only one round report but never fail (workload mixes grow round
    over round), and shapes under INSIGHTS_MIN_COUNT requests on either
    side only report (one slow request is not a class regression).
    `threshold_pct` is accepted for signature parity with the other
    comparers; the per-shape bound is the class constant."""
    del threshold_pct
    o_all, n_all = _insights_records(old), _insights_records(new)
    rows, failures = [], []
    if not o_all or not n_all:
        return rows, failures
    for key in sorted(set(o_all) & set(n_all)):
        o_shapes = o_all[key]["insights"]["shapes"]
        n_shapes = n_all[key]["insights"]["shapes"]
        for shape in sorted(set(o_shapes) | set(n_shapes)):
            o, n = o_shapes.get(shape), n_shapes.get(shape)
            row = {"config": key, "shape": shape}
            if o is None or n is None:
                row["status"] = "old-only" if n is None else "new-only"
                rows.append(row)
                continue
            o99, n99 = o.get("p99_ms"), n.get("p99_ms")
            row["old_count"] = o.get("count", 0)
            row["new_count"] = n.get("count", 0)
            row["old_p99_ms"] = o99
            row["new_p99_ms"] = n99
            status = "ok"
            if not isinstance(o99, (int, float)) or \
                    not isinstance(n99, (int, float)) or o99 <= 0:
                status = "no-latency-field"
            else:
                d99 = 100.0 * (n99 - o99) / o99
                row["p99_delta_pct"] = round(d99, 1)
                small = min(row["old_count"], row["new_count"]) \
                    < INSIGHTS_MIN_COUNT
                if small:
                    status = "low-count"
                elif d99 > INSIGHTS_P99_PCT:
                    status = "SHAPE-REGRESSION"
                    failures.append(
                        f"{key} shape {shape}: warm p99 {o99}ms -> "
                        f"{n99}ms (+{d99:.1f}% > "
                        f"{INSIGHTS_P99_PCT:g}% at equal shape key)")
            row["status"] = status
            rows.append(row)
    return rows, failures


def _maxsim_records(recs: Dict[str, dict]) -> Dict[str, dict]:
    """The MaxSim shape (BENCH_MAXSIM_*.json): records carrying a
    recall_at_10 field with a maxsim mode key."""
    return {k: r for k, r in recs.items()
            if r.get("mode") in ("maxsim", "maxsim_pq")
            and isinstance(r.get("recall_at_10"), (int, float))}


def compare_maxsim(old: Dict[str, dict], new: Dict[str, dict],
                   threshold_pct: float) -> Tuple[List[dict], List[str]]:
    """Gate the late-interaction tier (ISSUE 18) on RECALL, not just
    latency (the warm p50/p99 side rides the generic gate above):

    - at equal config key, recall@10 may not drop by more than
      MAXSIM_RECALL_DROP absolute between rounds — "the kernel got
      faster by returning worse top-k" fails the run;
    - the PQ arm's recall_vs_exact must clear MAXSIM_PQ_RECALL_FLOOR on
      the NEW side unconditionally (the committed acceptance bound) —
      a quantizer regression fails even against an old round that had
      already slipped."""
    del threshold_pct
    o_recs, n_recs = _maxsim_records(old), _maxsim_records(new)
    rows, failures = [], []
    for key in sorted(n_recs):
        n = n_recs[key]
        o = o_recs.get(key)
        row = {"config": key,
               "old_recall_at_10": o.get("recall_at_10")
               if o is not None else None,
               "new_recall_at_10": n["recall_at_10"]}
        status = "ok"
        rve = n.get("recall_vs_exact")
        if isinstance(rve, (int, float)):
            row["recall_vs_exact"] = rve
            if rve < MAXSIM_PQ_RECALL_FLOOR:
                status = "PQ-RECALL-FLOOR"
                failures.append(
                    f"{key}: PQ recall_vs_exact {rve} below the "
                    f"committed floor {MAXSIM_PQ_RECALL_FLOOR}")
        if o is not None and status == "ok":
            drop = float(o["recall_at_10"]) - float(n["recall_at_10"])
            row["recall_drop"] = round(drop, 4)
            if drop > MAXSIM_RECALL_DROP:
                status = "RECALL-REGRESSION"
                failures.append(
                    f"{key}: recall@10 {o['recall_at_10']} -> "
                    f"{n['recall_at_10']} (dropped {drop:.4f} > "
                    f"{MAXSIM_RECALL_DROP:g} at equal config key)")
        elif o is None:
            row["recall_drop"] = None
        row["status"] = status if o is not None or status != "ok" \
            else "new-only"
        rows.append(row)
    return rows, failures


def _blockmax_pairs(recs: Dict[str, dict]) -> List[Tuple[str, Optional[dict], dict]]:
    """(base key, unpruned record or None, pruned record) for every
    pruned-arm record (`blockmax: true`, mode suffixed `_bmx`) in the
    set. The unpruned partner is the record at the arm-neutral key —
    matched from the full set, so harnesses that only tag the pruned
    arm (the open-loop records) still pair."""
    pairs = []
    for key, on in sorted(recs.items()):
        if not key.endswith("_bmx") or on.get("blockmax") is not True:
            continue
        pairs.append((key[:-4], recs.get(key[:-4]), on))
    return pairs


def compare_blockmax(old: Dict[str, dict], new: Dict[str, dict],
                     threshold_pct: float) -> Tuple[List[dict], List[str]]:
    """Gate the block-max A/B WITHIN the new round — both arms of a
    blockmax run land in one file, keyed `X` / `X_bmx` at the same
    (docs, devices) config:

    - any top-k page-digest divergence between the arms fails: the
      pruned page must be byte-identical to the unpruned page (totals
      are exempt by design — the pruned arm reports lower bounds with
      relation "gte");
    - at ≤ BLOCKMAX_P50_MAX_DOCS docs, the pruned arm's warm p50 may
      not exceed the unpruned arm's by more than BLOCKMAX_P50_PCT;
    - each arm's cross-round drift rides the generic warm gate above
      (the `_bmx` suffix keeps the arms from mis-pairing there).

    The old file's pairs are context, not gates: a historical
    divergence was that round's failure, not this one's."""
    del threshold_pct, old
    rows, failures = [], []
    for base, off, on in _blockmax_pairs(new):
        row = {"config": base, "docs": on.get("docs"),
               "pruned_fraction": on.get("pruned_fraction")}
        if off is None:
            row["status"] = "pruned-only"
            rows.append(row)
            continue
        status = "ok"
        od, nd = off.get("page_digest"), on.get("page_digest")
        row["digest_match"] = (od == nd) if od and nd else None
        if od and nd and od != nd:
            status = "PAGE-DIVERGENCE"
            failures.append(
                f"{base}: pruned arm page digest {nd} != unpruned "
                f"{od} — block-max pruning changed a top-k page")
        o50, n50 = warm_p50(off), warm_p50(on)
        row["unpruned_warm_p50_ms"] = o50
        row["pruned_warm_p50_ms"] = n50
        docs = on.get("docs")
        if o50 and n50:
            d50 = 100.0 * (n50 - o50) / o50
            row["p50_delta_pct"] = round(d50, 1)
            if status == "ok" and isinstance(docs, int) \
                    and docs <= BLOCKMAX_P50_MAX_DOCS \
                    and d50 > BLOCKMAX_P50_PCT:
                status = "ENABLED-OVERHEAD"
                failures.append(
                    f"{base}: pruned arm warm p50 {o50}ms -> {n50}ms "
                    f"(+{d50:.1f}% > {BLOCKMAX_P50_PCT:g}% at "
                    f"{docs} docs ≤ {BLOCKMAX_P50_MAX_DOCS} — the "
                    f"gate must be ~free below the trigger scale)")
        row["status"] = status
        rows.append(row)
    return rows, failures


def render_blockmax(rows: List[dict]) -> str:
    headers = ["config", "docs", "pruned_fraction", "digest_match",
               "unpruned_warm_p50_ms", "pruned_warm_p50_ms",
               "p50_delta_pct", "status"]
    table = [headers] + [[str(r.get(h, "-")) for h in headers]
                         for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def render_maxsim(rows: List[dict]) -> str:
    headers = ["config", "old_recall_at_10", "new_recall_at_10",
               "recall_drop", "recall_vs_exact", "status"]
    table = [headers] + [[str(r.get(h, "-")) for h in headers]
                         for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def render_page(rows: List[dict]) -> str:
    headers = ["config", "result_page", "round_trips_per_wave",
               "old_d2h_bytes_per_wave", "new_d2h_bytes_per_wave",
               "bytes_ratio", "status"]
    table = [headers] + [[str(r.get(h, "-")) for h in headers]
                         for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def render_insights(rows: List[dict]) -> str:
    headers = ["config", "shape", "old_count", "new_count",
               "old_p99_ms", "new_p99_ms", "p99_delta_pct", "status"]
    table = [headers] + [[str(r.get(h, "-")) for h in headers]
                         for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def render_scaling(rows: List[dict]) -> str:
    headers = ["config", "devices", "old_efficiency", "new_efficiency",
               "efficiency_delta_pct", "old_skew_p50_ms",
               "new_skew_p50_ms", "status"]
    table = [headers] + [[str(r.get(h, "-")) for h in headers]
                         for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def render_interference(rows: List[dict]) -> str:
    headers = ["config", "ingest_rate", "old_p99_ms", "new_p99_ms",
               "p99_delta_pct", "old_ingest_dps", "new_ingest_dps",
               "ingest_delta_pct", "status"]
    table = [headers] + [[str(r.get(h, "-")) for h in headers]
                         for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def render_overload(rows: List[dict]) -> str:
    headers = ["config", "offered_rate", "old_goodput", "new_goodput",
               "goodput_delta_pct", "past_knee", "old_admitted_p99_ms",
               "new_admitted_p99_ms", "status"]
    table = [headers] + [[str(r.get(h, "-")) for h in headers]
                         for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def render(rows: List[dict]) -> str:
    headers = ["config", "old_warm_p50_ms", "new_warm_p50_ms",
               "delta_pct", "old_warm_p99_ms", "new_warm_p99_ms",
               "p99_delta_pct", "qps_delta_pct", "status"]
    table = [headers] + [[str(r.get(h, "-")) for h in headers]
                         for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(headers))]
    return "\n".join(
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        for row in table)


def main(argv: List[str]) -> int:
    threshold = 10.0
    args: List[str] = []
    rest = list(argv[1:])
    while rest:
        a = rest.pop(0)
        if a.startswith("--threshold"):
            threshold = float(a.split("=", 1)[1]) if "=" in a \
                else float(rest.pop(0))
        else:
            args.append(a)
    if len(args) != 2:
        print("usage: bench_compare.py [--threshold PCT] OLD.json NEW.json")
        return 2
    old, new = load_records(args[0]), load_records(args[1])
    if not old or not new:
        print(f"no parsable bench records in "
              f"{args[0] if not old else args[1]}")
        return 2
    rows, failures = compare(old, new, threshold)
    print(render(rows))
    ov_rows, ov_failures = compare_overload(old, new, threshold)
    if ov_rows:
        print("\noverload curve (goodput vs offered load):")
        print(render_overload(ov_rows))
        failures += ov_failures
    if_rows, if_failures = compare_interference(old, new, threshold)
    if if_rows:
        print("\ninterference sweep (search p99 / ingest throughput "
              "at equal ingest rate):")
        print(render_interference(if_rows))
        failures += if_failures
    sc_rows, sc_failures = compare_scaling(old, new, threshold)
    if sc_rows:
        print("\nmulti-chip scaling (per-chip efficiency / straggler "
              "skew at equal device count):")
        print(render_scaling(sc_rows))
        failures += sc_failures
    pg_rows, pg_failures = compare_page(old, new, threshold)
    if pg_rows:
        print("\nresult page (device round trips per wave / "
              "page-vs-legacy d2h bytes):")
        print(render_page(pg_rows))
        failures += pg_failures
    in_rows, in_failures = compare_insights(old, new, threshold)
    if in_rows:
        print("\nquery insights (per-shape warm p99 at equal shape "
              "key):")
        print(render_insights(in_rows))
        failures += in_failures
    mx_rows, mx_failures = compare_maxsim(old, new, threshold)
    if mx_rows:
        print("\nlate-interaction maxsim (recall@10 at equal config "
              "key / PQ recall-vs-exact floor):")
        print(render_maxsim(mx_rows))
        failures += mx_failures
    bm_rows, bm_failures = compare_blockmax(old, new, threshold)
    if bm_rows:
        print("\nblock-max A/B (pruned vs unpruned arm at equal "
              "config key — page-digest identity / ≤1M warm-p50):")
        print(render_blockmax(bm_rows))
        failures += bm_failures
    if failures:
        print(f"\nFAIL: {len(failures)} regression(s) "
              f"(warm p50/p99 beyond {threshold:g}% / overload "
              f"goodput-collapse / admitted-p99 breach):")
        for f in failures:
            print(f"  {f}")
        return 1
    print(f"\nOK: no warm-p50/p99 regression beyond {threshold:g}%, "
          f"no overload collapse")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
