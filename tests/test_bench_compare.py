"""Tier-1 smoke test for tools/bench_compare.py: the CI tripwire that
diffs two bench dumps and fails on a >threshold warm-p50 regression."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import bench_compare  # noqa: E402


def _write(path, records):
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return str(path)


OLD = [
    {"metric": "bm25_match_qps_100k_docs_tpu", "value": 1000,
     "p50_ms": 5.0},
    {"mode": "agg_terms", "metric": "agg_terms_qps_50k_docs_tpu",
     "value": 300, "warm_p50_ms": 10.0, "p50_ms": 40.0},
    {"mode": "hybrid", "metric": "hybrid_qps_50k_docs_64d_tpu",
     "value": 200, "warm_p50_ms": 20.0},
]


def test_load_keys_by_mode_then_metric(tmp_path):
    recs = bench_compare.load_records(_write(tmp_path / "a.json", OLD))
    assert set(recs) == {"bm25_match_qps_100k_docs_tpu", "agg_terms",
                         "hybrid"}


def test_warm_p50_prefers_warm_field():
    assert bench_compare.warm_p50({"warm_p50_ms": 10.0,
                                   "p50_ms": 40.0}) == 10.0
    assert bench_compare.warm_p50({"p50_ms": 5.0}) == 5.0
    assert bench_compare.warm_p50({"value": 1}) is None


def test_ok_within_threshold(tmp_path):
    new = [dict(r) for r in OLD]
    new[1] = dict(new[1], warm_p50_ms=10.9)      # +9% < 10%
    old_p = _write(tmp_path / "old.json", OLD)
    new_p = _write(tmp_path / "new.json", new)
    rows, failures = bench_compare.compare(
        bench_compare.load_records(old_p),
        bench_compare.load_records(new_p), 10.0)
    assert not failures
    assert all(r["status"] in ("ok",) for r in rows)


def test_regression_fails(tmp_path):
    new = [dict(r) for r in OLD]
    new[2] = dict(new[2], warm_p50_ms=25.0)      # +25% > 10%
    rows, failures = bench_compare.compare(
        bench_compare.load_records(_write(tmp_path / "o.json", OLD)),
        bench_compare.load_records(_write(tmp_path / "n.json", new)),
        10.0)
    assert len(failures) == 1 and "hybrid" in failures[0]
    assert [r for r in rows if r["status"] == "REGRESSION"]


def test_one_sided_configs_never_fail(tmp_path):
    new = OLD + [{"mode": "knn_exact", "warm_p50_ms": 1.0}]
    rows, failures = bench_compare.compare(
        bench_compare.load_records(_write(tmp_path / "o.json", OLD[:1])),
        bench_compare.load_records(_write(tmp_path / "n.json", new)),
        10.0)
    assert not failures
    assert {r["status"] for r in rows} <= {"ok", "new-only", "old-only"}


def test_improvement_is_ok(tmp_path):
    new = [dict(r, warm_p50_ms=1.0) if "warm_p50_ms" in r else dict(r)
           for r in OLD]
    _, failures = bench_compare.compare(
        bench_compare.load_records(_write(tmp_path / "o.json", OLD)),
        bench_compare.load_records(_write(tmp_path / "n.json", new)),
        10.0)
    assert not failures


CONC = [
    {"mode": "bm25_openloop", "metric": "bm25_openloop_qps_100k_docs_8c_cpu",
     "value": 400, "clients": 8, "arrival_rate": 400.0,
     "p50_ms": 4.0, "p99_ms": 30.0, "p999_ms": 60.0,
     "mean_queue_wait_ms": 1.5},
]


def test_warm_p99_field_resolution():
    # explicit warm_p99_ms always wins
    assert bench_compare.warm_p99({"warm_p99_ms": 12.0,
                                   "p99_ms": 99.0}) == 12.0
    # open-loop concurrent records (clients/arrival_rate) are warm by
    # construction: bare p99_ms counts
    assert bench_compare.warm_p99(CONC[0]) == 30.0
    # cold-inclusive p99_ms on ordinary configs does NOT count
    assert bench_compare.warm_p99({"p99_ms": 40.0}) is None


def test_concurrent_p99_regression_fails(tmp_path):
    new = [dict(CONC[0], p99_ms=40.0)]           # +33% tail, p50 flat
    rows, failures = bench_compare.compare(
        bench_compare.load_records(_write(tmp_path / "o.json", CONC)),
        bench_compare.load_records(_write(tmp_path / "n.json", new)),
        10.0)
    assert len(failures) == 1 and "warm p99" in failures[0]
    row = rows[0]
    assert row["status"] == "REGRESSION"
    assert row["p99_delta_pct"] > 30
    assert row["old_warm_p99_ms"] == 30.0


def test_warm_p99_gate_on_classic_configs(tmp_path):
    """agg/hybrid records carrying warm_p99_ms gate on the tail too —
    a p50-flat tail regression no longer slips through."""
    old = [{"mode": "agg_terms", "warm_p50_ms": 10.0,
            "warm_p99_ms": 20.0}]
    new = [{"mode": "agg_terms", "warm_p50_ms": 10.0,
            "warm_p99_ms": 40.0}]
    _, failures = bench_compare.compare(
        bench_compare.load_records(_write(tmp_path / "o.json", old)),
        bench_compare.load_records(_write(tmp_path / "n.json", new)),
        10.0)
    assert len(failures) == 1 and "warm p99" in failures[0]


def test_missing_p99_skips_tail_gate(tmp_path):
    """Configs without a warm p99 on either side keep the p50-only
    verdict (bench sets grow fields PR over PR)."""
    new = [dict(r) for r in OLD]
    rows, failures = bench_compare.compare(
        bench_compare.load_records(_write(tmp_path / "o.json", OLD)),
        bench_compare.load_records(_write(tmp_path / "n.json", new)),
        10.0)
    assert not failures
    assert all("p99_delta_pct" not in r for r in rows)


def test_concurrent_p99_within_threshold_ok(tmp_path):
    new = [dict(CONC[0], p99_ms=32.0)]           # +6.7% < 10%
    _, failures = bench_compare.compare(
        bench_compare.load_records(_write(tmp_path / "o.json", CONC)),
        bench_compare.load_records(_write(tmp_path / "n.json", new)),
        10.0)
    assert not failures


def test_cli_exit_codes(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "bench_compare.py")
    old_p = _write(tmp_path / "old.json", OLD)
    regressed = [dict(OLD[0], p50_ms=50.0)] + OLD[1:]
    bad_p = _write(tmp_path / "bad.json", regressed)
    ok = subprocess.run([sys.executable, tool, old_p, old_p],
                        capture_output=True, text=True, timeout=60)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "OK" in ok.stdout
    bad = subprocess.run([sys.executable, tool, old_p, bad_p],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1
    assert "REGRESSION" in bad.stdout
    # a loosened threshold passes the same pair
    loose = subprocess.run(
        [sys.executable, tool, "--threshold", "2000", old_p, bad_p],
        capture_output=True, text=True, timeout=60)
    assert loose.returncode == 0
    usage = subprocess.run([sys.executable, tool, old_p],
                           capture_output=True, text=True, timeout=60)
    assert usage.returncode == 2


# ------------------------------------------- scheduler-mode conc shape


CONC_OLD = {"mode": "bm25_openloop_8c_120rps", "value": 113.1,
            "clients": 8, "arrival_rate": 120.0, "p50_ms": 3.7,
            "p99_ms": 10.3}


def test_openloop_qps_regression_fails(tmp_path):
    """ISSUE 12: a conc record whose open-loop QPS drops beyond the
    threshold under the SAME offered load fails the gate."""
    new = dict(CONC_OLD, value=80.0)
    rows, failures = bench_compare.compare(
        {"bm25_openloop_8c_120rps": CONC_OLD},
        {"bm25_openloop_8c_120rps": new}, 10.0)
    assert failures and "open-loop QPS" in failures[0]
    assert rows[0]["status"] == "REGRESSION"
    assert rows[0]["qps_delta_pct"] < -10


def test_openloop_qps_gain_ok():
    new = dict(CONC_OLD, value=240.0, p99_ms=9.0)
    rows, failures = bench_compare.compare(
        {"bm25_openloop_8c_120rps": CONC_OLD},
        {"bm25_openloop_8c_120rps": new}, 10.0)
    assert not failures
    assert rows[0]["qps_delta_pct"] > 100


def test_scheduler_record_requires_observed_coalescing():
    """A scheduler-enabled record must carry co_batched > 1 evidence
    from the captured timelines — enabled-but-not-coalescing fails."""
    new = dict(CONC_OLD, value=240.0,
               scheduler={"enabled": True, "tail_co_batched_max": 1,
                          "co_batched": {"max": 1}})
    rows, failures = bench_compare.compare(
        {"bm25_openloop_8c_120rps": CONC_OLD},
        {"bm25_openloop_8c_120rps": new}, 10.0)
    assert failures and "co_batched" in failures[0]
    assert rows[0]["status"] == "NO-COALESCE"
    good = dict(CONC_OLD, value=240.0,
                scheduler={"enabled": True, "tail_co_batched_max": 5,
                           "co_batched": {"max": 6}})
    rows, failures = bench_compare.compare(
        {"bm25_openloop_8c_120rps": CONC_OLD},
        {"bm25_openloop_8c_120rps": good}, 10.0)
    assert not failures
    assert rows[0]["co_batched_max"] == 6


# --------------------------------------------- interference shape (ISSUE 13)

INTF_OLD = {
    "bm25_interference_4c_120rps_i0": {
        "mode": "bm25_interference_4c_120rps_i0", "value": 110.0,
        "ingest_rate": 0.0, "ingest_dps": 0.0, "clients": 4,
        "p50_ms": 4.0, "p99_ms": 10.0},
    "bm25_interference_4c_120rps_i30": {
        "mode": "bm25_interference_4c_120rps_i30", "value": 100.0,
        "ingest_rate": 30.0, "ingest_dps": 28.0, "clients": 4,
        "p50_ms": 5.0, "p99_ms": 40.0},
}


def test_interference_records_skip_generic_warm_gate():
    """Interference points carry `clients` + p99 but their tail includes
    churn-induced compile stalls — the generic 10% warm gate must not
    judge them (their own 15% gate does)."""
    new = {k: dict(v, p99_ms=v["p99_ms"] * 1.12)
           for k, v in INTF_OLD.items()}
    rows, failures = bench_compare.compare(INTF_OLD, new, 10.0)
    assert not rows and not failures


def test_interference_p99_regression_fails():
    new = {k: dict(v) for k, v in INTF_OLD.items()}
    new["bm25_interference_4c_120rps_i30"]["p99_ms"] = 50.0  # +25%
    rows, failures = bench_compare.compare_interference(
        INTF_OLD, new, 10.0)
    assert failures and "equal ingest rate" in failures[0]
    by_cfg = {r["config"]: r for r in rows}
    assert by_cfg["bm25_interference_4c_120rps_i30"]["status"] == \
        "P99-REGRESSION"
    assert by_cfg["bm25_interference_4c_120rps_i0"]["status"] == "ok"


def test_interference_p99_within_15_pct_ok():
    new = {k: dict(v, p99_ms=v["p99_ms"] * 1.14)
           for k, v in INTF_OLD.items()}
    rows, failures = bench_compare.compare_interference(
        INTF_OLD, new, 10.0)
    assert not failures
    assert all(r["status"] == "ok" for r in rows)


def test_interference_ingest_throughput_regression_fails():
    new = {k: dict(v) for k, v in INTF_OLD.items()}
    new["bm25_interference_4c_120rps_i30"]["ingest_dps"] = 20.0  # -28%
    rows, failures = bench_compare.compare_interference(
        INTF_OLD, new, 10.0)
    assert failures and "ingest throughput" in failures[0]
    by_cfg = {r["config"]: r for r in rows}
    assert by_cfg["bm25_interference_4c_120rps_i30"]["status"] == \
        "INGEST-REGRESSION"


def test_interference_one_sided_points_never_fail():
    new = {**{k: dict(v) for k, v in INTF_OLD.items()},
           "bm25_interference_4c_120rps_i60": {
               "mode": "bm25_interference_4c_120rps_i60",
               "value": 90.0, "ingest_rate": 60.0, "ingest_dps": 55.0,
               "clients": 4, "p50_ms": 6.0, "p99_ms": 80.0}}
    rows, failures = bench_compare.compare_interference(
        INTF_OLD, new, 10.0)
    assert not failures
    assert any(r.get("status") == "new-only" for r in rows)


def test_interference_cli_end_to_end(tmp_path):
    old_p = _write(tmp_path / "io.json", list(INTF_OLD.values()))
    bad = [dict(v, p99_ms=v["p99_ms"] * 2) for v in INTF_OLD.values()]
    bad_p = _write(tmp_path / "in.json", bad)
    assert bench_compare.main(["bench_compare.py", old_p, old_p]) == 0
    assert bench_compare.main(["bench_compare.py", old_p, bad_p]) == 1


# ------------------------------------- multi-chip scaling shape (ISSUE 14)

SCALE_OLD = {
    f"spmd_d{d}": {
        "mode": f"spmd_d{d}", "devices": d, "value": qps,
        "per_chip_efficiency": eff, "straggler_skew_p50_ms": 0.05,
        "warm_p50_ms": 10.0, "warm_p99_ms": 25.0}
    for d, qps, eff in ((1, 100.0, 1.0), (2, 170.0, 0.85),
                        (4, 280.0, 0.7), (8, 400.0, 0.5))
}


def test_scaling_records_skip_generic_warm_gate():
    new = {k: dict(v, warm_p50_ms=v["warm_p50_ms"] * 3) for k, v in
           SCALE_OLD.items()}
    rows, failures = bench_compare.compare(SCALE_OLD, new, 10.0)
    assert not failures     # absolute warm latency is box-state noise
    assert all("warm" not in (r.get("status") or "") for r in rows)


def test_scaling_efficiency_regression_fails_at_equal_d():
    new = {k: dict(v) for k, v in SCALE_OLD.items()}
    new["spmd_d4"]["per_chip_efficiency"] = 0.55    # -21% at D=4
    rows, failures = bench_compare.compare_scaling(SCALE_OLD, new, 10.0)
    assert failures and "per-chip efficiency" in failures[0]
    by_cfg = {r["config"]: r for r in rows}
    assert by_cfg["spmd_d4"]["status"] == "EFFICIENCY-REGRESSION"


def test_scaling_efficiency_within_15_pct_ok():
    new = {k: dict(v) for k, v in SCALE_OLD.items()}
    new["spmd_d4"]["per_chip_efficiency"] = 0.62    # -11%: within gate
    rows, failures = bench_compare.compare_scaling(SCALE_OLD, new, 10.0)
    assert not failures


def test_scaling_skew_regression_fails_past_floor():
    new = {k: dict(v) for k, v in SCALE_OLD.items()}
    new["spmd_d8"]["straggler_skew_p50_ms"] = 3.0   # 60x, past 1ms floor
    rows, failures = bench_compare.compare_scaling(SCALE_OLD, new, 10.0)
    assert failures and "straggler skew" in failures[0]


def test_scaling_subms_skew_noise_never_fails():
    new = {k: dict(v) for k, v in SCALE_OLD.items()}
    new["spmd_d8"]["straggler_skew_p50_ms"] = 0.4   # 8x but under 1ms
    rows, failures = bench_compare.compare_scaling(SCALE_OLD, new, 10.0)
    assert not failures


def test_scaling_one_sided_points_never_fail():
    new = {**{k: dict(v) for k, v in SCALE_OLD.items()},
           "spmd_d16": {"mode": "spmd_d16", "devices": 16,
                        "value": 500.0, "per_chip_efficiency": 0.3}}
    rows, failures = bench_compare.compare_scaling(SCALE_OLD, new, 10.0)
    assert not failures
    assert any(r.get("status") == "new-only" for r in rows)


def test_scaling_cli_end_to_end(tmp_path):
    old_p = _write(tmp_path / "so.json", list(SCALE_OLD.values()))
    bad = [dict(v, per_chip_efficiency=(v["per_chip_efficiency"] or 1)
                * 0.5) for v in SCALE_OLD.values()]
    bad_p = _write(tmp_path / "sn.json", bad)
    assert bench_compare.main(["bench_compare.py", old_p, old_p]) == 0
    assert bench_compare.main(["bench_compare.py", old_p, bad_p]) == 1


# ----------------------------------------------- query insights (ISSUE 15)

def _insights_rec(p99_by_shape, count=50):
    return {"mode": "bm25_insights_8c_120rps", "p50_ms": 1.0,
            "p99_ms": 5.0, "clients": 8,
            "insights": {"shapes": {
                s: {"count": count, "p50_ms": 1.0, "p99_ms": p99}
                for s, p99 in p99_by_shape.items()}}}


def test_insights_records_skip_generic_warm_gate():
    # the record's aggregate p99 moves with the shape MIX — only the
    # per-shape gate may judge it
    old = {"bm25_insights_8c_120rps": _insights_rec({"match:aa": 2.0})}
    new = {"bm25_insights_8c_120rps": _insights_rec({"match:aa": 50.0})}
    rows, failures = bench_compare.compare(old, new, 10.0)
    assert not rows and not failures


def test_insights_per_shape_p99_regression_fails_at_equal_key():
    old = {"x": _insights_rec({"match:aa": 10.0, "bool:bb": 20.0})}
    new = {"x": _insights_rec({"match:aa": 11.6, "bool:bb": 20.0})}
    rows, failures = bench_compare.compare_insights(old, new, 10.0)
    assert failures and "match:aa" in failures[0]
    assert any(r["status"] == "SHAPE-REGRESSION" for r in rows)


def test_insights_within_15_pct_ok():
    old = {"x": _insights_rec({"match:aa": 10.0})}
    new = {"x": _insights_rec({"match:aa": 11.4})}
    rows, failures = bench_compare.compare_insights(old, new, 10.0)
    assert not failures and rows[0]["status"] == "ok"


def test_insights_one_sided_shapes_never_fail():
    old = {"x": _insights_rec({"match:aa": 10.0})}
    new = {"x": _insights_rec({"match:aa": 10.0, "term:cc": 500.0})}
    rows, failures = bench_compare.compare_insights(old, new, 10.0)
    assert not failures
    assert any(r["status"] == "new-only" for r in rows)


def test_insights_low_count_shapes_never_fail():
    old = {"x": _insights_rec({"match:aa": 10.0}, count=3)}
    new = {"x": _insights_rec({"match:aa": 99.0}, count=3)}
    rows, failures = bench_compare.compare_insights(old, new, 10.0)
    assert not failures and rows[0]["status"] == "low-count"


def test_insights_cli_end_to_end(tmp_path):
    old_p = _write(tmp_path / "i_old.json",
                   [_insights_rec({"match:aa": 10.0})])
    bad_p = _write(tmp_path / "i_bad.json",
                   [_insights_rec({"match:aa": 30.0})])
    assert bench_compare.main(["bench_compare.py", old_p, old_p]) == 0
    assert bench_compare.main(["bench_compare.py", old_p, bad_p]) == 1


# ----------------------------------------------- result page (ISSUE 17)

PAGE_LEGACY = {"bm25_ab_page": {
    "mode": "bm25_ab_page", "warm_p50_ms": 120.0, "bodies": 64,
    "result_page": False, "round_trips_per_wave": 7.0,
    "d2h_bytes_per_wave": 9000.0}}
PAGE_NEW = {"bm25_ab_page": {
    "mode": "bm25_ab_page", "warm_p50_ms": 60.0, "bodies": 64,
    "result_page": True, "round_trips_per_wave": 1.0,
    "d2h_bytes_per_wave": 8600.0}}


def test_page_single_trip_ok_with_bytes_ratio():
    rows, failures = bench_compare.compare_page(
        PAGE_LEGACY, PAGE_NEW, 10.0)
    assert not failures
    assert rows[0]["status"] == "ok"
    assert rows[0]["bytes_ratio"] == round(8600.0 / 9000.0, 3)


def test_page_multi_trip_fails():
    bad = {"bm25_ab_page": dict(PAGE_NEW["bm25_ab_page"],
                                round_trips_per_wave=3.0)}
    rows, failures = bench_compare.compare_page(PAGE_LEGACY, bad, 10.0)
    assert failures and "round trips" in failures[0]
    assert rows[0]["status"] == "PAGE-MULTI-TRIP"


def test_page_legacy_arm_never_gated_on_trips():
    # the legacy arm reads many trips per wave BY DESIGN — only an arm
    # claiming result_page is held to the single-trip contract
    rows, failures = bench_compare.compare_page(
        PAGE_NEW, PAGE_LEGACY, 10.0)
    assert not failures


def test_page_without_ledger_reports_not_fails():
    arm = {"bm25_ab_page": {"mode": "bm25_ab_page", "warm_p50_ms": 60.0,
                            "result_page": True}}
    rows, failures = bench_compare.compare_page(PAGE_LEGACY, arm, 10.0)
    assert not failures and rows[0]["status"] == "no-ledger"


def test_page_warm_p50_rides_generic_gate():
    # the page arm must not regress warm p50 vs the legacy arm — that
    # side of the A/B is the ordinary warm gate, not compare_page
    slow = {"bm25_ab_page": dict(PAGE_NEW["bm25_ab_page"],
                                 warm_p50_ms=300.0)}
    rows, failures = bench_compare.compare(PAGE_LEGACY, slow, 10.0)
    assert failures


def test_page_cli_end_to_end(tmp_path):
    old_p = _write(tmp_path / "p_old.json", list(PAGE_LEGACY.values()))
    new_p = _write(tmp_path / "p_new.json", list(PAGE_NEW.values()))
    bad = [dict(v, round_trips_per_wave=4.0)
           for v in PAGE_NEW.values()]
    bad_p = _write(tmp_path / "p_bad.json", bad)
    assert bench_compare.main(["bench_compare.py", old_p, new_p]) == 0
    assert bench_compare.main(["bench_compare.py", old_p, bad_p]) == 1


# ------------------------------------------- late-interaction maxsim gate


MAXSIM_OLD = {
    "maxsim": {"mode": "maxsim", "metric": "maxsim_qps_10k_64d_tpu",
               "value": 600, "warm_p50_ms": 1.5, "recall_at_10": 1.0},
    "maxsim_pq": {"mode": "maxsim_pq",
                  "metric": "maxsim_pq_qps_10k_64d_tpu",
                  "value": 500, "warm_p50_ms": 2.0,
                  "recall_at_10": 0.97, "recall_vs_exact": 0.97},
}


def test_maxsim_recall_regression_fails():
    worse = {k: dict(v, recall_at_10=v["recall_at_10"] - 0.05)
             for k, v in MAXSIM_OLD.items()}
    worse["maxsim_pq"]["recall_vs_exact"] = 0.96  # floor still clear
    rows, failures = bench_compare.compare_maxsim(
        MAXSIM_OLD, worse, 10.0)
    assert failures and any("RECALL-REGRESSION" == r["status"]
                            for r in rows)


def test_maxsim_recall_within_drop_ok():
    near = {k: dict(v, recall_at_10=v["recall_at_10"] - 0.01)
            for k, v in MAXSIM_OLD.items()}
    near["maxsim_pq"]["recall_vs_exact"] = 0.96
    rows, failures = bench_compare.compare_maxsim(MAXSIM_OLD, near, 10.0)
    assert not failures and all(r["status"] == "ok" for r in rows)


def test_maxsim_pq_floor_fails_unconditionally():
    # even vs an old round that had already slipped below the floor
    slipped = {k: dict(v) for k, v in MAXSIM_OLD.items()}
    slipped["maxsim_pq"].update(recall_at_10=0.90, recall_vs_exact=0.90)
    rows, failures = bench_compare.compare_maxsim(
        slipped, slipped, 10.0)
    assert failures and any(r["status"] == "PQ-RECALL-FLOOR"
                            for r in rows)


def test_maxsim_new_only_reports_never_fails():
    rows, failures = bench_compare.compare_maxsim({}, MAXSIM_OLD, 10.0)
    assert not failures and all(r["status"] == "new-only" for r in rows)


def test_maxsim_warm_latency_rides_generic_gate():
    slow = {k: dict(v, warm_p50_ms=v["warm_p50_ms"] * 3)
            for k, v in MAXSIM_OLD.items()}
    rows, failures = bench_compare.compare(MAXSIM_OLD, slow, 10.0)
    assert failures


def test_maxsim_cli_end_to_end(tmp_path):
    old_p = _write(tmp_path / "mx_old.json", list(MAXSIM_OLD.values()))
    bad = [dict(v, recall_at_10=0.8, recall_vs_exact=0.8)
           if v["mode"] == "maxsim_pq" else dict(v)
           for v in MAXSIM_OLD.values()]
    bad_p = _write(tmp_path / "mx_bad.json", bad)
    assert bench_compare.main(["bench_compare.py", old_p, old_p]) == 0
    assert bench_compare.main(["bench_compare.py", old_p, bad_p]) == 1


# ------------------------------------------------ block-max A/B (ISSUE 20) --

def _keyed(*recs):
    return {r["mode"]: r for r in recs}


def _bmx_pair(base="spmd_1000k_d8", docs=1_000_000, off_p50=12.0,
              on_p50=12.5, off_digest="abc123", on_digest="abc123",
              pruned=0.29):
    off = {"mode": base, "docs": docs, "devices": 8, "blockmax": False,
           "warm_p50_ms": off_p50, "page_digest": off_digest}
    on = {"mode": base + "_bmx", "docs": docs, "devices": 8,
          "blockmax": True, "warm_p50_ms": on_p50,
          "page_digest": on_digest, "pruned_fraction": pruned}
    return off, on


def test_blockmax_identical_pages_within_p50_ok():
    new = _keyed(*_bmx_pair())
    rows, failures = bench_compare.compare_blockmax({}, new, 10.0)
    assert not failures
    assert rows[0]["status"] == "ok"
    assert rows[0]["digest_match"] is True


def test_blockmax_page_divergence_fails():
    new = _keyed(*_bmx_pair(on_digest="deadbeef"))
    rows, failures = bench_compare.compare_blockmax({}, new, 10.0)
    assert failures and rows[0]["status"] == "PAGE-DIVERGENCE"
    assert "page digest" in failures[0]


def test_blockmax_p50_regression_fails_at_or_below_1m():
    new = _keyed(*_bmx_pair(off_p50=10.0, on_p50=12.0))   # +20% > 15%
    rows, failures = bench_compare.compare_blockmax({}, new, 10.0)
    assert failures and rows[0]["status"] == "ENABLED-OVERHEAD"


def test_blockmax_p50_not_gated_above_1m():
    # past the trigger scale the pruned arm trades phase-A cost for
    # scan reduction — latency there is the scaling table's story, not
    # this gate's
    off, on = _bmx_pair(base="spmd_10000k_d8", docs=10_000_000,
                        off_p50=10.0, on_p50=13.0)
    rows, failures = bench_compare.compare_blockmax({}, _keyed(off, on),
                                                    10.0)
    assert not failures and rows[0]["status"] == "ok"
    assert rows[0]["p50_delta_pct"] == 30.0


def test_blockmax_pruned_only_reports_never_fails():
    _, on = _bmx_pair()
    rows, failures = bench_compare.compare_blockmax({}, _keyed(on), 10.0)
    assert not failures and rows[0]["status"] == "pruned-only"


def test_blockmax_old_round_pairs_never_fail():
    old = _keyed(*_bmx_pair(on_digest="deadbeef"))
    rows, failures = bench_compare.compare_blockmax(old, {}, 10.0)
    assert not rows and not failures


def test_blockmax_digest_divergence_beats_p50_status():
    new = _keyed(*_bmx_pair(off_p50=10.0, on_p50=12.0,
                            on_digest="deadbeef"))
    rows, failures = bench_compare.compare_blockmax({}, new, 10.0)
    assert rows[0]["status"] == "PAGE-DIVERGENCE"
    assert len(failures) == 1


def test_blockmax_cli_end_to_end(tmp_path):
    ok_off, ok_on = _bmx_pair()
    bad_off, bad_on = _bmx_pair(on_digest="deadbeef")
    ok_p = _write(tmp_path / "bmx_ok.json", [ok_off, ok_on])
    bad_p = _write(tmp_path / "bmx_bad.json", [bad_off, bad_on])
    assert bench_compare.main(["bench_compare.py", ok_p, ok_p]) == 0
    assert bench_compare.main(["bench_compare.py", ok_p, bad_p]) == 1
