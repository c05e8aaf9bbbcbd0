"""Tier-1 smoke test for tools/transfer_report.py: the offline
per-channel transfer report over ledger dumps (the
`GET /_telemetry/transfers` response, a bare snapshot, and JSONL
records that carry one at telemetry.transfers)."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import transfer_report  # noqa: E402


def _snapshot():
    return {
        "enabled": True, "waves": 12,
        "device_get": {"calls": 12, "total_ms": 214.0},
        "bytes_total": {"h2d": 5000, "d2h": 41943040},
        "channels": {
            "h2d": {"upload.literals": {
                "transfers": 12, "round_trips": 12, "bytes": 5000}},
            "d2h": {
                "scores": {"transfers": 12, "round_trips": 12,
                           "bytes": 20971520},
                "topk_ids": {"transfers": 12, "round_trips": 12,
                             "bytes": 20971520}},
        },
        "rolling": {
            "wave_bytes": {"count": 12.0, "p50": 3_000_000.0,
                           "p95": 3_400_000.0, "p99": 3_490_000.0,
                           "max": 3_500_000.0},
            "wave_device_get_ms": {"count": 12.0, "p50": 17.0,
                                   "p95": 19.5, "p99": 19.9,
                                   "max": 20.0}},
    }


def test_load_rest_response_shape(tmp_path):
    path = tmp_path / "dump.json"
    path.write_text(json.dumps({"transfers": _snapshot(),
                                "device_memory": {"classes": {}}}))
    snap = transfer_report.load_snapshot(str(path))
    assert snap is not None and snap["waves"] == 12


def test_load_bare_snapshot(tmp_path):
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(_snapshot()))
    assert transfer_report.load_snapshot(str(path))["waves"] == 12


def test_load_bench_jsonl(tmp_path):
    """JSONL records carry the snapshot at telemetry.transfers; the
    first carrying line wins."""
    path = tmp_path / "BENCH_test.json"
    with open(path, "w") as f:
        f.write(json.dumps({"metric": "other", "value": 1}) + "\n")
        f.write(json.dumps({"metric": "bm25", "value": 2,
                            "telemetry": {"transfers": _snapshot()}})
                + "\n")
    assert transfer_report.load_snapshot(str(path))["waves"] == 12


def test_channel_rows_sorted_by_bytes(tmp_path):
    rows = transfer_report.channel_rows(_snapshot())
    d2h = [r for r in rows if r["dir"] == "d2h"]
    assert len(d2h) == 2
    assert d2h[0]["pct_of_dir"] == 50.0
    h2d = [r for r in rows if r["dir"] == "h2d"]
    assert h2d[0]["channel"] == "upload.literals"


def test_summary_has_implied_bandwidth():
    lines = "\n".join(transfer_report.summary_lines(_snapshot()))
    assert "implied d2h bandwidth" in lines
    assert "device_get wall: 214.0ms" in lines
    # 40 MB over 214 ms ≈ 196 MB/s
    assert "196" in lines


def test_cli_smoke(tmp_path):
    path = tmp_path / "dump.json"
    path.write_text(json.dumps(_snapshot()))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools",
                                      "transfer_report.py"), str(path)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "scores" in r.stdout
    assert "pct_of_dir" in r.stdout


def test_cli_empty_input(tmp_path):
    path = tmp_path / "empty.json"
    path.write_text("")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools",
                                      "transfer_report.py"), str(path)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "no transfer ledger" in r.stdout


def test_live_ledger_roundtrip(tmp_path):
    """A real TransferLedger snapshot (not a hand-built fixture) parses
    and renders — schema drift between ledger.py and this tool fails
    here, not in a PROFILE round."""
    from opensearch_tpu.telemetry.ledger import TransferLedger
    ledger = TransferLedger()
    ledger.enabled = True
    wave = ledger.new_wave()
    ledger.record("scores", "d2h", 4096, wave=wave)
    ledger.record("upload.literals", "h2d", 128, wave=wave)
    ledger.note_device_get(2.5, nbytes=4096)
    path = tmp_path / "live.json"
    path.write_text(json.dumps({"transfers": ledger.snapshot()}))
    snap = transfer_report.load_snapshot(str(path))
    rows = transfer_report.channel_rows(snap)
    assert {r["channel"] for r in rows} == {"scores", "upload.literals"}
    assert any("implied" in ln
               for ln in transfer_report.summary_lines(snap))
