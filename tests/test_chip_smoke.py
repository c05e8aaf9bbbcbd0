"""chip_smoke.py off the chip: the explicit CPU dry run passes end to end
at tiny sizes, and anything else refuses to run without a TPU."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*flags):
    assert os.environ["JAX_PLATFORMS"] == "cpu"     # conftest pinned it
    return subprocess.run([sys.executable, SMOKE, *flags], cwd=REPO,
                          capture_output=True, text=True, timeout=600)


def test_dry_run_passes_every_phase_on_cpu():
    proc = _run("--dry-run")
    assert proc.returncode == 0, proc.stderr[-3000:]
    report, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    # the last line is the driver's: exactly these keys, nothing else
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert isinstance(verdict["device"]["count"], int)
    assert report["platform"] == "cpu" and report["dry_run"] is True
    assert report["failed"] == []
    assert all(p["ok"] for p in report["phases"])
    assert {p["phase"] for p in report["phases"]} >= {
        "served_msearch", "scale_match", "vectors_knn", "sharded_bodies",
        "node_checks"}


def test_refuses_to_run_without_a_tpu():
    proc = _run()
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""        # no result line off the chip
