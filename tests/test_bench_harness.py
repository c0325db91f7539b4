"""
The driver contract of bench.py: it measures the chip, so on a host
without a TPU it exits non-zero without running a stage on the CPU; a
stage subprocess writes its JSON result and exits non-zero when it
failed; and an unknown ``device_kind`` is an error, never a null metric.
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO_ROOT, "bench.py")
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_stage_subprocess_writes_json(tmp_path):
    out = tmp_path / "probe.json"
    proc = subprocess.run(
        [sys.executable, BENCH, "--stage", "backend_probe", str(out)],
        env=CPU_ENV,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(out.read_text())
    assert payload["device"]["platform"] == "cpu"
    assert payload["device"]["count"] >= 1
    assert payload["checksum"] == 28.0  # arange(8).sum() — transfer-only probe


def test_without_a_tpu_no_stage_runs_and_rc_is_nonzero(tmp_path):
    """No chip is a failed run: one JSON line with a null value and the
    reason, a non-zero exit, and no stage but the probe ever started."""
    partial_path = tmp_path / "partial.json"
    proc = subprocess.run(
        [sys.executable, BENCH],
        env={**CPU_ENV, "BENCH_PARTIAL_PATH": str(partial_path)},
        capture_output=True,
        text=True,
        timeout=300,
        cwd=str(tmp_path),
    )
    assert proc.returncode != 0
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    assert len(lines) == 1, lines
    record = json.loads(lines[0])
    assert record["metric"] == "autoencoders_trained_per_hour"
    assert record["value"] is None
    assert record["extra"]["device"]["platform"] == "cpu"
    assert "needs a TPU" in record["extra"]["errors"]["backend_probe_error"]
    partial = json.loads(partial_path.read_text())
    ran = {"backend_probe", "backend_probe_error"} | {
        "n_models", "epochs", "budget_s", "result"
    }
    assert set(partial) <= ran, set(partial) - ran


def test_failing_stage_exits_nonzero(tmp_path):
    """A stage that raises writes its error and exits non-zero — the
    parent records it and the run's exit code follows."""
    out = tmp_path / "stage.json"
    proc = subprocess.run(
        [sys.executable, BENCH, "--stage", "no_such_stage", str(out)],
        env=CPU_ENV,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert "error" in json.loads(out.read_text())


def test_unknown_device_kind_is_an_error():
    import bench

    assert bench.device_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(RuntimeError, match="no peaks for device_kind"):
        bench.device_peaks("cpu")
