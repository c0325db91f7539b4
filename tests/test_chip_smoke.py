"""
``chip_smoke.py`` off the chip: the command must refuse to pass without a
TPU, and its phase functions — the same code the chip runs at full size —
must hold at tiny sizes on the CPU, so the command is debugged here
before a chip call is spent on it.
"""

import os
import subprocess
import sys

import chip_smoke

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.Sizes(
    dense_machines=2,
    dense_tags=6,
    dense_epochs=1,
    dense_variants=("elu",),
    lstm_machines=1,
    lstm_tags=5,
    lstm_lookback=8,
    lstm_dims=(16, 8),
    train_days=2,
    request_rows=40,
    arrow_rows=70,
    fleet_rows=24,
    lstm_request_rows=40,
    concurrent_clients=1,
    ring_rows=600,
)


def test_command_fails_without_a_tpu(tmp_path):
    """``python chip_smoke.py`` names the device first and exits non-zero
    on anything but a TPU, printing no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=300,
        cwd=str(tmp_path),
    )
    assert proc.returncode != 0
    lines = proc.stdout.splitlines()
    assert lines and lines[0].startswith("jax=") and "platform=cpu" in lines[0]
    assert '"ok"' not in proc.stdout
    assert not os.path.exists(os.path.join(REPO_ROOT, "chip_smoke_out"))


def test_command_takes_no_arguments():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), "--cpu"],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 2
    assert proc.stdout == ""


def test_phases_hold_tiny_on_the_cpu(tmp_path, monkeypatch):
    """Trainer, both server runs and the several-device phase (the
    conftest's eight virtual devices) at tiny sizes: every check the chip
    run makes, made here."""
    # the ring path engages by row count; the tiny series must reach it
    monkeypatch.setenv("GORDO_TPU_RING_PREDICT_ROWS", "512")
    # a two-rung member ladder: a third of the warmup compiles
    monkeypatch.setenv("GORDO_TPU_BATCH_MAX_SIZE", "2")
    device = chip_smoke.device_header()
    smoke = chip_smoke.Smoke(device["platform"])
    chip_smoke.run_phases(smoke, TINY, str(tmp_path / "smoke"), device)
    assert smoke.failures == []
    # one entry per serving program and mode, each inside its tolerance
    assert {"default:fleet", "batching:fleet", "multichip:ring tail"} <= set(
        smoke.max_abs_diff
    )
    assert len(os.listdir(tmp_path / "smoke" / chip_smoke.REVISION)) >= 3
