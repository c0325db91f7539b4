"""
Precision-parity gate drills: pass on healthy bf16 and int8, fail on corrupted
quantization, crash == fail (never an exception), and the canary gate
(`evaluate_canary`) engages the precision check exactly when the active
serving precision is reduced.
"""

import os

import pytest

from gordo_tpu.lifecycle.gates import (
    GateConfig,
    evaluate_canary,
    evaluate_precision_parity,
)
from gordo_tpu.models.spec import FeedForwardSpec
from gordo_tpu.server.fleet_store import RevisionFleet

from tests.lifecycle.conftest import BASE_REVISION, NAMES
from tests.server.conftest import temp_env_vars

pytestmark = [pytest.mark.lifecycle, pytest.mark.precision]


@pytest.fixture
def fleet(models_root):
    """A fresh RevisionFleet per test (gate verdicts and cast buckets
    live on the fleet object — tests must not share them)."""
    fleet = RevisionFleet(os.path.join(models_root, BASE_REVISION))
    fleet.warm(NAMES)
    return fleet


def shared_spec(fleet) -> FeedForwardSpec:
    specs = fleet.loaded_specs()
    assert specs, "fleet did not load"
    return specs[NAMES[0]]


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_parity_gate_passes_healthy(fleet, precision):
    """Both reduced precisions of the ladder keep every member's anomaly
    verdicts: bfloat16 casts and per-channel int8 weights."""
    report = evaluate_precision_parity(fleet, shared_spec(fleet), precision)
    assert report.passed, report.failures
    parity = report.checks["parity"]
    assert parity["precision"] == precision
    assert parity["agreement_min"] >= 0.98
    assert set(parity["members"]) == set(NAMES)


def test_parity_gate_fails_on_corrupt_quantization(fleet, monkeypatch):
    def corrupt_cast(stacked, precision):
        import jax

        return jax.tree_util.tree_map(lambda a: a * 0.0, stacked)

    monkeypatch.setattr(
        "gordo_tpu.serve.precision.cast_bucket_params", corrupt_cast
    )
    report = evaluate_precision_parity(fleet, shared_spec(fleet), "bf16")
    assert not report.passed
    assert any("bf16" in failure for failure in report.failures)
    assert report.checks["parity"]["agreement_min"] < 0.98


def test_crashing_evaluation_is_a_failed_gate(fleet, monkeypatch):
    def boom(*args, **kwargs):
        raise RuntimeError("synthetic parity crash")

    monkeypatch.setattr("gordo_tpu.serve.precision.evaluate_parity", boom)
    report = evaluate_precision_parity(fleet, shared_spec(fleet), "bf16")
    assert not report.passed
    assert "crashed" in report.failures[0]
    # a KeyboardInterrupt must NOT be swallowed into a gate verdict
    monkeypatch.setattr(
        "gordo_tpu.serve.precision.evaluate_parity",
        lambda *a, **k: (_ for _ in ()).throw(KeyboardInterrupt()),
    )
    with pytest.raises(KeyboardInterrupt):
        evaluate_precision_parity(fleet, shared_spec(fleet), "bf16")


def test_canary_gate_engages_precision_parity_when_reduced(
    models_root, probe_windows, monkeypatch
):
    healthy, _ = probe_windows
    base = RevisionFleet(os.path.join(models_root, BASE_REVISION))
    canary = RevisionFleet(os.path.join(models_root, BASE_REVISION))
    frames = {name: healthy for name in NAMES}

    # f32 serving: the precision gate stays out of the report entirely
    gate = evaluate_canary(base, canary, frames, NAMES, GateConfig())
    assert gate.passed, gate.failures
    assert "precision_parity" not in gate.checks

    # bf16 serving: the canary must additionally prove verdict parity
    with temp_env_vars(GORDO_TPU_SERVE_PRECISION="bf16"):
        gate = evaluate_canary(base, canary, frames, NAMES, GateConfig())
        assert gate.passed, gate.failures
        assert gate.checks["precision_parity"]
        (entry,) = gate.checks["precision_parity"].values()
        assert entry["agreement_min"] >= 0.98

        # ... and a badly-quantizing canary is REJECTED (the loop's
        # rollback machinery then keeps the f32 base serving)
        def corrupt_cast(stacked, precision):
            import jax

            return jax.tree_util.tree_map(lambda a: a * 0.0, stacked)

        monkeypatch.setattr(
            "gordo_tpu.serve.precision.cast_bucket_params", corrupt_cast
        )
        fresh_canary = RevisionFleet(os.path.join(models_root, BASE_REVISION))
        gate = evaluate_canary(base, fresh_canary, frames, NAMES, GateConfig())
        assert not gate.passed
        assert any("bf16" in failure for failure in gate.failures)
