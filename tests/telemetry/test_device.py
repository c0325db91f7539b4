"""
Device-utilization telemetry (PR 9): the compile-cache hit counters,
their ``program_span`` / serving wiring, the memory snapshot's degrade
contract, and the resource sample's schema.
"""

import pytest

from gordo_tpu import telemetry
from gordo_tpu.telemetry import device

pytestmark = [pytest.mark.fleet_health, pytest.mark.observability]


@pytest.fixture(autouse=True)
def _fresh_counters():
    device.reset_program_counters()
    telemetry.reset_seen_programs()
    yield
    device.reset_program_counters()
    telemetry.reset_seen_programs()


def test_program_counters_accumulate_per_kind():
    device.note_program_execution(True)
    device.note_program_execution(False)
    device.note_program_execution(False)
    device.note_program_execution(True, kind="serve")
    counters = device.program_cache_counters()
    assert counters["build"] == {
        "compiles": 1,
        "cache_hits": 2,
        "hit_rate": round(2 / 3, 4),
    }
    assert counters["serve"]["compiles"] == 1
    assert counters["serve"]["hit_rate"] == 0.0


def test_program_span_feeds_the_counters():
    """program_span's first-call-per-signature attribution IS the
    compile-cache hit/miss signal — the same call that marks the span
    must feed the console counters, recorder active or not."""
    with telemetry.program_span("fleet_fit", ("spec", (4, 8))):
        pass
    with telemetry.program_span("fleet_fit", ("spec", (4, 8))):
        pass
    with telemetry.program_span("fleet_fit", ("spec", (8, 8))):
        pass
    counters = device.program_cache_counters()["build"]
    assert counters["compiles"] == 2
    assert counters["cache_hits"] == 1


def test_memory_snapshot_never_raises(monkeypatch):
    """On any backend the snapshot is a dict (or None when disabled) —
    platforms without Device.memory_stats degrade to available=False,
    they never break the caller."""
    snapshot = device.memory_snapshot()
    assert snapshot is None or isinstance(snapshot, dict)
    if isinstance(snapshot, dict):
        assert "available" in snapshot
        if snapshot["available"]:
            assert snapshot["bytes_in_use"] >= 0
            assert snapshot["peak_bytes_in_use"] >= snapshot["bytes_in_use"] * 0
    monkeypatch.setenv("GORDO_TPU_DEVICE_TELEMETRY", "0")
    assert device.memory_snapshot() is None
    monkeypatch.setenv("GORDO_TPU_DEVICE_TELEMETRY", "1")
    monkeypatch.setenv("GORDO_TPU_TELEMETRY", "0")
    assert device.memory_snapshot() is None


def test_utilization_snapshot_sections():
    device.note_program_execution(True)
    doc = device.utilization_snapshot()
    assert "compile_cache" in doc
    assert doc["compile_cache"]["build"]["compiles"] == 1
    # memory may be absent (no jax stats) but never truthy-and-empty
    if "memory" in doc:
        assert isinstance(doc["memory"], dict)
    # every snapshot names where the process runs
    assert doc["device"]["platform"] == "cpu"
    assert doc["device"]["count"] >= 1 and doc["device"]["device_kind"]


def test_memory_keys_a_backend_does_not_report_are_absent(monkeypatch):
    """``peak_bytes_in_use`` is reported only where a device reports it —
    never ``bytes_in_use`` under its name."""

    class FakeDevice:
        def memory_stats(self):
            return {"bytes_in_use": 10, "bytes_limit": 100}

    monkeypatch.setattr(device, "_local_devices", lambda: [FakeDevice()])
    snapshot = device.memory_snapshot()
    assert snapshot["available"] and snapshot["bytes_in_use"] == 10
    assert "peak_bytes_in_use" not in snapshot
    assert "max_peak_bytes_in_use" not in snapshot
    assert snapshot["utilization"] == 0.1


def test_persistent_cache_info_counts_entries(tmp_path, monkeypatch):
    cache_dir = tmp_path / "compile-cache"
    cache_dir.mkdir()
    (cache_dir / "entry-1").write_bytes(b"x" * 100)
    (cache_dir / "entry-2").write_bytes(b"y" * 50)
    device.note_compile_cache_dir(str(cache_dir))
    try:
        info = device.persistent_cache_info()
        assert (info["path"], info["entries"], info["bytes"]) == (
            str(cache_dir), 2, 150
        )
        # this process's lookups against it ride along
        assert {"hits", "misses"} <= set(info)
    finally:
        device.note_compile_cache_dir(None)
    # unconfigured, JAX's own variable names the directory to inventory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
    assert device.persistent_cache_info()["entries"] == 2
    # unconfigured and no variable -> None
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert device.persistent_cache_info() is None


def test_the_resource_sample_has_the_devices_memory_and_the_hosts_own_numbers():
    """What the fleet builder samples at the end of a device-heavy phase
    and hands to ``build_status.json["resources"]``: the memory snapshot
    as :func:`memory_snapshot` gives it (None when sampling is off), the
    process's peak resident set in bytes and the cores it may run on.
    No event is written: the status key is the sample's reader."""
    import os
    import resource

    sample = device.sample_resources()
    assert set(sample) == {"memory", "host_rss_peak_bytes", "host_cpu_count"}
    assert sample["memory"] == device.memory_snapshot()
    assert sample["host_cpu_count"] == len(os.sched_getaffinity(0))
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    assert 0 < sample["host_rss_peak_bytes"] <= peak  # a peak only grows
    ballast = bytearray(64 << 20)  # touched pages: the peak moves with the process
    assert device.sample_resources()["host_rss_peak_bytes"] >= sample["host_rss_peak_bytes"]
    del ballast
    assert not hasattr(device, "emit_device_utilization")


@pytest.mark.precision
def test_program_counters_bucket_by_precision():
    """The serve kind's counters gain a per-precision breakdown (the
    precision ladder's compile accounting); kinds fed without a
    precision stay exactly as before."""
    device.note_program_execution(True, kind="serve", precision="f32")
    device.note_program_execution(False, kind="serve", precision="f32")
    device.note_program_execution(True, kind="serve", precision="bf16")
    device.note_program_execution(True, kind="build")
    counters = device.program_cache_counters()
    serve = counters["serve"]
    assert serve["compiles"] == 2 and serve["cache_hits"] == 1
    assert serve["by_precision"]["f32"] == {"compiles": 1, "cache_hits": 1}
    assert serve["by_precision"]["bf16"] == {"compiles": 1, "cache_hits": 0}
    assert "by_precision" not in counters["build"]
    # the snapshot is a COPY: mutating it never corrupts the live counts
    serve["by_precision"]["f32"]["compiles"] = 999
    assert (
        device.program_cache_counters()["serve"]["by_precision"]["f32"][
            "compiles"
        ]
        == 1
    )
