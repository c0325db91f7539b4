"""(d) The build cells' child, tiny, on the CPU: the same code the chip
runs, as a function of sizes; and every way a build turns incorrect."""

import json
import os

import numpy as np
import pytest

import build_worker
import common
from harness import breakdown, correct, manifest
from harness.data import machine_names, machines_document
from jobs import read_spans, read_status
from tiny import CPU_DEVICE, build_spec, cell, quiet_start, tiny_config


@pytest.fixture(scope="module", params=["hourglass_build", "lstm_build"])
def report(request, tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp(request.param))
    spec = build_spec(request.param, run_dir, trace=True)
    counter, errors = quiet_start()
    return spec, build_worker.run(spec, dict(CPU_DEVICE), counter, errors)


def test_tiny_build_cell_is_correct(report):
    spec, found = report
    assert found["correct"], found["failures"]
    jobs = found["jobs"]
    # a job starts only while the last one's time still fits the window
    assert 1 <= len(jobs) <= 1 + int(spec["seconds"] / min(j["seconds"] for j in jobs))
    assert found["attempted"] == 2 * len(jobs) == found["verified"]
    assert found["window"]["end"] == jobs[-1]["end"]
    assert found["in_window"]["compiles"] == 0
    assert found["worst_fraction_of_scale"] < 1e-4
    # every job left its status and its spans; its artifacts are gone
    for job in jobs:
        assert job["status"]["state"] == "complete"
        assert any("fit" in p["program"] for p in job["programs"])
        assert {p["phase"] for p in job["phases"]} >= {"data_fetch", "cv_train", "final_fit"}
        assert not os.path.exists(job["output_dir"])
    if "lookback_window" not in spec["config"]:
        loss, low, high = found["loss_band"]
        assert low <= loss <= high


def test_per_layer_readers_read_the_tiny_run(report):
    spec, found = report
    c = cell(spec["cell"])
    evidence = dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"])
    values = {name: reader(evidence) for name, reader in c.readers().items()}
    assert 0 < values["build_host_share_pct"] < 100
    assert 0 < values["build_fetch_stage_share_pct"] < values["build_host_share_pct"]
    assert values["device_programs_per_job"] == 3  # cv fit, cv predict, final fit
    assert values["compiles_in_window"] == 0
    # the CPU has no device plane and reports no memory: nothing to read
    for name in ("fit_step_ms", "fit_mfu_pct", "device_idle_pct", "hbm_peak_pct"):
        assert values[name] is None
    assert breakdown.build(evidence) == {"device_ops": [], "idle_gaps": []}
    assert found["trace"]["window_s"] > 0 and found["trace"]["profile_start_wall_ns"]
    assert not os.path.exists(os.path.join(spec["run_dir"], "trace"))  # removed once reduced


@pytest.fixture(scope="module")
def one_job(tmp_path_factory):
    """One tiny hourglass job, kept on disk."""
    config = tiny_config(cell("hourglass_build").config)
    job_dir = str(tmp_path_factory.mktemp("job"))
    document = machines_document(config, 5, 0, 2, 2)
    record = common.build_job(document, job_dir, os.path.join(job_dir, "build"))
    record["index"] = 0
    record["status"] = read_status(record["output_dir"])
    record.update(read_spans(record["output_dir"]))
    return config, document, record, machine_names(5, 0, 2)


def test_a_clean_job_passes_every_check(one_job):
    config, document, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    checks = correct.Checks()
    assert correct.check_build_job(checks, record, names, config) == 2
    correct.check_programs(checks, record, config, 289)
    correct.check_artifact_forward(
        checks, reference, record["output_dir"], names[0], 32, 0, "cpu"
    )
    assert correct.check_loss_band(
        checks, reference, config, document, record["output_dir"], names[0]
    )
    assert checks.ok, checks.failures


def test_a_job_that_trained_fewer_epochs_is_incorrect(one_job):
    config, _, record, names = one_job
    stated = dict(config, epochs=config["epochs"] + 1)
    checks = correct.Checks()
    assert correct.check_build_job(checks, record, names, stated) == 0
    correct.check_programs(checks, record, stated, 289)
    assert any("epochs" in f for f in checks.failures) and len(checks.failures) >= 3


def test_a_job_over_fewer_rows_or_a_missing_artifact_is_incorrect(one_job):
    config, _, record, names = one_job
    checks = correct.Checks()
    correct.check_programs(checks, record, config, 100_000)
    assert not checks.ok
    checks = correct.Checks()
    assert correct.check_build_job(checks, record, names + ["not-built"], config) == 2
    assert any("no artifact" in f for f in checks.failures)


@pytest.mark.parametrize("key", ["failed", "fallbacks", "bucket_bisects", "state", "exit_code"])
def test_a_contained_fault_is_incorrect(one_job, key, tmp_path):
    config, _, record, names = one_job
    status = json.loads(json.dumps(record["status"]))
    broken = dict(record, output_dir=str(tmp_path))
    if key in ("failed", "fallbacks"):
        status["machines"][key] = 1
    elif key == "bucket_bisects":
        status["robustness"][key] = 1
    elif key == "state":
        status["state"] = "running"
    else:
        broken["exit_code"] = 1
    (tmp_path / "build_status.json").write_text(json.dumps(status))
    checks = correct.Checks()
    correct.check_build_job(checks, broken, [], config)
    assert not checks.ok


def test_a_perturbed_weight_is_incorrect(one_job, monkeypatch):
    config, _, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    honest = reference.layers_of

    def perturbed(estimator):
        layers = honest(estimator)
        W, b, activation = layers[-1]
        W = W.copy()
        W[0, 0] += 1.0
        return layers[:-1] + [(W, b, activation)]

    monkeypatch.setattr(reference, "layers_of", perturbed)
    for platform in ("cpu", "tpu"):
        checks = correct.Checks()
        correct.check_artifact_forward(
            checks, reference, record["output_dir"], names[0], 32, 0, platform
        )
        assert not checks.ok


def test_loss_outside_the_band_is_incorrect(one_job, monkeypatch):
    config, document, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    monkeypatch.setattr(reference, "loss_band", lambda X, y, config: (0.0, 1e-9))
    checks = correct.Checks()
    correct.check_loss_band(checks, reference, config, document, record["output_dir"], names[0])
    assert not checks.ok


def test_same_seed_same_machines_other_seed_others():
    config = cell("hourglass_build").config
    a = machines_document(config, 1, 0, 3, 2)
    assert a == machines_document(config, 1, 0, 3, 2)
    assert a != machines_document(config, 2, 0, 3, 2)
    assert a != machines_document(config, 1, 1, 3, 2)
    tags = [t for m in a["machines"] for t in m["dataset"]["tag_list"]]
    assert len(tags) == len(set(tags)) == 60
    rows = a["machines"][0]["dataset"]["data_provider"]["min_size"]
    assert rows == 289


def test_no_chip_is_refused(monkeypatch):
    with pytest.raises(common.NoChip, match="no accelerator"):
        common.require_chip(1)
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v9"

    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    with pytest.raises(common.NoChip, match="no peaks"):
        common.require_chip(1)
    Fake.device_kind = "TPU v5 lite"
    with pytest.raises(common.NoChip, match="needs 4 chips"):
        common.require_chip(4)
    assert common.require_chip(1)["peaks"]["bf16_flops_per_s"] == 197e12


def test_compiled_between():
    before = {"programs": 3, "cache_hits": 1, "cache_misses": 2, "compile_seconds": 0.0}
    after = {"programs": 9, "cache_hits": 6, "cache_misses": 3, "compile_seconds": 0.0}
    assert common.compiled_between(before, after, "/cache") == {"compiles": 1, "loads": 6}
    assert common.compiled_between(before, after, None) == {"compiles": 0, "loads": 6}
    assert np.isfinite(common.CompileCounter().snapshot()["compile_seconds"])


def test_a_traced_slice_is_cut_at_its_cap_while_the_job_runs_on(tmp_path):
    """``trace_max_seconds`` shorter than the job: the trace stops from
    its timer's thread while the main thread is still at work, the
    job's own stop afterwards finds nothing left to do, and the slice
    reduces to its own length."""
    import time

    import jax.numpy as jnp

    trace = common.Trace(str(tmp_path / "trace"))
    trace.start()
    timer = trace.stop_after(0.3)
    began = time.monotonic()
    while time.monotonic() - began < 1.0:  # the job
        jnp.ones((64, 64)).sum().block_until_ready()
    timer.cancel()
    trace.stop()
    assert 0.3 <= trace.stopped_wall - trace.started_wall < 0.9
    reduced = trace.reduce(1)
    assert 0.3 <= reduced["window_s"] < 0.9
    assert reduced["devices"] == []  # the CPU has no device plane
