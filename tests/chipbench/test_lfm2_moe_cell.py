"""The cell ``lfm2_moe_build`` at toy sizes on the CPU stand-in: the same
child the chip runs, as a function of sizes. The cell's own toy sizes
are here (``tiny.py`` swaps any windowed configuration for a toy LSTM)."""

import os

import numpy as np
import pytest

import build_worker
import common
import flops_backbone
from harness import correct, manifest
from harness.data import history_rows, machine_names, machines_document
from jobs import read_spans, read_status
from tiny import CPU_DEVICE, quiet_start

CELL = "lfm2_moe_build"

#: the estimator at toy widths: every layer kind, 2 of 8 experts held
TOY_ESTIMATOR = {
    "kind": "lfm2_moe", "lookback_window": 100,
    "layer_types": ["conv", "full_attention", "conv"], "num_dense_layers": 1,
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 48, "moe_intermediate_size": 24, "num_experts": 8,
    "experts_held": 2, "expert_offset": 2, "num_experts_per_tok": 2,
    "epochs": 2, "batch_size": 32,
}


def toy_config(config: dict) -> dict:
    """The cell's configuration with toy widths wherever a reader or the
    worker looks: the estimator, and the keys ``flops_backbone`` reads."""
    (path, _), = config["estimator"].items()
    return dict(
        config,
        estimator={path: dict(TOY_ESTIMATOR)},
        tags=5, lookback_window=100, epochs=2,
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=48, moe_intermediate_size=24, num_experts_per_tok=2,
        published={"num_hidden_layers": 24, "num_experts": 8},
        layer_types_held=["conv", "full_attention", "conv"],
        layer_ffns_held=["dense", "moe", "moe"],
    )


def the_cell() -> manifest.Cell:
    return manifest.Cell(manifest.load_manifest(), CELL)


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    c = the_cell()
    run_dir = str(tmp_path_factory.mktemp(CELL))
    spec = {
        "cell": CELL, "chips": 1, "config": toy_config(c.config),
        "traffic": dict(c.traffic, history_days=1, verify_rows=108, trace_max_seconds=20),
        "seed": 2147483659, "seconds": 1.0, "trace": True, "run_dir": run_dir,
    }
    counter, errors = quiet_start()
    return spec, build_worker.run(spec, dict(CPU_DEVICE), counter, errors)


def test_the_manifest_has_no_problems_with_the_cell():
    document = manifest.load_manifest()
    assert manifest.problems(document) == []
    c = the_cell()
    assert c.entry["chips"] == 1 and c.traffic["kind"] == "build_jobs"
    assert c.config["reduced"] == ["num_hidden_layers", "num_experts"]
    assert {m["name"] for m in c.per_layer} >= {
        "backbone_fit_step_ms", "backbone_fit_mfu_pct", "moe_expert_imbalance_pct",
        "moe_local_pair_share_pct", "hbm_peak_pct", "device_idle_pct",
    }
    # the readers that count an LSTM's operations, or the steps the
    # shapes say where padding steps are skipped, do not hold here
    assert not {"fit_mfu_pct", "fit_step_ms"} & {m["name"] for m in c.per_layer}


def test_the_toy_cell_is_correct(report):
    spec, found = report
    assert found["correct"], found["failures"]
    assert found["attempted"] == found["verified"] == len(found["jobs"])
    assert found["worst_fraction_of_scale"] < 1e-4
    # the step check held: the band is every finite loss
    loss, low, high = found["loss_band"]
    assert low == 0.0 and 0.0 < loss < high
    for job in found["jobs"]:
        fits = [p for p in job["programs"] if "fit" in p["program"]]
        # three folds and the final fit, one member a program, one compile
        assert len(fits) == 4 and all(p["members"] == 1 for p in fits)
        assert sum(bool(p["compile"]) for p in fits) == 0  # the warm-up job compiled it
        assert job["status"]["fit_counters"] and len(job["status"]["fit_counters"]) == 4
    assert sum(bool(p["compile"]) for p in found["warm_job"]["programs"] if "fit" in p["program"]) == 1


def test_every_new_reader_reads_the_toy_run(report):
    spec, found = report
    c = the_cell()
    evidence = dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"])
    readers = c.readers()
    share = readers["moe_local_pair_share_pct"](evidence)
    imbalance = readers["moe_expert_imbalance_pct"](evidence)
    assert 0 < share < 100 and imbalance >= 0
    # by hand from the spans
    fits = [p for j in found["jobs"] for p in j["programs"] if "fit" in p["program"]]
    here = sum(sum(p["pairs_here"]) for p in fits)
    total = sum(sum(p["pairs_total"]) for p in fits)
    assert share == pytest.approx(100.0 * here / total)
    # experts 2 and 3 are held: pairs here are the tokens routed to them
    for p in fits:
        for layer, pairs in enumerate(p["pairs_here"]):
            assert pairs == sum(p["router_tokens"][layer][2:4])
            assert sum(p["router_tokens"][layer]) == p["pairs_total"][layer]
        # the pairs in all are those of the windows trained, 2 experts a
        # token: a slot of padding routes nothing, a step of padding
        # alone is skipped
        windows, left = divmod(p["pairs_total"][0], p["epochs"] * 100 * 2)
        assert left == 0 and 0 < windows <= p["stacked_samples"]
        assert windows <= 32 * p["steps_run"] // p["epochs"] < windows + 32
    # the CPU has no device plane: the fit time is not there to read ...
    assert readers["backbone_fit_mfu_pct"](evidence) is None
    assert readers["backbone_fit_step_ms"](evidence) is None
    # ... and with one, the reader divides the hand count by it
    job = found["jobs"][found["traced_job"]]
    timed = dict(evidence, trace={"devices": [
        {"modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}}
    ]})
    useful = flops_backbone.job_useful_fit_flops(
        spec["config"], history_rows(1), job["programs"]
    )
    assert readers["backbone_fit_mfu_pct"](timed) == pytest.approx(
        100.0 * useful / (2.0 * CPU_DEVICE["peaks"]["bf16_flops_per_s"])
    )
    # the steps that ran, as the program counted them: fewer than the
    # shapes say; and the tokens it routed are the hand count's, no more
    job_fits = [p for p in job["programs"] if "fit" in p["program"]]
    ran = sum(p["steps_run"] for p in job_fits)
    assert ran < sum(p["epochs"] * p["stacked_samples"] // 32 for p in job_fits)
    trained = flops_backbone.trained_windows(spec["config"], history_rows(1))
    assert sum(p["pairs_total"][0] for p in job_fits) == trained * 2 * 100 * 2
    assert readers["backbone_fit_step_ms"](timed) == pytest.approx(2000.0 / ran)


def test_the_new_readers_find_nothing_in_a_program_without_the_counters(report):
    """The parent's program has no such counter: nothing is read, nothing raises."""
    spec, found = report
    c = the_cell()
    stripped = [
        dict(job, programs=[
            {k: v for k, v in p.items()
             if k not in ("pairs_here", "pairs_total", "router_tokens", "steps_run", "fit_counters")}
            for p in job["programs"]
        ])
        for job in found["jobs"]
    ]
    evidence = dict(found, jobs=stripped, cell=c.entry, config=spec["config"], traffic=spec["traffic"])
    for name in ("backbone_fit_mfu_pct", "backbone_fit_step_ms", "moe_expert_imbalance_pct",
                 "moe_local_pair_share_pct"):
        assert c.readers()[name](evidence) is None


def test_flops_backbone_against_a_hand_count():
    config = the_cell().config
    h, inter, moe = 2048, 7168, 1792
    conv = 2 * h * 6144 + 2 * 3 * h + 2 * h * h
    attention = 2 * h * (2048 + 512 + 512 + 2048) + 2 * 2 * 256.5 * h
    assert flops_backbone.operator_flops_per_token(config, "conv") == conv
    assert flops_backbone.operator_flops_per_token(config, "full_attention") == attention
    dense = 2 * 50 * h + 4 * conv + attention + 6 * h * inter + 4 * 2 * h * 32
    assert flops_backbone.dense_flops_per_token(config) == dense
    assert flops_backbone.pair_flops(config) == 6 * h * moe
    # 577 rows: 65 windows; folds train 17, 33, 49 of them, the final fit 65
    assert flops_backbone.trained_windows(config, 577) == 17 + 33 + 49 + 65
    windows = 164 * 5
    programs = [{"program": "fleet_windowed_fit", "pairs_here": [100, 200, 300, 400],
                 "pairs_total": [4000] * 4}] * 4
    ran_tokens = 4 * 4000 / 4
    experts = 6 * h * moe * 4000 * (windows * 512 / ran_tokens)
    assert flops_backbone.job_useful_fit_flops(config, 577, programs) == pytest.approx(
        3.0 * (dense * windows * 512 + experts + 2 * h * 50 * windows)
    )
    # a step of 32 windows at the deployment's share: 16.4 TFLOP useful
    step = 3.0 * (dense * 16384 + 6 * h * moe * 16384 * 4 + 2 * h * 50 * 32)
    assert 16.0e12 < step < 17.0e12
    with pytest.raises(KeyError):
        flops_backbone.job_useful_fit_flops(config, 577, [{"program": "fleet_windowed_fit"}])


def test_the_configuration_states_the_programs_own_count():
    import jax

    from gordo_tpu import serializer
    from gordo_tpu.models.backbone import trained_param_count

    config = the_cell().config
    estimator = serializer.from_definition(config["estimator"])
    spec = estimator._build_spec({
        k: v for k, v in estimator.kwargs.items() if k not in ("epochs", "batch_size")
    } | {"n_features": config["tags"], "n_features_out": config["tags"]})
    shapes = jax.eval_shape(lambda key: spec.init_fn()(key, spec), jax.random.PRNGKey(0))
    assert trained_param_count(shapes) == spec.param_count() == config["weights_per_member"]
    assert spec.layer_ops == tuple(config["layer_types_held"])
    assert spec.layer_ffns == tuple(config["layer_ffns_held"])
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "conv_L_cache",
                "num_attention_heads", "num_key_value_heads", "num_experts_per_tok"):
        assert getattr(spec, key) == config[key], key
    assert spec.num_experts == config["published"]["num_experts"] == 32
    assert spec.experts_held == config["num_experts"] == 8
    assert len(spec.layer_ops) == config["num_hidden_layers"] == 5


@pytest.fixture(scope="module")
def one_job(tmp_path_factory):
    """One toy job, kept on disk."""
    config = toy_config(the_cell().config)
    job_dir = str(tmp_path_factory.mktemp("job"))
    document = machines_document(config, 7, 0, 1, 1)
    record = common.build_job(document, job_dir, os.path.join(job_dir, "build"))
    record["index"] = 0
    record["status"] = read_status(record["output_dir"])
    record.update(read_spans(record["output_dir"]))
    return config, record, machine_names(7, 0, 1)


def check_forward(record, names, reference, platform="cpu"):
    checks = correct.Checks()
    correct.check_artifact_forward(
        checks, reference, record["output_dir"], names[0], 108, 0, platform
    )
    return checks


def test_a_clean_job_passes_and_a_perturbed_expert_weight_does_not(one_job, monkeypatch):
    config, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    checks = correct.Checks()
    assert correct.check_build_job(checks, record, names, config) == 1
    correct.check_programs(checks, record, config, history_rows(1) - 99)
    assert checks.ok, checks.failures
    assert check_forward(record, names, reference).ok
    honest = reference.layers_of

    def perturbed(estimator):
        layers = honest(estimator)
        w2 = layers["weights"]["layer_1"]["moe"]["w2"].copy()
        w2[0] += 1.0  # one held expert's down projection
        layers["weights"]["layer_1"]["moe"]["w2"] = w2
        return layers

    monkeypatch.setattr(reference, "layers_of", perturbed)
    for platform in ("cpu", "tpu"):
        assert not check_forward(record, names, reference, platform).ok


def test_a_dropped_pair_is_incorrect(one_job, monkeypatch):
    """A program that drops (token, expert) pairs (here: a capacity of
    half the pairs routed to each token) no longer matches the reference."""
    config, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    from gordo_tpu.models import backbone, training

    honest = backbone.route

    def dropping(spec, w, tokens):
        chosen, weights = honest(spec, w, tokens)
        return chosen, weights.at[:, -1].set(0.0)  # every token's last pair

    monkeypatch.setattr(backbone, "route", dropping)
    training.predict_fn.cache_clear()
    try:
        assert not check_forward(record, names, reference).ok
    finally:
        monkeypatch.undo()
        training.predict_fn.cache_clear()
    assert check_forward(record, names, reference).ok


def check_step(config, record, names, reference):
    """The harness's two calls on one artifact, in the worker's order."""
    checks = check_forward(record, names, reference)
    document = machines_document(config, 7, 0, 1, 1)
    band = correct.check_loss_band(
        checks, reference, config, document, record["output_dir"], names[0]
    )
    return checks, band


def test_a_fault_in_the_training_step_is_incorrect(one_job, monkeypatch, capfd):
    """A step whose forward is right and whose gradient is not (here:
    the dense feed-forward's cotangent doubled) passes the forward check
    and fails the step check, through ``correct.check_loss_band``."""
    import jax

    from gordo_tpu.models import backbone, training

    config, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    checks, band = check_step(config, record, names, reference)
    assert checks.ok and band[1] == 0.0, checks.failures
    sound = capfd.readouterr().out
    assert "chipbench step check: " in sound and '"over": []' in sound
    honest = backbone.dense_ffn

    def doubled_cotangent(w, u):
        out = honest(w, u)
        return 2.0 * out - jax.lax.stop_gradient(out)

    monkeypatch.setattr(backbone, "dense_ffn", doubled_cotangent)
    caches = (training.windowed_batch_loss_fn, training.windowed_loss_and_grad_norms_program)
    for cache in caches:
        cache.cache_clear()
    try:
        checks, band = check_step(config, record, names, reference)
    finally:
        monkeypatch.undo()
        for cache in caches:
            cache.cache_clear()
    assert [f for f in checks.failures if "outside the reference band" in f], checks.failures
    assert len(checks.failures) == 1  # the forward is what it was
    assert '"over": ["leaf"]' in capfd.readouterr().out


def test_the_next_precision_below_reads_apart_from_a_sound_build(tmp_path_factory, capfd):
    """The control of the step check's limits, at toy widths: the same
    toy job with ``compute_dtype: bfloat16`` (float32 weights, bfloat16
    activations and products) builds, fails the CPU's forward tolerance,
    and its step readings lie orders of magnitude above a float32
    build's (1e-7 here). The limits themselves are set at published
    widths on the chip, where the readings differ (PERF.md, section 6)."""
    import json

    config = toy_config(the_cell().config)
    (path, estimator), = config["estimator"].items()
    config = dict(config, estimator={path: dict(estimator, compute_dtype="bfloat16")})
    job_dir = str(tmp_path_factory.mktemp("bf16"))
    record = common.build_job(
        machines_document(config, 7, 0, 1, 1), job_dir, os.path.join(job_dir, "build")
    )
    assert record["exit_code"] == 0
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    checks, band = check_step(config, record, machine_names(7, 0, 1), reference)
    assert not checks.ok
    line = next(l for l in capfd.readouterr().out.splitlines() if l.startswith("chipbench step check: "))
    readings = json.loads(line.split(": ", 1)[1])
    assert readings["output"] > 1e-4 and readings["loss"] > 1e-4 and readings["leaf"] > 1e-3
    # limits under the readings: the band is empty, the run not correct
    X, y = np.zeros((108, 5), np.float32), np.ones((108, 5), np.float32)
    low, high = reference.loss_band(X, y, config, limits={"output": 1e-5})
    assert np.isnan(low) and np.isnan(high)


def test_a_checkout_without_the_backbone_ends_the_build_child_at_once(tmp_path):
    """The parent commit with this benchmark laid over it: the child of
    the new cell ends with exit code 5 as it loads the reference, before
    a single job; ``run.py``'s own checks of the same tree find nothing
    wrong, so every other cell runs there as before."""
    import shutil
    import subprocess
    import sys

    root = tmp_path / "checkout"
    shutil.copytree(
        manifest.CHIP_DIR, root / "benchmarks" / "chip",
        ignore=shutil.ignore_patterns("out", "__pycache__"),
    )
    (root / "gordo_tpu" / "models").mkdir(parents=True)
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    assert manifest.problems(manifest.load_manifest(str(root)), str(root)) == []
    child = tmp_path / "build_worker.py"  # the child's name is what the reference looks at
    child.write_text(
        "import sys\n"
        f"sys.path[:0] = [{str(root / 'benchmarks' / 'chip')!r}]\n"
        "from harness.manifest import load_module\n"
        f"load_module({str(root)!r}, 'reference', 'lfm2_moe_backbone')\n"
        "print('loaded')\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    refused = subprocess.run([sys.executable, str(child)], capture_output=True, text=True, env=env, timeout=120)
    assert refused.returncode == 5 and "loaded" not in refused.stdout
    assert "cannot build a backbone configuration" in refused.stderr
    (root / "gordo_tpu" / "models" / "backbone.py").write_text("")
    loaded = subprocess.run([sys.executable, str(child)], capture_output=True, text=True, env=env, timeout=120)
    assert loaded.returncode == 0 and "loaded" in loaded.stdout
