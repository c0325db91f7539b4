"""The cell ``keye_dsa_build`` at toy sizes on the CPU stand-in: the same
child the chip runs, as a function of sizes (after
``test_lfm2_moe_cell.py``; the cell's own toy sizes are here)."""

import json
import os

import numpy as np
import pytest

import build_worker
import common
import flops_backbone
import flops_sparse_backbone
from harness import correct, manifest
from harness.data import history_rows, machine_names, machines_document
from jobs import read_spans, read_status
from tiny import CPU_DEVICE, quiet_start

CELL = "keye_dsa_build"
LOOKBACK, TOPK = 100, 24

#: the estimator at toy widths: 2 layers, an indexer of 8 heads of 8
#: that keeps 24 of 100 rows, 2 of 8 experts held
TOY_SPARSE = {
    "indexer_head_dim": 8, "indexer_num_heads": 8, "indexer_num_kv_heads": 1,
    "topk": TOPK, "q_chunk_size": 32, "kv_chunk_size": 32,
}
TOY_ESTIMATOR = {
    "kind": "keye_vl2", "lookback_window": LOOKBACK, "num_hidden_layers": 2,
    "hidden_size": 32, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "moe_intermediate_size": 24, "num_experts": 8, "experts_held": 2, "expert_offset": 2,
    "num_experts_per_tok": 2, "sa_config": TOY_SPARSE, "epochs": 2, "batch_size": 32,
}


def toy_config(config: dict) -> dict:
    """The cell's configuration with toy widths wherever a reader or the
    worker looks: the estimator, and the keys ``flops_sparse_backbone``
    reads."""
    (path, _), = config["estimator"].items()
    return dict(
        config,
        estimator={path: dict(TOY_ESTIMATOR)},
        tags=5, lookback_window=LOOKBACK, epochs=2, batch_size=32,
        hidden_size=32, head_dim=16, num_attention_heads=4, num_key_value_heads=2,
        moe_intermediate_size=24, num_experts_per_tok=2, num_hidden_layers=2,
        sa_config=dict(TOY_SPARSE), published={"num_hidden_layers": 48, "num_experts": 8},
    )


def the_cell() -> manifest.Cell:
    return manifest.Cell(manifest.load_manifest(), CELL)


def kept_by_arithmetic(length=LOOKBACK, top_k=TOPK):
    return sum(min(t + 1, top_k) for t in range(length))


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
    assert c.entry["traffic"] == "jobs-1x57d" and c.traffic["history_days"] == 57
    # 8,209 rows are 17 windows of 8,192 with the row each predicts
    assert history_rows(57) - c.config["lookback_window"] - c.config["lookahead"] + 1 == 17
    assert c.traffic["verify_rows"] - c.config["lookback_window"] == 2
    assert c.config["reduced"] == ["num_hidden_layers", "num_experts"]
    assert {m["name"] for m in c.end_to_end} == {"models_built_per_hour", "setup_s"}
    reported = {m["name"] for m in c.per_layer}
    assert reported >= {
        "sparse_fit_mfu_pct", "backbone_fit_step_ms", "moe_expert_imbalance_pct", "moe_local_pair_share_pct",
        "hbm_peak_pct", "device_idle_pct", "compiles_in_window",
    }
    # ... every per-layer metric of lfm2_moe_build but the one that counts LFM2's shapes
    other = {m["name"] for m in manifest.Cell(document, "lfm2_moe_build").per_layer}
    assert other - reported == {"backbone_fit_mfu_pct"}
    assert not {"fit_mfu_pct", "fit_step_ms"} & reported
    # the new reader lists this cell alone; nothing that was there lost a cell
    assert [m["workloads"] for m in document["per_layer"] if m["name"] == "sparse_fit_mfu_pct"] == [[CELL]]
    assert [w["name"] for w in document["workloads"]][-1] == CELL
    assert [c["name"] for c in document["configs"]][-1] == "keye-vl2-30b-a3b-50tag-lb8192"


def test_the_manifest_case_expected_to_fail_fails_on_its_last_line_alone():
    """``test_manifest.py::test_config_entry_and_file`` for this
    configuration (``tests/conftest.py:MANIFEST_CASE_OUTGROWN``): every
    line of it but the last, which states another model's batch."""
    document = manifest.load_manifest()
    config = next(c for c in document["configs"] if c["name"] == "keye-vl2-30b-a3b-50tag-lb8192")
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    for key in ("source", "why"):
        assert 1 <= len(config[key]) <= 200 and "\n" not in config[key]
    assert config["file"].startswith("benchmarks/chip/configs/")
    stated = manifest.load_json(manifest.ROOT, config["file"])
    assert stated["source"] == config["source"] and stated["reduced"] == config["reduced"]
    assert any(w["config"] == config["name"] for w in document["workloads"])
    assert (stated["batch_size"], stated["epochs"]) == (2, 1)  # the last line's 32 and 5
    # and it is the only case the tree expects to fail
    from tests import conftest

    assert conftest.MANIFEST_CASE_OUTGROWN.endswith(f"[{config['name']}]")


def test_the_toy_cell_is_correct(report):
    spec, found = report
    assert found["correct"], found["failures"]
    assert found["attempted"] == found["verified"] == len(found["jobs"])
    assert found["worst_fraction_of_scale"] < 1e-4
    loss, low, high = found["loss_band"]  # the step check held: every finite loss
    assert low == 0.0 and 0.0 < loss < high
    for job in found["jobs"]:
        fits = [p for p in job["programs"] if "fit" in p["program"]]
        # three folds and the final fit, one member a program, one compile
        assert len(fits) == 4 and all(p["members"] == 1 for p in fits)
        assert sum(bool(p["compile"]) for p in fits) == 0  # the warm-up job compiled it
        assert job["status"]["fit_counters"] and len(job["status"]["fit_counters"]) == 4
        for p in fits:
            assert set(p["fit_counters"]) >= {
                "keys_selected", "keys_causal", "indexer_kl", "router_tokens", "pairs_here",
                "pairs_total", "steps_run", "index_topk", "num_experts",
            }
            assert p["index_topk"] == TOPK and len(p["indexer_kl"]) == 2
            assert all(kl > 0 for kl in p["indexer_kl"])
    assert sum(bool(p["compile"]) for p in found["warm_job"]["programs"] if "fit" in p["program"]) == 1


def test_the_new_reader_and_the_counters_of_the_toy_run(report):
    spec, found = report
    c = the_cell()
    evidence = dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"])
    readers = c.readers()
    assert 0 < readers["moe_local_pair_share_pct"](evidence) < 100
    assert readers["moe_expert_imbalance_pct"](evidence) >= 0
    fits = [p for j in found["jobs"] for p in j["programs"] if "fit" in p["program"]]
    for p in fits:
        # the windows trained, as the expert layer counts them, are the selection's
        windows, left = divmod(p["pairs_total"][0], LOOKBACK * 2)
        assert left == 0 and windows > 0
        assert p["keys_causal"] == [windows * LOOKBACK * (LOOKBACK + 1) / 2] * 2
        assert p["keys_selected"] == [windows * kept_by_arithmetic()] * 2
    # the CPU has no device plane: the fit time is not there to read ...
    for name in ("sparse_fit_mfu_pct", "backbone_fit_step_ms"):
        assert readers[name](evidence) is None
    # ... and with one, each reader divides by it
    job = found["jobs"][found["traced_job"]]
    timed = dict(evidence, trace={"devices": [{
        "modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": [],
    }]})
    useful = flops_sparse_backbone.job_useful_fit_flops(spec["config"], history_rows(1), job["programs"])
    assert readers["sparse_fit_mfu_pct"](timed) == pytest.approx(
        100.0 * useful / (2.0 * CPU_DEVICE["peaks"]["bf16_flops_per_s"])
    )
    ran = sum(p["steps_run"] for p in job["programs"] if "fit" in p["program"])
    assert readers["backbone_fit_step_ms"](timed) == pytest.approx(2000.0 / ran)


def test_the_new_reader_finds_nothing_in_a_program_without_the_counters(report):
    """The parent's program has no such counter: nothing is read, nothing raises."""
    spec, found = report
    c = the_cell()
    gone = ("keys_selected", "keys_causal", "indexer_kl", "index_topk", "fit_counters")
    stripped = [
        dict(job, programs=[{k: v for k, v in p.items() if k not in gone} for p in job["programs"]])
        for job in found["jobs"]
    ]
    evidence = dict(
        found, jobs=stripped, cell=c.entry, config=spec["config"], traffic=spec["traffic"],
        trace={"devices": [{"modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": []}]},
    )
    assert c.readers()["sparse_fit_mfu_pct"](evidence) is None
    # a backbone without the operator (lfm2_moe_build's evidence) reads the same way
    lfm2 = manifest.Cell(manifest.load_manifest(), "lfm2_moe_build")
    assert c.readers()["sparse_fit_mfu_pct"](dict(evidence, config=lfm2.config)) is None


def test_flops_sparse_backbone_against_a_hand_count():
    config = the_cell().config
    h = 2048
    projections = 2 * h * (2 * 4096 + 2 * 512 + 1024 + 64 + 16 + 128)
    assert flops_sparse_backbone.projection_flops_per_token(config) == projections
    assert flops_sparse_backbone.index_flops_per_pair(config) == 2 * 16 * 64 + 2 * 16
    assert flops_sparse_backbone.attention_flops_per_pair(config) == 4 * 32 * 128
    assert flops_backbone.pair_flops(config) == 6 * h * 768
    # 8,209 rows: 17 windows; folds train 5, 9, 13 of them, the final fit 17
    assert flops_backbone.trained_windows(config, 8209) == 5 + 9 + 13 + 17
    windows, causal, kept = 44, 8192 * 8193 // 2, kept_by_arithmetic(8192, 2048)
    assert round(100.0 * kept / causal, 1) == 43.7
    programs = [{
        "program": "fleet_windowed_fit", "pairs_here": [1000.0, 2000, 3000, 4000], "pairs_total": [8000] * 4,
        "keys_causal": [11.0 * causal] * 4, "keys_selected": [11.0 * kept] * 4,
    }] * 4
    by_hand = 3.0 * (
        (2 * 50 * h + 4 * projections) * windows * 8192
        + (2 * 16 * 64 + 2 * 16) * 4 * windows * causal
        + 4 * 32 * 128 * 4 * windows * kept
        + 6 * h * 768 * 4 * 10000
        + 2 * h * 50 * windows
    )
    assert flops_sparse_backbone.job_useful_fit_flops(config, 8209, programs) == pytest.approx(by_hand)
    # a step of 2 windows at even routing (16 of 128 experts: 1 pair a
    # token a layer): 17.7 TFLOP useful, 11% of them the selected attention's
    step = 3.0 * (
        (2 * 50 * h + 4 * projections) * 16384 + (2 * 16 * 64 + 2 * 16) * 4 * 2 * causal
        + 4 * 32 * 128 * 4 * 2 * kept + 6 * h * 768 * 4 * 16384 + 2 * h * 50 * 2
    )
    assert 17.5e12 < step < 18.0e12
    with pytest.raises(KeyError):
        flops_sparse_backbone.job_useful_fit_flops(config, 8209, [{"program": "fleet_windowed_fit"}])
    # the expert layer's counters alone (an lfm2_moe program) are not the selection's
    assert flops_sparse_backbone.fit_counters(
        [{"program": "fleet_windowed_fit", "pairs_here": [1], "pairs_total": [4]}]
    ) == []


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
    assert trained_param_count(shapes) == spec.param_count() == config["weights_per_member"] == 387_806_770
    assert spec.layer_ops == ("sparse_attention",) * 4 and spec.layer_ffns == ("moe",) * 4
    for key in ("hidden_size", "head_dim", "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts_per_tok", "lookback_window"):
        assert getattr(spec, key) == config[key], key
    assert (spec.norm_eps, spec.rope_theta) == (config["rms_norm_eps"], config["rope_theta"])
    sparse = config["sa_config"]
    assert (spec.index_n_heads, spec.index_head_dim, spec.index_topk, spec.index_chunk, spec.index_chunk) == (
        sparse["indexer_num_heads"], sparse["indexer_head_dim"], sparse["topk"],
        sparse["q_chunk_size"], sparse["kv_chunk_size"],
    )
    assert spec.num_experts == config["published"]["num_experts"] == config["num_local_experts"] == 128
    assert spec.experts_held == config["num_experts"] == 16
    assert len(spec.layer_ops) == config["num_hidden_layers"] == 4
    assert estimator.kwargs["batch_size"] == config["batch_size"] == 2
    assert estimator.kwargs["epochs"] == config["epochs"] == 1


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
    correct.check_artifact_forward(checks, reference, record["output_dir"], names[0], 108, 0, platform)
    return checks


def check_step(config, record, names, reference):
    """The harness's two calls on one artifact, in the worker's order."""
    checks = check_forward(record, names, reference)
    document = machines_document(config, 7, 0, 1, 1)
    band = correct.check_loss_band(checks, reference, config, document, record["output_dir"], names[0])
    return checks, band


def step_line(capfd) -> dict:
    line = next(l for l in capfd.readouterr().out.splitlines() if l.startswith("chipbench step check: "))
    return json.loads(line.split(": ", 1)[1])


def test_a_clean_job_passes_and_a_perturbed_indexer_weight_does_not(one_job, monkeypatch, capfd):
    config, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    checks = correct.Checks()
    assert correct.check_build_job(checks, record, names, config) == 1
    correct.check_programs(checks, record, config, history_rows(1) - (LOOKBACK - 1))
    assert checks.ok, checks.failures
    checks, band = check_step(config, record, names, reference)
    assert checks.ok and band[1] == 0.0, checks.failures
    sound = step_line(capfd)
    assert sound["over"] == [] and sound["windows"] == 1
    assert max(sound["output"], sound["loss"], sound["leaf"], sound["indexer_leaf"]) < 1e-4
    assert "indexer" in sound["worst_indexer_leaf"]
    honest = reference.layers_of

    def perturbed(estimator):
        layers = honest(estimator)
        w = layers["weights"]["layer_0"]["indexer"]["w"].copy()
        w[:, 0] = -w[:, 0] + 1.0  # one indexer head's weight: other keys are selected
        layers["weights"]["layer_0"]["indexer"]["w"] = w
        return layers

    monkeypatch.setattr(reference, "layers_of", perturbed)
    # other keys selected move the output by what a few keys of a softmax
    # weigh: past the CPU's tolerance and the step check's limit
    assert not check_forward(record, names, reference, "cpu").ok
    checks, band = check_step(config, record, names, reference)
    assert [f for f in checks.failures if "outside the reference band" in f], checks.failures
    assert "output" in step_line(capfd)["over"]


def test_dense_attention_in_place_of_the_selection_is_incorrect(one_job, monkeypatch, capfd):
    """A program that attends to every causal key (the selection left
    out "because T is small") no longer matches the reference: forward
    and step."""
    config, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    from gordo_tpu.models import backbone, training

    import jax.numpy as jnp

    def every_causal_key(spec, qi, ki, wi):
        blocks, chunk = qi.shape[:2]
        causal = jnp.tril(jnp.ones((blocks * chunk, blocks * chunk), bool))
        return backbone._pack_bits(causal.reshape(blocks * chunk, blocks, chunk)).reshape(
            blocks, chunk, blocks, -1
        )

    monkeypatch.setattr(backbone, "select_keys", every_causal_key)
    caches = (training.predict_fn, training.windowed_batch_loss_fn,
              training.windowed_loss_and_grad_norms_program)
    for cache in caches:
        cache.cache_clear()
    try:
        for platform in ("cpu", "tpu"):
            assert not check_forward(record, names, reference, platform).ok
        checks, band = check_step(config, record, names, reference)
    finally:
        monkeypatch.undo()
        for cache in caches:
            cache.cache_clear()
    assert [f for f in checks.failures if "outside the reference band" in f], checks.failures
    assert "output" in step_line(capfd)["over"]
    assert check_forward(record, names, reference).ok


def test_the_next_precision_below_reads_apart_from_a_sound_build(tmp_path_factory, capfd):
    """The control of the step check's limits, at toy widths: the same
    toy job with ``compute_dtype: bfloat16`` builds, fails the CPU's
    forward tolerance, and its step readings lie orders of magnitude
    above a float32 build's. The limits themselves are set at published
    widths on the chip (PERF.md, section 6)."""
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
    readings = step_line(capfd)
    print("toy control readings", {k: readings[k] for k in ("output", "loss", "leaf", "indexer_leaf_median", "over")})
    # past the limits the cell commits (the chip's readings set them), by more than one of them
    assert readings["limits"] == reference.STEP_LIMITS
    assert readings["output"] > reference.STEP_LIMITS["output"] and readings["loss"] > 1e-4
    assert readings["leaf"] > reference.STEP_LIMITS["leaf"] and len(readings["over"]) >= 2
    # limits under the readings: the band is empty, the run not correct
    X, y = np.zeros((108, 5), np.float32), np.ones((108, 5), np.float32)
    low, high = reference.loss_band(X, y, config, limits={"output": 1e-5})
    assert np.isnan(low) and np.isnan(high)


def test_a_checkout_without_the_kind_ends_the_build_child_at_once(tmp_path):
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
    factories = root / "gordo_tpu" / "models" / "factories"
    factories.mkdir(parents=True)
    (root / "gordo_tpu" / "models" / "backbone.py").write_text("")  # a backbone, as the parent has
    (factories / "backbone.py").write_text("def lfm2_moe(n_features):\n    ...\n")
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    assert manifest.problems(manifest.load_manifest(str(root)), str(root)) == []
    child = tmp_path / "build_worker.py"  # the child's name is what the reference looks at
    child.write_text(
        "import sys\n"
        f"sys.path[:0] = [{str(root / 'benchmarks' / 'chip')!r}]\n"
        "from harness.manifest import load_module\n"
        f"load_module({str(root)!r}, sys.argv[1], sys.argv[2])\n"
        "print('loaded')\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    def load(name):
        return subprocess.run(
            [sys.executable, str(child), "reference", name],
            capture_output=True, text=True, env=env, timeout=120,
        )

    refused = load("keye_sparse_backbone")
    assert refused.returncode == 5 and "loaded" not in refused.stdout
    assert "no kind keye_vl2" in refused.stderr
    assert load("lfm2_moe_backbone").returncode == 0  # the cell that was there still starts
    (factories / "backbone.py").write_text("def keye_vl2(n_features):\n    ...\n")
    loaded = load("keye_sparse_backbone")
    assert loaded.returncode == 0 and "loaded" in loaded.stdout
