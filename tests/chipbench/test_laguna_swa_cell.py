"""The cell ``laguna_swa_build`` at toy sizes on the CPU stand-in: the
same child the chip runs, as a function of sizes (after
``test_keye_dsa_cell.py``; the cell's own toy sizes are here)."""

import json
import os

import numpy as np
import pytest

import build_worker
import common
import flops_backbone
import flops_banded_backbone
from harness import correct, manifest
from harness.data import history_rows, machine_names, machines_document
from jobs import read_spans, read_status
from tiny import CPU_DEVICE, quiet_start

CELL = "laguna_swa_build"
CONFIG = "laguna-xs2-50tag-lb8192"
LOOKBACK, WINDOW, TILE = 100, 24, 32

#: the estimator at toy widths: the five layers of the cut (full, three
#: sliding, full) with 6 and 8 heads of 16, a window of 24 of 100 rows in
#: tiles of 32, 2 of 8 experts held beside a shared expert
TOY_ESTIMATOR = {
    "kind": "laguna", "lookback_window": LOOKBACK, "num_hidden_layers": 5,
    "hidden_size": 32, "head_dim": 16, "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "num_key_value_heads": 2, "intermediate_size": 48, "moe_intermediate_size": 24,
    "shared_expert_intermediate_size": 20, "num_experts": 8, "experts_held": 2, "expert_offset": 2,
    "num_experts_per_tok": 2, "sliding_window": WINDOW,
    "rope_parameters": {"full_attention": {"original_max_position_embeddings": 16}},
    "epochs": 2, "batch_size": 32,
}


def toy_config(config: dict) -> dict:
    """The cell's configuration with toy widths wherever a reader or the
    worker looks: the estimator, and the keys ``flops_banded_backbone``
    reads."""
    (path, _), = config["estimator"].items()
    return dict(
        config,
        estimator={path: dict(TOY_ESTIMATOR)},
        tags=5, lookback_window=LOOKBACK, epochs=2, batch_size=32,
        hidden_size=32, head_dim=16, num_key_value_heads=2, num_attention_heads_per_layer=[6, 8, 8, 8, 6],
        intermediate_size=48, moe_intermediate_size=24, shared_expert_intermediate_size=20,
        num_experts_per_tok=2, num_hidden_layers=5, published={"num_hidden_layers": 40, "num_experts": 8},
    )


def the_cell() -> manifest.Cell:
    return manifest.Cell(manifest.load_manifest(), CELL)


def attended_by_arithmetic(length=LOOKBACK, window=WINDOW):
    return sum(min(t + 1, window) for t in range(length))


def tiles_by_arithmetic(length=LOOKBACK, window=WINDOW, tile=TILE):
    back = -(-(window - 1) // tile)
    return sum(min(i, back) + 1 for i in range(-(-length // tile)))


@pytest.fixture(scope="module", autouse=True)
def tiles_of_32():
    """The tile is the program's constant (512 rows), not an option of
    the estimator: every build of this module runs in the test's own
    process, where 100 rows take tiles of 32."""
    from gordo_tpu.models import backbone

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backbone, "ATTENTION_TILE", TILE)
        yield


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


@pytest.mark.parametrize("cell,config,own", [
    (CELL, CONFIG, {"banded_fit_mfu_pct", "attention_pairs_wasted_pct"}),
    ("keye_dsa_build", "keye-vl2-30b-a3b-50tag-lb8192", {"sparse_fit_mfu_pct"}),
])
def test_the_manifest_has_no_problems_with_the_cell(cell, config, own):
    """The new cell, and the lines of ``test_keye_dsa_cell.py``'s test of
    the same name that a later cell leaves true (its two last lines, that
    the cell and its configuration are the manifest's last, are the new
    cell's now: ``tests/conftest.py:LAST_ENTRY_CASE_OUTGROWN``)."""
    document = manifest.load_manifest()
    assert manifest.problems(document) == []
    c = manifest.Cell(document, cell)
    assert c.entry["chips"] == 1 and c.traffic["kind"] == "build_jobs" and c.entry["config"] == config
    assert c.entry["traffic"] == "jobs-1x57d" and c.traffic["history_days"] == 57
    # 8,209 rows are 17 windows of 8,192 with the row each predicts
    assert history_rows(57) - c.config["lookback_window"] - c.config["lookahead"] + 1 == 17
    assert c.traffic["verify_rows"] - c.config["lookback_window"] == 2
    assert c.config["reduced"] == ["num_hidden_layers", "num_experts"]
    assert {m["name"] for m in c.end_to_end} == {"models_built_per_hour", "setup_s"}
    reported = {m["name"] for m in c.per_layer}
    assert reported >= own | {
        "backbone_fit_step_ms", "moe_expert_imbalance_pct", "moe_local_pair_share_pct",
        "hbm_peak_pct", "device_idle_pct", "compiles_in_window",
    }
    # ... every per-layer metric of lfm2_moe_build but the one that counts LFM2's shapes
    other = {m["name"] for m in manifest.Cell(document, "lfm2_moe_build").per_layer}
    assert other - reported == {"backbone_fit_mfu_pct"} and reported - other == own
    assert not {"fit_mfu_pct", "fit_step_ms"} & reported
    # a cell's own readers list it alone; nothing that was there lost a cell
    for name in own:
        assert [m["workloads"] for m in document["per_layer"] if m["name"] == name] == [[cell]]
    assert [w["name"] for w in document["workloads"]][-2:] == ["keye_dsa_build", CELL]
    assert [c["name"] for c in document["configs"]][-2:] == ["keye-vl2-30b-a3b-50tag-lb8192", CONFIG]
    # both backbone cells take the one traffic file, untouched: they differ in the architecture alone
    assert len(c.entry["why"]) <= 200 and len(c.config_entry["why"]) <= 200


@pytest.mark.parametrize("name", ["keye-vl2-30b-a3b-50tag-lb8192", CONFIG])
def test_the_manifest_cases_expected_to_fail_fail_on_their_last_line_alone(name):
    """``test_manifest.py::test_config_entry_and_file`` for the two
    configurations of 8,192-row windows
    (``tests/conftest.py:MANIFEST_CASES_OUTGROWN``): every line of it but
    the last, which states another model's batch."""
    document = manifest.load_manifest()
    config = next(c for c in document["configs"] if c["name"] == name)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    for key in ("source", "why"):
        assert 1 <= len(config[key]) <= 200 and "\n" not in config[key]
    assert config["file"].startswith("benchmarks/chip/configs/")
    stated = manifest.load_json(manifest.ROOT, config["file"])
    assert stated["source"] == config["source"] and stated["reduced"] == config["reduced"]
    assert any(w["config"] == config["name"] for w in document["workloads"])
    assert (stated["batch_size"], stated["epochs"]) == (2, 1)  # the last line's 32 and 5
    # and they are the only configurations the tree expects to fail there
    from tests import conftest

    assert [case.split("[")[1].rstrip("]") for case in conftest.MANIFEST_CASES_OUTGROWN] == [
        "keye-vl2-30b-a3b-50tag-lb8192", CONFIG,
    ]
    assert set(conftest.OUTGROWN) == set(conftest.MANIFEST_CASES_OUTGROWN) | {conftest.LAST_ENTRY_CASE_OUTGROWN}


def test_the_last_entry_case_expected_to_fail_fails_on_its_last_two_lines_alone():
    """``test_keye_dsa_cell.py::test_the_manifest_has_no_problems_with_the_cell``
    (``tests/conftest.py:LAST_ENTRY_CASE_OUTGROWN``, an expected failure
    that ISSUE 33 did not name: ``PERF.md`` 7 (f)): its own lines, run
    here but for the two that say its cell and configuration are the
    manifest's last, hold; those two are the new cell's now, and the
    parametrised copy above states every one of the others."""
    import inspect
    import textwrap

    import test_keye_dsa_cell as keye

    lines = inspect.getsource(keye.test_the_manifest_has_no_problems_with_the_cell).rstrip().splitlines()
    body, last = lines[1:-2], [line.strip() for line in lines[-2:]]
    assert last == [
        'assert [w["name"] for w in document["workloads"]][-1] == CELL',
        'assert [c["name"] for c in document["configs"]][-1] == "keye-vl2-30b-a3b-50tag-lb8192"',
    ]
    assert sum(line.strip().startswith("assert ") for line in body) == 11
    exec(textwrap.dedent("\n".join(body)), dict(vars(keye)))  # raises where a line no longer holds
    document = manifest.load_manifest()
    for line in last:  # and the two lines are what fails
        with pytest.raises(AssertionError):
            exec(line, dict(vars(keye), document=document))
    # every assertion of the body is one of the copy's, to the letter or with the cell's name a parameter
    copy = inspect.getsource(test_the_manifest_has_no_problems_with_the_cell)
    said = [line.strip() for line in body if line.strip().startswith("assert ")]
    own = {
        'assert c.entry["chips"] == 1 and c.traffic["kind"] == "build_jobs"': 'assert c.entry["chips"] == 1 and c.traffic["kind"] == "build_jobs" and',
        "assert reported >= {": "assert reported >= own | {",
        'assert other - reported == {"backbone_fit_mfu_pct"}': 'assert other - reported == {"backbone_fit_mfu_pct"} and',
        'assert [m["workloads"] for m in document["per_layer"] if m["name"] == "sparse_fit_mfu_pct"] == [[CELL]]':
            'assert [m["workloads"] for m in document["per_layer"] if m["name"] == name] == [[cell]]',
    }
    for line in said:
        assert own.get(line, line) in copy, line


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
                "pairs_attended", "pairs_multiplied", "router_tokens", "pairs_here", "pairs_total",
                "steps_run", "num_experts",
            }
            assert len(p["pairs_attended"]) == 5 and len(p["pairs_here"]) == 4
    assert sum(bool(p["compile"]) for p in found["warm_job"]["programs"] if "fit" in p["program"]) == 1


def test_the_new_readers_and_the_counters_of_the_toy_run(report):
    spec, found = report
    c = the_cell()
    evidence = dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"])
    readers = c.readers()
    assert 0 < readers["moe_local_pair_share_pct"](evidence) < 100
    assert readers["moe_expert_imbalance_pct"](evidence) >= 0
    fits = [p for j in found["jobs"] for p in j["programs"] if "fit" in p["program"]]
    full, sliding = LOOKBACK * (LOOKBACK + 1) / 2, attended_by_arithmetic()
    full_tiles, sliding_tiles = tiles_by_arithmetic(window=LOOKBACK), tiles_by_arithmetic()
    assert (full_tiles, sliding_tiles) == (10, 7)
    for p in fits:
        # the windows trained, as the expert layer counts them, are the band's
        windows, left = divmod(p["pairs_total"][0], LOOKBACK * 2)
        assert left == 0 and windows > 0
        assert p["pairs_attended"] == [windows * full] + [windows * sliding] * 3 + [windows * full]
        tiles = [full_tiles] + [sliding_tiles] * 3 + [full_tiles]
        assert p["pairs_multiplied"] == [windows * n * TILE * TILE for n in tiles]
    wasted = 100.0 * (1 - (2 * full + 3 * sliding) / ((2 * full_tiles + 3 * sliding_tiles) * TILE * TILE))
    assert readers["attention_pairs_wasted_pct"](evidence) == pytest.approx(wasted)
    # the CPU has no device plane: the fit time is not there to read ...
    for name in ("banded_fit_mfu_pct", "backbone_fit_step_ms"):
        assert readers[name](evidence) is None
    # ... and with one, each reader divides by it
    job = found["jobs"][found["traced_job"]]
    timed = dict(evidence, trace={"devices": [{
        "modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": [],
    }]})
    useful = flops_banded_backbone.job_useful_fit_flops(spec["config"], history_rows(1), job["programs"])
    assert readers["banded_fit_mfu_pct"](timed) == pytest.approx(
        100.0 * useful / (2.0 * CPU_DEVICE["peaks"]["bf16_flops_per_s"])
    )
    ran = sum(p["steps_run"] for p in job["programs"] if "fit" in p["program"])
    assert readers["backbone_fit_step_ms"](timed) == pytest.approx(2000.0 / ran)


def test_the_new_readers_find_nothing_in_a_program_without_the_counters(report):
    """The parent's program has no such counter: nothing is read, nothing raises."""
    spec, found = report
    c = the_cell()
    gone = ("pairs_attended", "pairs_multiplied", "fit_counters")
    stripped = [
        dict(job, programs=[{k: v for k, v in p.items() if k not in gone} for p in job["programs"]])
        for job in found["jobs"]
    ]
    evidence = dict(
        found, jobs=stripped, cell=c.entry, config=spec["config"], traffic=spec["traffic"],
        trace={"devices": [{"modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": []}]},
    )
    for name in ("banded_fit_mfu_pct", "attention_pairs_wasted_pct"):
        assert c.readers()[name](evidence) is None
        # a backbone without the operator (keye_dsa_build's evidence) reads the same way
        keye = manifest.Cell(manifest.load_manifest(), "keye_dsa_build")
        assert c.readers()[name](dict(evidence, config=keye.config)) is None
        assert c.readers()[name](dict(evidence, jobs=[])) is None


def test_flops_banded_backbone_against_a_hand_count():
    config = the_cell().config
    h = 2048
    assert flops_banded_backbone.held_layers(config) == [
        ("full_attention", "dense", 48), ("sliding_attention", "sparse", 64), ("sliding_attention", "sparse", 64),
        ("sliding_attention", "sparse", 64), ("full_attention", "sparse", 48),
    ]
    full = 2 * h * (2 * 48 * 128 + 2 * 1024 + 48)
    sliding = 2 * h * (2 * 64 * 128 + 2 * 1024 + 64)
    assert flops_banded_backbone.projection_flops_per_token(config, 48) == full
    assert flops_banded_backbone.projection_flops_per_token(config, 64) == sliding
    assert flops_banded_backbone.attention_flops_per_pair(config, 64) == 4 * 64 * 128
    assert flops_banded_backbone.feed_forward_flops_per_token(config, "dense") == 6 * h * 8192
    assert flops_banded_backbone.feed_forward_flops_per_token(config, "sparse") == 2 * h * 256 + 6 * h * 512
    assert flops_backbone.pair_flops(config) == 6 * h * 512
    # 8,209 rows: 17 windows; folds train 5, 9, 13 of them, the final fit 17
    assert flops_backbone.trained_windows(config, 8209) == 5 + 9 + 13 + 17
    windows, causal, band = 44, 8192 * 8193 // 2, attended_by_arithmetic(8192, 512)
    assert (causal, band) == (33_558_528, 4_063_488)
    programs = [{
        "program": "fleet_windowed_fit", "pairs_here": [1000.0, 2000, 3000, 4000], "pairs_total": [8000] * 4,
        "pairs_attended": [11.0 * causal] + [11.0 * band] * 3 + [11.0 * causal],
        "pairs_multiplied": [11.0 * 136 * 512 * 512] + [11.0 * 31 * 512 * 512] * 3 + [11.0 * 136 * 512 * 512],
    }] * 4
    per_token = 2 * 50 * h + 2 * full + 3 * sliding + 6 * h * 8192 + 4 * (2 * h * 256 + 6 * h * 512)
    by_hand = 3.0 * (
        per_token * windows * 8192
        + 4 * 48 * 128 * 2 * windows * causal + 4 * 64 * 128 * 3 * windows * band
        + 6 * h * 512 * 4 * 10000
        + 2 * h * 50 * windows
    )
    assert flops_banded_backbone.job_useful_fit_flops(config, 8209, programs) == pytest.approx(by_hand)
    # a step of 2 windows at even routing (16 of 256 experts: half a pair
    # a token a layer): 36.3 TFLOP useful, 34% of them the attention's pairs
    attention = 4 * 48 * 128 * 2 * 2 * causal + 4 * 64 * 128 * 3 * 2 * band
    step = 3.0 * (per_token * 16384 + attention + 6 * h * 512 * 4 * 8192 + 2 * h * 50 * 2)
    assert 36.0e12 < step < 36.5e12 and 0.33 < 3.0 * attention / step < 0.35
    with pytest.raises(KeyError):
        flops_banded_backbone.job_useful_fit_flops(config, 8209, [{"program": "fleet_windowed_fit"}])
    with pytest.raises(ValueError):  # a row a layer held, or the rows are another program's
        flops_banded_backbone.job_useful_fit_flops(
            config, 8209, [dict(programs[0], pairs_attended=[1.0], pairs_multiplied=[2.0])]
        )
    # the expert layer's counters alone (an lfm2_moe program) are not the band's
    assert flops_banded_backbone.fit_counters(
        [{"program": "fleet_windowed_fit", "pairs_here": [1], "pairs_total": [4]}]
    ) == []
    # the wasted share by hand: half of a sliding layer's tiles, 5.9% of a full one's
    evidence = {"jobs": [{"programs": programs[:1]}]}
    wasted = 100.0 * (1 - (2 * causal + 3 * band) / ((2 * 136 + 3 * 31) * 512 * 512))
    assert the_cell().readers()["attention_pairs_wasted_pct"](evidence) == pytest.approx(wasted)
    assert 17.0 < wasted < 17.5


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
    assert trained_param_count(shapes) == spec.param_count() == config["weights_per_member"] == 439_124_018
    held = config["num_hidden_layers"]
    assert spec.layer_ops == tuple(config["layer_types"][:held]) and len(spec.layer_ops) == held == 5
    assert spec.layer_ffns == tuple("dense" if kind == "dense" else "moe" for kind in config["mlp_layer_types"][:held])
    assert spec.layer_heads == tuple(config["num_attention_heads_per_layer"][:held]) == (48, 64, 64, 64, 48)
    for key in ("hidden_size", "head_dim", "intermediate_size", "moe_intermediate_size", "sliding_window",
                "shared_expert_intermediate_size", "num_key_value_heads", "num_experts_per_tok", "lookback_window"):
        assert getattr(spec, key) == config[key], key
    assert (spec.norm_eps, spec.routed_scaling_factor) == (config["rms_norm_eps"], config["moe_routed_scaling_factor"])
    for op in ("full_attention", "sliding_attention"):
        assert spec.rope_of(op) == config["rope_parameters"][op]
    assert spec.num_experts == config["published"]["num_experts"] == 256
    assert spec.experts_held == config["num_experts"] == config["experts_held"] == 16
    assert estimator.kwargs["batch_size"] == config["batch_size"] == 2
    assert estimator.kwargs["epochs"] == config["epochs"] == 1
    assert set(config["assumed"]) >= {"gate", "q_k_norm", "yarn", "router", "shared_expert", "epochs", "lookback_window"}


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


def test_a_clean_job_passes_and_a_perturbed_gate_weight_does_not(one_job, monkeypatch, capfd):
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
    assert max(sound["output"], sound["loss"], sound["leaf"], sound["grad_norm"]) < 1e-4
    honest = reference.layers_of

    def perturbed(estimator):
        layers = honest(estimator)
        gate = layers["weights"]["layer_4"]["attn"]["gate"].copy()
        gate[:, 0] = -gate[:, 0] + 1.0  # one head's gate in the last layer: that head weighs otherwise
        layers["weights"]["layer_4"]["attn"]["gate"] = gate
        return layers

    monkeypatch.setattr(reference, "layers_of", perturbed)
    assert not check_forward(record, names, reference, "cpu").ok
    checks, band = check_step(config, record, names, reference)
    assert [f for f in checks.failures if "outside the reference band" in f], checks.failures
    assert "output" in step_line(capfd)["over"]


def test_sliding_layers_that_attend_to_every_causal_key_are_incorrect(one_job, monkeypatch, capfd):
    """A program whose sliding layers see the whole causal past (the
    window left out "because the tiles mask anyway") no longer matches
    the reference: forward and step."""
    config, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    from gordo_tpu.models import backbone, training

    honest = backbone._banded_attention

    def every_causal_key(scope, window, q, k, v):
        return honest(scope, q.shape[0] * q.shape[1], q, k, v)

    monkeypatch.setattr(backbone, "_banded_attention", every_causal_key)
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
    print("toy control readings", {k: readings[k] for k in ("output", "loss", "leaf", "over")})
    # past the limits the cell commits (the chip's readings set them), by each of them
    assert readings["limits"] == reference.STEP_LIMITS
    assert all(readings[key] > limit for key, limit in reference.STEP_LIMITS.items())
    assert sorted(readings["over"]) == ["grad_norm", "leaf", "loss", "output"]
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
    (factories / "backbone.py").write_text("def lfm2_moe(n_features):\n    ...\n\n\ndef keye_vl2(n_features):\n    ...\n")
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

    refused = load("laguna_banded_backbone")
    assert refused.returncode == 5 and "loaded" not in refused.stdout
    assert "no kind laguna" in refused.stderr
    for there in ("lfm2_moe_backbone", "keye_sparse_backbone"):  # the cells that were there still start
        assert load(there).returncode == 0
    (factories / "backbone.py").write_text("def laguna(n_features):\n    ...\n")
    loaded = load("laguna_banded_backbone")
    assert loaded.returncode == 0 and "loaded" in loaded.stdout
