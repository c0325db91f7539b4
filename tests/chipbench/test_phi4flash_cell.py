"""The cell ``phi4flash_ssm_build`` at toy sizes on the CPU stand-in: the
same child the chip runs, as a function of sizes (after
``test_kanana_cell.py``; the cell's own toy sizes are here). Every
assertion about the manifest is one a later cell leaves true:
membership, never position, never "the only ones", never "this table
holds exactly"."""

import json
import os

import numpy as np
import pytest

import build_worker
import common
import flops_backbone
import flops_hybrid_backbone
from harness import correct, manifest
from harness.data import history_rows, machine_names, machines_document
from jobs import read_spans, read_status
# the harness's calls on one artifact, as the cell before this one drives them
from test_kanana_cell import check_forward, check_step, rebuilt_program_is_incorrect, step_line
from tiny import CPU_DEVICE, quiet_start

CELL = "phi4flash_ssm_build"
CONFIG = "phi4-mini-flash-50tag-lb8192"
CUT = ["mamba", "sliding_attention", "mamba", "full_attention", "gmu", "cross_attention"]
#: 100 rows in tiles of 8 and chunks of 16: 13 blocks of queries (a full
#: or a cross layer visits 1 + 2 + .. + 13 = 91 tiles; a window of 24
#: rows reaches three tiles back: 1 + 2 + 3 + 10 x 4 = 46), 7 chunks of
#: scan, the last one short
LOOKBACK, TILE, CHUNK, WINDOW = 100, 8, 16, 24
HEADS, KV_HEADS, HIDDEN, WIDTH = 8, 4, 32, 48
SIZES = {"ssm_inner": 2 * HIDDEN, "ssm_state": 16, "ssm_conv": 4, "ssm_dt_rank": 2, "head_dim": HIDDEN // HEADS}

#: the estimator at toy widths: the cut's six layers at hidden 32, 8
#: query heads of 4 over 4 key/value heads (4 differential heads over 2
#: pairs, so two heads read one pair), an inner stream of 64
TOY_ESTIMATOR = {
    "kind": "phi4flash", "lookback_window": LOOKBACK, "num_hidden_layers": 6, "layer_types": CUT,
    "hidden_size": HIDDEN, "num_attention_heads": HEADS, "num_key_value_heads": KV_HEADS,
    "intermediate_size": WIDTH, "sliding_window": WINDOW, "epochs": 2, "batch_size": 32,
}


def toy_config(config: dict) -> dict:
    """The cell's configuration with toy widths wherever a reader or the
    worker looks: the estimator, and the keys ``flops_hybrid_backbone``
    reads."""
    (path, _), = config["estimator"].items()
    return dict(
        config,
        estimator={path: dict(TOY_ESTIMATOR)},
        tags=5, lookback_window=LOOKBACK, epochs=2, batch_size=32,
        hidden_size=HIDDEN, num_attention_heads=HEADS, num_key_value_heads=KV_HEADS,
        intermediate_size=WIDTH, sliding_window=WINDOW, assumed_sizes=dict(SIZES),
    )


def the_cell() -> manifest.Cell:
    return manifest.Cell(manifest.load_manifest(), CELL)


@pytest.fixture(scope="module", autouse=True)
def toy_blocks():
    """The tile and the chunk are the program's constants (512 and 256
    rows), not options of the estimator: every build of this module runs
    in the test's own process, where 100 rows take tiles of 8 and chunks
    of 16."""
    from gordo_tpu.models import backbone

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backbone, "ATTENTION_TILE", TILE)
        patch.setattr(backbone, "SCAN_CHUNK", CHUNK)
        yield


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    c = the_cell()
    run_dir = str(tmp_path_factory.mktemp(CELL))
    spec = {
        "cell": CELL, "chips": 1, "config": toy_config(c.config),
        "traffic": dict(c.traffic, history_days=1, verify_rows=108, trace_max_seconds=20),
        "seed": 2147483741, "seconds": 1.0, "trace": True, "run_dir": run_dir,
    }
    counter, errors = quiet_start()
    return spec, build_worker.run(spec, dict(CPU_DEVICE), counter, errors)


# ---------------------------------------------------------------------------
# the manifest: what the new entries say


def test_the_manifest_has_no_problems_with_the_cell():
    document = manifest.load_manifest()
    assert manifest.problems(document) == []
    c = manifest.Cell(document, CELL)
    assert c.entry["chips"] == 1 and c.traffic["kind"] == "build_jobs" and c.entry["config"] == CONFIG
    assert c.entry["traffic"] == "jobs-1x57d" and c.traffic["history_days"] == 57
    # 8,209 rows are 17 windows of 8,192 with the row each predicts
    assert history_rows(57) - c.config["lookback_window"] - c.config["lookahead"] + 1 == 17
    assert c.traffic["verify_rows"] - c.config["lookback_window"] == 2
    assert c.config["reduced"] == c.config_entry["reduced"] == ["num_hidden_layers"]
    assert {m["name"] for m in c.end_to_end} == {"models_built_per_hour", "setup_s"}
    reported = {m["name"] for m in c.per_layer}
    # every reader that lists kanana_mla_build but its own share of the
    # roofline, the two that read experts and the one that takes a fit
    # program by a router's counters (PERF.md 7 (q)), and this cell's own
    other = {m["name"] for m in manifest.Cell(document, "kanana_mla_build").per_layer}
    assert other - reported == {
        "latent_fit_mfu_pct", "moe_expert_imbalance_pct", "moe_local_pair_share_pct", "attention_pairs_wasted_pct",
    }
    assert reported - other == {"hybrid_fit_mfu_pct"}
    assert {"backbone_fit_step_ms", "build_dump_share_pct", "collect_gbps",
            "host_cores_busy", "host_rss_peak_gb", "hbm_peak_pct", "device_programs_per_job"} <= reported
    assert CELL in [w["name"] for w in document["workloads"]]
    assert CONFIG in [entry["name"] for entry in document["configs"]]
    assert len(c.entry["why"]) <= 200 and len(c.config_entry["why"]) <= 200 and len(c.config_entry["source"]) <= 200
    (entry,) = [m for m in document["per_layer"] if m["name"] == "hybrid_fit_mfu_pct"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "hybrid_fit_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "fused training programs", "moves": "models_built_per_hour",
    }
    assert CELL in entry["workloads"]
    # appended: the cell follows the cells that were there in every list it joined
    for metric in document["per_layer"] + document["end_to_end"]:
        cells = metric.get("workloads") or []
        if CELL in cells and "kanana_mla_build" in cells:
            assert cells.index(CELL) > cells.index("kanana_mla_build"), metric["name"]
    names = [w["name"] for w in document["workloads"]]
    assert names.index(CELL) > names.index("kanana_mla_build")


def test_what_the_configuration_file_states():
    c = the_cell()
    assert c.config_entry["source"] == c.config["source"] and c.config_entry["source"].endswith("/config.json")
    assert c.config["published"] == {"num_hidden_layers": 32} and c.config["num_hidden_layers"] == 6
    assert c.config["layer_types"] == CUT == c.config["estimator"]["gordo_tpu.models.JaxBackboneForecast"]["layer_types"]
    for group in ("deployment", "replaced", "left_out", "assumed"):
        assert c.config[group]
    assert "pipeline of whole layers" in c.config["deployment"] and "8 : 1 : 7" in c.config["deployment"]
    assert "vocab_size" in c.config["replaced"]
    assert set(c.config["assumed"]) >= {
        "scan_sizes", "scan", "memory", "differential_pair", "bias", "norms", "sliding_window", "positions",
        "initialisation", "state_before_a_window", "epochs", "batch_size", "lookback_window", "lookahead", "tags",
        "optimizer", "scan_chunk", "attention_tiles",
    }
    assert set(c.config["left_out"]) >= {
        "max_position_embeddings", "tie_word_embeddings", "lm_head_bias", "embd_pdrop, resid_pdrop",
        "num_key_value_heads",
    }
    # no width differs from the catalog row: the key that does is the cut of depth
    from gordo_tpu.models.factories.backbone import MAMBA_1_DEFAULTS, PHI_4_MINI_FLASH_CONFIG

    assert {k for k, v in PHI_4_MINI_FLASH_CONFIG.items() if c.config[k] != v} == set(c.config["reduced"])
    # and the sizes the row does not spell are Mamba-1's at this hidden size
    h = c.config["hidden_size"]
    assert c.config["assumed_sizes"] == {
        "ssm_inner": MAMBA_1_DEFAULTS["expand"] * h, "ssm_state": MAMBA_1_DEFAULTS["d_state"],
        "ssm_conv": MAMBA_1_DEFAULTS["d_conv"], "ssm_dt_rank": h // MAMBA_1_DEFAULTS["dt_rank_divisor"],
        "head_dim": h // c.config["num_attention_heads"],
    }


def test_the_configuration_holds_every_line_but_the_batch():
    """``test_manifest.py::test_config_entry_and_file`` for this
    configuration (``tests/conftest.py:MANIFEST_CASES_OUTGROWN``): every
    line of it but the last, which states another model's batch."""
    document = manifest.load_manifest()
    config = next(c for c in document["configs"] if c["name"] == CONFIG)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    for key in ("source", "why"):
        assert 1 <= len(config[key]) <= 200 and "\n" not in config[key]
    assert config["file"].startswith("benchmarks/chip/configs/")
    stated = manifest.load_json(manifest.ROOT, config["file"])
    assert stated["source"] == config["source"] and stated["reduced"] == config["reduced"]
    assert any(w["config"] == config["name"] for w in document["workloads"])
    assert (stated["batch_size"], stated["epochs"]) == (1, 1)  # the last line's 32 and 5
    from tests import conftest

    case = f"tests/chipbench/test_manifest.py::test_config_entry_and_file[{CONFIG}]"
    assert case in conftest.MANIFEST_CASES_OUTGROWN and conftest.OUTGROWN[case]


@pytest.mark.parametrize("name", ["fetch_cpu_parallelism", "fetch_resample_cpu_ms", "stack_gbps"])
def test_the_readers_this_cell_did_not_join_list_what_they_listed(name):
    """A job of one machine fetches, resamples and stacks next to
    nothing: the cell stays out of those three, as the backbone cells
    before it."""
    (entry,) = [m for m in manifest.load_manifest()["per_layer"] if m["name"] == name]
    assert CELL not in entry["workloads"] and "kanana_mla_build" not in entry["workloads"]


@pytest.mark.parametrize("name", ["latent_fit_mfu_pct", "moe_expert_imbalance_pct", "moe_local_pair_share_pct"])
def test_the_cell_stays_out_of_the_readers_of_what_it_has_not(name):
    (entry,) = [m for m in manifest.load_manifest()["per_layer"] if m["name"] == name]
    assert CELL not in entry["workloads"] and "kanana_mla_build" in entry["workloads"]


# ---------------------------------------------------------------------------
# the toy cell on the CPU stand-in


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
        assert len(job["programs"]) == 7  # and three predict-and-score programs
        assert job["status"]["fit_counters"] and len(job["status"]["fit_counters"]) == 4
        for p in fits:
            assert set(p["fit_counters"]) >= {
                "pairs_attended", "pairs_multiplied", "scan_steps", "steps_run",
                "ssm_inner", "ssm_state", "scan_chunk", "memory_width", "memory_reads", "kv_reads",
            }
            # no expert: none of the three that say which are held, no router's counts
            assert not {"num_experts", "experts_held", "expert_offset", "gate_active", "keys_selected"} & set(p)
            assert not {"pairs_here", "pairs_total", "router_tokens"} & set(p)
            assert len(p["pairs_attended"]) == 3 and len(p["scan_steps"]) == 2
    assert sum(bool(p["compile"]) for p in found["warm_job"]["programs"] if "fit" in p["program"]) == 1


def test_the_counters_of_the_toy_run(report):
    spec, found = report
    fits = [p for j in found["jobs"] for p in j["programs"] if "fit" in p["program"]]
    causal = LOOKBACK * (LOOKBACK + 1) / 2
    banded = WINDOW * (WINDOW + 1) / 2 + (LOOKBACK - WINDOW) * WINDOW
    for p in fits:
        # the windows trained, as the scans count them, are the tiles'
        windows, left = divmod(p["scan_steps"][0], LOOKBACK)
        assert left == 0 and windows > 0 and p["scan_steps"] == [windows * LOOKBACK] * 2
        assert p["pairs_attended"] == [windows * banded, windows * causal, windows * causal]
        assert p["pairs_multiplied"] == [windows * 46 * TILE * TILE] + [windows * 91 * TILE * TILE] * 2
        assert (p["ssm_inner"], p["ssm_state"], p["scan_chunk"], p["memory_width"]) == (2 * HIDDEN, 16, CHUNK, 2 * HIDDEN)
        assert p["memory_reads"] == [2] and p["kv_reads"] == [3]
    # a fold trains a share of the history's windows and the padded slots add none
    assert sum(p["scan_steps"][0] for p in fits[:4]) == LOOKBACK * 2 * flops_backbone.trained_windows(
        spec["config"], history_rows(1)
    )
    # the status file says the same, and ``build-status`` what the scan holds and who reads whom
    from gordo_tpu.telemetry.progress import render_status

    status = found["jobs"][0]["status"]
    assert (
        f"scan {2 * HIDDEN} x 16 in chunks of {CHUNK}; 1 layer reads layer 2's output, 1 reads layer 3's keys"
        in render_status(status)
    )


def test_every_listed_reader_reads_the_toy_run(report):
    spec, found = report
    c = the_cell()
    evidence = dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"])
    readers = c.readers()
    assert set(readers) == {m["name"] for m in c.per_layer} and "hybrid_fit_mfu_pct" in readers
    # the CPU has no device plane: what the trace alone can say is not there to read ...
    silent = {name for name, read in readers.items() if read(evidence) is None}
    assert {"hybrid_fit_mfu_pct", "backbone_fit_step_ms"} <= silent
    assert silent <= {"hybrid_fit_mfu_pct", "backbone_fit_step_ms", "device_idle_pct", "hbm_peak_pct"}
    assert readers["device_programs_per_job"](evidence) == 7 and readers["compiles_in_window"](evidence) == 0
    # the accepted reader of the tiles' waste takes a fit program by a
    # router's counters, which this program has none of (PERF.md 7 (q))
    assert "attention_pairs_wasted_pct" not in readers
    for name in ("collect_gbps", "host_cores_busy", "host_rss_peak_gb", "build_dump_share_pct"):
        assert readers[name](evidence) > 0, name
    # ... and with one, each reader divides by it
    job = found["jobs"][found["traced_job"]]
    timed = dict(evidence, trace={"devices": [{
        "modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": [],
    }]})
    useful = flops_hybrid_backbone.job_useful_fit_flops(spec["config"], history_rows(1), job["programs"])
    assert readers["hybrid_fit_mfu_pct"](timed) == pytest.approx(
        100.0 * useful / (2.0 * CPU_DEVICE["peaks"]["bf16_flops_per_s"])
    )
    ran = sum(p["steps_run"] for p in job["programs"] if "fit" in p["program"])
    assert readers["backbone_fit_step_ms"](timed) == pytest.approx(2000.0 / ran)


def test_the_new_reader_finds_nothing_in_a_program_without_the_counters(report):
    """The parent's program has no such kind and no such counter: nothing
    is read, nothing raises."""
    spec, found = report
    c = the_cell()
    gone = ("scan_steps", "fit_counters")
    stripped = [
        dict(job, programs=[{k: v for k, v in p.items() if k not in gone} for p in job["programs"]])
        for job in found["jobs"]
    ]
    trace = {"devices": [{"modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": []}]}
    evidence = dict(found, jobs=stripped, cell=c.entry, config=spec["config"], traffic=spec["traffic"], trace=trace)
    read = c.readers()["hybrid_fit_mfu_pct"]
    assert read(evidence) is None and read(dict(evidence, jobs=[])) is None
    # a backbone of another family (kanana_mla_build's configuration) reads the same way
    other = manifest.Cell(manifest.load_manifest(), "kanana_mla_build")
    assert read(dict(found, cell=c.entry, config=other.config, traffic=spec["traffic"], trace=trace)) is None
    # and the other families' readers find nothing of theirs in this one
    theirs = other.readers()["latent_fit_mfu_pct"]
    assert theirs(dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"], trace=trace)) is None


def test_flops_hybrid_backbone_against_a_hand_count():
    config = the_cell().config
    h, d = 2560, 5120
    mamba = 2 * (h * 2 * d + 4 * d + d * (160 + 32) + 160 * d + d * h)
    assert flops_hybrid_backbone.mixer_flops_per_token(config, "mamba") == mamba == 2 * 41_144_320
    assert flops_hybrid_backbone.mixer_flops_per_token(config, "gmu") == 2 * 26_214_400
    for op in ("sliding_attention", "full_attention"):
        assert flops_hybrid_backbone.mixer_flops_per_token(config, op) == 2 * (13_107_200 + 6_553_600)
    assert flops_hybrid_backbone.mixer_flops_per_token(config, "cross_attention") == 2 * 13_107_200
    assert flops_hybrid_backbone.attention_flops_per_pair(config) == 2 * 20 * 2 * (64 + 128) == 15_360
    assert flops_hybrid_backbone.scan_flops_per_row(config) == 7 * d * 16 + 3 * d == 588_800
    # 8,209 rows: 17 windows; folds train 5, 9, 13 of them, the final fit 17
    assert flops_backbone.trained_windows(config, 8209) == 5 + 9 + 13 + 17
    windows, causal, banded = 44, 8192 * 8193 // 2, 512 * 513 // 2 + (8192 - 512) * 512
    assert (causal, banded) == (33_558_528, 4_063_488)
    programs = [{
        "program": "fleet_windowed_fit", "scan_steps": [11.0 * 8192] * 2,
        "pairs_attended": [11.0 * banded, 11.0 * causal, 11.0 * causal],
        "pairs_multiplied": [11.0 * 31 * 512 * 512, 11.0 * 136 * 512 * 512, 11.0 * 136 * 512 * 512],
    }] * 4
    per_token = 2 * 50 * h + 2 * mamba + 2 * 39_321_600 + 52_428_800 + 26_214_400 + 6 * 6 * h * 10240
    by_hand = 3.0 * (
        per_token * windows * 8192
        + 15_360 * windows * (banded + 2 * causal)
        + 588_800 * windows * 2 * 8192
        + 2 * h * 50 * windows
    )
    assert flops_hybrid_backbone.job_useful_fit_flops(config, 8209, programs) == pytest.approx(by_hand)
    # a step of one window, forward: ISSUE 45's 10.37 T of products (7.73 T
    # of them the feed-forwards), 1.09 T of attended pairs, and scans that
    # no peak of products describes: 11.5 T forward, 34.4 T a step
    products, attention, scans = per_token * 8192, 15_360 * (banded + 2 * causal), 588_800 * 2 * 8192
    assert [round(part / 1e12, 2) for part in (products, 6 * 6 * h * 10240 * 8192, attention)] == [10.37, 7.73, 1.09]
    assert round(scans / 1e9, 2) == 9.65 and round((products + attention + scans) / 1e12, 1) == 11.5
    assert round(3 * (products + attention + scans) / 1e12, 1) == 34.4
    with pytest.raises(KeyError):
        flops_hybrid_backbone.job_useful_fit_flops(config, 8209, [{"program": "fleet_windowed_fit"}])
    with pytest.raises(ValueError):  # a row an attention layer, a row a mamba layer
        flops_hybrid_backbone.job_useful_fit_flops(config, 8209, [dict(programs[0], scan_steps=[1.0] * 3)])
    # the tiles' wasted share from the same counters, to the digits ISSUE 45 printed
    attended, multiplied = (sum(programs[0][name]) for name in ("pairs_attended", "pairs_multiplied"))
    assert (attended, multiplied) == (11.0 * 71_180_544, 11.0 * 79_429_632)
    assert round(100.0 * (1 - attended / multiplied), 3) == 10.385


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
    # ISSUE 45 wrote 633,327,282: its sum left out the final LayerNorm's 5,120 that its own addends list
    assert trained_param_count(shapes) == spec.param_count() == config["weights_per_member"] == 633_332_402
    assert spec.param_count() == 633_068_672 + 130_560 + 5_120 + 128_050
    assert list(spec.layer_ops) == CUT and spec.layer_ffns == ("dense",) * 6
    assert (spec.ssm_inner, spec.ssm_state, spec.ssm_conv, spec.ssm_dt_rank) == tuple(
        config["assumed_sizes"][k] for k in ("ssm_inner", "ssm_state", "ssm_conv", "ssm_dt_rank")
    )
    assert np.isclose(16 * spec.param_count() / 1e9, 10.13, atol=0.005)


# ---------------------------------------------------------------------------
# what ``correct`` holds a build to


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


def test_the_trace_says_what_the_scans_hold_beside_the_fit_program(one_job):
    from gordo_tpu.telemetry.trace_analysis import build_breakdown, render_analysis

    _, record, _ = one_job
    with open(os.path.join(record["output_dir"], "build_trace.jsonl")) as f:
        spans = [json.loads(line) for line in f]
    rendered = render_analysis({"trace": "t", "spans_read": len(spans), "build_breakdown": build_breakdown(spans)})
    said = f"scan {2 * HIDDEN} x 16 in chunks of {CHUNK}; 1 layer reads layer 2's output, 1 reads layer 3's keys"
    assert f"  program fleet_windowed_fit [validation_slots=0, {said}]" in rendered


def test_a_clean_job_passes_and_a_memory_taken_after_the_gate_does_not(one_job, monkeypatch, capfd):
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
    honest = reference.mamba

    def gated(u, w, sizes):  # M after the gate: another model
        out, y = honest(u, w, sizes)
        z = (u @ w["in_proj"])[..., sizes["ssm_inner"]:]
        return out, y * (z / (1 + np.e ** -z))

    monkeypatch.setattr(reference, "mamba", gated)
    assert not check_forward(record, names, reference, "cpu").ok
    checks, band = check_step(config, record, names, reference)
    assert [f for f in checks.failures if "outside the reference band" in f], checks.failures
    assert "output" in step_line(capfd)["over"]


def test_the_step_check_runs_the_programs_side_at_full_precision_too(one_job, monkeypatch, capfd):
    """On the TPU a float32 product at the default precision is one
    bfloat16 pass, and what the scan integrates of that over a window
    says where the build left the weights, not whether the program is
    right (``STEP_LIMITS``): the step check asks the program for its
    step and its forward under "highest", as it asks the reference."""
    import jax

    config, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    estimator = correct.find_estimator(correct.load_artifact(record["output_dir"], names[0])[0])
    seen = []
    for method in ("training_loss_and_grad_norms", "predict"):
        honest = getattr(type(estimator), method)

        def spy(self, *args, _honest=honest, _method=method, **kwargs):
            seen.append((_method, jax.config.jax_default_matmul_precision))
            return _honest(self, *args, **kwargs)

        monkeypatch.setattr(type(estimator), method, spy)
    checks, band = check_step(config, record, names, reference)
    assert checks.ok and band[1] == 0.0, checks.failures
    assert ("training_loss_and_grad_norms", "highest") in seen and ("predict", "highest") in seen
    assert step_line(capfd)["products_at"] == "highest"
    assert jax.config.jax_default_matmul_precision is None  # and leaves the process as it found it


def test_a_pair_of_heads_that_are_not_neighbours_is_incorrect(one_job, capfd):
    """Which two heads make a pair is a statement of the configuration:
    a program that pairs head ``j`` with head ``j + heads / 2`` (the
    first and the second half, as differential transformers elsewhere
    do) no longer matches the reference, forward and step."""
    from gordo_tpu.models import backbone

    honest = backbone._heads

    def halves(spec, w, u, op="full_attention", kv=None):
        q, k, v = honest(spec, w, u, op, kv)
        heads = q.shape[2]
        order = np.arange(heads).reshape(2, heads // 2).T.reshape(-1)  # 0, h/2, 1, h/2 + 1, ..
        return q[:, :, order], k, v

    rebuilt_program_is_incorrect(one_job, capfd, lambda patch: patch.setattr(backbone, "_heads", halves))


def test_a_state_carried_into_a_chunk_twice_is_incorrect(one_job, capfd):
    """The chunks are blocks of the computation: a scan whose chunks
    each start from zero (no state handed from chunk to chunk) is
    another model at any window longer than a chunk."""
    from gordo_tpu.models import backbone

    honest = backbone._selective_scan

    def forgetful(x, dt, a, b, c):
        import jax

        return jax.vmap(lambda *rows: honest(*(r[None] for r in rows[:2]), a, *(r[None] for r in rows[2:]))[0],
                        in_axes=(0, 0, 0, 0))(x, dt, b, c)

    rebuilt_program_is_incorrect(one_job, capfd, lambda patch: patch.setattr(backbone, "_selective_scan", forgetful))
