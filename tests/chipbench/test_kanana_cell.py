"""The cell ``kanana_mla_build`` at toy sizes on the CPU stand-in: the
same child the chip runs, as a function of sizes (after
``test_smallthinker_cell.py``; the cell's own toy sizes are here). Every
assertion about the manifest is one a later cell leaves true:
membership, never position, never "the only ones", never "this table
holds exactly"."""

import copy
import inspect
import json
import os
import textwrap

import numpy as np
import pytest

import build_worker
import common
import flops_backbone
import flops_latent_backbone
from harness import correct, manifest
from harness.data import history_rows, machine_names, machines_document
from jobs import read_spans, read_status
from tiny import CPU_DEVICE, quiet_start

CELL = "kanana_mla_build"
CONFIG = "kanana-2-30b-a3b-50tag-lb8192"
#: every layer attends to every causal row: 100 rows in tiles of 8, 13
#: blocks of queries that visit 1 + 2 + .. + 13 = 91 tiles
LOOKBACK, TILE = 100, 8
LAYERS, HELD, OFFSET, TOP_K, WIDTH = 3, 4, 12, 3, 12
HEADS, RANK, NOPE, ROPE, VALUE = 4, 8, 4, 4, 4

#: the estimator at toy widths: a leading dense layer and two routed
#: ones, 4 heads whose scores are 8 wide (4 + 4 rotary: two pairs, so
#: that the interleaved layout and the half-rotation differ) and whose
#: values are 4 wide off a latent of 8, 4 of 16 experts held, 3 a token
TOY_ESTIMATOR = {
    "kind": "kanana", "lookback_window": LOOKBACK, "num_hidden_layers": LAYERS,
    "hidden_size": 32, "num_attention_heads": HEADS, "kv_lora_rank": RANK, "qk_nope_head_dim": NOPE,
    "qk_rope_head_dim": ROPE, "v_head_dim": VALUE, "intermediate_size": 48, "moe_intermediate_size": WIDTH,
    "n_routed_experts": 16, "experts_held": HELD, "expert_offset": OFFSET, "num_experts_per_tok": TOP_K,
    "rope_theta": 100.0, "epochs": 2, "batch_size": 32,
}


def toy_config(config: dict) -> dict:
    """The cell's configuration with toy widths wherever a reader or the
    worker looks: the estimator, and the keys ``flops_latent_backbone``
    reads."""
    (path, _), = config["estimator"].items()
    return dict(
        config,
        estimator={path: dict(TOY_ESTIMATOR)},
        tags=5, lookback_window=LOOKBACK, epochs=2, batch_size=32,
        hidden_size=32, num_attention_heads=HEADS, kv_lora_rank=RANK, qk_nope_head_dim=NOPE,
        qk_rope_head_dim=ROPE, v_head_dim=VALUE, intermediate_size=48, moe_intermediate_size=WIDTH,
        num_experts_per_tok=TOP_K, num_hidden_layers=LAYERS,
        published={"num_hidden_layers": 48, "n_routed_experts": 16},
    )


def the_cell() -> manifest.Cell:
    return manifest.Cell(manifest.load_manifest(), CELL)


@pytest.fixture(scope="module", autouse=True)
def tiles_of_8():
    """The tile is the program's constant (512 rows), not an option of
    the estimator: every build of this module runs in the test's own
    process, where 100 rows take tiles of 8."""
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
        "seed": 2147483693, "seconds": 1.0, "trace": True, "run_dir": run_dir,
    }
    counter, errors = quiet_start()
    return spec, build_worker.run(spec, dict(CPU_DEVICE), counter, errors)


# ---------------------------------------------------------------------------
# the manifest: what the new entries say, and the lines of the benchmark's own
# tests that they outgrow, restated in the form that stays true


def test_the_manifest_has_no_problems_with_the_cell():
    document = manifest.load_manifest()
    assert manifest.problems(document) == []
    c = manifest.Cell(document, CELL)
    assert c.entry["chips"] == 1 and c.traffic["kind"] == "build_jobs" and c.entry["config"] == CONFIG
    assert c.entry["traffic"] == "jobs-1x57d" and c.traffic["history_days"] == 57
    # 8,209 rows are 17 windows of 8,192 with the row each predicts
    assert history_rows(57) - c.config["lookback_window"] - c.config["lookahead"] + 1 == 17
    assert c.traffic["verify_rows"] - c.config["lookback_window"] == 2
    assert c.config["reduced"] == c.config_entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert {m["name"] for m in c.end_to_end} == {"models_built_per_hour", "setup_s"}
    reported = {m["name"] for m in c.per_layer}
    # every reader that lists smallthinker_build but its own share of the roofline, and this cell's
    other = {m["name"] for m in manifest.Cell(document, "smallthinker_build").per_layer}
    assert other - reported == {"prerouted_fit_mfu_pct"} and reported - other == {"latent_fit_mfu_pct"}
    assert not {"fit_mfu_pct", "fit_step_ms", "backbone_fit_mfu_pct", "sparse_fit_mfu_pct", "banded_fit_mfu_pct"} & reported
    assert CELL in [w["name"] for w in document["workloads"]]
    assert CONFIG in [entry["name"] for entry in document["configs"]]
    assert len(c.entry["why"]) <= 200 and len(c.config_entry["why"]) <= 200 and len(c.config_entry["source"]) <= 200
    (entry,) = [m for m in document["per_layer"] if m["name"] == "latent_fit_mfu_pct"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "latent_fit_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "fused training programs", "moves": "models_built_per_hour",
    }
    assert CELL in entry["workloads"]
    # appended: the cell follows the cells that were there in every list it joined
    for metric in document["per_layer"] + document["end_to_end"]:
        cells = metric.get("workloads") or []
        if CELL in cells and "smallthinker_build" in cells:
            assert cells.index(CELL) > cells.index("smallthinker_build"), metric["name"]
    names = [w["name"] for w in document["workloads"]]
    assert names.index(CELL) > names.index("smallthinker_build")


def test_what_the_configuration_file_states():
    c = the_cell()
    assert c.config_entry["source"] == c.config["source"] and c.config_entry["source"].endswith("/config.json")
    assert c.config["published"] == {"num_hidden_layers": 48, "n_routed_experts": 128}
    assert (c.config["num_hidden_layers"], c.config["n_routed_experts"]) == (5, 16)
    assert (c.config["experts_held"], c.config["expert_offset"]) == (16, 0)
    for group in ("deployment", "replaced", "left_out", "assumed"):
        assert c.config[group]
    assert "eight chips" in c.config["deployment"] and "vocab_size" in c.config["replaced"]
    assert set(c.config["assumed"]) >= {
        "shared_experts", "router", "bias_buffer", "router_epsilon", "kv_a_layernorm", "rotary", "attention_scale",
        "q_k_norm", "bias", "initialisation", "epochs", "batch_size", "lookback_window", "lookahead", "tags",
        "optimizer", "attention_tiles",
    }
    assert set(c.config["left_out"]) >= {
        "max_position_embeddings", "tie_word_embeddings", "num_key_value_heads", "head_dim", "multi-token prediction",
    }
    # no width differs from the catalog row: the keys that do are the two cuts of scale
    from gordo_tpu.models.factories.backbone import KANANA_2_30B_A3B_CONFIG

    assert {k for k, v in KANANA_2_30B_A3B_CONFIG.items() if c.config[k] != v} == set(c.config["reduced"])


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
    assert (stated["batch_size"], stated["epochs"]) == (2, 1)  # the last line's 32 and 5
    from tests import conftest

    case = f"tests/chipbench/test_manifest.py::test_config_entry_and_file[{CONFIG}]"
    assert case in conftest.MANIFEST_CASES_OUTGROWN and conftest.OUTGROWN[case]


#: line 306 of ``test_smallthinker_cell.py``: the last name of a joined
#: reader's ``workloads`` is ``smallthinker_build``
POP = 'assert entry["workloads"].pop() == CELL'
#: ... in the form that stays true: this cell and the cells appended
#: after it, whichever they are, are taken off
TAKEN_OFF = 'del entry["workloads"][entry["workloads"].index(CELL):]'


@pytest.mark.parametrize("name", ["collect_gbps", "host_cores_busy", "host_rss_peak_gb"])
def test_the_three_marked_cases_of_the_joined_readers_fail_on_line_306_alone(name):
    """``test_smallthinker_cell.py::test_the_six_cases_of_the_appended_readers_fail_on_the_named_lines_alone``
    for the three readers whose lists this cell joined
    (``tests/conftest.py:JOINED_CASES_OUTGROWN``): every line of the
    case holds once line 306 takes off ``smallthinker_build`` *and the
    cells appended after it* (the entry then equals ISSUE 37's table to
    the letter, the six readers follow ``build_dump_share_pct`` in the
    table's order, ``prerouted_fit_mfu_pct`` comes after them); as it
    stands the line pops this cell's name and fails."""
    import test_smallthinker_cell as small

    marked = small.test_the_six_cases_of_the_appended_readers_fail_on_the_named_lines_alone
    lines = textwrap.dedent(inspect.getsource(marked)).splitlines()
    start = next(i for i, line in enumerate(lines) if line.startswith("def ")) + 1
    body = textwrap.dedent("\n".join(lines[start:]))
    assert body.count(POP) == 1 and name in small.HOST_READERS_JOINED
    scope = dict(vars(small), name=name)
    exec(body.replace(POP, TAKEN_OFF), scope)  # raises where another line no longer holds
    # what it held the manifest to, less the cells appended from smallthinker_build on
    import test_host_accounting as host

    (entry,) = [m for m in scope["document"]["per_layer"] if m["name"] == name]
    assert entry["workloads"] == host.TABLE[name][3]
    (whole,) = [m for m in manifest.load_manifest()["per_layer"] if m["name"] == name]
    appended = whole["workloads"][len(entry["workloads"]):]
    assert appended[0] == small.CELL and CELL in appended[1:]
    # line 306 as it stands: the last name is no longer smallthinker_build's
    document = copy.deepcopy(host.MANIFEST)
    (entry,) = [m for m in document["per_layer"] if m["name"] == name]
    with pytest.raises(AssertionError):
        exec(POP, {"entry": entry, "CELL": small.CELL})
    from tests import conftest

    case = (
        "tests/chipbench/test_smallthinker_cell.py::"
        f"test_the_six_cases_of_the_appended_readers_fail_on_the_named_lines_alone[{name}]"
    )
    assert case in conftest.JOINED_CASES_OUTGROWN and conftest.OUTGROWN[case]


@pytest.mark.parametrize("name", ["fetch_cpu_parallelism", "fetch_resample_cpu_ms", "stack_gbps"])
def test_the_readers_this_cell_did_not_join_list_what_they_listed(name):
    """A job of one machine fetches, resamples and stacks next to
    nothing: the cell stays out of those three, as the backbone cells
    before it."""
    (entry,) = [m for m in manifest.load_manifest()["per_layer"] if m["name"] == name]
    assert CELL not in entry["workloads"] and "smallthinker_build" not in entry["workloads"]


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
                "pairs_attended", "pairs_multiplied", "router_tokens", "pairs_here", "pairs_total", "steps_run",
                "num_experts", "kv_lora_rank", "qk_rope_head_dim", "v_head_dim", "kv_expanded_dim",
            }
            assert "gate_active" not in p and "keys_selected" not in p  # no quantity of its own that the data decides
            assert len(p["pairs_attended"]) == LAYERS and len(p["pairs_here"]) == LAYERS - 1
    assert sum(bool(p["compile"]) for p in found["warm_job"]["programs"] if "fit" in p["program"]) == 1


def test_the_counters_of_the_toy_run(report):
    spec, found = report
    fits = [p for j in found["jobs"] for p in j["programs"] if "fit" in p["program"]]
    causal = LOOKBACK * (LOOKBACK + 1) / 2
    for p in fits:
        # the windows trained, as the expert layer counts them, are the tiles'
        windows, left = divmod(p["pairs_total"][0], LOOKBACK * TOP_K)
        assert left == 0 and windows > 0
        assert p["pairs_attended"] == [windows * causal] * LAYERS  # all three layers: a latent layer is an attention in tiles
        assert p["pairs_multiplied"] == [windows * 91 * TILE * TILE] * LAYERS
        assert (p["num_experts"], p["experts_held"], p["expert_offset"]) == (16, HELD, OFFSET)
        assert (p["kv_lora_rank"], p["qk_rope_head_dim"], p["v_head_dim"]) == (RANK, ROPE, VALUE)
        assert p["kv_expanded_dim"] == HEADS * (NOPE + VALUE)
        assert [sum(row[OFFSET : OFFSET + HELD]) for row in p["router_tokens"]] == p["pairs_here"]
        assert [sum(row) for row in p["router_tokens"]] == p["pairs_total"]
    # the status file says the same, and ``build-status`` what a row keeps of itself
    from gordo_tpu.telemetry.progress import render_status

    status = found["jobs"][0]["status"]
    assert f"latent {RANK} + {ROPE} of {HEADS * (NOPE + VALUE)} floats a row" in render_status(status)


def test_every_listed_reader_reads_the_toy_run(report):
    spec, found = report
    c = the_cell()
    evidence = dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"])
    readers = c.readers()
    assert set(readers) == {m["name"] for m in c.per_layer} and "latent_fit_mfu_pct" in readers
    # the CPU has no device plane: what the trace alone can say is not there to read ...
    silent = {name for name, read in readers.items() if read(evidence) is None}
    assert {"latent_fit_mfu_pct", "backbone_fit_step_ms"} <= silent
    assert silent <= {"latent_fit_mfu_pct", "backbone_fit_step_ms", "device_idle_pct", "hbm_peak_pct"}
    assert 0 < readers["moe_local_pair_share_pct"](evidence) < 100
    assert readers["moe_expert_imbalance_pct"](evidence) >= 0
    assert readers["device_programs_per_job"](evidence) == 7 and readers["compiles_in_window"](evidence) == 0
    wasted = 100.0 * (1 - (LOOKBACK * (LOOKBACK + 1) / 2) / (91 * TILE * TILE))
    assert readers["attention_pairs_wasted_pct"](evidence) == pytest.approx(wasted)
    for name in ("collect_gbps", "host_cores_busy", "host_rss_peak_gb", "build_dump_share_pct"):
        assert readers[name](evidence) > 0, name
    # ... and with one, each reader divides by it
    job = found["jobs"][found["traced_job"]]
    timed = dict(evidence, trace={"devices": [{
        "modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": [],
    }]})
    useful = flops_latent_backbone.job_useful_fit_flops(spec["config"], history_rows(1), job["programs"])
    assert readers["latent_fit_mfu_pct"](timed) == pytest.approx(
        100.0 * useful / (2.0 * CPU_DEVICE["peaks"]["bf16_flops_per_s"])
    )
    ran = sum(p["steps_run"] for p in job["programs"] if "fit" in p["program"])
    assert readers["backbone_fit_step_ms"](timed) == pytest.approx(2000.0 / ran)


def test_the_new_reader_finds_nothing_in_a_program_without_the_counters(report):
    """The parent's program has no such kind and no such counter: nothing
    is read, nothing raises."""
    spec, found = report
    c = the_cell()
    gone = ("pairs_attended", "pairs_multiplied", "fit_counters")
    stripped = [
        dict(job, programs=[{k: v for k, v in p.items() if k not in gone} for p in job["programs"]])
        for job in found["jobs"]
    ]
    trace = {"devices": [{"modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": []}]}
    evidence = dict(found, jobs=stripped, cell=c.entry, config=spec["config"], traffic=spec["traffic"], trace=trace)
    read = c.readers()["latent_fit_mfu_pct"]
    assert read(evidence) is None and read(dict(evidence, jobs=[])) is None
    # a banded backbone of another family (smallthinker_build's evidence) reads the same way
    other = manifest.Cell(manifest.load_manifest(), "smallthinker_build")
    assert read(dict(found, cell=c.entry, config=other.config, traffic=spec["traffic"], trace=trace)) is None
    # and the other families' readers find nothing of theirs in this one
    theirs = other.readers()["prerouted_fit_mfu_pct"]
    assert theirs(dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"], trace=trace)) is None


def test_flops_latent_backbone_against_a_hand_count():
    config = the_cell().config
    h = 2048
    projections = 2 * (h * 32 * 192 + h * 576 + 512 * 32 * 256 + 32 * 128 * h)
    assert flops_latent_backbone.projection_flops_per_token(config) == projections == 2 * (26_345_984 - 512)
    assert flops_latent_backbone.attention_flops_per_pair(config) == 2 * 32 * (192 + 128) == 20_480
    assert flops_latent_backbone.feed_forward_flops_per_token(config, True) == 6 * h * 6144
    assert flops_latent_backbone.feed_forward_flops_per_token(config, False) == 2 * h * 128 + 6 * h * 1536
    assert flops_backbone.pair_flops(config) == 6 * h * 768
    # 8,209 rows: 17 windows; folds train 5, 9, 13 of them, the final fit 17
    assert flops_backbone.trained_windows(config, 8209) == 5 + 9 + 13 + 17
    windows, causal = 44, 8192 * 8193 // 2
    assert causal == 33_558_528 and 136 * 512 * 512 == 35_651_584
    programs = [{
        "program": "fleet_windowed_fit", "pairs_here": [1000.0, 2000, 3000, 4000], "pairs_total": [8000] * 4,
        "pairs_attended": [11.0 * causal] * 5, "pairs_multiplied": [11.0 * 136 * 512 * 512] * 5,
    }] * 4
    per_token = 2 * 50 * h + 5 * projections + 6 * h * 6144 + 4 * (2 * h * 128 + 6 * h * 1536)
    by_hand = 3.0 * (
        per_token * windows * 8192
        + 20_480 * windows * 5 * causal
        + 6 * h * 768 * 4 * 10000
        + 2 * h * 50 * windows
    )
    assert flops_latent_backbone.job_useful_fit_flops(config, 8209, programs) == pytest.approx(by_hand)
    # a step of 2 windows at even routing (16 of 128 experts: 0.75 pairs a
    # token a layer): ISSUE 41's 6.87 T of attended pairs, 4.32 T of
    # projections, 1.24 + 1.24 + 0.46 + 0.03 T of feed-forwards: 14.16 T forward
    attention = 20_480 * 2 * 5 * causal
    tokens = 16384
    parts = (
        5 * projections * tokens, 4 * 6 * h * 1536 * tokens, 6 * h * 6144 * tokens,
        6 * h * 768 * 4 * tokens * 0.75, 4 * 2 * h * 128 * tokens,
    )
    assert [round(part / 1e12, 2) for part in (attention,) + parts] == [6.87, 4.32, 1.24, 1.24, 0.46, 0.03]
    whole = attention + sum(parts) + 2 * 50 * h * tokens
    assert round(whole / 1e12, 2) == 14.16 and 0.48 < attention / whole < 0.49
    with pytest.raises(KeyError):
        flops_latent_backbone.job_useful_fit_flops(config, 8209, [{"program": "fleet_windowed_fit"}])
    with pytest.raises(ValueError):  # a row a layer held, or the rows are another program's
        flops_latent_backbone.job_useful_fit_flops(
            config, 8209, [dict(programs[0], pairs_attended=[1.0] * 4, pairs_multiplied=[2.0] * 4)]
        )
    with pytest.raises(ValueError):  # the dense layer routes nothing: four rows, not five
        flops_latent_backbone.job_useful_fit_flops(config, 8209, [dict(programs[0], pairs_here=[1.0] * 5)])
    # the wasted share by hand, to the digits ISSUE 41 printed
    evidence = {"jobs": [{"programs": programs[:1]}]}
    wasted = the_cell().readers()["attention_pairs_wasted_pct"](evidence)
    assert wasted == pytest.approx(100.0 * (1 - 33_558_528 / 35_651_584)) and round(wasted, 3) == 5.871


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
    assert trained_param_count(shapes) == spec.param_count() == config["weights_per_member"] == 510_495_282
    held = config["num_hidden_layers"]
    assert len(spec.layer_ops) == held == 5 and set(spec.layer_ops) == {"full_attention"}
    assert spec.layer_ffns == ("dense",) * config["first_k_dense_replace"] + ("moe",) * 4
    for ours, theirs in (
        ("hidden_size", "hidden_size"), ("num_attention_heads", "num_attention_heads"),
        ("num_key_value_heads", "num_key_value_heads"), ("kv_lora_rank", "kv_lora_rank"),
        ("qk_nope_head_dim", "qk_nope_head_dim"), ("qk_rope_head_dim", "qk_rope_head_dim"),
        ("v_head_dim", "v_head_dim"), ("head_dim", "qk_head_dim"), ("rope_interleave", "rope_interleave"),
        ("intermediate_size", "intermediate_size"), ("moe_intermediate_size", "moe_intermediate_size"),
        ("num_experts_per_tok", "num_experts_per_tok"), ("routed_scaling_factor", "routed_scaling_factor"),
        ("rope_theta", "rope_theta"), ("norm_eps", "rms_norm_eps"), ("lookback_window", "lookback_window"),
    ):
        assert getattr(spec, ours) == config[theirs], ours
    assert spec.shared_expert_intermediate_size == config["n_shared_experts"] * config["moe_intermediate_size"] == 1536
    assert spec.num_experts == config["published"]["n_routed_experts"] == 128
    assert spec.experts_held == config["n_routed_experts"] == config["experts_held"] == 16
    assert (spec.router, spec.router_input, spec.expert_activation) == ("sigmoid_bias", "ffn_input", "silu")
    assert estimator.kwargs["batch_size"] == config["batch_size"] == 2
    assert estimator.kwargs["epochs"] == config["epochs"] == 1


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


def test_the_trace_says_what_a_row_keeps_beside_the_fit_program(one_job):
    from gordo_tpu.telemetry.trace_analysis import build_breakdown, render_analysis

    _, record, _ = one_job
    with open(os.path.join(record["output_dir"], "build_trace.jsonl")) as f:
        spans = [json.loads(line) for line in f]
    rendered = render_analysis({"trace": "t", "spans_read": len(spans), "build_breakdown": build_breakdown(spans)})
    said = f"latent {RANK} + {ROPE} of {HEADS * (NOPE + VALUE)} floats a row"
    assert f"  program fleet_windowed_fit [validation_slots=0, {said}]" in rendered


def test_a_clean_job_passes_and_a_normed_rotary_key_does_not(one_job, monkeypatch, capfd):
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
        w = layers["weights"]["layer_2"]["attn"]["wkv_a"].copy()
        w[:, RANK:] = -w[:, RANK:]  # the shared key's columns in the last layer: every head's scores move
        layers["weights"]["layer_2"]["attn"]["wkv_a"] = w
        return layers

    monkeypatch.setattr(reference, "layers_of", perturbed)
    assert not check_forward(record, names, reference, "cpu").ok
    checks, band = check_step(config, record, names, reference)
    assert [f for f in checks.failures if "outside the reference band" in f], checks.failures
    assert "output" in step_line(capfd)["over"]


def rebuilt_program_is_incorrect(one_job, capfd, patch):
    """Forward and step of the built artifact through a program that
    ``patch`` (a function of a ``MonkeyPatch``) has changed."""
    config, record, names = one_job
    reference = manifest.load_module(manifest.ROOT, "reference", config["reference"])
    from gordo_tpu.models import training

    caches = (training.predict_fn, training.windowed_batch_loss_fn,
              training.windowed_loss_and_grad_norms_program)
    with pytest.MonkeyPatch.context() as monkeypatch:
        patch(monkeypatch)
        for cache in caches:
            cache.cache_clear()
        try:
            assert not check_forward(record, names, reference, "cpu").ok
            checks, band = check_step(config, record, names, reference)
        finally:
            monkeypatch.undo()
            for cache in caches:
                cache.cache_clear()
    assert [f for f in checks.failures if "outside the reference band" in f], checks.failures
    assert "output" in step_line(capfd)["over"]
    assert check_forward(record, names, reference).ok


def test_a_rotary_embedding_in_the_half_rotation_layout_is_incorrect(one_job, capfd):
    """``rope_interleave`` is a statement: a program that turns the
    trailing part in the other kinds' layout (dimension i with i + d / 2)
    no longer matches the reference, forward and step."""
    from gordo_tpu.models import backbone

    rebuilt_program_is_incorrect(
        one_job, capfd, lambda patch: patch.setattr(backbone, "interleaved_rotary", backbone.rotary)
    )


def test_a_latent_that_is_not_normed_is_incorrect(one_job, capfd):
    from gordo_tpu.models import backbone

    honest = backbone._latent_heads

    def no_norm(spec, w, u):
        import jax.numpy as jnp

        return honest(spec, dict(w, kv_norm=jnp.ones_like(w["kv_norm"]) * 0.5), u)

    rebuilt_program_is_incorrect(one_job, capfd, lambda patch: patch.setattr(backbone, "_latent_heads", no_norm))


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
    print("toy control readings", {k: readings[k] for k in ("output", "loss", "leaf", "grad_norm", "over")})
    assert readings["limits"] == reference.STEP_LIMITS
    assert "output" in readings["over"] and len(readings["over"]) >= 2
    # limits under the readings: the band is empty, the run not correct
    X, y = np.zeros((108, 5), np.float32), np.ones((108, 5), np.float32)
    low, high = reference.loss_band(X, y, config, limits={"output": 1e-5})
    assert np.isnan(low) and np.isnan(high)


def test_the_leaves_a_flat_softmax_starves_and_a_router_are_read_over_the_floor():
    """``wq``, ``wkv_a`` and a router are read as the siblings read
    ``wq``, ``wk`` and a router: over a floor of a hundredth of the
    whole gradient's norm, so that a leaf that carries little reads what
    it adds to the whole's square and one that carries much its own
    relative error; every other leaf as ``lfm2_moe_backbone`` reads it."""
    reference = manifest.load_module(manifest.ROOT, "reference", the_cell().config["reference"])
    sibling = manifest.load_module(manifest.ROOT, "reference", "smallthinker_prerouted_backbone")
    assert reference.FLOORED_LEAVES == ("['wq']", "['wkv_a']", "['router']")

    def norms(wq=0.02, wkv_a=0.5, router=0.3, wkv_b=2.0):
        return {
            "head": {"W": 170.0},
            "layer_1": {"attn": {"wq": wq, "wkv_a": wkv_a, "wkv_b": wkv_b}, "moe": {"router": router}},
        }

    ref = norms()
    whole = reference.step_readings(1.0, ref, 1.0, ref)["reference"]["grad_norm"]
    floor = 1e-2 * whole
    for leaf, value in (("wq", 0.04), ("wkv_a", 0.55), ("router", 0.31)):
        readings = reference.step_readings(1.0, norms(**{leaf: value}), 1.0, ref)
        want = abs(value**2 - ref["layer_1"]["attn" if leaf != "router" else "moe"][leaf] ** 2)
        want /= 2 * (ref["layer_1"]["attn" if leaf != "router" else "moe"][leaf] ** 2 + floor**2)
        assert readings["worst_leaf"].endswith(f"['{leaf}']") and readings["leaf"] == pytest.approx(want)
    # a wq twice what it should be reads far less than its own relative error, a router three times too large is over
    assert reference.step_readings(1.0, norms(wq=0.04), 1.0, ref)["leaf"] < 1e-3
    assert reference.step_readings(1.0, norms(router=0.9), 1.0, ref)["leaf"] > reference.STEP_LIMITS["leaf"]
    assert reference.step_readings(1.0, norms(router=0.31), 1.0, ref)["router_leaf"] == pytest.approx(0.01 / 0.3)
    # every other leaf by its own norm, and the router as the sibling reads it
    off = reference.step_readings(1.0, norms(wkv_b=2.02), 1.0, ref)
    assert off["worst_leaf"].endswith("['wkv_b']") and off["leaf"] == pytest.approx(0.01)
    both = norms(router=0.31)
    assert reference.step_readings(1.0, both, 1.0, ref)["leaf"] == sibling.step_readings(1.0, both, 1.0, ref)["leaf"]


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
    kinds = "".join(
        f"def {kind}(n_features):\n    ...\n\n\n" for kind in ("lfm2_moe", "keye_vl2", "laguna", "smallthinker")
    )
    (factories / "backbone.py").write_text(kinds)
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

    refused = load("kanana_latent_backbone")
    assert refused.returncode == 5 and "loaded" not in refused.stdout
    assert "no kind kanana" in refused.stderr
    for there in ("lfm2_moe_backbone", "keye_sparse_backbone", "laguna_banded_backbone", "smallthinker_prerouted_backbone"):
        assert load(there).returncode == 0  # the cells that were there still start
    (factories / "backbone.py").write_text(kinds + "def kanana(n_features):\n    ...\n")
    loaded = load("kanana_latent_backbone")
    assert loaded.returncode == 0 and "loaded" in loaded.stdout
