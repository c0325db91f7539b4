"""The cell ``smallthinker_build`` at toy sizes on the CPU stand-in: the
same child the chip runs, as a function of sizes (after
``test_laguna_swa_cell.py``; the cell's own toy sizes are here). Every
assertion about the manifest is one a later cell leaves true:
membership, never position, never "the only ones"."""

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
import flops_prerouted_backbone
from harness import correct, manifest
from harness.data import history_rows, machine_names, machines_document
from jobs import read_spans, read_status
from tiny import CPU_DEVICE, quiet_start

CELL = "smallthinker_build"
CONFIG = "smallthinker-21b-a3b-50tag-lb8192"
#: a band of 30 of 100 rows in tiles of 8: a block of queries visits its
#: diagonal tile, three whole tiles and an edge tile
LOOKBACK, WINDOW, TILE = 100, 30, 8
LAYERS, HELD, TOP_K, WIDTH = 4, 2, 3, 24

#: the cells of 8,192-row windows, each with its configuration, what its
#: file cuts and the readers that are its own
LONG_WINDOW_CELLS = [
    (CELL, CONFIG, ["num_hidden_layers", "moe_num_primary_experts"], {"prerouted_fit_mfu_pct"}),
    ("laguna_swa_build", "laguna-xs2-50tag-lb8192", ["num_hidden_layers", "num_experts"], {"banded_fit_mfu_pct"}),
    ("keye_dsa_build", "keye-vl2-30b-a3b-50tag-lb8192", ["num_hidden_layers", "num_experts"], {"sparse_fit_mfu_pct"}),
]

#: the estimator at toy widths: the four layers of the cut (full without
#: positions, three sliding with rotary) with 14 heads of 16 over 2
#: key/value heads (a group is 7), 2 of 8 experts held, 3 a token
TOY_ESTIMATOR = {
    "kind": "smallthinker", "lookback_window": LOOKBACK, "num_hidden_layers": LAYERS,
    "hidden_size": 32, "head_dim": 16, "num_attention_heads": 14, "num_key_value_heads": 2,
    "moe_ffn_hidden_size": WIDTH, "moe_num_primary_experts": 8, "experts_held": HELD, "expert_offset": 2,
    "moe_num_active_primary_experts": TOP_K, "sliding_window_size": WINDOW,
    "epochs": 2, "batch_size": 32,
}


def toy_config(config: dict) -> dict:
    """The cell's configuration with toy widths wherever a reader or the
    worker looks: the estimator, and the keys ``flops_prerouted_backbone``
    reads."""
    (path, _), = config["estimator"].items()
    return dict(
        config,
        estimator={path: dict(TOY_ESTIMATOR)},
        tags=5, lookback_window=LOOKBACK, epochs=2, batch_size=32,
        hidden_size=32, head_dim=16, num_attention_heads=14, num_key_value_heads=2,
        moe_ffn_hidden_size=WIDTH, moe_num_active_primary_experts=TOP_K, num_hidden_layers=LAYERS,
        published={"num_hidden_layers": 52, "moe_num_primary_experts": 8},
    )


def the_cell() -> manifest.Cell:
    return manifest.Cell(manifest.load_manifest(), CELL)


def attended_by_arithmetic(length=LOOKBACK, window=WINDOW):
    return sum(min(t + 1, window) for t in range(length))


def tiles_by_arithmetic(length=LOOKBACK, window=WINDOW, tile=TILE):
    back = -(-(window - 1) // tile)
    return sum(min(i, back) + 1 for i in range(-(-length // tile)))


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


@pytest.mark.parametrize("cell,config,reduced,own", LONG_WINDOW_CELLS)
def test_the_manifest_has_no_problems_with_the_cell(cell, config, reduced, own):
    """The new cell, and every line of ``test_laguna_swa_cell.py``'s test
    of the same name, for the cells it was about, that a later cell
    leaves true (where it said "the last" and "alone": ``is among`` and
    ``lists it``)."""
    document = manifest.load_manifest()
    assert manifest.problems(document) == []
    c = manifest.Cell(document, cell)
    assert c.entry["chips"] == 1 and c.traffic["kind"] == "build_jobs" and c.entry["config"] == config
    assert c.entry["traffic"] == "jobs-1x57d" and c.traffic["history_days"] == 57
    # 8,209 rows are 17 windows of 8,192 with the row each predicts
    assert history_rows(57) - c.config["lookback_window"] - c.config["lookahead"] + 1 == 17
    assert c.traffic["verify_rows"] - c.config["lookback_window"] == 2
    assert c.config["reduced"] == c.config_entry["reduced"] == reduced
    assert {m["name"] for m in c.end_to_end} == {"models_built_per_hour", "setup_s"}
    reported = {m["name"] for m in c.per_layer}
    assert reported >= own | {
        "backbone_fit_step_ms", "moe_expert_imbalance_pct", "moe_local_pair_share_pct",
        "hbm_peak_pct", "device_idle_pct", "compiles_in_window", "device_programs_per_job",
        "build_dump_share_pct", "fit_host_wrap_pct",
    }
    # every per-layer metric of lfm2_moe_build that reads no LFM2 shape
    other = {m["name"] for m in manifest.Cell(document, "lfm2_moe_build").per_layer}
    assert other - reported <= {"backbone_fit_mfu_pct"}
    assert not {"fit_mfu_pct", "fit_step_ms", "backbone_fit_mfu_pct"} & reported
    # a cell's own readers list it, and nothing that was there lost a cell
    for name in own:
        (listed,) = [m["workloads"] for m in document["per_layer"] if m["name"] == name]
        assert cell in listed
    assert cell in [w["name"] for w in document["workloads"]]
    assert config in [entry["name"] for entry in document["configs"]]
    assert len(c.entry["why"]) <= 200 and len(c.config_entry["why"]) <= 200


def test_what_this_cell_joined_and_what_it_stayed_out_of():
    document = manifest.load_manifest()
    lists = {m["name"]: m.get("workloads") for m in document["per_layer"] + document["end_to_end"]}
    joined = {
        "models_built_per_hour", "build_host_share_pct", "build_fetch_stage_share_pct", "device_programs_per_job",
        "device_idle_pct", "hbm_peak_pct", "compiles_in_window", "build_unspanned_pct", "fetch_parallelism",
        "fetch_provider_share_pct", "fit_host_wrap_pct", "job_retrace_s", "warm_job_compile_path_s",
        "build_dump_share_pct", "moe_expert_imbalance_pct", "moe_local_pair_share_pct", "backbone_fit_step_ms",
        "attention_pairs_wasted_pct", "prerouted_fit_mfu_pct",
        # the host's accounting of a job that collects and dumps 1.85 GB (``HOST_READERS_JOINED``)
        "collect_gbps", "host_cores_busy", "host_rss_peak_gb",
    }
    assert {name for name, cells in lists.items() if cells and CELL in cells} == joined
    assert lists["attention_pairs_wasted_pct"][:1] == ["laguna_swa_build"]
    (entry,) = [m for m in document["per_layer"] if m["name"] == "prerouted_fit_mfu_pct"]
    assert entry == {
        "name": "prerouted_fit_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "fused training programs", "moves": "models_built_per_hour", "workloads": [CELL],
    }
    c = the_cell()
    assert c.config_entry["source"] == c.config["source"] and c.config_entry["source"].endswith("/config.json")
    assert c.config["published"] == {"num_hidden_layers": 52, "moe_num_primary_experts": 64}
    for group in ("deployment", "replaced", "left_out", "assumed"):
        assert c.config[group]
    assert set(c.config["assumed"]) >= {
        "router_input", "router", "load_balancing", "feed_forward", "q_k_norm", "bias", "rotary", "sliding_window",
        "attention_scale", "initialisation", "epochs", "batch_size", "lookback_window", "lookahead", "tags",
        "optimizer", "attention_tiles",
    }
    assert set(c.config["left_out"]) >= {"max_position_embeddings", "secondary experts", "tie_word_embeddings"}


@pytest.mark.parametrize("name", [config for _, config, _, _ in LONG_WINDOW_CELLS])
def test_a_configuration_of_long_windows_holds_every_line_but_the_batch(name):
    """``test_manifest.py::test_config_entry_and_file`` for the
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
    # its case is among those the tree expects to fail, with a reason
    from tests import conftest

    case = f"tests/chipbench/test_manifest.py::test_config_entry_and_file[{name}]"
    assert case in conftest.MANIFEST_CASES_OUTGROWN and conftest.OUTGROWN[case]


def body_of(function, without=()):
    """The body of ``function`` as (the lines that are left, the lines
    taken out), ``without`` the lines that start so; both as they stand
    in a module, not in a function."""
    lines = textwrap.dedent(inspect.getsource(function)).splitlines()
    start = next(i for i, line in enumerate(lines) if line.startswith("def ")) + 1
    kept, taken = [], []
    for line in textwrap.dedent("\n".join(lines[start:])).splitlines():
        (taken if line.strip().startswith(tuple(without)) and without else kept).append(line)
    return kept, taken


LAST_TWO = (
    'assert [w["name"] for w in document["workloads"]][-2:]',
    'assert [c["name"] for c in document["configs"]][-2:]',
)
LISTED_ALONE = ("for name in own:", 'assert [m["workloads"] for m in document["per_layer"] if m["name"] == name]')


@pytest.mark.parametrize("cell,config,own,named", [
    ("laguna_swa_build", "laguna-xs2-50tag-lb8192", {"banded_fit_mfu_pct", "attention_pairs_wasted_pct"},
     LAST_TWO + LISTED_ALONE),
    ("keye_dsa_build", "keye-vl2-30b-a3b-50tag-lb8192", {"sparse_fit_mfu_pct"}, LAST_TWO),
])
def test_the_marked_cell_cases_fail_on_the_named_lines_alone(cell, config, own, named):
    """``test_laguna_swa_cell.py::test_the_manifest_has_no_problems_with_the_cell``,
    both cases (``tests/conftest.py:LAGUNA_CASES_OUTGROWN``): its own
    lines, run here but for the named ones, hold; the named ones are what
    fails: that ``laguna_swa_build`` and its configuration are the
    manifest's last two with ``keye_dsa_build``, and for the cell whose
    ``attention_pairs_wasted_pct`` this cell joined, that its readers list
    it alone."""
    import test_laguna_swa_cell as laguna

    kept, taken = body_of(laguna.test_the_manifest_has_no_problems_with_the_cell, without=named)
    scope = dict(vars(laguna), cell=cell, config=config, own=own)
    assert sum(line.strip().startswith("assert ") for line in kept) == 14 - sum(n.startswith("assert ") for n in named)
    exec("\n".join(kept), scope)  # raises where a line no longer holds
    assert len(taken) == len(named)
    failing = [line for line in taken if line.strip().startswith("assert ")]
    for line in failing:
        if line.startswith(" "):  # the loop's line, under its loop
            line = f"for name in own:\n{line}"
        with pytest.raises(AssertionError):
            exec(line, dict(scope))
    from tests import conftest

    marked = [case for case in conftest.LAGUNA_CASES_OUTGROWN if f"no_problems_with_the_cell[{cell}-" in case]
    assert len(marked) == 1 and conftest.OUTGROWN[marked[0]]


@pytest.mark.parametrize("name", ["keye-vl2-30b-a3b-50tag-lb8192", "laguna-xs2-50tag-lb8192"])
def test_the_marked_configuration_cases_fail_on_their_last_lines_alone(name):
    """``test_laguna_swa_cell.py::test_the_manifest_cases_expected_to_fail_fail_on_their_last_line_alone``,
    both cases: every line but the two last holds; those say that
    ``tests/conftest.py:OUTGROWN`` holds exactly PR 33's three cases."""
    import test_laguna_swa_cell as laguna

    named = ("assert [case.split(", '"keye-vl2-30b-a3b-50tag-lb8192", CONFIG,', "]", "assert set(conftest.OUTGROWN) ==")
    kept, taken = body_of(
        laguna.test_the_manifest_cases_expected_to_fail_fail_on_their_last_line_alone, without=named
    )
    scope = dict(vars(laguna), name=name)
    assert sum(line.strip().startswith("assert ") for line in kept) == 6
    exec("\n".join(kept), scope)
    assert len(taken) == 4
    for statement in ("\n".join(taken[:3]), taken[3]):
        with pytest.raises(AssertionError):
            exec(textwrap.dedent(statement), dict(scope))
    from tests import conftest

    marked = [case for case in conftest.LAGUNA_CASES_OUTGROWN if case.endswith(f"last_line_alone[{name}]")]
    assert len(marked) == 1 and conftest.OUTGROWN[marked[0]]


#: the three of PR 37's six readers that read this cell's jobs (its
#: ``collect`` part, the process's CPU, the resident set): the cell is
#: appended to their lists. ISSUE 39 kept it out of them for the pin in
#: ``test_host_accounting.py`` alone; that test's six cases are outgrown
#: by the appended entry whatever these lists say (below), so the pin no
#: longer buys anything, and the driver gets the new cell's host numbers
HOST_READERS_JOINED = ("collect_gbps", "host_cores_busy", "host_rss_peak_gb")


@pytest.mark.parametrize("name", [
    "collect_gbps", "fetch_cpu_parallelism", "fetch_resample_cpu_ms", "host_cores_busy", "host_rss_peak_gb",
    "stack_gbps",
])
def test_the_six_cases_of_the_appended_readers_fail_on_the_named_lines_alone(name):
    """``test_host_accounting.py::test_the_manifest_entry_is_the_issues_table``
    (``tests/conftest.py:APPENDED_CASES_OUTGROWN``): the entry is the
    table's, to the letter, but for this cell appended to the
    ``workloads`` of the three readers it joined; the last line says the
    six readers are the manifest's last six, which a later appended entry
    ends. What stays true of it: the six follow ``build_dump_share_pct``
    in the table's order, and nothing stands between them."""
    import test_host_accounting as host

    kept, taken = body_of(host.test_the_manifest_entry_is_the_issues_table, without=("assert names[-6:]",))
    scope = dict(vars(host), name=name)
    if name in HOST_READERS_JOINED:
        with pytest.raises(AssertionError):
            exec("\n".join(kept), dict(scope))
        # ... and holds of the manifest less that one appended cell
        document = copy.deepcopy(host.MANIFEST)
        (entry,) = [m for m in document["per_layer"] if m["name"] == name]
        assert entry["workloads"].pop() == CELL
        scope["MANIFEST"] = document
    exec("\n".join(kept), scope)
    assert len(taken) == 1
    with pytest.raises(AssertionError):
        exec(textwrap.dedent(taken[0]), scope)
    names = scope["names"]
    first = names.index("build_dump_share_pct")
    assert names[first : first + 7] == ["build_dump_share_pct"] + list(host.TABLE)
    assert names.index("prerouted_fit_mfu_pct") > first + 6  # appended after them
    from tests import conftest

    case = f"tests/chipbench/test_host_accounting.py::test_the_manifest_entry_is_the_issues_table[{name}]"
    assert case in conftest.APPENDED_CASES_OUTGROWN and conftest.OUTGROWN[case]


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
                "pairs_attended", "pairs_multiplied", "router_tokens", "pairs_here", "pairs_total",
                "gate_active", "gate_total", "steps_run", "num_experts",
            }
            assert len(p["pairs_attended"]) == len(p["pairs_here"]) == len(p["gate_active"]) == LAYERS
    assert sum(bool(p["compile"]) for p in found["warm_job"]["programs"] if "fit" in p["program"]) == 1


def test_the_counters_of_the_toy_run(report):
    spec, found = report
    fits = [p for j in found["jobs"] for p in j["programs"] if "fit" in p["program"]]
    full, sliding = LOOKBACK * (LOOKBACK + 1) / 2, attended_by_arithmetic()
    full_tiles, sliding_tiles = tiles_by_arithmetic(window=LOOKBACK), tiles_by_arithmetic()
    assert (full_tiles, sliding_tiles) == (91, 55)  # 13 blocks: 1 + .. + 13; 1 + 2 + 3 + 4 + 9 x 5
    for p in fits:
        # the windows trained, as the expert layer counts them, are the band's
        windows, left = divmod(p["pairs_total"][0], LOOKBACK * TOP_K)
        assert left == 0 and windows > 0
        assert p["pairs_attended"] == [windows * full] + [windows * sliding] * 3
        assert p["pairs_multiplied"] == [windows * n * TILE * TILE for n in [full_tiles] + [sliding_tiles] * 3]
        assert p["gate_total"] == [pairs * WIDTH for pairs in p["pairs_here"]]
        assert all(0 <= active <= total for active, total in zip(p["gate_active"], p["gate_total"]))
        assert 0 < sum(p["gate_active"]) < sum(p["gate_total"])
        assert (p["num_experts"], p["experts_held"], p["expert_offset"]) == (8, HELD, 2)


def test_every_listed_reader_reads_the_toy_run(report):
    spec, found = report
    c = the_cell()
    evidence = dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"])
    readers = c.readers()
    assert set(readers) == {m["name"] for m in c.per_layer} and "prerouted_fit_mfu_pct" in readers
    # the CPU has no device plane: what the trace alone can say is not there to read ...
    silent = {name for name, read in readers.items() if read(evidence) is None}
    assert {"prerouted_fit_mfu_pct", "backbone_fit_step_ms"} <= silent
    assert silent <= {"prerouted_fit_mfu_pct", "backbone_fit_step_ms", "device_idle_pct", "hbm_peak_pct"}
    assert 0 < readers["moe_local_pair_share_pct"](evidence) < 100
    assert readers["moe_expert_imbalance_pct"](evidence) >= 0
    assert readers["device_programs_per_job"](evidence) == 7 and readers["compiles_in_window"](evidence) == 0
    full, sliding = LOOKBACK * (LOOKBACK + 1) / 2, attended_by_arithmetic()
    wasted = 100.0 * (1 - (full + 3 * sliding) / ((91 + 3 * 55) * TILE * TILE))
    assert readers["attention_pairs_wasted_pct"](evidence) == pytest.approx(wasted)
    # ... and with one, each reader divides by it
    job = found["jobs"][found["traced_job"]]
    timed = dict(evidence, trace={"devices": [{
        "modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": [],
    }]})
    useful = flops_prerouted_backbone.job_useful_fit_flops(spec["config"], history_rows(1), job["programs"])
    assert readers["prerouted_fit_mfu_pct"](timed) == pytest.approx(
        100.0 * useful / (2.0 * CPU_DEVICE["peaks"]["bf16_flops_per_s"])
    )
    ran = sum(p["steps_run"] for p in job["programs"] if "fit" in p["program"])
    assert readers["backbone_fit_step_ms"](timed) == pytest.approx(2000.0 / ran)


def test_the_new_reader_finds_nothing_in_a_program_without_the_counters(report):
    """The parent's program has no such kind and no such counter: nothing
    is read, nothing raises."""
    spec, found = report
    c = the_cell()
    gone = ("pairs_attended", "pairs_multiplied", "gate_active", "gate_total", "fit_counters")
    stripped = [
        dict(job, programs=[{k: v for k, v in p.items() if k not in gone} for p in job["programs"]])
        for job in found["jobs"]
    ]
    trace = {"devices": [{"modules": {"jit_fleet_windowed_fit": {"seconds": 2.0, "count": 4}}, "ops": []}]}
    evidence = dict(found, jobs=stripped, cell=c.entry, config=spec["config"], traffic=spec["traffic"], trace=trace)
    read = c.readers()["prerouted_fit_mfu_pct"]
    assert read(evidence) is None and read(dict(evidence, jobs=[])) is None
    assert c.readers()["attention_pairs_wasted_pct"](evidence) is None
    # a banded backbone of another family (laguna_swa_build's evidence) reads the same way
    laguna = manifest.Cell(manifest.load_manifest(), "laguna_swa_build")
    assert read(dict(found, cell=c.entry, config=laguna.config, traffic=spec["traffic"], trace=trace)) is None


def test_flops_prerouted_backbone_against_a_hand_count():
    config = the_cell().config
    h = 2560
    per_layer = 2 * h * (2 * 28 * 128 + 2 * 4 * 128 + 64)
    assert flops_prerouted_backbone.projection_flops_per_token(config) == per_layer
    assert flops_prerouted_backbone.attention_flops_per_pair(config) == 4 * 28 * 128
    assert flops_prerouted_backbone.pair_flops(config) == 6 * h * 768
    # 8,209 rows: 17 windows; folds train 5, 9, 13 of them, the final fit 17
    assert flops_backbone.trained_windows(config, 8209) == 5 + 9 + 13 + 17
    windows, causal, band = 44, 8192 * 8193 // 2, attended_by_arithmetic(8192, 4096)
    assert (causal, band) == (33_558_528, 25_167_872) and causal + 3 * band == 109_062_144
    full_tiles, band_tiles = tiles_by_arithmetic(8192, 8192, 512), tiles_by_arithmetic(8192, 4096, 512)
    assert (full_tiles, band_tiles) == (136, 108)  # a band 9 tiles wide: the diagonal, seven whole, an edge
    assert (full_tiles + 3 * band_tiles) * 512 * 512 == 120_586_240
    programs = [{
        "program": "fleet_windowed_fit", "pairs_here": [1000.0, 2000, 3000, 4000], "pairs_total": [8000] * 4,
        "pairs_attended": [11.0 * causal] + [11.0 * band] * 3,
        "pairs_multiplied": [11.0 * 136 * 512 * 512] + [11.0 * 108 * 512 * 512] * 3,
    }] * 4
    by_hand = 3.0 * (
        (2 * 50 * h + 4 * per_layer) * windows * 8192
        + 4 * 28 * 128 * windows * (causal + 3 * band)
        + 6 * h * 768 * 4 * 10000
        + 2 * h * 50 * windows
    )
    assert flops_prerouted_backbone.job_useful_fit_flops(config, 8209, programs) == pytest.approx(by_hand)
    # a step of 2 windows at even routing (16 of 64 experts: 1.5 pairs a
    # token a layer): ISSUE 39's 3.13 + 2.77 + 1.16 TFLOP forward, 44% of them the attention's pairs
    attention = 4 * 28 * 128 * 2 * (causal + 3 * band)
    experts = 6 * h * 768 * 4 * 16384 * 1.5
    dense = (2 * 50 * h + 4 * per_layer) * 16384
    assert (round(attention / 1e12, 2), round(dense / 1e12, 2), round(experts / 1e12, 2)) == (3.13, 2.77, 1.16)
    assert 0.43 < attention / (attention + dense + experts) < 0.45
    with pytest.raises(KeyError):
        flops_prerouted_backbone.job_useful_fit_flops(config, 8209, [{"program": "fleet_windowed_fit"}])
    with pytest.raises(ValueError):  # a row a layer held, or the rows are another program's
        flops_prerouted_backbone.job_useful_fit_flops(
            config, 8209, [dict(programs[0], pairs_attended=[1.0] * 5, pairs_multiplied=[2.0] * 5)]
        )
    # the wasted share by hand, to the digits ISSUE 39 printed
    evidence = {"jobs": [{"programs": programs[:1]}]}
    wasted = the_cell().readers()["attention_pairs_wasted_pct"](evidence)
    assert wasted == pytest.approx(100.0 * (1 - 109_062_144 / 120_586_240)) and round(wasted, 3) == 9.557


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
    assert trained_param_count(shapes) == spec.param_count() == config["weights_per_member"] == 462_310_450
    held = config["num_hidden_layers"]
    assert len(spec.layer_ops) == held == 4 and set(spec.layer_ffns) == {"moe"}
    assert [int(op == "sliding_attention") for op in spec.layer_ops] == config["sliding_window_layout"][:held]
    assert [int(spec.rope_of(op)["rope_type"] != "none") for op in spec.layer_ops] == config["rope_layout"][:held]
    assert len(config["rope_layout"]) == len(config["sliding_window_layout"]) == 52
    for ours, theirs in (
        ("hidden_size", "hidden_size"), ("head_dim", "head_dim"), ("num_attention_heads", "num_attention_heads"),
        ("num_key_value_heads", "num_key_value_heads"), ("moe_intermediate_size", "moe_ffn_hidden_size"),
        ("num_experts_per_tok", "moe_num_active_primary_experts"), ("sliding_window", "sliding_window_size"),
        ("norm_eps", "rms_norm_eps"), ("lookback_window", "lookback_window"),
    ):
        assert getattr(spec, ours) == config[theirs], ours
    assert spec.rope_of("sliding_attention")["rope_theta"] == config["rope_theta"] == 1_500_000
    assert spec.num_experts == config["published"]["moe_num_primary_experts"] == 64
    assert spec.experts_held == config["moe_num_primary_experts"] == config["experts_held"] == 16
    assert (spec.router, spec.router_input, spec.expert_activation) == ("softmax_of_chosen", "layer_input", "relu")
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


def test_a_clean_job_passes_and_a_perturbed_router_does_not(one_job, monkeypatch, capfd):
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
        router = layers["weights"]["layer_3"]["moe"]["router"].copy()
        router[:, 2] = -router[:, 2]  # a held expert's column in the last layer: other tokens reach it
        layers["weights"]["layer_3"]["moe"]["router"] = router
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


def test_a_router_that_reads_the_normed_tensor_after_the_attention_is_incorrect(one_job, capfd):
    """The other place a router could stand: a program that makes its
    plan where the other kinds do, from the tensor the experts read, no
    longer matches the reference, forward and step."""
    from gordo_tpu.models import backbone

    honest = backbone.block

    def routed_after(spec, op, ffn, w, h, active=None):
        import dataclasses

        return honest(dataclasses.replace(spec, router_input="ffn_input"), op, ffn, w, h, active)

    rebuilt_program_is_incorrect(one_job, capfd, lambda patch: patch.setattr(backbone, "block", routed_after))


def test_full_layers_that_rotate_are_incorrect(one_job, capfd):
    """A program that gives its full layers the sliding layers' rotary
    embedding ("every attention rotates") no longer matches the
    reference: without positions is a statement, not a default."""
    from gordo_tpu.models.spec import BackboneSpec

    def every_operator_rotates(self, op):
        return {"rope_theta": self.rope_theta, "partial_rotary_factor": 1.0, "rope_type": "default"}

    rebuilt_program_is_incorrect(
        one_job, capfd, lambda patch: patch.setattr(BackboneSpec, "rope_of", every_operator_rotates)
    )


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


def test_a_routers_leaf_is_read_over_the_floor():
    """The norms of the sound build whose layer 0 router moved 12 tokens
    (seed 2147393505 on the v5e, PERF.md 6): read by its own norm the
    router is 2.5e-2 off, as the sibling reads it and over what any sound
    leaf reads; over the floor it is 9e-4 and the worst leaf is an
    expert's matrix at 1.7e-3. A router three times what it should be is still
    over the limit, and ``wq`` and ``wk`` are read as the sibling reads
    them."""
    reference = manifest.load_module(manifest.ROOT, "reference", the_cell().config["reference"])
    sibling = manifest.load_module(manifest.ROOT, "reference", "laguna_banded_backbone")

    def norms(router, w3=0.394644388, wk=0.021609714):
        return {
            "head": {"W": 169.483120195},
            "layer_0": {"attn": {"wk": wk}, "moe": {"router": router, "w3": w3}},
        }

    ref = norms(0.328163067)
    got = norms(0.319847196, w3=0.394644388 * (1 - 1.687e-3), wk=0.037665203)
    readings = reference.step_readings(1.0, got, 1.0, ref)
    assert readings["worst_leaf"] == "['layer_0']['moe']['w3']"
    assert readings["leaf"] == pytest.approx(1.687e-3, rel=1e-3)
    assert readings["router_leaf"] == pytest.approx(2.534e-2, rel=1e-3)
    old = sibling.step_readings(1.0, got, 1.0, ref)
    assert old["worst_leaf"] == "['layer_0']['moe']['router']" and old["leaf"] > reference.STEP_LIMITS["leaf"]
    assert {k: readings[k] for k in ("loss", "grad_norm")} == {k: old[k] for k in ("loss", "grad_norm")}
    # the router alone, over the floor: what it adds to the whole's square
    alone = reference.step_readings(1.0, norms(0.319847196), 1.0, ref)
    assert alone["worst_leaf"] == "['layer_0']['moe']['router']" and alone["leaf"] == pytest.approx(9.0e-4, rel=2e-2)
    tripled = reference.step_readings(1.0, norms(3 * 0.328163067), 1.0, ref)
    assert tripled["worst_leaf"] == "['layer_0']['moe']['router']" and tripled["leaf"] > reference.STEP_LIMITS["leaf"]
    # wq and wk: the sibling's reading, to the digit
    only_wk = norms(0.328163067, wk=0.037665203)
    assert reference.step_readings(1.0, only_wk, 1.0, ref)["leaf"] == sibling.step_readings(1.0, only_wk, 1.0, ref)["leaf"] > 0


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
    kinds = "def lfm2_moe(n_features):\n    ...\n\n\ndef keye_vl2(n_features):\n    ...\n\n\ndef laguna(n_features):\n    ...\n"
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

    refused = load("smallthinker_prerouted_backbone")
    assert refused.returncode == 5 and "loaded" not in refused.stdout
    assert "no kind smallthinker" in refused.stderr
    for there in ("lfm2_moe_backbone", "keye_sparse_backbone", "laguna_banded_backbone"):
        assert load(there).returncode == 0  # the cells that were there still start
    (factories / "backbone.py").write_text(kinds + "\n\ndef smallthinker(n_features):\n    ...\n")
    loaded = load("smallthinker_prerouted_backbone")
    assert loaded.returncode == 0 and "loaded" in loaded.stdout
