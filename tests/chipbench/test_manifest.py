"""(a) The manifest and every file it names; (b) a later PR adds a
configuration, a cell and a per-layer metric as files and entries only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import manifest

MANIFEST = manifest.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_manifest_has_no_problems():
    assert manifest.problems(MANIFEST) == []


def test_the_pending_serve_cell_merges_as_entries_only():
    """The serve cell waits in ``pending/``: merged, the manifest has no
    problem but the bounds that are still to be measured."""
    grown = manifest.with_pending(MANIFEST, "hourglass_serve")
    assert manifest.problems(grown) == []
    cell = manifest.Cell(grown, "hourglass_serve")
    assert {m["name"] for m in cell.end_to_end} == {
        "rows_scored_per_s", "request_p50_ms", "request_p99_ms", "setup_s"
    }
    assert len(cell.per_layer) == 9 and set(cell.readers()) == {m["name"] for m in cell.per_layer}
    assert all(m["bound"] is None for m in grown["end_to_end"] if "request" in m["name"])
    assert "hourglass_serve" not in CELLS


def test_manifest_has_exactly_the_contracts_keys():
    assert sorted(MANIFEST) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"]
    )
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_names_units_and_sources(metric):
    assert manifest.NAME.match(metric["name"])
    assert manifest.UNIT.match(metric["unit"]) and len(metric["unit"]) <= 16
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in manifest.SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MANIFEST["end_to_end"]:
        assert set(metric) <= allowed | {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    for cell in metric.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    for key in ("source", "why"):
        assert 1 <= len(config[key]) <= 200 and "\n" not in config[key]
    assert config["file"].startswith("benchmarks/chip/configs/")
    document = manifest.load_json(manifest.ROOT, config["file"])
    assert document["source"] == config["source"]
    assert document["reduced"] == config["reduced"]
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])
    # no width is cut: the file states the published widths
    assert document["batch_size"] == 32 and document["epochs"] == 5


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = manifest.Cell(MANIFEST, name)
    assert set(cell.entry) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell.entry["why"]) <= 200
    assert callable(cell.generator().run)
    assert callable(cell.reference().forward)
    assert set(cell.readers()) == {m["name"] for m in cell.per_layer}
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert all(m["moves"] in reported for m in cell.per_layer)
    assert manifest.traffic_path(manifest.ROOT, cell.entry["traffic"]).endswith(
        manifest.TRAFFIC_SUFFIXES
    )


def test_files_under_paths_are_named_from_the_characters_of_a_name():
    import re

    for path in MANIFEST["paths"]:
        for directory, dirs, files in os.walk(os.path.join(manifest.ROOT, path)):
            dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


@pytest.fixture()
def grown_tree(tmp_path):
    """A copy of the benchmark (and nothing of the program but an empty
    ``gordo_tpu/``) that gains one configuration, one traffic mix, one
    cell and one per-layer metric: new files and manifest entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(
        manifest.CHIP_DIR, root / "benchmarks" / "chip",
        ignore=shutil.ignore_patterns("out", "__pycache__"),
    )
    (root / "gordo_tpu").mkdir()
    before = {
        os.path.relpath(p, root): open(p, "rb").read()
        for p in _files(root / "benchmarks" / "chip")
    }
    chip = root / "benchmarks" / "chip"
    config = manifest.load_json(manifest.ROOT, MANIFEST["configs"][0]["file"])
    config.update(name="hourglass-ae-40tag", tags=40, source="a later PR's source")
    (chip / "configs" / "hourglass-ae-40tag.json").write_text(json.dumps(config))
    traffic = dict(manifest.Cell(MANIFEST, "hourglass_build").traffic, machines_per_job=64)
    (chip / "traffic" / "jobs-64x90d.json").write_text(json.dumps(traffic))
    (chip / "layer_metrics" / "jobs_in_window.py").write_text(
        '"""Jobs the window completed."""\n\n\ndef read(evidence):\n'
        '    return len(evidence["jobs"])\n'
    )
    grown = json.loads(json.dumps(MANIFEST))
    grown["configs"].append(
        {"name": "hourglass-ae-40tag", "source": "a later PR's source",
         "file": "benchmarks/chip/configs/hourglass-ae-40tag.json", "reduced": [],
         "why": "a wider machine"}
    )
    grown["workloads"].append(
        {"name": "hourglass40_build", "config": "hourglass-ae-40tag",
         "traffic": "jobs-64x90d", "chips": 1, "why": "added as data"}
    )
    grown["end_to_end"][0]["workloads"].append("hourglass40_build")
    grown["per_layer"].append(
        {"name": "jobs_in_window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "fleet build",
         "moves": "models_built_per_hour", "workloads": ["hourglass40_build"]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(grown))
    return root, grown, before


def _files(root):
    for directory, _, files in os.walk(root):
        for name in files:
            yield os.path.join(directory, name)


def test_a_later_pr_adds_a_cell_as_files_and_entries_only(grown_tree):
    root, grown, before = grown_tree
    assert manifest.problems(grown, str(root)) == []
    cell = manifest.Cell(grown, "hourglass40_build", str(root))
    assert cell.config["tags"] == 40 and cell.traffic["machines_per_job"] == 64
    assert cell.readers()["jobs_in_window"]({"jobs": [1, 2, 3]}) == 3
    # no file that was there has changed
    for relative, content in before.items():
        assert open(root / relative, "rb").read() == content


def test_run_py_resolves_the_added_cell_and_refuses_without_a_chip(grown_tree):
    """``run.py`` of the grown tree finds the new cell, hands its
    configuration and traffic to the child, and, the child finding no
    chip, exits non-zero with no result line."""
    root, _, _ = grown_tree
    proc = subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "run.py"),
         "--workload", "hourglass40_build", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300, cwd=str(root),
    )
    # the child cannot import the (empty) program either way; what is
    # checked is that the parent resolved the cell before starting it
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout
    run_dir = root / "benchmarks" / "chip" / "out" / "hourglass40_build" / "1"
    spec = json.loads((run_dir / "spec.json").read_text())
    assert spec["config"]["name"] == "hourglass-ae-40tag"
    assert spec["traffic"]["machines_per_job"] == 64


def test_run_py_refuses_without_a_chip_and_prints_no_metric():
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.CHIP_DIR, "run.py"),
         "--workload", "hourglass_build", "--seed", "991", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300, cwd=manifest.ROOT,
    )
    shutil.rmtree(os.path.join(manifest.OUT_DIR, "hourglass_build", "991"), ignore_errors=True)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout
    assert "no accelerator" in proc.stderr


def test_run_py_refuses_a_directory_without_the_program(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: non-zero exit,
    no result."""
    shutil.copytree(
        manifest.CHIP_DIR, tmp_path / "benchmarks" / "chip",
        ignore=shutil.ignore_patterns("out", "__pycache__"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "chip" / "run.py"),
         "--workload", "hourglass_build", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path),
    )
    assert proc.returncode != 0 and '"metrics"' not in proc.stdout
