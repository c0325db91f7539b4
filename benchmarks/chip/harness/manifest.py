"""
``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name the manifest
gives it. A later PR adds a cell by adding files and manifest entries;
no file that is here has to be edited.
"""

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List

#: benchmarks/chip/
CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the checkout
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))
#: everything a run writes
OUT_DIR = os.path.join(CHIP_DIR, "out")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


class ManifestError(Exception):
    """The manifest, or a file it names, is missing or malformed."""


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as exc:
        raise ManifestError(f"cannot read {path}: {exc}") from exc


def with_pending(manifest: Dict[str, Any], name: str, root: str = ROOT) -> Dict[str, Any]:
    """``manifest`` grown by the entries of ``pending/<name>.json``: a
    cell whose files are in the tree and whose manifest entries wait for
    the PR that first measures it on the chip."""
    pending = load_json(root, os.path.join(os.path.relpath(CHIP_DIR, ROOT), "pending", f"{name}.json"))
    grown = json.loads(json.dumps(manifest))
    for group in ("workloads", "end_to_end", "per_layer"):
        grown[group].extend(pending.get(group, []))
    return grown


def chip_dir(root: str) -> str:
    return os.path.join(root, os.path.relpath(CHIP_DIR, ROOT))


def load_json(root: str, relative: str) -> Dict[str, Any]:
    path = os.path.join(root, relative)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as exc:
        raise ManifestError(f"cannot read {relative}: {exc}") from exc


def load_module(root: str, directory: str, name: str):
    """``benchmarks/chip/<directory>/<name>.py`` of the checkout at
    ``root``, by file path (names may hold ``-`` and ``.``)."""
    path = os.path.join(chip_dir(root), directory, f"{name}.py")
    if not os.path.isfile(path):
        raise ManifestError(f"no {directory}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{directory}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def traffic_path(root: str, traffic: str) -> str:
    """The data file of a traffic mix: ``traffic/<name>.<suffix>``."""
    for suffix in TRAFFIC_SUFFIXES:
        path = os.path.join(chip_dir(root), "traffic", traffic + suffix)
        if os.path.isfile(path):
            return path
    raise ManifestError(f"no traffic/{traffic}.json")


def metrics_of(manifest: Dict[str, Any], group: str, cell: str) -> List[dict]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that
    ``cell`` reports: those that list it, and those that list no cell."""
    return [
        m
        for m in manifest[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


class Cell:
    """One entry of ``workloads`` with everything it resolves to."""

    def __init__(self, manifest: Dict[str, Any], name: str, root: str = ROOT):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise ManifestError(
                f"no workload {name!r}; the manifest has {sorted(cells)}"
            )
        self.root = root
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in manifest["configs"]}
        if self.entry["config"] not in configs:
            raise ManifestError(f"{name}: no config {self.entry['config']!r}")
        self.config_entry = configs[self.entry["config"]]
        self.config = load_json(root, self.config_entry["file"])
        with open(traffic_path(root, self.entry["traffic"])) as f:
            self.traffic = json.load(f)
        self.end_to_end = metrics_of(manifest, "end_to_end", name)
        self.per_layer = metrics_of(manifest, "per_layer", name)

    def generator(self):
        """The one general generator of this cell's kind of traffic."""
        return load_module(self.root, "traffic", self.traffic["kind"])

    def reference(self):
        return load_module(self.root, "reference", self.config["reference"])

    def readers(self) -> Dict[str, Callable[[dict], Any]]:
        return {
            m["name"]: load_module(self.root, "layer_metrics", m["name"]).read
            for m in self.per_layer
        }


def problems(manifest: Dict[str, Any], root: str = ROOT) -> List[str]:
    """Everything wrong with the manifest and the files it names, as the
    benchmark's own tests and ``run.py`` check it (the driver checks the
    rest of its contract itself)."""
    found: List[str] = []

    def name_ok(value: Any, what: str) -> None:
        if not isinstance(value, str) or not NAME.match(value):
            found.append(f"{what}: {value!r} is not a name")

    for group in ("end_to_end", "per_layer"):
        for metric in manifest.get(group, []):
            name_ok(metric.get("name"), f"{group} metric")
            if not UNIT.match(str(metric.get("unit", ""))):
                found.append(f"{metric.get('name')}: unit {metric.get('unit')!r}")
            if metric.get("better") not in ("lower", "higher"):
                found.append(f"{metric.get('name')}: better {metric.get('better')!r}")
            if metric.get("source") not in SOURCES:
                found.append(f"{metric.get('name')}: source {metric.get('source')!r}")
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in manifest.get(g, [])]
    if len(names) != len(set(names)):
        found.append("two metrics share a name")
    e2e = {m["name"] for m in manifest.get("end_to_end", [])}
    if "setup_s" not in e2e:
        found.append("no setup_s among end_to_end")
    for metric in manifest.get("per_layer", []):
        if metric.get("moves") not in e2e:
            found.append(f"{metric['name']}: moves {metric.get('moves')!r}")
    for config in manifest.get("configs", []):
        name_ok(config.get("name"), "config")
        for key in config.get("reduced", []):
            name_ok(key, f"{config.get('name')}.reduced")
    pairs = set()
    for entry in manifest.get("workloads", []):
        name_ok(entry.get("name"), "workload")
        name_ok(entry.get("traffic"), f"{entry.get('name')}.traffic")
        pair = (entry.get("config"), entry.get("traffic"))
        if pair in pairs:
            found.append(f"{pair} appears twice")
        pairs.add(pair)
        try:
            cell = Cell(manifest, entry["name"], root)
            cell.generator()
            cell.reference()
            cell.readers()
        except (ManifestError, KeyError) as exc:
            found.append(f"{entry.get('name')}: {exc}")
            continue
        reported = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in reported or len(reported) < 2 or not cell.per_layer:
            found.append(f"{cell.name}: needs setup_s, one more end-to-end "
                         "metric and a per-layer metric")
        for metric in cell.per_layer:
            if metric["moves"] not in reported:
                found.append(
                    f"{cell.name}: {metric['name']} moves {metric['moves']}, "
                    "which the cell does not report"
                )
    return found
