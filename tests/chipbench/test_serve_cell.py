"""(d) The serve cell, tiny, on the CPU: the parent's generator against
the child's own code behind a stand-in for the chip; and every way a
serve run turns incorrect."""

import json
import os
import subprocess
import sys

import pytest

from harness import breakdown, child, manifest
from tiny import cell

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = {
    "machines_served": 3, "collection_history_days": 2, "rows_choices": [32, 100, 300], "pool": 16, "verify_responses": 6, "clients": 3,
    "trace_after_seconds": 0.5, "trace_seconds": 0.5, "warmup_clients": 2,
}


def _start_on_cpu(root, proc, spec):
    """``harness.child.start_child`` with the tests' CPU entry."""
    return child.start_child(
        root, proc, spec,
        script=os.path.join(HERE, "cpu_serve_worker.py"),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One tiny traced run; ``evaluate`` is called again on the same
    records with one answer turned into a 500, and with an error in the
    server's log."""
    c = cell("hourglass_serve")
    c.traffic = dict(c.traffic, **TINY)
    generator = c.generator()
    generator.start_child = _start_on_cpu
    honest = generator.evaluate
    verdicts = {}

    def evaluate(cell_, seed, report, clients, *rest):
        verdicts["honest"] = honest(cell_, seed, report, clients, *rest)
        record = clients.records[0]
        record["status"] = 500
        verdicts["non_200"] = honest(cell_, seed, report, clients, *rest)
        record["status"] = 200
        logged = dict(report, errors_logged=["gordo_tpu.server: compiled path refused"])
        verdicts["error_logged"] = honest(cell_, seed, logged, clients, *rest)
        wrong = dict(cell_.config, tags=cell_.config["tags"] + 1)
        cell_.config, kept = wrong, cell_.config
        verdicts["thresholds"] = honest(cell_, seed, report, clients, *rest)
        cell_.config = kept
        return verdicts["honest"]

    generator.evaluate = evaluate
    run_dir = str(tmp_path_factory.mktemp("serve"))
    evidence = generator.run(c, 4, 2.0, True, run_dir)
    return c, evidence, verdicts, run_dir


def test_tiny_serve_cell_is_correct(run):
    c, evidence, _, run_dir = run
    assert evidence["correct"], evidence["failures"]
    assert evidence["attempted"] > 20 and evidence["failed"] == 0
    assert evidence["worst_fraction_of_scale"] < 1e-4
    assert evidence["in_window"]["compiles"] == 0
    assert evidence["end_to_end"]["request_p99_ms"] >= evidence["end_to_end"]["request_p50_ms"] > 0
    assert evidence["end_to_end"]["rows_scored_per_s"] > 0
    assert evidence["collection"]["status"]["machines"]["completed"] == 3
    assert evidence["ingest"]["host_transfers"] + evidence["ingest"]["dlpack_transfers"] == evidence["attempted"]
    assert not os.path.exists(os.path.join(run_dir, "collection"))
    assert "lie beyond the 99th percentile" in evidence["notes"][0]


def test_the_child_has_ended(run):
    _, _, _, run_dir = run
    with open(os.path.join(run_dir, "spec.json")) as f:
        port = json.load(f)["port"]
    import socket

    with socket.socket() as sock:
        assert sock.connect_ex(("127.0.0.1", port)) != 0


@pytest.mark.parametrize("case", ["non_200", "error_logged", "thresholds"])
def test_serve_run_turns_incorrect(run, case):
    _, _, verdicts, _ = run
    assert verdicts["honest"]["correct"]
    assert not verdicts[case]["correct"]
    if case == "non_200":
        assert verdicts[case]["failed"] == 1
        assert "HTTP 500" in verdicts[case]["failures"][0]


def test_per_layer_readers_read_the_tiny_run(run):
    c, evidence, _, _ = run
    evidence = dict(evidence, cell=c.entry, config=c.config, traffic=c.traffic)
    values = {name: reader(evidence) for name, reader in c.readers().items()}
    for name in ("host_stages_p50_ms", "device_ingest_p50_ms", "inference_p50_ms"):
        assert values[name] > 0
    assert values["dlpack_share_pct"] is not None
    assert values["serve_compiles_in_window"] == 0
    for name in ("pallas_dense_roofline", "kernel_busy_share_pct",
                 "serve_device_idle_pct", "serve_hbm_peak_pct"):
        assert values[name] is None  # no device plane, no memory stats on the CPU
    assert breakdown.build(evidence) == {"device_ops": [], "idle_gaps": []}


def test_draws_come_from_the_seed():
    generator = cell("hourglass_serve").generator()
    traffic = dict(cell("hourglass_serve").traffic)
    a = generator.draw_requests(traffic, 1)
    assert a == generator.draw_requests(traffic, 1) != generator.draw_requests(traffic, 2)
    assert len(a) == traffic["pool"]
    assert {d["rows"] for d in a} == set(traffic["rows_choices"])
    machines = [d["machine"] for d in a]
    assert machines.count(0) > machines.count(traffic["machines_served"] - 1)  # Zipf


def test_server_timing_is_parsed():
    generator = cell("hourglass_serve").generator()
    header = "model_resolve;dur=0.06, inference;dur=1.83, request_walltime_s;dur=0.045"
    assert generator.parse_server_timing(header) == {
        "model_resolve": 0.06, "inference": 1.83, "request_walltime_s": 0.045,
    }
    assert generator.parse_server_timing(None) == {}


def test_gaps_are_named_by_what_the_host_did():
    intervals = [(10.0, 12.0, "build phase stage"), (12.0, 20.0, "build phase cv_train")]
    assert breakdown.name_gap(10.1, 11.9, intervals, "x") == "build phase stage"
    assert breakdown.name_gap(30.0, 31.0, intervals, "between build phases") == "between build phases"
    evidence = {
        "requests": [{"sent": 100.0, "seconds": 1.0}],
        "trace": {"profile_start_wall_ns": 100e9, "devices": [{
            "ops": [["fusion.1", 0.5, 10], ["copy.2", 0.1, 3]],
            "gaps": [[0.2e9, 0.3e9], [2.0e9, 1.0e9]],
        }]},
    }
    assert breakdown.build(evidence) == {
        "device_ops": [["fusion.1", 0.5], ["copy.2", 0.1]],
        "idle_gaps": [["no request in flight", 1.0], ["request in flight", 0.3]],
    }


def test_the_parent_encodes_without_the_program(tmp_path):
    """The bodies are encoded with nothing of ``gordo_tpu`` (and so no
    JAX) in the process, and decode as the program's own encoder's do."""
    script = (
        "import sys, json; sys.path[:0] = [%r, %r]\n"
        "import numpy as np\n"
        "from harness import manifest\n"
        "from harness.data import request_rows\n"
        "m = manifest.with_pending(manifest.load_manifest(), 'hourglass_serve')\n"
        "g = manifest.Cell(m, 'hourglass_serve').generator()\n"
        "X = request_rows({'a': {'min': 0, 'max': 1}, 'b': {'min': 2, 'max': 3}}, 5, np.random.RandomState(0))\n"
        "open(%r, 'wb').write(g.encode_arrow(X, X))\n"
        "print(json.dumps(sorted(m for m in sys.modules if m in ('jax', 'gordo_tpu'))))\n"
    ) % (manifest.CHIP_DIR, manifest.ROOT, str(tmp_path / "body"))
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout.splitlines()[-1]) == []
    import numpy as np

    from gordo_tpu.server import wire
    from harness.data import request_rows

    X = request_rows({"a": {"min": 0, "max": 1}, "b": {"min": 2, "max": 3}}, 5, np.random.RandomState(0))
    assert (tmp_path / "body").read_bytes() == wire.encode_request(X, X)
