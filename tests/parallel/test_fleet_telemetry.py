"""
Fleet-build telemetry acceptance: the span stream covers every build
phase with compile time attributed separately from run time, per-member
training summaries land in BuildMetadata and Prometheus, and the
``build_status.json`` surface shows live progress mid-build (exercised
through the fault-injection kill site) and renders through the
``build-status`` CLI.
"""

import json
import os

import pytest

from gordo_tpu import serializer, telemetry
from gordo_tpu.machine import Machine
from gordo_tpu.parallel import FleetBuilder, journal
from gordo_tpu.utils import faults
from gordo_tpu.utils.faults import FaultRule, inject

pytestmark = pytest.mark.observability

DATASET = {
    "type": "RandomDataset",
    "train_start_date": "2020-01-01T00:00:00+00:00",
    "train_end_date": "2020-01-05T00:00:00+00:00",
}

MODEL = {
    "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "gordo_tpu.models.JaxAutoEncoder": {
                "kind": "feedforward_hourglass",
                "encoding_layers": 1,
                "epochs": 1,
            }
        }
    }
}

#: the pipeline phases the ISSUE's acceptance criterion names: plan →
#: fetch → stage → CV → final fit → dump must all appear as spans
REQUIRED_PHASES = {
    "plan",
    "data_fetch",
    "stage",
    "cv_train",
    "final_fit",
    "dump",
}


def make_machine(name, tags=("t1", "t2")):
    return Machine.from_config(
        {
            "name": name,
            "model": MODEL,
            "dataset": {**DATASET, "tag_list": list(tags)},
        },
        project_name="telemetry-test",
    )


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def read_trace(output_dir):
    path = os.path.join(output_dir, telemetry.progress.BUILD_TRACE_FILE)
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_span_stream_covers_every_phase_and_attributes_compile(tmp_path):
    """One CPU fleet build emits spans for every pipeline phase, device
    programs carry bucket attribution (member count, shape, bytes), and
    a second build of the same fleet shows the SAME program signatures
    as steady-state runs — first-call compile attributed separately."""
    telemetry.reset_seen_programs()
    machines = [make_machine("sp-a"), make_machine("sp-b")]
    out = tmp_path / "out"
    builder = FleetBuilder(machines)
    results = builder.build(output_dir=str(out))
    assert len(results) == 2

    spans = read_trace(str(out))
    phases = {
        s["attributes"]["phase"]
        for s in spans
        if s["name"] == "build_phase"
    }
    assert REQUIRED_PHASES <= phases

    # the whole build is one trace, rooted at fleet_build
    roots = [s for s in spans if s["name"] == "fleet_build"]
    assert len(roots) == 1
    assert len({s["context"]["trace_id"] for s in spans}) == 1

    programs = [s for s in spans if s["name"] == "device_program"]
    assert programs, "device programs must be traced"
    for span in programs:
        attrs = span["attributes"]
        assert attrs["program"]
        assert attrs["members"] >= 1
        assert attrs["shape"].startswith("(")
        assert attrs.get("bytes", 0) > 0 or "predict" in attrs["program"]
    # compile-vs-run attribution within one build: the FIRST occurrence
    # of each (program, stacked-shape) signature is the compile, every
    # later one a steady-state run. (Under the test mesh the CV and
    # final-fit buckets pad to the same stacked shape, so the final fit
    # is already a cache hit — exactly the signal this layer exists for.)
    seen_signatures = set()
    for span in programs:
        signature = (
            span["attributes"]["program"],
            span["attributes"]["shape"],
        )
        assert span["attributes"]["compile"] == (
            signature not in seen_signatures
        )
        seen_signatures.add(signature)
    assert any(s["attributes"]["compile"] for s in programs)

    # per-member training summaries: events in the trace AND metadata
    trained = [s for s in spans if s["name"] == "member_trained"]
    assert sorted(s["attributes"]["machine"] for s in trained) == [
        "sp-a",
        "sp-b",
    ]
    for _, machine in results:
        training = machine.metadata.build_metadata.model.training
        assert training.final_loss is not None
        assert training.best_loss <= training.final_loss or (
            training.best_loss is not None
        )
        assert training.epochs_run == 1 and training.epochs_configured == 1
        assert training.early_stop_epoch is None
    # ... and in the dumped artifact metadata
    meta = serializer.load_metadata(str(out / "sp-a"))
    summary = meta["metadata"]["build_metadata"]["model"]["training"]
    assert summary["epochs_run"] == 1
    assert summary["final_loss"] is not None

    # second build, same fleet: identical program signatures are now
    # cache hits — compile=False runs, separately attributed
    out2 = tmp_path / "out2"
    FleetBuilder([make_machine("sp-a"), make_machine("sp-b")]).build(
        output_dir=str(out2)
    )
    programs2 = [
        s for s in read_trace(str(out2)) if s["name"] == "device_program"
    ]
    assert programs2 and all(
        not s["attributes"]["compile"] for s in programs2
    )


def test_prometheus_build_metrics_exported(tmp_path):
    from prometheus_client import REGISTRY

    telemetry.reset_seen_programs()
    builder = FleetBuilder([make_machine("pm-a")])
    builder.build(output_dir=str(tmp_path / "out"))

    def sample(name, labels):
        return REGISTRY.get_sample_value(name, labels)

    for phase in REQUIRED_PHASES:
        count = sample(
            "gordo_fleet_build_phase_duration_seconds_count",
            {"project": "telemetry-test", "phase": phase},
        )
        assert count and count >= 1, phase
    assert (
        sample(
            "gordo_fleet_member_final_loss_count",
            {"project": "telemetry-test"},
        )
        >= 1
    )
    assert (
        sample(
            "gordo_fleet_build_machines_completed",
            {"project": "telemetry-test"},
        )
        >= 1
    )
    # at least one program compiled for this project's shapes
    compile_count = sum(
        s.value
        for metric in REGISTRY.collect()
        if metric.name == "gordo_fleet_compile_duration_seconds"
        for s in metric.samples
        if s.name.endswith("_count")
        and s.labels.get("project") == "telemetry-test"
    )
    assert compile_count >= 1


def test_build_status_shows_live_progress_mid_build_and_after_kill(
    tmp_path, monkeypatch
):
    """The acceptance drill: a process death mid-dump (the existing
    ``process_kill_after_n_machines`` site) leaves a ``build_status.json``
    still in state ``running`` whose completed count already includes
    every machine journaled before the kill — with the heartbeat
    throttle at 0 the status is never behind the journal — and the
    ``build-status`` CLI renders it."""
    from click.testing import CliRunner

    monkeypatch.setenv(telemetry.HEARTBEAT_ENV, "0")

    from gordo_tpu.cli.cli import gordo_tpu_cli
    from gordo_tpu.parallel.journal import BuildJournal

    out = tmp_path / "out"
    names = [f"ks-{i}" for i in range(3)]
    with inject(
        FaultRule("process_kill_after_n_machines", after=1, times=None)
    ):
        with pytest.raises(SystemExit):
            FleetBuilder([make_machine(n) for n in names]).build(
                output_dir=str(out)
            )

    doc = telemetry.load_status(str(out))
    assert doc is not None
    assert doc["state"] == "running"  # the kill outran finish()
    journaled_built = [
        name
        for name, entry in BuildJournal.load(str(out)).machines().items()
        if entry["status"] == "built"
    ]
    assert len(journaled_built) >= 2
    assert doc["machines"]["completed"] >= len(journaled_built)
    assert doc["machines"]["total"] == 3
    assert doc["phases"]["dump"]["status"] == "running"

    rendered = telemetry.render_status(doc)
    assert "running" in rendered and "/3 done" in rendered

    runner = CliRunner()
    result = runner.invoke(gordo_tpu_cli, ["build-status", str(out)])
    assert result.exit_code == 0
    assert "running" in result.output
    raw = runner.invoke(
        gordo_tpu_cli, ["build-status", str(out), "--as-json"]
    )
    assert json.loads(raw.output)["state"] == "running"

    # resume completes the fleet and the status reflects it
    resumer = FleetBuilder([make_machine(n) for n in names])
    resumer.build(output_dir=str(out), resume=True)
    doc = telemetry.load_status(str(out))
    assert doc["state"] == "complete"
    assert doc["machines"]["resumed"] == len(resumer.resumed)
    assert (
        doc["machines"]["completed"] + doc["machines"]["resumed"]
        == doc["machines"]["total"]
    )


def test_failed_machines_counted_and_status_completes(tmp_path):
    out = tmp_path / "out"
    machines = [make_machine("ok-m"), make_machine("dead-m")]
    builder = FleetBuilder(machines, data_retries=0, data_backoff=0)
    with inject(FaultRule("data_fetch", match="dead-*", times=None)):
        results = builder.build(output_dir=str(out))
    assert [m.name for _, m in results] == ["ok-m"]
    doc = telemetry.load_status(str(out))
    assert doc["state"] == "complete"
    assert doc["machines"]["failed"] == 1
    assert doc["machines"]["completed"] == 1
    spans = read_trace(str(out))
    failed_events = [s for s in spans if s["name"] == "machine_failed"]
    assert [s["attributes"]["machine"] for s in failed_events] == ["dead-m"]


def test_an_instrumented_build_writes_the_health_ledger(tmp_path):
    """The build narrates each machine into ``fleet_health.json`` beside
    the artifacts: what ``fleet-status`` and the lifecycle supervisor
    read before the first request is ever served."""
    from gordo_tpu.telemetry.fleet_health import load_health, reset_ledgers

    reset_ledgers()
    out = tmp_path / "out"
    try:
        results = FleetBuilder([make_machine("led-a"), make_machine("led-b")]).build(
            output_dir=str(out)
        )
    finally:
        reset_ledgers()
    assert len(results) == 2
    assert (out / telemetry.FLEET_HEALTH_FILE).is_file()
    machines = load_health(str(out))["machines"]
    assert sorted(machines) == ["led-a", "led-b"]
    assert [machines[name]["build"]["failed"] for name in sorted(machines)] == [False, False]


def test_telemetry_off_leaves_no_trace_files(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.TELEMETRY_ENV, "0")
    out = tmp_path / "out"
    builder = FleetBuilder([make_machine("off-m")])
    results = builder.build(output_dir=str(out))
    assert len(results) == 1
    assert telemetry.load_status(str(out)) is None
    assert not (out / telemetry.progress.BUILD_TRACE_FILE).exists()
    # the artifact contract is untouched
    assert serializer.load_metadata(str(out / "off-m"))


def test_serving_store_ignores_telemetry_files(tmp_path):
    """build_status.json / build_trace.jsonl are builder droppings: the
    model listing and the serving store must never mistake them for
    artifacts, and revision cleanup must treat a directory holding only
    them as empty."""
    out = tmp_path / "out"
    FleetBuilder([make_machine("srv-m")]).build(output_dir=str(out))
    assert (out / "build_status.json").is_file()
    assert (out / "build_trace.jsonl").is_file()
    assert serializer.list_model_dirs(str(out)) == ["srv-m"]
    from gordo_tpu.server.fleet_store import RevisionFleet

    assert RevisionFleet(str(out)).warm() == ["srv-m"]
    assert serializer.is_builder_dropping("build_status.json")
    assert serializer.is_builder_dropping("build_trace.jsonl")


def test_the_dump_phase_says_what_it_wrote(tmp_path):
    """The ``write`` part of ``dump`` carries the bytes of the
    ``model.pkl`` files it wrote and the part of them hashed on the
    helper thread: none of a dense member's few KB."""
    names = ["dw-a", "dw-b"]
    out = tmp_path / "out"
    FleetBuilder([make_machine(n) for n in names]).build(output_dir=str(out))

    parts = telemetry.load_status(str(out))["phases"]["dump"]["parts"]
    assert parts["write"]["count"] == 2 and parts["write"]["seconds"] > 0.0
    written = [
        s["attributes"]
        for s in read_trace(str(out))
        if s["name"] == "build_part" and s["attributes"]["part"] == "write"
    ]
    assert len(written) == 1 and written[0]["phase"] == "dump"
    assert written[0]["bytes"] == sum(
        os.path.getsize(out / n / serializer.MODEL_FILE) for n in names
    )
    assert written[0]["bytes_hashed_beside_write"] == 0
    for n in names:
        assert journal.artifact_complete(str(out / n))


# -- what the host computed, moved and waited for (PR 37) ---------------------


@pytest.fixture(scope="module")
def accounted(tmp_path_factory):
    """One tiny CPU build of two machines: its status and its spans."""
    out = str(tmp_path_factory.mktemp("accounted") / "out")
    FleetBuilder([make_machine("acc-a"), make_machine("acc-b")]).build(output_dir=out)
    return telemetry.load_status(out), read_trace(out)


#: every key PR 37 added to ``build_status.json``, as a path into it
NEW_STATUS_KEYS = [
    ("resources", "hbm_peak_bytes"),
    ("resources", "host_rss_peak_bytes"),
    ("resources", "host_cpu_count"),
    ("phases", "data_fetch", "cpu_seconds"),
    ("phases", "data_fetch", "process_cpu_seconds"),
    ("phases", "cv_train", "process_cpu_seconds"),
    ("phases", "data_fetch", "parts", "machine_fetch", "cpu_seconds"),
    ("phases", "data_fetch", "parts", "resample_join", "cpu_seconds"),
    ("phases", "data_fetch", "parts", "provider_read", "cpu_seconds"),
    ("phases", "cv_train", "parts", "stack", "bytes"),
    ("phases", "cv_train", "parts", "stack", "cpu_seconds"),
    ("phases", "cv_train", "parts", "h2d", "bytes"),
    ("phases", "cv_train", "parts", "collect", "bytes"),
    ("phases", "cv_train", "parts", "collect", "d2h_seconds"),
    ("phases", "final_fit", "parts", "stack", "bytes"),
    ("phases", "final_fit", "parts", "collect", "d2h_seconds"),
    ("phases", "cv_predict", "parts", "collect", "bytes"),
    ("phases", "dump", "parts", "write", "cpu_seconds"),
    ("phases", "dump", "parts", "write", "bytes"),
    ("phases", "dump", "parts", "serialize", "cpu_seconds"),
]


@pytest.mark.parametrize("path", NEW_STATUS_KEYS, ids="/".join)
def test_a_tiny_build_yields_every_key_of_the_hosts_accounting(accounted, path):
    node, _ = accounted
    for key in path:
        assert key in node, path
        node = node[key]
    if path[-1] != "hbm_peak_bytes":  # the CPU backend reports no memory
        assert isinstance(node, (int, float)) and node >= 0


def test_the_accounting_adds_no_line_and_no_span_name(accounted):
    """The spans a job wrote before carry the new attributes; the
    ``device_utilization`` events went (their numbers are in the
    status's ``resources``), and nothing else was added."""
    _, spans = accounted
    assert {s["name"] for s in spans} == {
        "fleet_build", "build_phase", "build_part", "device_program",
        "member_trained", "machine_built", "fleet_plan", "fleet_plan_accuracy",
    }
    collects = [
        s["attributes"] for s in spans
        if s["name"] == "build_part" and s["attributes"]["part"] == "collect"
    ]
    assert len(collects) == 3  # cv fit, cv predict, final fit
    assert all(a["d2h_seconds"] > 0 and a["bytes"] > 0 for a in collects)
