"""The six readers of PR 24 (what lies inside the build phases) on the
tiny CPU run: the same code the chip runs, as a function of sizes."""

import json

import pytest

import build_worker
from tiny import CPU_DEVICE, build_spec, cell, quiet_start

NEW = (
    "build_unspanned_pct", "fetch_parallelism", "fetch_provider_share_pct",
    "fit_host_wrap_pct", "job_retrace_s", "warm_job_compile_path_s",
)


@pytest.fixture(scope="module", params=["hourglass_build", "lstm_build"])
def evidence(request, tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp(request.param + "_parts"))
    spec = build_spec(request.param, run_dir, trace=True)
    counter, errors = quiet_start()
    found = build_worker.run(spec, dict(CPU_DEVICE), counter, errors)
    c = cell(spec["cell"])
    return c, dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"])


@pytest.fixture(scope="module")
def values(evidence):
    c, found = evidence
    return {name: reader(found) for name, reader in c.readers().items()}


def test_the_cell_lists_the_six_readers_and_the_run_is_correct(evidence):
    c, found = evidence
    assert found["correct"], found["failures"]
    listed = {m["name"]: m for m in c.per_layer}
    assert set(NEW) <= set(listed)
    assert listed["warm_job_compile_path_s"]["moves"] == "setup_s"
    assert {listed[n]["layer"] for n in NEW} == {
        "fleet build", "dataset", "fused training programs", "device",
    }


def test_the_job_is_spanned_from_the_command_to_its_reporters(values):
    # the command's own start and end are all that lies under no phase
    assert 0.0 <= values["build_unspanned_pct"] < 5.0


def test_the_dataset_readers(values):
    assert 0.5 <= values["fetch_parallelism"] <= 16.0  # data_workers
    assert 0.0 < values["fetch_provider_share_pct"] < 100.0


def test_the_host_work_around_the_fit_programs(evidence, values):
    _, found = evidence
    assert 0.0 < values["fit_host_wrap_pct"] < 100.0
    # it is part of the two phases it is read from
    for job in found["jobs"]:
        phases = job["status"]["phases"]
        wrap = sum(
            part["seconds"]
            for phase in ("cv_train", "final_fit")
            for part in phases[phase]["parts"].values()
        )
        assert wrap <= phases["cv_train"]["seconds"] + phases["final_fit"]["seconds"]


def test_the_compile_path_readers(evidence, values):
    _, found = evidence
    assert values["job_retrace_s"] > 0.0
    # the warm-up job compiles what the window's jobs only load or find
    assert values["warm_job_compile_path_s"] > values["job_retrace_s"]
    assert found["warm_job"]["status"]["compile"]["programs"] >= max(
        job["status"]["compile"]["programs"] for job in found["jobs"]
    )


def test_what_the_cells_reported_before_is_still_reported(values):
    assert values["device_programs_per_job"] == 3  # cv fit, cv predict, final fit
    assert values["compiles_in_window"] == 0
    assert 0 < values["build_fetch_stage_share_pct"] < values["build_host_share_pct"] < 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_an_older_programs_evidence(evidence, name):
    """The parent writes no ``parts`` and no ``compile``: a reader of
    them returns None and does not raise; the share under no phase
    needs neither and reads the older status as it is."""
    c, found = evidence
    older = json.loads(json.dumps({k: found[k] for k in ("jobs", "warm_job")}, default=str))
    for job in older["jobs"] + [older["warm_job"]]:
        job["status"].pop("compile")
        for phase in job["status"]["phases"].values():
            phase.pop("parts", None)
    value = c.readers()[name](older)
    if name == "build_unspanned_pct":
        assert 0.0 <= value < 5.0
    else:
        assert value is None
    for job in older["jobs"] + [older["warm_job"]]:
        job["status"] = {}
    assert c.readers()[name](older) is None
