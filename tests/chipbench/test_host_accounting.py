"""The six readers of the host's accounting (PR 37): what a part
computed (``cpu_seconds``), what it moved (``bytes``), the process's
CPU a phase and the peak resident set, each on a hand-made evidence and
on the tiny CPU run. (ISSUE 37's seventh, ``collect_d2h_gbps``, read the
same number as ``collect_gbps`` in every cell and went at review; the
``d2h_seconds`` it read stays on the ``collect`` entry for
``build-status``, and the last test here holds it inside its part.)"""

import json

import jax
import pytest

import build_worker
from harness import manifest
from tiny import CPU_DEVICE, build_spec, cell, quiet_start

MANIFEST = manifest.load_manifest()

FETCH = ["hourglass_build", "lstm_build", "lstm_build_x4"]
COLLECT = ["lstm_build", "lstm_build_x4", "lfm2_moe_build", "keye_dsa_build", "laguna_swa_build"]
ALL = ["hourglass_build", "lstm_build", "lstm_build_x4", "lfm2_moe_build", "keye_dsa_build", "laguna_swa_build"]

#: ISSUE 37's table, less ``collect_d2h_gbps``: name -> (unit, better, layer, cells)
TABLE = {
    "fetch_cpu_parallelism": ("x", "higher", "dataset", FETCH),
    "fetch_resample_cpu_ms": ("ms", "lower", "dataset", FETCH),
    "stack_gbps": ("GB/s", "higher", "fused training programs", FETCH),
    "collect_gbps": ("GB/s", "higher", "fused training programs", COLLECT),
    "host_cores_busy": ("x", "higher", "fleet build", ALL),
    "host_rss_peak_gb": ("GB", "lower", "fleet build", ALL),
}

CASES = [(name, c) for name, entry in TABLE.items() for c in entry[3]]


def _job(seconds, rss=None, **phases):
    status = {"phases": phases}
    if rss is not None:
        status["resources"] = {
            "hbm_peak_bytes": None, "host_rss_peak_bytes": rss, "host_cpu_count": 13,
        }
    return {"seconds": seconds, "status": status}


def _jobs():
    """Three jobs; a reader's value is the median of its three readings."""
    def job(seconds, rss, fetch_wall, fetch_cpu, join_cpu, stack, collect, process_cpu):
        stack_bytes, stack_seconds = stack
        collect_bytes, collect_seconds, d2h = collect
        return _job(
            seconds, rss,
            data_fetch={
                "seconds": fetch_wall, "process_cpu_seconds": process_cpu[0],
                "parts": {
                    "machine_fetch": {"seconds": 16 * fetch_wall, "count": 160, "cpu_seconds": fetch_cpu},
                    "resample_join": {"seconds": 5 * fetch_wall, "count": 160, "cpu_seconds": join_cpu},
                },
            },
            cv_train={
                "seconds": 4.0, "process_cpu_seconds": process_cpu[1],
                "parts": {
                    "stack": {"seconds": 0.75 * stack_seconds, "count": 1, "cpu_seconds": 0.5, "bytes": 3 * stack_bytes // 4},
                    "collect": {
                        "seconds": 0.75 * collect_seconds, "count": 3, "cpu_seconds": 0.25,
                        "bytes": 3 * collect_bytes // 4, "d2h_seconds": 0.75 * d2h,
                    },
                    "init": {"seconds": 0.25, "count": 1, "cpu_seconds": 0.125},
                },
            },
            final_fit={
                "seconds": 2.0, "process_cpu_seconds": process_cpu[2],
                "parts": {
                    "stack": {"seconds": 0.25 * stack_seconds, "count": 1, "cpu_seconds": 0.125, "bytes": stack_bytes // 4},
                    "collect": {
                        "seconds": 0.25 * collect_seconds, "count": 1, "cpu_seconds": 0.125,
                        "bytes": collect_bytes // 4, "d2h_seconds": 0.25 * d2h,
                    },
                },
            },
            # a phase timed before the build had a recorder: no CPU key
            config_load={"seconds": 0.5},
        )

    return [
        job(16.0, 2 * 10**9, 8.0, 8.0, 1.6, (4 * 10**8, 2.0), (2 * 10**9, 4.0, 1.0), (9.0, 10.0, 5.0)),
        job(20.0, 3 * 10**9, 5.0, 20.0, 3.2, (4 * 10**8, 1.0), (2 * 10**9, 2.0, 0.5), (8.0, 1.0, 1.0)),
        job(10.0, 4 * 10**9, 4.0, 2.0, 0.8, (4 * 10**8, 0.5), (2 * 10**9, 1.0, 0.25), (3.0, 1.0, 1.0)),
    ]


#: by hand, from ``_jobs``: the median of the three jobs' readings
EXPECTED = {
    "fetch_cpu_parallelism": 1.0,  # 8/8, 20/5, 2/4
    "fetch_resample_cpu_ms": 10.0,  # 1.6, 3.2, 0.8 s over 160 machines
    "stack_gbps": 0.4,  # 0.4 GB over 2.0, 1.0, 0.5 s
    "collect_gbps": 1.0,  # 2 GB over 4.0, 2.0, 1.0 s
    "host_cores_busy": 0.5,  # 24/16, 10/20, 5/10
    "host_rss_peak_gb": 4.0,  # the last job's: a peak only grows
}
#: and of the first job alone
EXPECTED_OF_THE_FIRST = {
    "fetch_cpu_parallelism": 1.0, "fetch_resample_cpu_ms": 10.0, "stack_gbps": 0.2,
    "collect_gbps": 0.5, "host_cores_busy": 1.5, "host_rss_peak_gb": 2.0,
}


@pytest.mark.parametrize("name", sorted(TABLE))
def test_the_manifest_entry_is_the_issues_table(name):
    unit, better, layer, cells = TABLE[name]
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": better, "source": "program_counter",
        "layer": layer, "moves": "models_built_per_hour", "workloads": cells,
    }
    # appended: the entries that were there are where they were
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-6:] == list(TABLE) and names[-7] == "build_dump_share_pct"


@pytest.mark.parametrize("name,cell_name", CASES)
def test_the_reader_gives_the_arithmetic(name, cell_name):
    read = manifest.Cell(MANIFEST, cell_name).readers()[name]
    assert read({"jobs": _jobs()}) == pytest.approx(EXPECTED[name])
    # one job: its own reading
    assert read({"jobs": _jobs()[:1]}) == pytest.approx(EXPECTED_OF_THE_FIRST[name])


@pytest.mark.parametrize("name,cell_name", CASES)
def test_the_reader_finds_nothing_in_an_older_programs_evidence(name, cell_name):
    """The parent writes seconds and counts alone: every reader returns
    None and none raises, whatever is missing."""
    read = manifest.Cell(MANIFEST, cell_name).readers()[name]
    older = json.loads(json.dumps(_jobs()))
    for job in older:
        job["status"].pop("resources")
        for phase in job["status"]["phases"].values():
            phase.pop("process_cpu_seconds", None)
            for part in phase.get("parts", {}).values():
                for key in ("cpu_seconds", "bytes", "d2h_seconds"):
                    part.pop(key, None)
    assert read({"jobs": older}) is None
    for job in older:
        for phase in job["status"]["phases"].values():
            phase.pop("parts", None)
    assert read({"jobs": older}) is None
    assert read({"jobs": [{"seconds": 1.0, "status": {}}]}) is None
    assert read({"jobs": [{"seconds": 1.0, "status": None}]}) is None
    assert read({"jobs": []}) is None


def test_a_part_in_one_of_the_two_phases_is_read_from_that_one():
    """A job whose final fit stacked nothing (every machine cached)
    still reads its CV's rate."""
    read = manifest.Cell(MANIFEST, "hourglass_build").readers()["stack_gbps"]
    (job,) = _jobs()[:1]
    del job["status"]["phases"]["final_fit"]
    assert read({"jobs": [job]}) == pytest.approx(0.3 / 1.5)


# -- on the tiny CPU run ----------------------------------------------------------


@pytest.fixture(scope="module", params=["hourglass_build", "lstm_build"])
def evidence(request, tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp(request.param + "_host"))
    spec = build_spec(request.param, run_dir, trace=True)
    counter, errors = quiet_start()
    found = build_worker.run(spec, dict(CPU_DEVICE), counter, errors)
    c = cell(spec["cell"])
    yield c, dict(found, cell=c.entry, config=spec["config"], traffic=spec["traffic"])
    # leave the process's programs as cold as they were: a later file's
    # warm-up job (test_build_parts.py::test_the_compile_path_readers)
    # expects to compile what its window's jobs only find
    jax.clear_caches()


def test_every_reader_of_the_cell_reads_the_tiny_run(evidence):
    c, found = evidence
    assert found["correct"], found["failures"]
    readers = c.readers()
    listed = sorted(name for name, entry in TABLE.items() if c.name in entry[3])
    assert set(listed) <= set(readers)
    values = {name: readers[name](found) for name in listed}
    assert all(value is not None and value > 0 for value in values.values()), values
    # sixteen pool threads cannot compute on more cores than the host has
    cores = found["jobs"][-1]["status"]["resources"]["host_cpu_count"]
    assert values["fetch_cpu_parallelism"] <= cores + 0.5
    assert values["host_cores_busy"] <= cores + 0.5


def test_the_fetch_is_inside_its_collect_in_every_job(evidence):
    _, found = evidence
    for job in found["jobs"] + [found["warm_job"]]:
        for phase in ("cv_train", "final_fit", "cv_predict"):
            collect = job["status"]["phases"][phase]["parts"]["collect"]
            assert 0.0 < collect["d2h_seconds"] <= collect["seconds"]
            assert collect["bytes"] > 0
