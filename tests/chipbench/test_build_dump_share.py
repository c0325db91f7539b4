"""``build_dump_share_pct`` (PR 35): the ``dump`` phase's share of a
job, for every build cell, read as ``build_fetch_stage_share_pct`` reads
its phases."""

import pytest

from harness import manifest

MANIFEST = manifest.load_manifest()
BUILD_CELLS = next(
    m for m in MANIFEST["end_to_end"] if m["name"] == "models_built_per_hour"
)["workloads"]


def _job(seconds, **phases):
    return {
        "seconds": seconds,
        "status": {"phases": {p: {"seconds": s} for p, s in phases.items()}},
    }


def test_the_manifest_lists_it_for_every_build_cell():
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == "build_dump_share_pct")
    assert entry == {
        "name": "build_dump_share_pct", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "fleet build",
        "moves": "models_built_per_hour", "workloads": BUILD_CELLS,
    }


@pytest.mark.parametrize("name", BUILD_CELLS)
def test_the_reader_is_the_dump_phase_over_the_job(name):
    read = manifest.Cell(MANIFEST, name).readers()["build_dump_share_pct"]
    jobs = [
        _job(20.0, dump=5.0, cv_train=10.0),
        _job(40.0, dump=4.0, data_fetch=30.0),
        _job(10.0, dump=1.0),
    ]
    assert read({"jobs": jobs}) == pytest.approx(10.0)  # median of 25, 10, 10
    # a job that never reached the phase reads 0, as the sibling readers do
    assert read({"jobs": [_job(10.0, cv_train=9.0)]}) == 0.0
