"""
Deterministic fault-injection coverage of the fleet build supervisor:
crash-safe atomic dumps, the build journal + --resume, bucket bisection
with sequential degradation, and data-plane retry — every path the
reference got for free from Argo pod isolation, exercised on CPU.
"""

import os

import numpy as np
import pytest

from gordo_tpu import serializer
from gordo_tpu.machine import Machine
from gordo_tpu.parallel import FleetBuilder
from gordo_tpu.parallel.journal import (
    JOURNAL_FILE,
    BuildJournal,
    artifact_complete,
    clean_staging_dirs,
)
from gordo_tpu.utils import faults
from gordo_tpu.utils.faults import FaultRule, inject

pytestmark = pytest.mark.faults

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


def make_machine(name, tags=("t1", "t2"), model=None):
    return Machine.from_config(
        {
            "name": name,
            "model": model or MODEL,
            "dataset": {**DATASET, "tag_list": list(tags)},
        },
        project_name="fault-test",
    )


@pytest.fixture(autouse=True)
def _clean_registry():
    faults.clear()
    yield
    faults.clear()


def artifact_dirs(output_dir):
    return sorted(
        e
        for e in os.listdir(output_dir)
        if os.path.isdir(os.path.join(output_dir, e)) and not e.startswith(".")
    )


def staging_dirs(output_dir):
    return [e for e in os.listdir(output_dir) if e.startswith(".") and ".tmp-" in e]


# -- the acceptance path: kill after N machines, then --resume -----------


def test_kill_mid_fleet_then_resume_completes_without_rebuilds(tmp_path):
    """A process death after N machines + ``--resume`` must yield the
    same artifact contract as an uninterrupted build: every machine's
    artifact complete, journaled machines NOT rebuilt, and no
    ``.tmp-*`` staging dirs anywhere the serving store could see."""
    out = tmp_path / "out"
    names = [f"mk-{i}" for i in range(4)]
    machines = [make_machine(n) for n in names]

    # First two artifact dumps land; every later dump dies mid-write
    # (SystemExit, like a kill — and _try_call must NOT swallow it).
    with inject(
        FaultRule("dump_artifact", after=2, times=None, exc=SystemExit)
    ):
        with pytest.raises(SystemExit):
            FleetBuilder(machines).build(output_dir=str(out))

    done = artifact_dirs(out)
    assert len(done) == 2
    assert staging_dirs(out) == []  # atomic dump cleaned its staging dirs
    journal = BuildJournal.load(str(out))
    state = journal.machines()
    assert sorted(n for n, e in state.items() if e["status"] == "built") == done
    # interrupted machines are journaled at their last completed phase
    for name in set(names) - set(done):
        assert state[name]["status"] in ("planned", "data_loaded", "cv_done")
    for name in done:
        assert artifact_complete(str(out / name))

    before = {
        name: (
            (out / name / "model.pkl").read_bytes(),
            (out / name / "model.pkl").stat().st_mtime_ns,
        )
        for name in done
    }

    resumer = FleetBuilder([make_machine(n) for n in names])
    results = resumer.build(output_dir=str(out), resume=True)

    assert sorted(resumer.resumed) == done
    assert sorted(m.name for _, m in results) == sorted(set(names) - set(done))
    assert resumer.build_errors == {}
    assert artifact_dirs(out) == sorted(names)
    assert staging_dirs(out) == []
    # journaled-complete machines were not rebuilt: bytes AND mtime equal
    for name in done:
        assert (
            (out / name / "model.pkl").read_bytes(),
            (out / name / "model.pkl").stat().st_mtime_ns,
        ) == before[name]
    final_state = BuildJournal.load(str(out)).machines()
    assert all(e["status"] == "built" for e in final_state.values())
    # contract parity with an uninterrupted build: same dir set, same
    # files per dir, every artifact loadable and servable
    uninterrupted = tmp_path / "uninterrupted"
    FleetBuilder([make_machine(n) for n in names]).build(
        output_dir=str(uninterrupted)
    )
    assert artifact_dirs(uninterrupted) == artifact_dirs(out)
    for name in names:
        assert sorted(os.listdir(out / name)) == sorted(
            os.listdir(uninterrupted / name)
        )
        model = serializer.load(str(out / name))
        assert model.aggregate_threshold_ is not None


def test_process_kill_site_fires_after_machine_completes(tmp_path):
    """The ``process_kill_after_n_machines`` site fires AFTER the Nth+1
    machine's artifact landed and was journaled — the journal is never
    behind the artifacts."""
    out = tmp_path / "out"
    machines = [make_machine(f"pk-{i}") for i in range(3)]
    with inject(
        FaultRule("process_kill_after_n_machines", after=1, times=None)
    ):
        with pytest.raises(SystemExit):
            FleetBuilder(machines).build(output_dir=str(out))
    done = artifact_dirs(out)
    assert len(done) >= 2  # the first pass-through + the firing machine
    state = BuildJournal.load(str(out)).machines()
    for name in done:
        assert state[name]["status"] == "built"
    resumer = FleetBuilder([make_machine(f"pk-{i}") for i in range(3)])
    resumer.build(output_dir=str(out), resume=True)
    assert sorted(resumer.resumed) == done
    assert artifact_dirs(out) == sorted(m.name for m in machines)


def test_resume_rebuilds_on_config_hash_mismatch(tmp_path):
    out = tmp_path / "out"
    FleetBuilder([make_machine("cfg-m")]).build(output_dir=str(out))
    mtime = (out / "cfg-m" / "model.pkl").stat().st_mtime_ns

    changed_model = {
        "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
            "base_estimator": {
                "gordo_tpu.models.JaxAutoEncoder": {
                    "kind": "feedforward_hourglass",
                    "encoding_layers": 1,
                    "epochs": 2,  # config changed → hash changed
                }
            }
        }
    }
    resumer = FleetBuilder([make_machine("cfg-m", model=changed_model)])
    resumer.build(output_dir=str(out), resume=True)
    assert resumer.resumed == []
    assert (out / "cfg-m" / "model.pkl").stat().st_mtime_ns != mtime


def test_resume_rebuilds_corrupt_artifact(tmp_path):
    """A journal that says ``built`` is never trusted over the artifact:
    a truncated model.pkl fails the checksum and rebuilds."""
    out = tmp_path / "out"
    FleetBuilder([make_machine("cor-m")]).build(output_dir=str(out))
    model_path = out / "cor-m" / "model.pkl"
    model_path.write_bytes(model_path.read_bytes()[:10])
    assert not artifact_complete(str(out / "cor-m"))

    resumer = FleetBuilder([make_machine("cor-m")])
    results = resumer.build(output_dir=str(out), resume=True)
    assert resumer.resumed == []
    assert [m.name for _, m in results] == ["cor-m"]
    assert artifact_complete(str(out / "cor-m"))
    assert serializer.load(str(out / "cor-m")).aggregate_threshold_ is not None


def test_resumable_names_mirrors_builder_resume_filter(tmp_path):
    """Every process of a multi-host build must derive the same resume
    skip-set (one SPMD program): the read-only helper non-coordinators
    use has to agree exactly with the coordinator's builder filter."""
    from gordo_tpu.parallel.journal import resumable_names

    out = tmp_path / "out"
    names = [f"mh-{i}" for i in range(3)]
    FleetBuilder([make_machine(n) for n in names[:2]]).build(output_dir=str(out))

    machines = [make_machine(n) for n in names]
    helper_view = resumable_names(str(out), machines)
    resumer = FleetBuilder(machines)
    resumer.build(output_dir=str(out), resume=True)
    assert sorted(helper_view) == sorted(resumer.resumed) == names[:2]


# -- bucket degradation ---------------------------------------------------


def test_resource_exhausted_bisects_and_isolates_poison_member(tmp_path):
    """An injected per-bucket RESOURCE_EXHAUSTED completes the build via
    bisection: the poisonous machine is isolated out of the fleet path
    and rebuilt sequentially; healthy machines never notice."""
    out = tmp_path / "out"
    machines = [
        make_machine("good-a"),
        make_machine("poison-x"),
        make_machine("good-b"),
    ]
    builder = FleetBuilder(machines)
    with inject(FaultRule("device_program", match="poison-*", times=None)):
        results = builder.build(output_dir=str(out))

    assert builder.build_errors == {}
    assert sorted(m.name for _, m in results) == ["good-a", "good-b", "poison-x"]
    assert set(builder.degraded) == {"poison-x"}
    assert builder.robustness["sequential_degraded"] == 1
    assert builder.robustness["bucket_bisects"] >= 1
    # trainer-internal splits are attributed to the machines that rode
    # through them, so artifact metadata agrees with the fleet counters
    by_name = {m.name: m for _, m in results}
    assert (
        by_name["good-a"].metadata.build_metadata.robustness.bucket_bisects >= 1
    )
    assert artifact_dirs(out) == ["good-a", "good-b", "poison-x"]
    for _, machine in results:
        loaded = serializer.load(str(out / machine.name))
        assert loaded.aggregate_threshold_ is not None


def test_over_packed_bucket_resolves_by_splitting():
    """A device error that stops reproducing once the bucket is smaller
    (the over-packed-HBM case) resolves purely by bisection — every
    machine still builds on the fleet path, nothing degrades."""
    from gordo_tpu.parallel.fleet import FleetTrainer

    machines = [make_machine(f"pack-{i}") for i in range(4)]
    builder = FleetBuilder(machines)
    trainer = builder.trainer
    big_bucket_failures = {"n": 0}
    real = FleetTrainer._train_bucket

    def oom_on_big_buckets(self, spec, n_padded, bucket, config, m_padded=None, **where):
        if len(bucket) > 2:
            big_bucket_failures["n"] += 1
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory (injected)")
        return real(self, spec, n_padded, bucket, config, m_padded=m_padded, **where)

    FleetTrainer._train_bucket = oom_on_big_buckets
    try:
        results = builder.build()
    finally:
        FleetTrainer._train_bucket = real

    assert big_bucket_failures["n"] >= 1
    assert builder.build_errors == {} and builder.degraded == {}
    assert sorted(m.name for _, m in results) == [m.name for m in machines]
    assert builder.robustness["bucket_bisects"] >= 1


# -- data-plane retry -----------------------------------------------------


def test_data_fetch_retries_through_transient_failures(tmp_path):
    out = tmp_path / "out"
    machines = [make_machine("flaky-m"), make_machine("steady-m")]
    builder = FleetBuilder(machines, data_retries=2, data_backoff=0)
    with inject(FaultRule("data_fetch", match="flaky-*", times=2)):
        results = builder.build(output_dir=str(out))

    assert builder.build_errors == {}
    assert sorted(m.name for _, m in results) == ["flaky-m", "steady-m"]
    assert builder.robustness["data_fetch_retries"] == 2
    by_name = {m.name: m for _, m in results}
    flaky_meta = by_name["flaky-m"].metadata.build_metadata.robustness
    assert flaky_meta.data_fetch_retries == 2
    steady_meta = by_name["steady-m"].metadata.build_metadata.robustness
    assert steady_meta.data_fetch_retries == 0
    # the counters ride into the dumped artifact metadata
    meta = serializer.load_metadata(str(out / "flaky-m"))
    assert (
        meta["metadata"]["build_metadata"]["robustness"]["data_fetch_retries"]
        == 2
    )


def test_data_fetch_exhaustion_fails_only_that_machine():
    machines = [make_machine("dead-m"), make_machine("live-m")]
    builder = FleetBuilder(machines, data_retries=1, data_backoff=0)
    with inject(FaultRule("data_fetch", match="dead-*", times=None)):
        results = builder.build()
    assert [m.name for _, m in results] == ["live-m"]
    assert set(builder.build_errors) == {"dead-m"}
    assert isinstance(builder.build_errors["dead-m"], faults.FaultInjected)


# -- atomic dumps ---------------------------------------------------------


def test_dump_fault_leaves_no_partial_artifact(tmp_path):
    """A failure mid-dump (after files staged, before the rename) must
    leave NOTHING at the artifact path — no staging dir, no half-written
    model.pkl a resume or the serving store could load."""
    out = tmp_path / "out"
    machines = [make_machine("dump-ok"), make_machine("dump-bad")]
    builder = FleetBuilder(machines)
    with inject(
        FaultRule("dump_artifact", match="dump-bad", times=None, exc=OSError)
    ):
        results = builder.build(output_dir=str(out))
    assert [m.name for _, m in results] == ["dump-ok"]
    assert set(builder.build_errors) == {"dump-bad"}
    assert artifact_dirs(out) == ["dump-ok"]
    assert staging_dirs(out) == []
    state = BuildJournal.load(str(out)).machines()
    assert state["dump-bad"]["status"] == "failed"


def test_serving_store_ignores_journal_and_staging_dirs(tmp_path):
    out = tmp_path / "out"
    FleetBuilder([make_machine("served-m")]).build(output_dir=str(out))
    assert (out / JOURNAL_FILE).is_file()
    (out / ".leftover.tmp-123abc").mkdir()  # as a killed builder leaves it
    (out / ".leftover.tmp-123abc" / "model.pkl").write_bytes(b"partial")

    from gordo_tpu.server.fleet_store import RevisionFleet

    assert RevisionFleet(str(out)).warm() == ["served-m"]


# -- journal + staging plumbing ------------------------------------------


class TestBuildJournal:
    def test_record_and_load_round_trip(self, tmp_path):
        journal = BuildJournal(str(tmp_path))
        journal.record("m-1", "planned", config_hash="abc")
        journal.record("m-1", "built")
        journal.record("m-2", "failed", error="ValueError('boom')")
        loaded = BuildJournal.load(str(tmp_path))
        assert loaded.get("m-1") == {"status": "built", "config_hash": "abc"}
        assert loaded.get("m-2")["error"] == "ValueError('boom')"

    def test_corrupt_journal_starts_fresh(self, tmp_path):
        (tmp_path / JOURNAL_FILE).write_text("{not json")
        assert BuildJournal.load(str(tmp_path)).machines() == {}

    def test_unknown_status_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            BuildJournal(str(tmp_path)).record("m", "half-done")

    def test_event_overlay_is_durable_and_compacts(self, tmp_path):
        """Per-machine record(flush=True) appends O(1) event lines that
        load() applies over the base; flush() compacts them away."""
        journal = BuildJournal(str(tmp_path))
        for i in range(20):
            journal.record(f"m-{i}", "planned", flush=True)
        assert os.path.isfile(journal.events_path)
        assert len(BuildJournal.load(str(tmp_path)).machines()) == 20

        journal.flush()
        assert not os.path.exists(journal.events_path)
        import json

        with open(journal.path) as f:
            assert len(json.load(f)["machines"]) == 20
        assert len(BuildJournal.load(str(tmp_path)).machines()) == 20

    def test_torn_event_tail_is_tolerated(self, tmp_path):
        journal = BuildJournal(str(tmp_path))
        journal.record("m-ok", "built", flush=True)
        with open(journal.events_path, "a") as f:
            f.write('{"name": "m-torn", "status": "bu')  # kill mid-append
        loaded = BuildJournal.load(str(tmp_path))
        assert loaded.get("m-ok")["status"] == "built"
        assert loaded.get("m-torn") is None

    def test_clean_staging_dirs_spares_artifacts(self, tmp_path):
        (tmp_path / "real-model").mkdir()
        (tmp_path / ".dead.tmp-1").mkdir()
        (tmp_path / ".dead2.tmp-xyz").mkdir()
        removed = clean_staging_dirs(str(tmp_path), min_age_seconds=0)
        assert sorted(removed) == [".dead.tmp-1", ".dead2.tmp-xyz"]
        assert (tmp_path / "real-model").is_dir()
        assert clean_staging_dirs(str(tmp_path / "missing")) == []

    def test_clean_staging_dirs_spares_live_builders_fresh_dirs(self, tmp_path):
        """On a shared volume a FRESH staging dir may be another live
        builder's in-flight dump — the default sweep must spare it."""
        import os as _os
        import time as _time

        fresh = tmp_path / ".inflight.tmp-2"
        fresh.mkdir()
        old = tmp_path / ".orphan.tmp-3"
        old.mkdir()
        hours_ago = _time.time() - 7200
        _os.utime(old, (hours_ago, hours_ago))
        removed = clean_staging_dirs(str(tmp_path))
        assert removed == [".orphan.tmp-3"]
        assert fresh.is_dir()


# -- prometheus export ----------------------------------------------------


def test_robustness_counters_exported_to_prometheus(tmp_path):
    from prometheus_client import REGISTRY

    machines = [make_machine("prom-flaky")]
    builder = FleetBuilder(machines, data_retries=1, data_backoff=0)
    with inject(FaultRule("data_fetch", match="prom-*", times=1)):
        builder.build()
    assert builder.robustness["data_fetch_retries"] == 1
    value = REGISTRY.get_sample_value(
        "gordo_fleet_build_data_fetch_retries_total",
        {"project": "fault-test"},
    )
    assert value is not None and value >= 1
