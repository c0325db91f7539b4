"""
Planner ↔ trainer/builder integration: the packed strategy must not
change member numerics for unchanged buckets, a build persists its
FleetPlan + journal hash, ``plan_only`` is deterministic, and a plan
replays end to end through ``--plan-from`` + ``--resume`` (only unbuilt
members are replanned after a mid-build kill).
"""

import json
import os

import numpy as np
import pytest

from gordo_tpu import serializer, telemetry
from gordo_tpu.machine import Machine
from gordo_tpu.models.factories import feedforward_symmetric
from gordo_tpu.models.training import FitConfig
from gordo_tpu.parallel import FleetBuilder, FleetMember, FleetTrainer
from gordo_tpu.parallel.journal import BuildJournal
from gordo_tpu.planner import PLAN_FILE, FleetPlan
from gordo_tpu.utils import faults
from gordo_tpu.utils.faults import FaultRule, inject

pytestmark = pytest.mark.planner

SPEC = feedforward_symmetric(3, dims=(6, 3), funcs=("tanh", "tanh"))
CONFIG = FitConfig(epochs=3, batch_size=16, shuffle=False)

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
                "epochs": 2,
            }
        }
    }
}


def make_machine(name, tags=("t1", "t2")):
    return Machine.from_config(
        {
            "name": name,
            "model": MODEL,
            "dataset": {**DATASET, "tag_list": list(tags)},
        },
        project_name="plan-test",
    )


def _member(name, n, seed):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 3).astype(np.float32)
    return FleetMember(name=name, spec=SPEC, X=X, y=X.copy(), seed=seed)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def test_packed_matches_naive_numerics_for_unchanged_buckets():
    """Members whose pad target is the same under both strategies train
    to IDENTICAL params — repacking neighbors must never leak into a
    member's numerics (the acceptance criterion's no-divergence half)."""
    import jax

    members = [_member(f"big{i}", 128, i) for i in range(4)] + [
        _member(f"small{i}", 40, 100 + i) for i in range(2)
    ]
    naive = {
        r.name: r
        for r in FleetTrainer(plan_strategy="naive").train(members, CONFIG)
    }
    packed = {
        r.name: r
        for r in FleetTrainer(plan_strategy="packed").train(members, CONFIG)
    }
    assert sorted(naive) == sorted(packed)
    # n=128 sits on BOTH ladders (pow2 and the 1.25 geometric rung set),
    # so those members' padded shape is unchanged: exact same training.
    for name in ("big0", "big1", "big2", "big3"):
        for a, b in zip(
            jax.tree_util.tree_leaves(naive[name].params),
            jax.tree_util.tree_leaves(packed[name].params),
        ):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # merged members (40 → a different rung than pow2 64) still converge
    for name in ("small0", "small1"):
        assert np.isfinite(packed[name].history.history["loss"]).all()


def _split_bin_plan(members):
    """A packed plan whose HBM cap forces sibling bins (2 members each)
    sharing an m_padded rung — the shape the m_padded fixes guard."""
    from gordo_tpu import planner

    cost_model = planner.CostModel()
    per_member = cost_model.predict_hbm_bytes(
        SPEC, 1, 128, CONFIG.batch_size
    )
    buckets = planner.plan_train_buckets(
        members,
        CONFIG,
        strategy="packed",
        cost_model=cost_model,
        hbm_cap=int(2.5 * per_member),
    )
    assert all(b.m_padded is not None for b in buckets)  # the premise
    return planner.build_plan_doc(
        [(CONFIG, buckets)],
        "packed",
        (1, 1),
        None,
        planner.config_fingerprint([m.name for m in members]),
    )


def test_planned_m_padded_bucket_still_bisects_on_oom(monkeypatch):
    """The OOM recovery ladder must shrink the member axis: a bucket
    whose PLANNED m_padded rung over-sizes device memory bisects into
    halves that drop the rung (padding a half back up to the planned
    shape would re-OOM identically, forever)."""
    calls = []
    real = FleetTrainer._train_bucket

    def oom_at_planned_rung(self, spec, n_padded, bucket, config, m_padded=None, **where):
        calls.append((len(bucket), m_padded))
        if m_padded is not None:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory (injected)")
        return real(self, spec, n_padded, bucket, config, m_padded=m_padded, **where)

    monkeypatch.setattr(FleetTrainer, "_train_bucket", oom_at_planned_rung)
    members = [_member(f"mp{i}", 128, i) for i in range(4)]
    results = FleetTrainer(
        plan_strategy="packed", fleet_plan=_split_bin_plan(members)
    ).train(members, CONFIG)
    assert all(r.error is None for r in results)
    assert any(m_padded is not None for _, m_padded in calls)  # rung tried
    full = max(n for n, _ in calls)
    assert all(
        m_padded is None for n, m_padded in calls if n < full
    )  # every bisected half dropped the floor


def test_builder_packed_persists_plan_journal_and_accuracy(tmp_path):
    """A packed build drops fleet_plan.json beside the artifacts, the
    journal records the plan hash, and the trace carries the plan +
    predicted-vs-actual accuracy events."""
    telemetry.reset_seen_programs()
    out = tmp_path / "out"
    machines = [
        make_machine("pl-a"),
        make_machine("pl-b"),
        make_machine("pl-c", tags=("t1", "t2", "t3")),
    ]
    builder = FleetBuilder(machines, plan_strategy="packed")
    results = builder.build(output_dir=str(out))
    assert len(results) == 3
    for _, machine in results:
        assert serializer.load(str(out / machine.name)) is not None

    plan = FleetPlan.load(str(out / PLAN_FILE))
    assert plan.strategy == "packed"
    assert plan.covers(["pl-a", "pl-b", "pl-c"])
    assert plan.totals["members"] == 3

    journal_plan = BuildJournal.load(str(out)).plan()
    assert journal_plan == {"plan_hash": plan.plan_hash, "strategy": "packed"}

    with open(out / telemetry.progress.BUILD_TRACE_FILE) as f:
        spans = [json.loads(line) for line in f]
    planned = [s for s in spans if s["name"] == "fleet_plan"]
    assert len(planned) == 1
    assert planned[0]["attributes"]["plan_hash"] == plan.plan_hash
    assert planned[0]["attributes"]["replayed"] is False
    accuracy = [s for s in spans if s["name"] == "fleet_plan_accuracy"]
    assert len(accuracy) == 1
    attrs = accuracy[0]["attributes"]
    assert attrs["predicted_compiles"] == plan.totals["compiles"]
    assert attrs["actual_fit_s"] >= 0.0
    # the bucket_plan phase is part of the traced build
    phases = {
        s["attributes"]["phase"] for s in spans if s["name"] == "build_phase"
    }
    assert "bucket_plan" in phases


def test_plan_only_is_deterministic(tmp_path):
    """Same machines + cost table => byte-identical plan JSON (what
    `gordo-tpu plan` prints and the journal hash is derived from)."""
    machines = lambda: [make_machine("det-a"), make_machine("det-b")]  # noqa: E731
    first = FleetBuilder(machines(), plan_strategy="packed").plan_only()
    second = FleetBuilder(machines(), plan_strategy="packed").plan_only()
    assert first.to_json() == second.to_json()
    assert first.plan_hash == second.plan_hash
    assert first.totals["members"] == 2
    # and it round-trips through the file the CLI writes
    path = str(tmp_path / "plan.json")
    first.save(path)
    assert FleetPlan.load(path).to_json() == first.to_json()


def test_plan_from_replays_across_kill_and_resume(tmp_path):
    """The acceptance path: emit a plan, build from it, die after one
    machine, resume FROM THE SAME PLAN — journaled machines are not
    rebuilt, only unbuilt members are (re)planned, and their planned pad
    targets survive the resume."""
    out = tmp_path / "out"
    names = [f"rp-{i}" for i in range(4)]
    plan = FleetBuilder(
        [make_machine(n) for n in names], plan_strategy="packed"
    ).plan_only()
    assert plan.covers(names)

    # the first two artifact dumps land; every later one dies mid-write
    # (SystemExit, like the process_kill site's exit during dump)
    with inject(FaultRule("dump_artifact", after=2, times=None, exc=SystemExit)):
        with pytest.raises(SystemExit):
            FleetBuilder(
                [make_machine(n) for n in names],
                plan_strategy="packed",
                fleet_plan=plan,
            ).build(output_dir=str(out))

    journal = BuildJournal.load(str(out))
    done = sorted(
        n for n, e in journal.machines().items() if e["status"] == "built"
    )
    assert done and len(done) < len(names)
    assert journal.plan()["plan_hash"] == plan.plan_hash

    before = {n: (out / n / "model.pkl").stat().st_mtime_ns for n in done}
    resumer = FleetBuilder(
        [make_machine(n) for n in names],
        plan_strategy="packed",
        fleet_plan=plan,
    )
    results = resumer.build(output_dir=str(out), resume=True)
    assert sorted(resumer.resumed) == done
    assert sorted(m.name for _, m in results) == sorted(set(names) - set(done))
    # resumed artifacts untouched: their members were never replanned
    for name in done:
        assert (out / name / "model.pkl").stat().st_mtime_ns == before[name]
    # the journal still records the replayed plan's identity
    assert BuildJournal.load(str(out)).plan()["plan_hash"] == plan.plan_hash
    for name in names:
        assert serializer.load(str(out / name)) is not None
    # the resumed build replayed the same plan: every unbuilt member's
    # bucket (and pad target) came from the original document
    trainer_plan = resumer.trainer.fleet_plan
    assert trainer_plan is not None
    assert trainer_plan.plan_hash == plan.plan_hash


def test_replayed_plan_strategy_covers_live_packed_members(
    tmp_path, monkeypatch
):
    """`build-fleet --plan-from <packed plan>` with no --plan-strategy:
    the plan's strategy must ride onto the trainer, so CV fold members
    and plan-uncovered members pack with the strategy the operator
    opted into — not silently naive while the journal says packed."""
    import gordo_tpu.parallel.fleet as fleet_mod

    strategies_seen = []
    real = fleet_mod.plan_train_buckets

    def spy(members, config, strategy=None, **kwargs):
        strategies_seen.append(strategy)
        return real(members, config, strategy=strategy, **kwargs)

    monkeypatch.setattr(fleet_mod, "plan_train_buckets", spy)
    machines = [make_machine("st-a"), make_machine("st-b")]
    plan = FleetBuilder(machines, plan_strategy="packed").plan_only()
    builder = FleetBuilder(
        [make_machine("st-a"), make_machine("st-b")], fleet_plan=plan
    )
    builder.build(output_dir=str(tmp_path / "out"))
    assert strategies_seen and all(s == "packed" for s in strategies_seen)
    # the switch does not outlive the build on the (builder-owned) trainer
    assert builder.trainer.plan_strategy is None


def test_fresh_build_replans_when_no_plan_given(tmp_path):
    """Without --plan-from, each build computes (and persists) its own
    plan; a trainer reused across builds must not leak the previous
    fleet's plan into the next build."""
    out_a = tmp_path / "a"
    out_b = tmp_path / "b"
    trainer = FleetTrainer(plan_strategy="naive")
    FleetBuilder([make_machine("fr-a")], trainer=trainer).build(
        output_dir=str(out_a)
    )
    plan_a = FleetPlan.load(str(out_a / PLAN_FILE))
    assert plan_a.covers(["fr-a"])
    FleetBuilder([make_machine("fr-b")], trainer=trainer).build(
        output_dir=str(out_b)
    )
    plan_b = FleetPlan.load(str(out_b / PLAN_FILE))
    assert plan_b.covers(["fr-b"])
    assert not plan_b.covers(["fr-a"])
