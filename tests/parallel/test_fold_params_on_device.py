"""A fold model's parameters never cross the link: a CV fit leaves them
on the device (``FleetTrainer.train(params_on_device=True)``), the
group's predict-and-score program takes them there
(``FleetTrainer.device_params``), and they go when their chunk is scored.
The oracle is the same build with the same parameters forced through the
host on their way to the same predict call."""

import gc
import hashlib
import json
import os
import warnings
import weakref

import jax
import numpy as np
import pytest

from gordo_tpu.machine import Machine
from gordo_tpu.parallel import FleetBuilder, FleetTrainer
from gordo_tpu.parallel.fleet import tree_nbytes
from gordo_tpu.parallel.mesh import make_mesh
from gordo_tpu.telemetry.progress import BUILD_TRACE_FILE
from gordo_tpu.telemetry.trace_analysis import build_breakdown, render_analysis
from gordo_tpu.utils import faults
from gordo_tpu.utils.faults import FaultRule, inject

from .test_fleet_backbone import TOY

RANDOM = {
    "type": "RandomDataset",
    "train_start_date": "2020-01-01T00:00:00+00:00",
    "train_end_date": "2020-01-03T00:00:00+00:00",
    "tag_list": ["t 1", "t2", "t3"],
}
SHORT = {  # 145 rows: 45 windows of the toy backbone's 100
    "type": "TimeSeriesDataset",
    "data_provider": {"type": "RandomDataProvider", "min_size": 145, "max_size": 145},
    "train_start_date": "2020-01-01T00:00:00+00:00",
    "train_end_date": "2020-01-02T00:00:00+00:00",
    "resolution": "10min",
    "tag_list": ["bb-0", "bb-1", "bb-2", "bb-3"],
}
ESTIMATORS = {
    "dense": (
        {"gordo_tpu.models.JaxAutoEncoder": {
            "kind": "feedforward_hourglass", "encoding_layers": 1, "epochs": 2,
        }},
        RANDOM,
    ),
    "windowed": (
        {"gordo_tpu.models.JaxLSTMAutoEncoder": {
            "kind": "lstm_symmetric", "dims": [4], "funcs": ["tanh"],
            "lookback_window": 4, "epochs": 2,
        }},
        RANDOM,
    ),
    "backbone": ({"gordo_tpu.models.JaxBackboneForecast": dict(TOY)}, SHORT),
}


def machine(kind, name, evaluation=None, **detector):
    estimator, dataset = ESTIMATORS[kind]
    config = {
        "name": name,
        "model": {"gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
            "base_estimator": {"sklearn.pipeline.Pipeline": {
                "steps": ["sklearn.preprocessing.MinMaxScaler", estimator]
            }},
            **detector,
        }},
        "dataset": dict(dataset),
    }
    if evaluation:
        config["evaluation"] = evaluation
    return Machine.from_config(config, project_name="fold-params")


def through_the_host(patch, moved):
    """The round trip the change took out, made by the test: a group's
    stacked parameters fetched (``jax.device_get``) and handed to the
    same predict call as host arrays; ``moved`` collects their bytes."""
    real = FleetTrainer.device_params

    def fetched(self, spec, results):
        host = jax.device_get(real(self, spec, results))
        assert all(
            isinstance(leaf, np.ndarray) for leaf in jax.tree_util.tree_leaves(host)
        )
        moved.append(tree_nbytes(host))
        return host

    patch.setattr(FleetTrainer, "device_params", fetched)


def four_device_trainer():
    return FleetTrainer(mesh=make_mesh(jax.devices()[:4]))


def build(machines, out, trainer=None):
    """A whole build into ``out``: ``(builder, [(model, machine)],
    spans)``."""
    builder = FleetBuilder(machines, trainer=trainer)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # sklearn: R2 of one row
        results = builder.build(output_dir=str(out))
    with open(os.path.join(str(out), BUILD_TRACE_FILE)) as f:
        spans = [json.loads(line) for line in f]
    return builder, results, spans


def artifact_md5(out, name):
    with open(os.path.join(str(out), name, "model.pkl"), "rb") as f:
        return hashlib.md5(f.read()).hexdigest()


def attributes(spans, name, **where):
    return [
        s["attributes"] for s in spans
        if s["name"] == name
        and all(s["attributes"].get(k) == v for k, v in where.items())
    ]


def predict_programs(spans):
    return [
        a for a in attributes(spans, "device_program") if "predict" in a["program"]
    ]


def same_to_the_bit(got, expected):
    """Scores and metadata (numbers, lists, dictionaries of them)."""
    if isinstance(expected, dict):
        assert list(got) == list(expected)
        for key in expected:
            same_to_the_bit(got[key], expected[key])
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))


# -- (a) the same build, to the bit ----------------------------------------------


@pytest.fixture(scope="module", params=sorted(ESTIMATORS))
def both_ways(request, tmp_path_factory):
    """Two machines of one kind built twice on the CPU's four-device
    mesh, where their six fold models take two members of padding: as
    the program does it, and with the fold parameters through the host."""
    kind = request.param
    root = tmp_path_factory.mktemp(f"fold-params-{kind}")

    def machines():
        return [machine(kind, f"{kind}-a"), machine(kind, f"{kind}-b", window=6)]

    device = build(machines(), root / "device", four_device_trainer())
    moved = []
    with pytest.MonkeyPatch.context() as patch:
        through_the_host(patch, moved)
        host = build(machines(), root / "host", four_device_trainer())
    return kind, root, device, host, moved


def test_a_build_is_the_same_to_the_bit_with_fold_parameters_on_the_device(both_ways):
    kind, root, (builder, built, _), (host_builder, host_built, _), _ = both_ways
    assert not builder.build_errors and not host_builder.build_errors
    assert len(built) == len(host_built) == 2
    for (model, made), (host_model, host_made) in zip(built, host_built):
        mine = made.metadata.build_metadata.model.cross_validation
        theirs = host_made.metadata.build_metadata.model.cross_validation
        assert mine.scores and mine.scores == theirs.scores
        got, expected = model.get_metadata(), host_model.get_metadata()
        assert "feature-thresholds-per-fold" in expected
        same_to_the_bit(got, expected)
        assert artifact_md5(root / "device", made.name) == artifact_md5(
            root / "host", made.name
        )


# -- (b) what the spans say ------------------------------------------------------


def test_cv_collect_moves_the_histories_alone(both_ways):
    """``losses`` and ``val_losses`` (float32 an epoch) and ``epochs_ran``
    (an int32) of every member of the fit program's padded block; the
    final fit's ``collect`` still brings its parameters."""
    _, _, (_, _, spans), _, moved = both_ways
    collected = {
        phase: sum(
            a["bytes"] for a in attributes(spans, "build_part", part="collect", phase=phase)
        )
        for phase in ("cv_train", "final_fit")
    }
    phases = {
        s["context"]["span_id"]: s["attributes"]["phase"]
        for s in spans if s["name"] == "build_phase"
    }
    histories = {"cv_train": 0, "final_fit": 0}
    for span in spans:
        if span["name"] == "device_program" and span["attributes"]["program"].endswith("_fit"):
            a = span["attributes"]
            histories[phases[span["parent_id"]]] += a["stacked_members"] * (8 * a["epochs"] + 4)
    assert collected["cv_train"] == histories["cv_train"] > 0
    assert collected["final_fit"] > histories["final_fit"] + sum(moved) / 8
    for a in attributes(spans, "build_part", part="collect", phase="cv_train"):
        assert 0.0 <= a["d2h_seconds"]


def test_cv_predict_hands_over_no_parameter_and_the_program_says_so(both_ways):
    _, _, (_, _, spans), (_, _, host_spans), moved = both_ways

    def handed_over(side):
        return sum(
            a["bytes"] for a in attributes(side, "build_part", part="h2d", phase="cv_predict")
        )

    assert sum(moved) > 0
    assert handed_over(host_spans) - handed_over(spans) == sum(moved)
    programs, host_programs = predict_programs(spans), predict_programs(host_spans)
    assert programs and len(programs) == len(host_programs)
    for program in programs:
        assert program["params_resident_members"] == program["members"] > 0
    for program in host_programs:
        assert program["params_resident_members"] == 0 < program["members"]
    # the fit programs say nothing of it
    for a in attributes(spans, "device_program"):
        assert ("params_resident_members" in a) == ("predict" in a["program"])


def test_the_parts_of_every_phase_still_sum_to_it(both_ways):
    _, _, (_, _, spans), _, _ = both_ways
    found = build_breakdown(spans)
    for phase in ("cv_train", "cv_predict", "cv_score", "final_fit"):
        entry = found["phases"][phase]
        assert 0.0 <= entry["self_seconds"] <= entry["seconds"]
    parts = found["phases"]["cv_predict"]["parts"]
    assert {"stack", "h2d", "collect"} <= set(parts)
    (program,) = [p for p in parts if p.startswith("program ")]
    assert parts[program]["params_resident_members"] == parts[program]["members"] > 0
    rendered = render_analysis(
        {"trace": "t", "spans_read": len(spans), "build_breakdown": found}
    )
    members = parts[program]["members"]
    assert f"  {program} [members={members}, params_resident_members={members}]" in rendered


# -- (c) the contracts of the path, each with parameters on the device ------------


@pytest.fixture(autouse=True)
def _clean_registry():
    faults.clear()
    yield
    faults.clear()


class Built:
    """A build under a spy: the CV calls of ``train``, and where every
    predict program took its parameters."""

    def __init__(self, machines, tmp_path, patch, name="out"):
        self.cv_calls = []
        real = FleetTrainer.train

        def spied(trainer, members, config, **kwargs):
            if "::fold" in members[0].name:
                self.cv_calls.append(kwargs)
            return real(trainer, members, config, **kwargs)

        patch.setattr(FleetTrainer, "train", spied)
        self.builder, self.results, self.spans = build(machines, tmp_path / name)
        self.names = sorted(made.name for _, made in self.results)

    def on_the_device(self):
        assert self.cv_calls and all(
            call == {"params_on_device": True} for call in self.cv_calls
        )
        programs = predict_programs(self.spans)
        assert programs
        for program in programs:
            assert program["params_resident_members"] == program["members"]
        return programs

    def scores(self):
        return {
            made.name: made.metadata.build_metadata.model.cross_validation.scores
            for _, made in self.results
        }


def dense_machines(*names):
    return [machine("dense", name) for name in names]


def test_a_diverged_fold_members_retry_replaces_its_block(tmp_path, monkeypatch):
    """The reseeded member trains in a bucket of its own, so its group's
    parameters are gathered on the device out of two blocks."""
    real = FleetTrainer._train_once
    state = {"poisoned": None}
    gathered = []

    def poison_one_fold(self, members, config, *on_device):
        results = real(self, members, config, *on_device)
        if state["poisoned"] is None and "::fold" in results[0].name:
            state["poisoned"] = results[1].name
            results[1].history.history["loss"] = [float("nan")]
        return results

    real_params = FleetTrainer.device_params

    def watched(self, spec, results):
        gathered.append(len({id(r.block) for r in results}))
        return real_params(self, spec, results)

    monkeypatch.setattr(FleetTrainer, "_train_once", poison_one_fold)
    monkeypatch.setattr(FleetTrainer, "device_params", watched)
    built = Built(dense_machines("retry-a", "retry-b"), tmp_path, monkeypatch)
    assert built.names == ["retry-a", "retry-b"] and not built.builder.build_errors
    assert built.builder.robustness["fleet_retries"] == 1
    assert gathered == [2]  # one group, the retried member's block beside the rest's
    built.on_the_device()
    for folds in built.scores().values():
        for per_fold in folds.values():
            assert np.isfinite(list(per_fold.values())).all()


def test_a_bucket_that_bisects_scores_out_of_its_halves_blocks(tmp_path, monkeypatch):
    """Four machines' twelve fold members train as 3 + 3 + 3 + 3 after
    two device errors; the one group they score as is gathered from the
    four blocks, and every score is the unsplit build's."""
    whole = Built(dense_machines("pack-0", "pack-1", "pack-2", "pack-3"), tmp_path, monkeypatch, "whole")
    real = FleetTrainer._train_bucket
    failures = []

    def oom_on_big_buckets(self, spec, n_padded, bucket, config, m_padded=None, **where):
        if len(bucket) > 4:
            failures.append(len(bucket))
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory (injected)")
        return real(self, spec, n_padded, bucket, config, m_padded=m_padded, **where)

    monkeypatch.setattr(FleetTrainer, "_train_bucket", oom_on_big_buckets)
    halves = Built(dense_machines("pack-0", "pack-1", "pack-2", "pack-3"), tmp_path, monkeypatch, "halves")
    assert failures == [12, 6, 6]
    assert halves.names == whole.names and not halves.builder.build_errors
    assert halves.builder.robustness["bucket_bisects"] >= 3
    (program,) = halves.on_the_device()
    assert program["members"] == 12
    for name, scores in whole.scores().items():
        for metric, folds in scores.items():
            np.testing.assert_allclose(
                list(halves.scores()[name][metric].values()), list(folds.values()),
                rtol=1e-5, atol=1e-7,
            )


def test_a_member_that_fails_in_isolation_carries_no_parameters(tmp_path, monkeypatch):
    """``params`` None, no block, its ``error``: the machine degrades to
    the sequential builder and its neighbours score on the device."""
    seen = []
    real = FleetTrainer.train

    def watched(self, members, config, **kwargs):
        results = real(self, members, config, **kwargs)
        seen.extend(r for r in results if "::fold" in r.name)
        return results

    monkeypatch.setattr(FleetTrainer, "train", watched)
    with inject(FaultRule("device_program", match="poison-*", times=None)):
        builder, results, spans = build(
            dense_machines("good-a", "poison-x", "good-b"), tmp_path / "out"
        )
    assert sorted(m.name for _, m in results) == ["good-a", "good-b", "poison-x"]
    assert set(builder.degraded) == {"poison-x"} and not builder.build_errors
    failed = [r for r in seen if r.error is not None]
    assert {r.name.split("::")[0] for r in failed} == {"poison-x"}
    assert all(r.params is None and r.block is None for r in failed)
    sound = [r for r in seen if r.error is None]
    assert sound and all(r.params is None and r.block is not None for r in sound)
    programs = predict_programs(spans)
    assert programs
    for program in programs:
        assert program["params_resident_members"] == program["members"]


def median_error(y_true, y_pred, multioutput="uniform_average"):
    from sklearn.metrics import median_absolute_error

    return median_absolute_error(y_true, y_pred, multioutput=multioutput)


def test_a_member_left_to_the_hosts_scoring_predicts_from_the_device_too(tmp_path, monkeypatch):
    """Its predictions come to the host (``fetch_members``); its
    parameters do not."""
    machines = [
        machine("dense", "plain-a"),
        machine("dense", "odd-one", evaluation={"metrics": ["r2_score", median_error]}),
        machine("dense", "plain-b"),
    ]
    built = Built(machines, tmp_path, monkeypatch)
    assert built.names == ["odd-one", "plain-a", "plain-b"]
    (program,) = built.on_the_device()
    assert program["members"] == 9
    counts = {
        a["part"]: a["count"]
        for a in attributes(built.spans, "build_part", phase="cv_score")
        if "count" in a
    }
    assert counts == {"device_scores": 6, "metric_scores": 3, "thresholds": 3}
    assert "median-error" in built.scores()["odd-one"]
    assert "median-error" not in built.scores()["plain-a"]


def test_the_any_exception_halving_isolates_a_machine_and_the_rest_score_on_the_device(
    tmp_path, monkeypatch
):
    real = FleetTrainer.train
    calls = []

    def flaky(self, members, config, **kwargs):
        names = [m.name for m in members]
        calls.append(len(names))
        bad = [n for n in names if n.startswith("split-1")]
        if bad and len(names) > 1:
            raise RuntimeError("chunk-level failure")
        if bad:
            raise RuntimeError("bad machine alone")
        return real(self, members, config, **kwargs)

    monkeypatch.setattr(FleetTrainer, "train", flaky)
    built = Built(dense_machines("split-0", "split-1", "split-2"), tmp_path, monkeypatch)
    assert built.names == ["split-0", "split-2"]
    assert set(built.builder.build_errors) == {"split-1"}
    assert len(calls) > 3  # the halving recursed
    programs = built.on_the_device()
    assert sum(program["members"] for program in programs) >= 6


# -- (d) the block goes with its chunk ---------------------------------------------


@pytest.mark.parametrize("outcome", ["scored", "halved", "scoring-failed"])
def test_no_fold_parameter_outlives_its_chunk(outcome, monkeypatch):
    """After ``_train_and_score_folds`` returns, ``jax.live_arrays()``
    holds no leaf of the chunk's fold parameters: without a collection
    by the cycle detector, which a backbone's next fit could not wait
    for. Also where the chunk's first attempt failed and its halves
    trained, and where the scoring itself failed and the error is kept."""
    leaves = []
    real = FleetTrainer.train
    attempts = []

    def watched(self, members, config, **kwargs):
        attempts.append(len(members))
        if outcome == "halved" and len(attempts) == 1:
            raise RuntimeError("chunk-level failure")
        results = real(self, members, config, **kwargs)
        for result in results:
            for leaf in jax.tree_util.tree_leaves(result.block):
                leaves.append(weakref.ref(leaf))
        return results

    monkeypatch.setattr(FleetTrainer, "train", watched)
    if outcome == "scoring-failed":

        def refuse(self, *args, **kwargs):
            raise RuntimeError("the predict program failed")

        monkeypatch.setattr(FleetTrainer, "predict_bucket", refuse)
    builder = FleetBuilder(dense_machines("live-a", "live-b"))
    plans, fallbacks = builder._plan_all()
    assert not fallbacks
    plans = builder._load_all_data(plans)
    per_plan_folds, grouped = builder._split_folds(plans)
    ((config, (members, fold_items)),) = grouped.items()
    fold_state = {plan.machine.name: {} for plan in plans}
    gc.collect()
    gc.disable()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            builder._train_and_score_folds(
                members, fold_items, config, per_plan_folds, fold_state
            )
        alive = [ref() for ref in leaves if ref() is not None]
        held = [a for a in jax.live_arrays() if any(a is leaf for leaf in alive)]
    finally:
        gc.enable()
    assert leaves and attempts[0] == 6
    assert held == [] and alive == []
    if outcome == "scoring-failed":
        assert set(builder.build_errors) == {"live-a", "live-b"}
    else:
        assert not builder.build_errors
        assert all(plan.cv_scores for plan in plans)
