"""Fold scores and thresholds computed where the predictions are
(``parallel/fleet.fold_scores`` inside a group's predict program) against
the host's per-machine-fold sklearn and numpy code, which stays for the
evaluations a program cannot express and is the oracle here: the same
seeded predictions scored both ways, and the choice between the two."""

import functools
import types
import warnings

import jax
import numpy as np
import pandas as pd
import pytest
from sklearn.metrics import median_absolute_error
from sklearn.preprocessing import (
    MaxAbsScaler,
    MinMaxScaler,
    RobustScaler,
    StandardScaler,
)

from gordo_tpu import telemetry
from gordo_tpu.machine import Machine
from gordo_tpu.models.anomaly.diff import (
    DiffBasedAnomalyDetector,
    DiffBasedKFCVAnomalyDetector,
)
from gordo_tpu.models.nn import init_fn_for
from gordo_tpu.models.training import History
from gordo_tpu.parallel import host_blocks
from gordo_tpu.parallel import FleetBuilder, fleet_build
from gordo_tpu.parallel.fleet import FleetResult, fold_scores, stack_member_params
from gordo_tpu.parallel.fleet_build import (
    _Plan,
    _fold_scaler_parameters,
    _per_tag_affine,
    _scaler_parameters,
    _take_rows,
)
from gordo_tpu.telemetry import SpanRecorder

TAG_SCALE = np.array([1.0, 10.0, 100.0], np.float32)
BLOCK = 64  # training rows of a seeded plan, and as many to score


def host_scored(monkeypatch):
    """Every machine through the host's scoring code: the oracle."""
    monkeypatch.setattr(
        FleetBuilder, "_device_scoring", classmethod(lambda cls, plan: None)
    )


def close(got, expected, what=""):
    """Within 1e-5 of the value (of 0.1 for a share of 1 that is smaller),
    NaN where the other is NaN."""
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(expected, np.float64),
        rtol=1e-5, atol=1e-6, equal_nan=True, err_msg=what,
    )


def identical(got, expected):
    """Metadata values (a list a tag, or a dictionary a tag of a value a
    fold) equal to the bit, NaN where the other is NaN."""
    if isinstance(expected, dict):
        assert list(got) == list(expected)
        for key in expected:
            identical(got[key], expected[key])
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))


def same_fold_state(got, expected):
    """Two ``fold_state`` entries: the same keys, feature thresholds
    equal to the bit, aggregates close."""
    assert list(got) == list(expected)
    for key, value in expected.items():
        if isinstance(value, dict):
            assert list(got[key]) == list(value)
            pairs = [(got[key][fold], value[fold]) for fold in value]
        else:
            pairs = [(got[key], value)]
        for mine, theirs in pairs:
            assert type(mine) is type(theirs), key
            if isinstance(theirs, pd.Series):
                assert mine.name == theirs.name and mine.dtype == theirs.dtype
                np.testing.assert_array_equal(mine.to_numpy(), theirs.to_numpy(), key)
            else:
                close(mine, theirs, key)


def same_cv_scores(got, expected):
    assert list(got) == list(expected)
    for key, folds in expected.items():
        assert list(got[key]) == list(folds)
        close(list(got[key].values()), list(folds.values()), key)


# -- the arithmetic: seeded predictions scored both ways ------------------------


def seeded_plan(name, seed, detector, scoring_scaler, metrics=None):
    rng = np.random.RandomState(seed)
    y = pd.DataFrame(
        (rng.normal(size=(2 * BLOCK, 3)) * TAG_SCALE + TAG_SCALE).astype(np.float32),
        columns=["tag 0", "tag-1", "tag 2"],
    )
    evaluation = {"scoring_scaler": scoring_scaler, "metrics": metrics}
    return _Plan(
        machine=types.SimpleNamespace(name=name, evaluation=evaluation),
        dataset=None, model_obj=None, detector=detector, pipeline=None,
        estimator=None, y=y, y_arr=y.to_numpy(),
    )


def score_both_ways(make_plans, sizes, window):
    """The plans' one fold each (``sizes`` rows after ``BLOCK`` training
    rows) scored from the same seeded predictions on the device and on
    the host: ``[(plans, fold_state)]``, device first."""
    sides = []
    for on_device in (True, False):
        plans = make_plans()
        builder = FleetBuilder([])
        group = [(plan, 2) for plan in plans]
        fold_rows = [
            (np.arange(BLOCK), np.arange(BLOCK, BLOCK + n), np.arange(BLOCK, BLOCK + n))
            for n in sizes
        ]
        rng = np.random.RandomState(7)
        predictions = np.zeros((len(plans), max(sizes), 3), np.float32)
        for i, (plan, n) in enumerate(zip(plans, sizes)):
            noise = rng.normal(size=(n, 3)) * 0.1 * TAG_SCALE
            predictions[i, :n] = plan.y_arr[BLOCK : BLOCK + n] + noise
        state = {plan.machine.name: {} for plan in plans}
        if on_device:
            scoring = builder._fold_scoring(
                group, fold_rows, window, host_blocks.Lease()
            )
            program = jax.jit(jax.vmap(functools.partial(fold_scores, window=window)))
            scores = program(
                scoring.y_true, predictions, scoring.rows,
                scoring.metric_scaler, scoring.error_scaler,
            )
            predicted = jax.numpy.asarray(predictions), jax.device_get(scores)
        else:
            scoring, predicted = None, predictions
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # sklearn: R2 of one row
            builder._adopt_fold_scores(group, fold_rows, scoring, predicted, state)
        sides.append((plans, state))
    return sides


SCALERS = {
    "minmax": (MinMaxScaler, "sklearn.preprocessing.MinMaxScaler"),
    "minmax-range": (
        functools.partial(MinMaxScaler, feature_range=(-2, 3)),
        {"sklearn.preprocessing.MinMaxScaler": {"feature_range": [-2, 3]}},
    ),
    "standard": (StandardScaler, "sklearn.preprocessing.StandardScaler"),
    "standard-no-mean": (
        functools.partial(StandardScaler, with_mean=False),
        {"sklearn.preprocessing.StandardScaler": {"with_mean": False}},
    ),
    "robust": (RobustScaler, "sklearn.preprocessing.RobustScaler"),
    "none": (MinMaxScaler, None),  # no scoring scaler; a detector always has one
}


@pytest.mark.parametrize("window", [None, 5, 144], ids=["plain", "window-5", "window-144"])
@pytest.mark.parametrize("scaler", sorted(SCALERS))
def test_device_scores_match_the_hosts(scaler, window):
    """Unequal folds in one group (61 rows, 7, exactly a run's 6, 4 and
    1), each listed scaler as scoring scaler and as the detector's, a
    detector window shorter and longer than the folds."""
    make_scaler, definition = SCALERS[scaler]
    sizes = [61, 7, 6, 4, 1]

    def make_plans():
        return [
            seeded_plan(
                f"m-{i}", i,
                DiffBasedAnomalyDetector(scaler=make_scaler(), window=window),
                definition,
            )
            for i in range(len(sizes))
        ]

    (device_plans, device_state), (host_plans, host_state) = score_both_ways(
        make_plans, sizes, window
    )
    for mine, theirs in zip(device_plans, host_plans):
        assert FleetBuilder._device_scoring(mine) is not None
        same_cv_scores(mine.cv_scores, theirs.cv_scores)
        same_fold_state(device_state[mine.machine.name], host_state[mine.machine.name])
    assert device_state["m-3"]["threshold_run_rows"] == 4
    assert device_state["m-4"]["threshold_run_rows"] == 1
    assert np.isnan(device_plans[4].cv_scores["r2-score"]["fold-3"])  # one row
    if window == 144:  # no fold holds a complete run of it
        assert np.isnan(device_state["m-0"]["smooth_aggregate_threshold"])


def test_a_plan_without_a_detector_scores_metrics_only():
    def make_plans():
        return [seeded_plan("bare", 3, None, "sklearn.preprocessing.MinMaxScaler")]

    (device_plans, device_state), (host_plans, host_state) = score_both_ways(
        make_plans, [40], None
    )
    same_cv_scores(device_plans[0].cv_scores, host_plans[0].cv_scores)
    assert device_state == host_state == {"bare": {}}


@pytest.mark.parametrize("rows", [40, 4], ids=["runs-of-6", "one-run-of-4"])
def test_a_nan_row_is_skipped_by_the_thresholds_and_flagged_unscorable(rows):
    """pandas' rule, as ``_rolling_min_max`` states it: a run that holds a
    NaN is skipped, per column; no complete run gives NaN. sklearn's
    metrics refuse such a fold, which ``unscorable`` says."""
    rng = np.random.RandomState(11)
    y_true = rng.normal(size=(BLOCK, 3)).astype(np.float32)
    y_pred = y_true + rng.normal(size=(BLOCK, 3)).astype(np.float32)
    y_pred[2] = np.nan  # a whole row
    y_pred[20, 1] = np.nan  # one tag of another
    identity = _scaler_parameters(None, 3)
    scores = jax.jit(functools.partial(fold_scores, window=12))(
        y_true, y_pred, rows, identity, identity
    )
    assert float(scores["unscorable"]) == 1.0
    run = min(rows, 6)
    abs_err = np.abs(y_true - y_pred)[:rows]
    mse = np.mean(np.square(y_pred - y_true), axis=1)[:rows]
    np.testing.assert_array_equal(
        scores["feature_thresholds"], FleetBuilder._rolling_min_max(abs_err, run)
    )
    close(scores["aggregate_threshold"], FleetBuilder._rolling_min_max(mse, run))
    np.testing.assert_array_equal(
        scores["smooth_feature_thresholds"], FleetBuilder._rolling_min_max(abs_err, 12)
    )
    if rows == 4:  # the one run holds the NaN row: no threshold at all
        assert np.isnan(np.asarray(scores["feature_thresholds"])).all()


def test_a_sound_fold_is_not_flagged_and_an_empty_one_is():
    y = np.random.RandomState(5).normal(size=(BLOCK, 3)).astype(np.float32)
    identity = _scaler_parameters(None, 3)
    score = jax.jit(fold_scores)
    assert float(score(y, y + 1, 9, identity, identity)["unscorable"]) == 0.0
    assert float(score(y, y + 1, 0, identity, identity)["unscorable"]) == 1.0
    # a value that is not finite past the rows that count spoils nothing
    padded = y.copy()
    padded[9:] = np.inf
    assert float(score(y, padded, 9, identity, identity)["unscorable"]) == 0.0


def test_sklearns_zero_denominator_rule():
    """A constant target tag scores 1.0 where the prediction is exact and
    0.0 where it is not, in R2 and explained variance alike."""
    y_true = np.tile(np.array([[2.0, 2.0, 0.5]], np.float32), (BLOCK, 1))
    y_true[:, 2] = np.linspace(0, 1, BLOCK)
    y_pred = y_true.copy()
    y_pred[::2, 1] += 0.25
    identity = _scaler_parameters(None, 3)
    scores = jax.jit(fold_scores)(y_true, y_pred, 30, identity, identity)
    from sklearn.metrics import explained_variance_score, r2_score

    for name, metric in (
        ("r2_score", r2_score), ("explained_variance_score", explained_variance_score),
    ):
        expected = metric(y_true[:30], y_pred[:30], multioutput="raw_values")
        assert list(expected) == [1.0, 0.0, 1.0]
        np.testing.assert_array_equal(scores[name], expected)


@pytest.mark.parametrize("scaler", sorted(set(SCALERS) - {"none"}))
def test_scaler_parameters_are_the_scalers_own_transform(scaler):
    """A fold's scaler fitted without ``clone().fit`` has the parameters
    of one fitted with it, to the bit, and the four numbers a tag
    transform as the scaler does."""
    make_scaler, _ = SCALERS[scaler]
    rng = np.random.RandomState(13)
    y_train = (rng.normal(size=(500, 3)) * TAG_SCALE).astype(np.float32)
    y_train[:, 1] = 4.0  # a constant tag: sklearn scales it by 1
    fitted = make_scaler().fit(y_train)
    assert _per_tag_affine(fitted)
    parameters = _scaler_parameters(fitted, 3)
    np.testing.assert_array_equal(
        _fold_scaler_parameters(make_scaler(), y_train), parameters
    )
    x = (rng.normal(size=(50, 3)) * TAG_SCALE).astype(np.float32)
    shift, mul, div, add = parameters
    close((x - shift) * mul / div + add, fitted.transform(x))


@pytest.mark.parametrize(
    "rows, view",
    [
        (np.arange(3, 9), True), (np.arange(0), True), (np.array([4]), True),
        (np.array([3, 5, 6]), False), (np.array([5, 4, 3]), False),
        (np.array([2, 2, 3]), False),
    ],
    ids=["a-run", "no-row", "one-row", "a-gap", "descending", "a-repeat"],
)
def test_rows_are_taken_as_a_view_only_of_one_ascending_run(rows, view):
    array = np.arange(40.0).reshape(10, 4)
    taken = _take_rows(array, rows)
    np.testing.assert_array_equal(taken, array[rows])
    assert np.shares_memory(taken, array) == (view and len(rows) > 0)


@pytest.mark.parametrize(
    "scaler",
    [MaxAbsScaler(), MinMaxScaler(clip=True), type("Mine", (MinMaxScaler,), {})()],
    ids=["another-transformer", "clipping-minmax", "a-subclass"],
)
def test_what_four_numbers_a_tag_cannot_say_is_not_affine(scaler):
    assert not _per_tag_affine(scaler)
    plan = seeded_plan("m", 0, DiffBasedAnomalyDetector(scaler=scaler), None)
    assert FleetBuilder._device_scoring(plan) is None


# -- the programs: a dense and a windowed group through the trainer ---------------

DATASET = {
    "type": "RandomDataset",
    "train_start_date": "2020-01-01T00:00:00+00:00",
    "train_end_date": "2020-01-03T00:00:00+00:00",
    "tag_list": ["t 1", "t2", "t3"],
}
DENSE = {
    "gordo_tpu.models.JaxAutoEncoder": {
        "kind": "feedforward_hourglass", "encoding_layers": 1, "epochs": 1,
    }
}
WINDOWED = {
    "gordo_tpu.models.JaxLSTMAutoEncoder": {
        "kind": "lstm_symmetric", "dims": [4], "funcs": ["tanh"],
        "lookback_window": 4, "epochs": 1,
    }
}


def detector(estimator, kind="DiffBasedAnomalyDetector", **kwargs):
    return {
        f"gordo_tpu.models.anomaly.diff.{kind}": {
            "base_estimator": {
                "sklearn.pipeline.Pipeline": {
                    "steps": ["sklearn.preprocessing.MinMaxScaler", estimator]
                }
            },
            **kwargs,
        }
    }


def machine(name, model, evaluation=None, end=None):
    config = {"name": name, "model": model, "dataset": dict(DATASET)}
    if end:
        config["dataset"]["train_end_date"] = end
    if evaluation:
        config["evaluation"] = evaluation
    return Machine.from_config(config, project_name="fold-scores")


def folds_of(rows):
    return {"cv": {"sklearn.model_selection.TimeSeriesSplit": {
        "n_splits": 3, "test_size": rows,
    }}}


def seeded_train(poison=()):
    """``FleetTrainer.train`` without the training: every fold model is
    its spec's seeded initial weights, so two builders score the same
    predictions; a member named in ``poison`` predicts NaN. As the
    builder asks of its fold models, the parameters are left on the
    device: one block a spec, a member a row."""

    def train(members, config, params_on_device=False):
        assert params_on_device
        results, by_spec = [], {}
        for member in members:
            params = init_fn_for(member.spec)(
                jax.random.PRNGKey(member.seed), member.spec
            )
            params = jax.tree_util.tree_map(np.asarray, params)
            if member.name in poison:
                params = jax.tree_util.tree_map(lambda a: a * np.nan, params)
            results.append(
                FleetResult(
                    name=member.name, seed=member.seed, params=params,
                    history=History(history={"loss": [0.0]}, params={}, epoch=[0]),
                )
            )
            by_spec.setdefault(member.spec, []).append(results[-1])
        for of_spec in by_spec.values():
            block = jax.device_put(stack_member_params(of_spec))
            for row, result in enumerate(of_spec):
                result.params, result.block, result.row = None, block, row
        return results

    return train


def cross_validate(machines, poison=()):
    """The builder's own cross-validation of staged plans, the fold
    models seeded instead of trained: ``(builder, plans, build_part
    spans)``."""
    builder = FleetBuilder(machines)
    builder.recorder = recorder = SpanRecorder()
    builder.trainer.train = seeded_train(poison)
    with telemetry.activate(recorder):
        plans, fallbacks = builder._plan_all()
        assert not fallbacks
        plans = builder._load_all_data(plans)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # sklearn: R2 of one row
            builder._run_cross_validation(plans)
    return builder, plans, recorder.finished("build_part")


def part_counts(parts):
    counts = {}
    for span in parts:
        attributes = span["attributes"]
        if attributes["phase"] == "cv_score" and "count" in attributes:
            counts[attributes["part"]] = (
                counts.get(attributes["part"], 0) + attributes["count"]
            )
    return counts


GROUPS = {
    # unequal histories and folds in one group: thirds of two days and of
    # one, folds of 4 rows (a run of 4) and of 1
    "dense": lambda: [
        machine("dense-a", detector(DENSE)),
        machine("dense-b", detector(DENSE), end="2020-01-02T00:00:00+00:00"),
        machine("dense-c", detector(DENSE), evaluation=folds_of(4)),
        machine("dense-d", detector(DENSE), evaluation=folds_of(1)),
    ],
    "dense-window": lambda: [
        machine("dw-a", detector(DENSE, window=12)),
        machine("dw-b", detector(DENSE, window=12), evaluation=folds_of(9)),
    ],
    "windowed": lambda: [
        machine("lstm-a", detector(WINDOWED)),
        machine("lstm-b", detector(WINDOWED), end="2020-01-02T00:00:00+00:00"),
        machine("lstm-c", detector(WINDOWED), evaluation=folds_of(8)),
        machine("lstm-d", WINDOWED),  # no detector: metrics only
    ],
}


@pytest.fixture(scope="module", params=sorted(GROUPS))
def both_ways(request):
    """One group cross-validated twice from the same seeded fold models:
    scored by its predict program, and by the host."""
    machines = GROUPS[request.param]
    device = cross_validate(machines())
    with pytest.MonkeyPatch.context() as patch:
        host_scored(patch)
        host = cross_validate(machines())
    return device, host


def test_a_group_scores_the_same_through_its_predict_program(both_ways):
    (builder, plans, _), (host_builder, host_plans, _) = both_ways
    assert not builder.build_errors and not host_builder.build_errors
    for mine, theirs in zip(plans, host_plans):
        same_cv_scores(mine.cv_scores, theirs.cv_scores)
        if mine.detector is None:
            continue
        got, expected = mine.detector.get_metadata(), theirs.detector.get_metadata()
        assert list(got) == list(expected)
        identical(got["feature-thresholds"], expected["feature-thresholds"])
        identical(
            got["feature-thresholds-per-fold"], expected["feature-thresholds-per-fold"]
        )
        close(got["aggregate-threshold"], expected["aggregate-threshold"])
        close(
            list(got["aggregate-thresholds-per-fold"].values()),
            list(expected["aggregate-thresholds-per-fold"].values()),
        )
        assert got.get("thresholds-degraded") == expected.get("thresholds-degraded")
        assert got.get("threshold-run-rows") == expected.get("threshold-run-rows")
        if "smooth-feature-thresholds" in expected:
            identical(
                got["smooth-feature-thresholds"], expected["smooth-feature-thresholds"]
            )
            close(got["smooth-aggregate-threshold"], expected["smooth-aggregate-threshold"])


def test_the_parts_count_the_machine_folds_on_each_side(both_ways):
    (_, plans, parts), (_, _, host_parts) = both_ways
    folds = 3 * len(plans)
    assert part_counts(parts) == {
        "device_scores": folds, "metric_scores": 0, "thresholds": 0,
    }
    assert part_counts(host_parts) == {
        "device_scores": 0, "metric_scores": folds, "thresholds": folds,
    }
    # the host's side fetched every prediction inside its program, the
    # device's side none but the scores
    for side, program in ((parts, "_score"), (host_parts, "")):
        collects = [
            s for s in side
            if s["attributes"]["part"] == "collect"
            and s["attributes"]["phase"] == "cv_predict"
        ]
        assert len(collects) == 1, program


def median_error(y_true, y_pred, multioutput="uniform_average"):
    """A user's metric: a callable of the evaluation's list."""
    return median_absolute_error(y_true, y_pred, multioutput=multioutput)


HOST_ONLY = {
    "custom-metric": lambda name: machine(
        name, detector(DENSE),
        evaluation={"metrics": ["r2_score", median_error]},
    ),
    "unlisted-metric": lambda name: machine(
        name, detector(DENSE),
        evaluation={"metrics": ["sklearn.metrics.median_absolute_error"]},
    ),
    "unlisted-scoring-scaler": lambda name: machine(
        name, detector(DENSE),
        evaluation={"scoring_scaler": "sklearn.preprocessing.MaxAbsScaler"},
    ),
    "unlisted-detector-scaler": lambda name: machine(
        name, detector(DENSE, scaler="sklearn.preprocessing.MaxAbsScaler")
    ),
    "kfcv-detector": lambda name: machine(
        name, detector(DENSE, kind="DiffBasedKFCVAnomalyDetector")
    ),
}


@pytest.mark.parametrize("case", sorted(HOST_ONLY))
def test_what_a_program_cannot_express_takes_the_host_path_alone(case, monkeypatch):
    """One machine of a group whose evaluation the program cannot express
    is scored on the host, its neighbours on the device, and every
    number is what an all-host build gives."""

    def machines():
        return [
            machine("plain-a", detector(DENSE)),
            HOST_ONLY[case]("odd-one"),
            machine("plain-b", detector(DENSE)),
        ]

    builder, plans, parts = cross_validate(machines())
    assert not builder.build_errors
    chosen = [FleetBuilder._device_scoring(plan) is not None for plan in plans]
    assert chosen == [True, False, True]
    odd_folds = 5 if case == "kfcv-detector" else 3
    counts = part_counts(parts)
    assert counts["device_scores"] == 6 and counts["metric_scores"] == odd_folds
    host_scored(monkeypatch)
    host_builder, host_plans, _ = cross_validate(machines())
    for mine, theirs in zip(plans, host_plans):
        same_cv_scores(mine.cv_scores, theirs.cv_scores)
        close(mine.detector.aggregate_threshold_, theirs.detector.aggregate_threshold_)
        np.testing.assert_array_equal(
            np.asarray(mine.detector.feature_thresholds_),
            np.asarray(theirs.detector.feature_thresholds_),
        )
    if case == "custom-metric":
        assert "median-error" in plans[1].cv_scores
        assert "median-error" not in plans[0].cv_scores
    if case == "kfcv-detector":
        assert isinstance(plans[1].detector, DiffBasedKFCVAnomalyDetector)


def test_a_nan_prediction_fails_the_build_as_the_hosts_code_does(monkeypatch):
    """The program flags the fold, the host's code meets it and raises
    sklearn's own error: the same machines fail, for the same reason."""

    def machines():
        return [machine("sound", detector(DENSE)), machine("spoiled", detector(DENSE))]

    poison = ("spoiled::fold1",)
    builder, _, parts = cross_validate(machines(), poison)
    host_scored(monkeypatch)
    host_builder, _, _ = cross_validate(machines(), poison)
    assert set(builder.build_errors) == set(host_builder.build_errors) == {
        "sound", "spoiled",
    }
    for name, error in builder.build_errors.items():
        assert isinstance(error, ValueError) and "NaN" in str(error)
        assert str(error) == str(host_builder.build_errors[name])


# -- a whole build ----------------------------------------------------------------


def test_a_build_gives_the_same_artifact_metadata_both_ways(monkeypatch, tmp_path):
    """Trained fold models, CV, thresholds, final fit and dump: the
    models' metadata of a build scored on the device is the all-host
    build's (training is deterministic a seed)."""

    def machines():
        return [
            machine("whole-a", detector(DENSE, window=6)),
            machine("whole-b", detector(DENSE, window=6), evaluation=folds_of(5)),
        ]

    built = fleet_build(machines(), output_dir=str(tmp_path / "device"))
    host_scored(monkeypatch)
    host_built = fleet_build(machines(), output_dir=str(tmp_path / "host"))
    assert len(built) == len(host_built) == 2
    for (model, made), (host_model, host_made) in zip(built, host_built):
        mine = made.metadata.build_metadata.model
        theirs = host_made.metadata.build_metadata.model
        same_cv_scores(mine.cross_validation.scores, theirs.cross_validation.scores)
        got, expected = model.get_metadata(), host_model.get_metadata()
        assert list(got) == list(expected)
        identical(got["feature-thresholds"], expected["feature-thresholds"])
        identical(got["smooth-feature-thresholds"], expected["smooth-feature-thresholds"])
        close(got["aggregate-threshold"], expected["aggregate-threshold"])
        close(got["smooth-aggregate-threshold"], expected["smooth-aggregate-threshold"])
    assert built[1][0].get_metadata()["thresholds-degraded"] is True
