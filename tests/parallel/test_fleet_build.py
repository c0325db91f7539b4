import numpy as np
import pandas as pd
import pytest

from gordo_tpu import serializer
from gordo_tpu.machine import Machine
from gordo_tpu.parallel import FleetBuilder, fleet_build

DATASET = {
    "type": "RandomDataset",
    "train_start_date": "2020-01-01T00:00:00+00:00",
    "train_end_date": "2020-01-05T00:00:00+00:00",
}

DETECTOR_MODEL = {
    "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "sklearn.pipeline.Pipeline": {
                "steps": [
                    "sklearn.preprocessing.MinMaxScaler",
                    {
                        "gordo_tpu.models.JaxAutoEncoder": {
                            "kind": "feedforward_hourglass",
                            "encoding_layers": 1,
                            "epochs": 2,
                        }
                    },
                ]
            }
        }
    }
}


def make_machine(name, tags, model=None):
    return Machine.from_config(
        {
            "name": name,
            "model": model or DETECTOR_MODEL,
            "dataset": {**DATASET, "tag_list": tags},
        },
        project_name="fleet-test",
    )


def test_fleet_build_detectors(tmp_path):
    # two machines share an architecture bucket (same tag count), one differs
    machines = [
        make_machine("m-a", ["t1", "t2", "t3"]),
        make_machine("m-b", ["t4", "t5", "t6"]),
        make_machine("m-c", ["t7", "t8"]),
    ]
    results = fleet_build(machines, output_dir=str(tmp_path))
    assert len(results) == 3
    for model, machine in results:
        assert hasattr(model, "anomaly")
        assert model.aggregate_threshold_ is not None
        assert len(model.feature_thresholds_) == len(
            machine.dataset.tag_list
        )
        bm = machine.metadata.build_metadata
        assert bm.model.model_offset == 0
        scores = bm.model.cross_validation.scores
        n_tags = len(machine.dataset.tag_list)
        assert len(scores) == 4 * (n_tags + 1)
        assert {"fold-mean", "fold-std", "fold-1", "fold-2", "fold-3"} <= set(
            scores["explained-variance-score"]
        )
        # artifacts on disk, loadable, servable
        loaded = serializer.load(str(tmp_path / machine.name))
        X, y = machine.dataset.get_data()
        frame = loaded.anomaly(X, y)
        assert len(frame) == len(X)


def test_fleet_build_matches_model_builder_thresholds():
    """Fleet CV must produce the same thresholds as the sequential
    ModelBuilder path for the same machine."""
    from gordo_tpu.builder import ModelBuilder

    machine = make_machine("parity", ["t1", "t2"])
    fleet_model, _ = fleet_build([make_machine("parity", ["t1", "t2"])])[0]
    seq_model, _ = ModelBuilder(machine).build()
    np.testing.assert_allclose(
        fleet_model.feature_thresholds_.values.astype(float),
        seq_model.feature_thresholds_.values.astype(float),
        rtol=0.2,
    )
    np.testing.assert_allclose(
        fleet_model.aggregate_threshold_, seq_model.aggregate_threshold_, rtol=0.2
    )


def test_fleet_build_lstm():
    model_def = {
        "gordo_tpu.models.JaxLSTMAutoEncoder": {
            "kind": "lstm_symmetric",
            "dims": [4],
            "funcs": ["tanh"],
            "lookback_window": 4,
            "epochs": 1,
        }
    }
    results = fleet_build([make_machine("lstm-m", ["t1", "t2"], model=model_def)])
    model, machine = results[0]
    assert machine.metadata.build_metadata.model.model_offset == 3
    X, _ = machine.dataset.get_data()
    assert len(model.predict(X)) == len(X) - 3


def test_fleet_build_fallback_for_non_jax_models():
    model_def = {
        "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
            "base_estimator": "sklearn.linear_model.LinearRegression"
        }
    }
    results = fleet_build([make_machine("sk-m", ["t1", "t2"], model=model_def)])
    model, machine = results[0]
    assert model.aggregate_threshold_ is not None
    assert machine.metadata.build_metadata.model.model_training_duration_sec > 0


def test_cross_val_only_mode():
    machine = Machine.from_config(
        {
            "name": "cv-only",
            "model": DETECTOR_MODEL,
            "dataset": {**DATASET, "tag_list": ["t1", "t2"]},
            "evaluation": {"cv_mode": "cross_val_only"},
        },
        project_name="fleet-test",
    )
    model, built = fleet_build([machine])[0]
    assert built.metadata.build_metadata.model.cross_validation.scores
    assert built.metadata.build_metadata.model.model_training_duration_sec == 0.0


def test_fleet_kfcv_matches_sequential():
    """KFCV thresholds: fleet chronological stitching must track the
    sequential path (same folds, same smoothing order)."""
    from gordo_tpu.builder import ModelBuilder

    model_def = {
        "gordo_tpu.models.anomaly.diff.DiffBasedKFCVAnomalyDetector": {
            "base_estimator": {
                "gordo_tpu.models.JaxAutoEncoder": {
                    "kind": "feedforward_hourglass",
                    "encoding_layers": 1,
                    "epochs": 2,
                }
            },
            "window": 12,
        }
    }
    fleet_model, _ = fleet_build(
        [make_machine("kfcv-m", ["t1", "t2"], model=model_def)]
    )[0]
    seq_model, _ = ModelBuilder(
        make_machine("kfcv-m", ["t1", "t2"], model=model_def)
    ).build()
    np.testing.assert_allclose(
        fleet_model.aggregate_threshold_, seq_model.aggregate_threshold_, rtol=0.35
    )
    np.testing.assert_allclose(
        np.asarray(fleet_model.feature_thresholds_, dtype=float),
        np.asarray(seq_model.feature_thresholds_, dtype=float),
        rtol=0.35,
    )


def test_smoothed_threshold_metadata_present():
    model_def = {
        "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
            "base_estimator": {
                "gordo_tpu.models.JaxAutoEncoder": {
                    "kind": "feedforward_hourglass",
                    "encoding_layers": 1,
                    "epochs": 1,
                }
            },
            "window": 12,
        }
    }
    model, _ = fleet_build([make_machine("sm-m", ["t1", "t2"], model=model_def)])[0]
    meta = model.get_metadata()
    assert "smooth-feature-thresholds-per-fold" in meta
    assert "smooth-aggregate-thresholds-per-fold" in meta


def test_fleet_build_fail_fast_false_continues(tmp_path):
    """One machine's data failure must not stop the fleet (the reference
    DAG runs failFast:false — argo-workflow.yml.template)."""
    good = make_machine("good-machine", ["tag-1", "tag-2"])
    # n_samples_threshold above the row count forces InsufficientDataError
    bad = Machine.from_config(
        {
            "name": "bad-machine",
            "model": DETECTOR_MODEL,
            "dataset": {
                **DATASET,
                "tag_list": ["tag-1", "tag-2"],
                "n_samples_threshold": 10_000_000,
            },
        },
        project_name="fleet-test",
    )
    builder = FleetBuilder([good, bad])
    results = builder.build(output_dir=str(tmp_path))
    assert [m.name for _, m in results] == ["good-machine"]
    assert set(builder.build_errors) == {"bad-machine"}
    from gordo_tpu.dataset.exceptions import InsufficientDataError

    assert isinstance(builder.build_errors["bad-machine"], InsufficientDataError)
    # good machine's artifacts still landed
    assert (tmp_path / "good-machine" / "model.pkl").exists()
    assert not (tmp_path / "bad-machine").exists()


def test_try_call_propagates_shutdown_signals():
    """_try_call's broad capture exists for failFast:false semantics
    only — interpreter shutdown (Ctrl-C, SystemExit/injected kill) must
    propagate, never become a per-machine build error."""
    from gordo_tpu.parallel.fleet_build import _try_call

    def raise_(exc):
        raise exc

    with pytest.raises(KeyboardInterrupt):
        _try_call(raise_, KeyboardInterrupt())
    with pytest.raises(SystemExit):
        _try_call(raise_, SystemExit(137))
    captured = _try_call(raise_, RuntimeError("per-machine"))
    assert isinstance(captured, RuntimeError)
    assert _try_call(lambda: None) is None


def test_fleet_build_fail_fast_true_raises_fleet_build_error():
    """fail_fast=True surfaces the first FleetBuildError instead of
    recording it: here a windowed (LSTM) model with scattered KFold CV
    folds, which have no clean window mapping."""
    from gordo_tpu.parallel.fleet_build import FleetBuildError

    machine = Machine.from_config(
        {
            "name": "ff-lstm",
            "model": {
                "gordo_tpu.models.JaxLSTMAutoEncoder": {
                    "kind": "lstm_symmetric",
                    "dims": [4],
                    "funcs": ["tanh"],
                    "lookback_window": 4,
                    "epochs": 1,
                }
            },
            "dataset": {**DATASET, "tag_list": ["t1", "t2"]},
            "evaluation": {
                "cv": {
                    "sklearn.model_selection.KFold": {
                        "n_splits": 3,
                        "shuffle": True,
                        "random_state": 0,
                    }
                }
            },
        },
        project_name="fleet-test",
    )
    with pytest.raises(FleetBuildError):
        FleetBuilder([machine], fail_fast=True).build()
    # failFast:false records the same failure instead of raising
    builder = FleetBuilder([machine])
    assert builder.build() == []
    assert isinstance(builder.build_errors["ff-lstm"], FleetBuildError)


def test_final_fit_divergence_retry_counts_into_metadata(monkeypatch):
    """FleetTrainer.train's diverged-member reseed retry must surface in
    the built machine's BuildMetadata robustness counters."""
    from gordo_tpu.parallel import FleetTrainer

    machine = make_machine("retry-meta", ["t1", "t2"])
    builder = FleetBuilder([machine])
    real = FleetTrainer._train_once
    state = {"poisoned": False}

    def poison_first_final_fit(self, members, config, *on_device):
        results = real(self, members, config, *on_device)
        # poison exactly one result once: the final-fit members carry the
        # machine name itself (CV fold members are name::foldN)
        if not state["poisoned"] and any(r.name == "retry-meta" for r in results):
            state["poisoned"] = True
            for r in results:
                if r.name == "retry-meta":
                    r.history.history["loss"] = [float("nan")]
        return results

    monkeypatch.setattr(FleetTrainer, "_train_once", poison_first_final_fit)
    results = builder.build()
    assert len(results) == 1
    _, built = results[0]
    robustness = built.metadata.build_metadata.robustness
    assert robustness.fleet_retries == 1
    assert builder.robustness["fleet_retries"] == 1
    estimator = results[0][0].base_estimator.steps[-1][1]
    assert np.isfinite(estimator._history.history["loss"][-1])


def test_fleet_build_fail_fast_true_raises():
    bad = Machine.from_config(
        {
            "name": "bad-machine",
            "model": DETECTOR_MODEL,
            "dataset": {
                **DATASET,
                "tag_list": ["tag-1"],
                "n_samples_threshold": 10_000_000,
            },
        },
        project_name="fleet-test",
    )
    from gordo_tpu.dataset.exceptions import InsufficientDataError

    with pytest.raises(InsufficientDataError):
        FleetBuilder([bad], fail_fast=True).build()


def test_fleet_build_register_failure_not_dumped(tmp_path, monkeypatch):
    """A machine that fails at the register step must not leave artifacts
    in output_dir (its build is an error, not a product)."""
    from gordo_tpu.builder.build_model import ModelBuilder

    good = make_machine("reg-good", ["t1", "t2"])
    doomed = make_machine("reg-doomed", ["t3", "t4"])
    register_dir = tmp_path / "register"
    output_dir = tmp_path / "out"

    original_register = ModelBuilder.register

    def failing_register(self, model, machine, register_directory):
        if machine.name == "reg-doomed":
            raise OSError("disk full")
        return original_register(self, model, machine, register_directory)

    monkeypatch.setattr(ModelBuilder, "register", failing_register)
    builder = FleetBuilder([good, doomed])
    results = builder.build(
        output_dir=str(output_dir), model_register_dir=str(register_dir)
    )
    assert [m.name for _, m in results] == ["reg-good"]
    assert set(builder.build_errors) == {"reg-doomed"}
    assert (output_dir / "reg-good" / "model.pkl").exists()
    assert not (output_dir / "reg-doomed").exists()


def test_cv_chunking_by_bytes_preserves_order():
    from gordo_tpu.parallel.fleet_build import _chunk_by_bytes
    from gordo_tpu.parallel import FleetMember
    from gordo_tpu.models.factories import feedforward_hourglass

    spec = feedforward_hourglass(4)
    members = [
        FleetMember(name=f"c{i}", spec=spec,
                    X=(X := np.zeros((50, 4), np.float32)), y=X, seed=i)
        for i in range(7)
    ]
    items = [(f"plan{i}", i % 3) for i in range(7)]
    per_member = members[0].X.nbytes  # y aliased -> not double-counted
    chunks = _chunk_by_bytes(members, items, budget=per_member * 3)
    assert [len(ms) for ms, _ in chunks] == [3, 3, 1]
    flat_items = [it for _, its in chunks for it in its]
    assert flat_items == items  # order preserved across chunk boundaries
    # a budget smaller than one member still yields 1-member chunks
    tiny = _chunk_by_bytes(members, items, budget=1)
    assert [len(ms) for ms, _ in tiny] == [1] * 7


def test_cv_chunk_split_retry_isolates_bad_machine(monkeypatch):
    """A fold bucket that fails as a whole must split-retry down to the
    bad machine: the healthy machines' CV still completes."""
    from gordo_tpu.parallel import FleetBuilder, FleetTrainer

    machines = [make_machine(f"split-{i}", ["t1", "t2"]) for i in range(3)]
    builder = FleetBuilder(machines)
    real_train = builder.trainer.train
    calls = {"n": 0}

    def flaky_train(members, config, **kwargs):
        calls["n"] += 1
        # fail any chunk containing the bad machine AND another member —
        # forcing the halving retry to isolate it
        names = [m.name for m in members]
        bad = [n for n in names if n.startswith("split-1")]
        if bad and len(names) > 1:
            raise RuntimeError("chunk-level failure")
        if bad:
            raise RuntimeError("bad machine alone")
        return real_train(members, config, **kwargs)

    monkeypatch.setattr(builder.trainer, "train", flaky_train)
    results = builder.build()
    names = {m.name for _, m in results}
    assert names == {"split-0", "split-2"}
    assert set(builder.build_errors) == {"split-1"}
    assert calls["n"] > 3  # the halving retry actually recursed


class TestRollingMinMax:
    """FleetBuilder._rolling_min_max replaced the per-(machine, fold)
    pandas rolling(w).min().max() threshold statistic; parity with the
    pandas expression is the contract (reference diff.py:196-212)."""

    @pytest.mark.parametrize("window", [1, 6, 144])
    @pytest.mark.parametrize("n", [4, 6, 150, 400])
    def test_series_parity(self, window, n):
        rng = np.random.RandomState(window * 1000 + n)
        values = rng.rand(n)
        expected = pd.Series(values).rolling(window).min().max()
        actual = FleetBuilder._rolling_min_max(values, window)
        if np.isnan(expected):
            assert np.isnan(actual)
        else:
            assert actual == pytest.approx(expected, rel=1e-12)

    @pytest.mark.parametrize("window", [6, 30])
    def test_frame_parity(self, window):
        rng = np.random.RandomState(7)
        values = rng.rand(200, 4)
        expected = pd.DataFrame(values).rolling(window).min().max().to_numpy()
        actual = FleetBuilder._rolling_min_max(values, window)
        np.testing.assert_allclose(actual, expected, rtol=1e-12)

    def test_nan_windows_skipped_like_pandas(self):
        values = np.array([1.0, 2.0, np.nan, 4.0, 5.0, 6.0, 7.0, 8.0])
        expected = pd.Series(values).rolling(3).min().max()
        actual = FleetBuilder._rolling_min_max(values, 3)
        assert actual == pytest.approx(expected)

    def test_all_nan_returns_nan(self):
        assert np.isnan(FleetBuilder._rolling_min_max(np.full(10, np.nan), 3))
