"""
Anomaly-detector tests against fast sklearn base estimators (the reference's
strategy — tests/gordo/machine/model/anomaly/test_anomaly_detectors.py runs
these against sklearn models, no deep nets needed).
"""

from datetime import timedelta

import numpy as np
import pandas as pd
import pytest
from sklearn.linear_model import LinearRegression
from sklearn.preprocessing import MinMaxScaler, RobustScaler

from gordo_tpu.models.anomaly import (
    DiffBasedAnomalyDetector,
    DiffBasedKFCVAnomalyDetector,
)

EXPECTED_COLS = {
    "start",
    "end",
    "model-input",
    "model-output",
    "tag-anomaly-scaled",
    "tag-anomaly-unscaled",
    "total-anomaly-scaled",
    "total-anomaly-unscaled",
    "anomaly-confidence",
    "total-anomaly-confidence",
}


@pytest.fixture
def frame():
    rng = np.random.RandomState(1)
    index = pd.date_range("2020-01-01", periods=300, freq="10min", tz="UTC")
    data = rng.rand(300, 3) * 10
    return pd.DataFrame(data, columns=["t1", "t2", "t3"], index=index)


@pytest.mark.parametrize("scaler", [MinMaxScaler(), RobustScaler()])
@pytest.mark.parametrize("shuffle", [False, True])
def test_tss_detector_full_flow(frame, scaler, shuffle):
    det = DiffBasedAnomalyDetector(
        base_estimator=LinearRegression(), scaler=scaler, shuffle=shuffle
    )
    det.cross_validate(X=frame, y=frame)
    det.fit(frame, frame)

    assert det.feature_thresholds_ is not None
    assert len(det.feature_thresholds_) == 3
    assert np.isfinite(det.aggregate_threshold_)
    assert set(det.aggregate_thresholds_per_fold_) == {"fold-0", "fold-1", "fold-2"}
    assert det.feature_thresholds_per_fold_.shape == (3, 3)

    out = det.anomaly(frame, frame, frequency=timedelta(minutes=10))
    assert set(out.columns.get_level_values(0)) == EXPECTED_COLS
    assert len(out) == len(frame)
    # LinearRegression reconstructs X≈X, so errors are ~0
    assert (out["total-anomaly-unscaled"] < 1e-10).all()


def test_smoothed_variants(frame):
    det = DiffBasedAnomalyDetector(
        base_estimator=LinearRegression(), window=12, smoothing_method="sma"
    )
    det.cross_validate(X=frame, y=frame)
    det.fit(frame, frame)
    out = det.anomaly(frame, frame)
    got = set(out.columns.get_level_values(0))
    assert {
        "smooth-tag-anomaly-scaled",
        "smooth-tag-anomaly-unscaled",
        "smooth-total-anomaly-scaled",
        "smooth-total-anomaly-unscaled",
    } <= got
    assert det.smooth_aggregate_threshold_ is not None
    meta = det.get_metadata()
    assert meta["smoothing-method"] == "sma"
    assert "smooth-feature-thresholds" in meta


@pytest.mark.parametrize("smoothing_method", ["smm", "sma", "ewma"])
def test_kfcv_detector(frame, smoothing_method):
    det = DiffBasedKFCVAnomalyDetector(
        base_estimator=LinearRegression(),
        window=24,
        smoothing_method=smoothing_method,
        threshold_percentile=0.95,
    )
    det.cross_validate(X=frame, y=frame)
    det.fit(frame, frame)
    assert np.isfinite(det.aggregate_threshold_)
    assert len(det.feature_thresholds_) == 3
    out = det.anomaly(frame, frame, frequency=timedelta(minutes=10))
    assert len(out) == len(frame)


def test_require_thresholds_enforced(frame):
    det = DiffBasedAnomalyDetector(base_estimator=LinearRegression())
    det.fit(frame, frame)
    with pytest.raises(AttributeError):
        det.anomaly(frame, frame)

    relaxed = DiffBasedAnomalyDetector(
        base_estimator=LinearRegression(), require_thresholds=False
    )
    relaxed.fit(frame, frame)
    out = relaxed.anomaly(frame, frame)
    assert "anomaly-confidence" not in set(out.columns.get_level_values(0))


def test_attribute_delegation(frame):
    det = DiffBasedAnomalyDetector(base_estimator=LinearRegression())
    det.fit(frame, frame)
    # coef_ lives on the base estimator
    assert det.coef_.shape == (3, 3)
    with pytest.raises(AttributeError):
        det.into_definition  # serializer hooks must not delegate


def test_get_metadata_structure(frame):
    det = DiffBasedAnomalyDetector(base_estimator=LinearRegression())
    det.cross_validate(X=frame, y=frame)
    det.fit(frame, frame)
    meta = det.get_metadata()
    assert "feature-thresholds" in meta
    assert "aggregate-threshold" in meta
    assert "feature-thresholds-per-fold" in meta


@pytest.mark.parametrize("rows, run", [(17, 4), (24, 6), (300, 6)])
def test_a_fold_that_scores_fewer_than_six_rows_takes_its_thresholds_over_those(frame, rows, run):
    """``rolling(6).min().max()`` of a fold of four rows is no value at
    all: such a fold takes the minimum over the rows it has
    (``threshold_run``), a fold of six or more the reference's run."""
    from gordo_tpu.models.anomaly.diff import threshold_run

    short = frame.iloc[:rows]
    det = DiffBasedAnomalyDetector(base_estimator=LinearRegression(), scaler=MinMaxScaler())
    det.cross_validate(X=short, y=short)
    assert threshold_run(rows // 4) == run
    # a model whose thresholds come from a shorter run says so, no other does
    meta = det.get_metadata()
    assert meta.get("thresholds-degraded", False) == (run < 6)
    assert meta.get("threshold-run-rows", 6) == run
    assert np.isfinite(det.aggregate_threshold_) and np.isfinite(det.feature_thresholds_).all()
    assert np.isfinite(det.feature_thresholds_per_fold_.to_numpy()).all()
    # the last fold's errors, by hand
    test = short.iloc[-(rows // 4):]
    fold_model = DiffBasedAnomalyDetector(base_estimator=LinearRegression(), scaler=MinMaxScaler())
    fold_model.fit(short.iloc[: -(rows // 4)], short.iloc[: -(rows // 4)])
    mae = (test - fold_model.predict(test)).abs()
    np.testing.assert_allclose(det.feature_thresholds_, mae.rolling(run).min().max(), rtol=1e-6, atol=1e-12)
