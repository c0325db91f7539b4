"""
Diff-based anomaly detectors — the production model family.

Math parity with the reference (gordo/machine/model/anomaly/diff.py):

``DiffBasedAnomalyDetector``
    Wraps any estimator + scaler. ``cross_validate`` runs
    TimeSeriesSplit(3); per fold it computes per-tag MAE and the per-
    timestep MSE of *scaled* residuals; thresholds are
    ``metric.rolling(6).min().max()`` of the **last** fold (over every row
    of a fold that scored fewer than six: :func:`threshold_run`; plus optional
    ``window``-smoothed variants). ``anomaly`` emits tag-level scaled /
    unscaled errors, total (mean-square) errors, optional smoothed columns,
    and confidence = error / threshold.

``DiffBasedKFCVAnomalyDetector``
    Shuffled KFold(5); thresholds are the ``threshold_percentile`` quantile
    of window-smoothed validation errors stitched over all folds.

Engine note: the base estimator's predict is the jitted JAX forward; the
pandas threshold/rolling arithmetic is host-side by design (tiny data,
rich semantics).
"""

import logging
from datetime import timedelta
from typing import Optional, Union

import numpy as np
import pandas as pd
from sklearn.base import BaseEstimator, TransformerMixin
from sklearn.exceptions import NotFittedError
from sklearn.metrics import explained_variance_score
from sklearn.model_selection import KFold, TimeSeriesSplit
from sklearn.model_selection import cross_validate as sklearn_cross_validate
from sklearn.preprocessing import MinMaxScaler
from sklearn.utils import shuffle as sklearn_shuffle

from .. import utils as model_utils
from ..base import GordoBase
from .base import AnomalyDetectorBase

logger = logging.getLogger(__name__)


def _default_base_estimator():
    from ..estimators import JaxAutoEncoder

    return JaxAutoEncoder(kind="feedforward_hourglass")


#: rows of the run whose minimum a threshold takes: the reference's
#: ``metric.rolling(6).min().max()``
THRESHOLD_RUN = 6


def threshold_run(rows: int) -> int:
    """The run a fold of ``rows`` scored rows takes its thresholds over:
    :data:`THRESHOLD_RUN`, or every row the fold has where it has fewer
    (a lookback of weeks leaves a history of months a handful of target
    rows a fold, and ``rolling(6)`` of four values is no value at all: a
    model without thresholds, which the server refuses to score). Such
    thresholds are weaker than the reference's: the metadata of a model
    that serves them says ``thresholds-degraded`` and the rows of the run
    (``threshold-run-rows``; the last fold's, whose thresholds serve)."""
    return max(1, min(THRESHOLD_RUN, rows))


class DiffBasedAnomalyDetector(AnomalyDetectorBase):
    def __init__(
        self,
        base_estimator: Optional[BaseEstimator] = None,
        scaler: Optional[TransformerMixin] = None,
        require_thresholds: bool = True,
        shuffle: bool = False,
        window: Optional[int] = None,
        smoothing_method: Optional[str] = None,
    ):
        """
        Diff-error anomaly detection around ``base_estimator``; the scaler is
        fit on ``y`` *after* training purely for error scaling.
        """
        self.base_estimator = (
            base_estimator if base_estimator is not None else _default_base_estimator()
        )
        self.scaler = scaler if scaler is not None else MinMaxScaler()
        self.require_thresholds = require_thresholds
        self.shuffle = shuffle
        self.window = window
        self.smoothing_method = smoothing_method
        if self.window is not None and self.smoothing_method is None:
            self.smoothing_method = "smm"

    def __getattr__(self, item):
        # Transparent delegation into the base estimator (reference
        # diff.py:78-86); __getattr__ only fires on missing attributes.
        # Dunders, privates, and the serializer hooks must NOT delegate:
        # leaking the base estimator's into_definition would serialize the
        # detector as if it were its base estimator.
        if item.startswith("_") or item in ("into_definition", "from_definition"):
            raise AttributeError(item)
        try:
            return getattr(self.__dict__["base_estimator"], item)
        except KeyError:
            raise AttributeError(item)

    def get_params(self, deep: bool = True) -> dict:
        params = {
            "base_estimator": self.base_estimator,
            "scaler": self.scaler,
            "shuffle": self.shuffle,
        }
        if self.window is not None:
            params["window"] = self.window
            params["smoothing_method"] = self.smoothing_method
        return params

    def get_metadata(self) -> dict:
        metadata = {}
        if hasattr(self, "feature_thresholds_"):
            metadata["feature-thresholds"] = self.feature_thresholds_.tolist()
        if hasattr(self, "aggregate_threshold_"):
            metadata["aggregate-threshold"] = self.aggregate_threshold_
        if hasattr(self, "feature_thresholds_per_fold_"):
            metadata["feature-thresholds-per-fold"] = (
                self.feature_thresholds_per_fold_.to_dict()
            )
        if hasattr(self, "aggregate_thresholds_per_fold_"):
            metadata["aggregate-thresholds-per-fold"] = (
                self.aggregate_thresholds_per_fold_
            )
        if getattr(self, "threshold_run_rows_", THRESHOLD_RUN) < THRESHOLD_RUN:
            # the thresholds served are a minimum over fewer rows than
            # the reference's run of six: said where a reader looks
            metadata["thresholds-degraded"] = True
            metadata["threshold-run-rows"] = self.threshold_run_rows_
        metadata["window"] = self.window
        metadata["smoothing-method"] = self.smoothing_method
        if getattr(self, "smooth_feature_thresholds_", None) is not None:
            metadata["smooth-feature-thresholds"] = (
                self.smooth_feature_thresholds_.tolist()
            )
        if getattr(self, "smooth_aggregate_threshold_", None) is not None:
            metadata["smooth-aggregate-threshold"] = self.smooth_aggregate_threshold_
        if hasattr(self, "smooth_feature_thresholds_per_fold_"):
            metadata["smooth-feature-thresholds-per-fold"] = (
                self.smooth_feature_thresholds_per_fold_.to_dict()
            )
        if hasattr(self, "smooth_aggregate_thresholds_per_fold_"):
            metadata["smooth-aggregate-thresholds-per-fold"] = (
                self.smooth_aggregate_thresholds_per_fold_
            )
        if isinstance(self.base_estimator, GordoBase):
            metadata.update(self.base_estimator.get_metadata())
        else:
            metadata.update(
                {
                    "scaler": str(self.scaler),
                    "base_estimator": str(self.base_estimator),
                    "shuffle": self.shuffle,
                }
            )
        return metadata

    def score(self, X, y, sample_weight=None) -> float:
        if hasattr(self.base_estimator, "score"):
            return self.base_estimator.score(X, y)
        out = self.base_estimator.predict(X)
        y = np.asarray(getattr(y, "values", y))
        return explained_variance_score(y[-len(out):], out)

    def fit(self, X, y) -> "DiffBasedAnomalyDetector":
        if self.shuffle:
            X_s, y_s = sklearn_shuffle(X, y, random_state=0)
            self.base_estimator.fit(X_s, y_s)
        else:
            self.base_estimator.fit(X, y)
        self.scaler.fit(y)  # used only for error scaling in .anomaly()
        return self

    def cross_validate(
        self,
        *,
        X: Union[pd.DataFrame, np.ndarray],
        y: Union[pd.DataFrame, np.ndarray],
        cv=None,
        **kwargs,
    ):
        """
        TimeSeriesSplit(3) CV; updates threshold attributes from the folds
        (final thresholds = last fold's).
        """
        if cv is None:
            cv = TimeSeriesSplit(n_splits=3)
        kwargs.update(dict(return_estimator=True, cv=cv))
        cv_output = sklearn_cross_validate(self, X=X, y=y, **kwargs)

        feature_folds = {}
        smooth_feature_folds = {}
        self.aggregate_thresholds_per_fold_ = {}
        self.smooth_aggregate_thresholds_per_fold_ = {}
        tag_thresholds_fold = None
        aggregate_threshold_fold = None
        smooth_tag_thresholds_fold = None
        smooth_aggregate_threshold_fold = None

        for i, ((_, test_idxs), fold_model) in enumerate(
            zip(kwargs["cv"].split(X, y), cv_output["estimator"])
        ):
            X_test = X.iloc[test_idxs] if isinstance(X, pd.DataFrame) else X[test_idxs]
            y_pred = fold_model.predict(X_test)
            # Align y for any model offset (LSTM outputs fewer rows)
            test_idxs = test_idxs[-len(y_pred):]
            y_true = y.iloc[test_idxs] if isinstance(y, pd.DataFrame) else y[test_idxs]

            scaled_mse = self._scaled_mse_per_timestep(fold_model, y_true, y_pred)
            mae = self._absolute_error(y_true, y_pred)

            run = self.threshold_run_rows_ = threshold_run(len(scaled_mse))
            aggregate_threshold_fold = float(scaled_mse.rolling(run).min().max())
            self.aggregate_thresholds_per_fold_[f"fold-{i}"] = aggregate_threshold_fold

            tag_thresholds_fold = mae.rolling(run).min().max()
            tag_thresholds_fold.name = f"fold-{i}"
            feature_folds[f"fold-{i}"] = tag_thresholds_fold

            if self.window is not None:
                smooth_aggregate_threshold_fold = float(
                    scaled_mse.rolling(self.window).min().max()
                )
                self.smooth_aggregate_thresholds_per_fold_[f"fold-{i}"] = (
                    smooth_aggregate_threshold_fold
                )
                smooth_tag_thresholds_fold = mae.rolling(self.window).min().max()
                smooth_tag_thresholds_fold.name = f"fold-{i}"
                smooth_feature_folds[f"fold-{i}"] = smooth_tag_thresholds_fold

        self.feature_thresholds_per_fold_ = (
            pd.DataFrame(feature_folds).T if feature_folds else pd.DataFrame()
        )
        self.smooth_feature_thresholds_per_fold_ = (
            pd.DataFrame(smooth_feature_folds).T
            if smooth_feature_folds
            else pd.DataFrame()
        )
        # Final thresholds come from the last fold
        self.feature_thresholds_ = tag_thresholds_fold
        self.aggregate_threshold_ = aggregate_threshold_fold
        self.smooth_feature_thresholds_ = smooth_tag_thresholds_fold
        self.smooth_aggregate_threshold_ = smooth_aggregate_threshold_fold
        return cv_output

    @staticmethod
    def _scaled_mse_per_timestep(model, y_true, y_pred) -> pd.Series:
        try:
            scaled_y_true = model.scaler.transform(y_true)
        except (NotFittedError, ValueError):
            scaled_y_true = model.scaler.fit_transform(y_true)
        scaled_y_pred = model.scaler.transform(y_pred)
        mse = np.mean(np.square(scaled_y_pred - scaled_y_true), axis=1)
        return pd.Series(np.asarray(mse))

    @staticmethod
    def _absolute_error(y_true, y_pred) -> pd.DataFrame:
        return pd.DataFrame(
            np.abs(np.asarray(getattr(y_true, "values", y_true)) - np.asarray(y_pred))
        )

    def _smoothing(self, metric):
        if self.smoothing_method == "smm":
            return metric.rolling(self.window).median()
        if self.smoothing_method == "sma":
            return metric.rolling(self.window).mean()
        if self.smoothing_method == "ewma":
            return metric.ewm(span=self.window).mean()
        raise ValueError(f"Unknown smoothing_method {self.smoothing_method!r}")

    def anomaly(
        self,
        X: pd.DataFrame,
        y: pd.DataFrame,
        frequency: Optional[timedelta] = None,
        model_output: Optional[np.ndarray] = None,
    ) -> pd.DataFrame:
        """
        Build the anomaly response DataFrame for ``X``/``y``.

        ``model_output`` short-circuits the base estimator's predict with
        an already-computed reconstruction — the fleet serving route
        scores whole spec buckets as one fused device program and then
        assembles each machine's full anomaly frame from its slice.
        """
        if not hasattr(X, "values"):
            raise ValueError("Unable to find X.values property")

        if model_output is None:
            model_output = (
                self.predict(X)
                if hasattr(self.base_estimator, "predict")
                else self.transform(X)
            )

        data = model_utils.make_base_dataframe(
            tags=X.columns,
            model_input=X.values,
            model_output=model_output,
            target_tag_list=y.columns,
            index=getattr(X, "index", None),
            frequency=frequency,
        )

        model_out_scaled = pd.DataFrame(
            self.scaler.transform(data["model-output"]),
            columns=data["model-output"].columns,
            index=data.index,
        )

        # Scaled per-tag anomaly; y offset-aligned to the model output
        scaled_y = self.scaler.transform(y)
        tag_anomaly_scaled = np.abs(model_out_scaled - scaled_y[-len(data):, :])
        tag_anomaly_scaled.columns = pd.MultiIndex.from_product(
            (("tag-anomaly-scaled",), tag_anomaly_scaled.columns)
        )
        data = data.join(tag_anomaly_scaled)
        data["total-anomaly-scaled"] = np.square(data["tag-anomaly-scaled"]).mean(axis=1)

        unscaled_abs_diff = pd.DataFrame(
            data=np.abs(
                data["model-output"].to_numpy() - np.asarray(y)[-len(data):, :]
            ),
            index=data.index,
            columns=pd.MultiIndex.from_product(
                (("tag-anomaly-unscaled",), list(y.columns))
            ),
        )
        data = data.join(unscaled_abs_diff)
        data["total-anomaly-unscaled"] = np.square(
            data["tag-anomaly-unscaled"]
        ).mean(axis=1)

        if self.window is not None and self.smoothing_method is not None:
            smooth_scaled = self._smoothing(tag_anomaly_scaled)
            smooth_scaled.columns = smooth_scaled.columns.set_levels(
                ["smooth-tag-anomaly-scaled"], level=0
            )
            data = data.join(smooth_scaled)
            data["smooth-total-anomaly-scaled"] = self._smoothing(
                data["total-anomaly-scaled"]
            )
            smooth_unscaled = self._smoothing(unscaled_abs_diff)
            smooth_unscaled.columns = smooth_unscaled.columns.set_levels(
                ["smooth-tag-anomaly-unscaled"], level=0
            )
            data = data.join(smooth_unscaled)
            data["smooth-total-anomaly-unscaled"] = self._smoothing(
                data["total-anomaly-unscaled"]
            )

        if hasattr(self, "feature_thresholds_") and self.feature_thresholds_ is not None:
            confidence = unscaled_abs_diff.values / np.asarray(
                self.feature_thresholds_.values, dtype=float
            )
            data = data.join(
                pd.DataFrame(
                    confidence,
                    index=unscaled_abs_diff.index,
                    columns=pd.MultiIndex.from_product(
                        (("anomaly-confidence",), data["model-output"].columns)
                    ),
                )
            )

        if hasattr(self, "aggregate_threshold_") and self.aggregate_threshold_ is not None:
            data["total-anomaly-confidence"] = (
                data["total-anomaly-scaled"] / self.aggregate_threshold_
            )

        if self.require_thresholds and not any(
            hasattr(self, attr)
            for attr in ("feature_thresholds_", "aggregate_threshold_")
        ):
            raise AttributeError(
                f"`require_thresholds={self.require_thresholds}` however "
                "`.cross_validate` was not called to calculate thresholds "
                "before `.anomaly`"
            )
        return data


class DiffBasedKFCVAnomalyDetector(DiffBasedAnomalyDetector):
    def __init__(
        self,
        base_estimator: Optional[BaseEstimator] = None,
        scaler: Optional[TransformerMixin] = None,
        require_thresholds: bool = True,
        shuffle: bool = True,
        window: int = 144,
        smoothing_method: str = "smm",
        threshold_percentile: float = 0.99,
    ):
        """
        KFold(5, shuffled) variant: thresholds are the
        ``threshold_percentile`` quantile of smoothed validation errors.
        """
        super().__init__(
            base_estimator=base_estimator,
            scaler=scaler,
            require_thresholds=require_thresholds,
            shuffle=shuffle,
            window=window,
            smoothing_method=smoothing_method,
        )
        self.threshold_percentile = threshold_percentile

    def get_params(self, deep: bool = True) -> dict:
        return {
            "base_estimator": self.base_estimator,
            "scaler": self.scaler,
            "window": self.window,
            "smoothing_method": self.smoothing_method,
            "shuffle": self.shuffle,
            "threshold_percentile": self.threshold_percentile,
        }

    def get_metadata(self) -> dict:
        metadata = {}
        if hasattr(self, "feature_thresholds_"):
            metadata["feature-thresholds"] = self.feature_thresholds_.tolist()
        if hasattr(self, "aggregate_threshold_"):
            metadata["aggregate-threshold"] = self.aggregate_threshold_
        if isinstance(self.base_estimator, GordoBase):
            metadata.update(self.base_estimator.get_metadata())
        else:
            metadata.update(
                {
                    "scaler": str(self.scaler),
                    "base_estimator": str(self.base_estimator),
                    "shuffle": self.shuffle,
                    "window": self.window,
                    "smoothing-method": self.smoothing_method,
                    "threshold-percentile": self.threshold_percentile,
                }
            )
        return metadata

    def cross_validate(
        self,
        *,
        X: Union[pd.DataFrame, np.ndarray],
        y: Union[pd.DataFrame, np.ndarray],
        cv=None,
        **kwargs,
    ):
        if cv is None:
            cv = KFold(n_splits=5, shuffle=True, random_state=0)
        kwargs.update(dict(return_estimator=True, cv=cv))
        cv_output = sklearn_cross_validate(self, X=X, y=y, **kwargs)

        y = pd.DataFrame(y)
        y_pred = pd.DataFrame(
            np.zeros_like(y, dtype=float), index=y.index, columns=y.columns
        )
        y_val_mse = pd.Series(np.full(len(y), np.nan), index=y.index)

        for (_, test_idxs), fold_model in zip(
            kwargs["cv"].split(X, y), cv_output["estimator"]
        ):
            X_test = (
                X.iloc[test_idxs].to_numpy()
                if isinstance(X, pd.DataFrame)
                else X[test_idxs]
            )
            y_pred.iloc[test_idxs] = fold_model.predict(X_test)
            y_val_mse.iloc[test_idxs] = self._scaled_mse_per_timestep(
                fold_model, y.iloc[test_idxs], y_pred.iloc[test_idxs]
            ).to_numpy()

        self.aggregate_threshold_ = float(self._calculate_threshold(y_val_mse))
        self.feature_thresholds_ = self._calculate_feature_thresholds(y, y_pred)
        return cv_output

    def _calculate_feature_thresholds(self, y_true, y_pred):
        return self._calculate_threshold(self._absolute_error(y_true, y_pred))

    def _calculate_threshold(self, validation_metric):
        return self._smoothing(validation_metric).quantile(self.threshold_percentile)
