"""
ModelBuilder: the full train pipeline for one machine.

Reference parity: gordo/builder/build_model.py — seeding, dataset fetch,
model construction from definition, CV per ``evaluation.cv_mode``
(full_build / cross_val_only / build_only) with per-tag + aggregate metric
scorers, final fit, model-offset determination, metadata assembly, artifact
save, and the content-addressed build cache over the disk registry.

Engine difference: ``model.fit`` dispatches into the fused JAX training
program; the builder itself stays host-side orchestration.
"""

import datetime
import hashlib
import json
import logging
import os
import random
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np
import pandas as pd
from sklearn import metrics
from sklearn.base import BaseEstimator, TransformerMixin
from sklearn.model_selection import cross_validate
from sklearn.pipeline import Pipeline

import gordo_tpu
from .. import serializer
from ..dataset import GordoBaseDataset
from ..machine import Machine
from ..machine.metadata import (
    BuildMetadata,
    CrossValidationMetaData,
    DatasetBuildMetadata,
    DriftBaselineMetadata,
    ModelBuildMetadata,
    TrainingSummaryMetadata,
)
from ..models.base import GordoBase
from ..models.utils import metric_wrapper
from ..telemetry import device_identity
from ..utils import disk_registry

logger = logging.getLogger(__name__)


class ModelBuilder:
    def __init__(self, machine: Machine):
        self.machine = machine
        self._cached_model_path: Optional[str] = None

    @property
    def cache_key(self) -> str:
        return self.calculate_cache_key(self.machine)

    @property
    def cached_model_path(self) -> Optional[str]:
        return self._cached_model_path

    def build(
        self,
        output_dir: Optional[Union[os.PathLike, str]] = None,
        model_register_dir: Optional[Union[os.PathLike, str]] = None,
        replace_cache: bool = False,
    ) -> Tuple[Union[BaseEstimator, Pipeline], Machine]:
        """
        Build the model; when a register dir is given, probe the
        content-addressed cache first and short-circuit on a hit
        (reference: build_model.py:104-190).
        """
        if not model_register_dir:
            model, machine = self._build()
        else:
            logger.debug(
                "Model register dir %s; cache key %s",
                model_register_dir,
                self.cache_key,
            )
            cached = self.load_cached(model_register_dir, replace_cache=replace_cache)
            if cached is not None:
                model, machine = cached
            else:
                model, machine = self._build()
                self.register(model, machine, model_register_dir)
        if output_dir:
            self._save_model(model, machine, output_dir)
        return model, machine

    def load_cached(
        self,
        model_register_dir: Union[os.PathLike, str],
        replace_cache: bool = False,
    ) -> Optional[Tuple[Union[BaseEstimator, Pipeline], Machine]]:
        """
        Probe the content-addressed cache; on a hit return the loaded model
        and its machine with the retrieval date stamped into user metadata
        (reference: build_model.py:135-183).
        """
        if replace_cache:
            self.delete_cached_model(model_register_dir)
        cached_model_path = self.check_cache(model_register_dir)
        if not cached_model_path:
            return None
        model = serializer.load(cached_model_path)
        metadata = serializer.load_metadata(cached_model_path)
        metadata["metadata"]["user_defined"]["date_of_retrieval"] = str(
            datetime.datetime.now(datetime.timezone.utc)
        )
        self._cached_model_path = cached_model_path
        return model, Machine.from_dict(metadata)

    def register(
        self,
        model: Union[BaseEstimator, Pipeline],
        machine: Machine,
        model_register_dir: Union[os.PathLike, str],
    ) -> str:
        """Save artifacts under ``builds/<cache_key>`` and record the path
        in the disk registry for future cache hits."""
        self._cached_model_path = self._save_model(
            model,
            machine,
            os.path.join(str(model_register_dir), "builds", self.cache_key),
        )
        disk_registry.write_key(
            model_register_dir, self.cache_key, self._cached_model_path
        )
        return self._cached_model_path

    def _build(self) -> Tuple[Union[BaseEstimator, Pipeline], Machine]:
        """Train: fetch data → build model → CV → fit → metadata."""
        self.set_seed(seed=1337)

        machine = self.machine.copy()

        # Fetch data (the IO hot spot; duration recorded as
        # query_duration_sec — reference build_model.py:208-215)
        logger.info("Fetching data for machine %s", machine.name)
        start = time.time()
        dataset = (
            machine.dataset
            if isinstance(machine.dataset, GordoBaseDataset)
            else GordoBaseDataset.from_dict(machine.dataset)
        )
        X, y = dataset.get_data()
        time_elapsed_data = time.time() - start

        model = serializer.from_definition(machine.model)

        cv_duration_sec: Optional[float] = None
        scores: Dict[str, Any] = {}
        split_metadata: Dict[str, Any] = {}

        cv_mode = machine.evaluation.get("cv_mode", "full_build").lower()
        if cv_mode in ("cross_val_only", "full_build"):
            metrics_list = self.metrics_from_list(machine.evaluation.get("metrics"))
            if hasattr(model, "predict"):
                logger.debug("Starting cross validation")
                start = time.time()
                scaler = machine.evaluation.get("scoring_scaler")
                metrics_dict = self.build_metrics_dict(metrics_list, y, scaler=scaler)

                split_obj = serializer.from_definition(
                    machine.evaluation.get(
                        "cv",
                        {"sklearn.model_selection.TimeSeriesSplit": {"n_splits": 3}},
                    )
                )
                split_metadata = self.build_split_dict(X, split_obj)

                cv_kwargs = dict(
                    X=X, y=y, scoring=metrics_dict, return_estimator=True, cv=split_obj
                )
                if hasattr(model, "cross_validate"):
                    cv = model.cross_validate(**cv_kwargs)
                else:
                    cv = cross_validate(model, **cv_kwargs)

                for metric_name in metrics_dict:
                    fold_values = cv[f"test_{metric_name}"]
                    val = {
                        "fold-mean": fold_values.mean(),
                        "fold-std": fold_values.std(),
                        "fold-max": fold_values.max(),
                        "fold-min": fold_values.min(),
                    }
                    val.update(
                        {
                            f"fold-{i + 1}": raw
                            for i, raw in enumerate(fold_values.tolist())
                        }
                    )
                    scores[metric_name] = val
                cv_duration_sec = time.time() - start
            else:
                logger.debug("Model has no predict; skipping scoring")

            if cv_mode == "cross_val_only":
                machine.metadata.build_metadata = BuildMetadata(
                    model=ModelBuildMetadata(
                        cross_validation=CrossValidationMetaData(
                            cv_duration_sec=cv_duration_sec,
                            scores=scores,
                            splits=split_metadata,
                        )
                    ),
                    dataset=DatasetBuildMetadata(
                        query_duration_sec=time_elapsed_data,
                        dataset_meta=dataset.get_metadata(),
                    ),
                )
                return model, machine

        logger.debug("Starting to train model")
        start = time.time()
        model.fit(X, y)
        time_elapsed_model = time.time() - start

        machine.metadata.build_metadata = BuildMetadata(
            model=ModelBuildMetadata(
                model_offset=self._determine_offset(model, X),
                model_creation_date=str(
                    datetime.datetime.now(datetime.timezone.utc).astimezone()
                ),
                model_builder_version=gordo_tpu.__version__,
                model_training_duration_sec=time_elapsed_model,
                cross_validation=CrossValidationMetaData(
                    cv_duration_sec=cv_duration_sec,
                    scores=scores,
                    splits=split_metadata,
                ),
                model_meta=self._extract_metadata_from_model(model),
                training=self._extract_training_summary(model),
                device=device_identity() or {},
            ),
            dataset=DatasetBuildMetadata(
                query_duration_sec=time_elapsed_data,
                dataset_meta=dataset.get_metadata(),
            ),
            drift_baseline=self._drift_baseline(X),
        )
        return model, machine

    @staticmethod
    def _drift_baseline(X) -> DriftBaselineMetadata:
        """The lifecycle drift monitor's training baseline (raw-input
        feature stats); a frame it cannot summarize — exotic dtypes from
        a custom provider — degrades to an empty baseline (the monitor
        then self-calibrates) rather than failing the build."""
        try:
            return DriftBaselineMetadata.from_frame(X)
        except Exception as exc:  # noqa: BLE001 - baseline is advisory
            logger.debug("No drift baseline for this frame: %r", exc)
            return DriftBaselineMetadata()

    @staticmethod
    def _extract_training_summary(model) -> TrainingSummaryMetadata:
        """Training-history summary (final/best loss, epochs, early
        stop) dug out of the fitted estimator's ``History`` carry, so
        sequential builds record the same ``training`` block as fleet
        builds (machines degraded out of the fleet path included)."""

        def find_history(obj, depth=0):
            if obj is None or depth > 4:
                return None
            if isinstance(obj, Pipeline):
                return find_history(obj.steps[-1][1], depth + 1)
            history = getattr(obj, "_history", None)
            if history is not None and hasattr(history, "history"):
                return history
            base = getattr(obj, "base_estimator", None)
            if base is not None and base is not obj:
                return find_history(base, depth + 1)
            return None

        history = find_history(model)
        if history is None:
            return TrainingSummaryMetadata()
        try:
            return TrainingSummaryMetadata.from_history(history)
        except (TypeError, ValueError, AttributeError):
            return TrainingSummaryMetadata()

    @staticmethod
    def set_seed(seed: int):
        # JAX RNG is explicit (threaded through fit as PRNG keys); numpy /
        # stdlib seeds cover sklearn shuffles and any host-side sampling.
        random.seed(seed)
        np.random.seed(seed)

    @staticmethod
    def build_split_dict(X: pd.DataFrame, split_obj) -> dict:
        """Record train/test index boundaries per CV fold."""
        split_metadata: Dict[str, Any] = {}
        for i, (train, test) in enumerate(split_obj.split(X)):
            split_metadata.update(
                {
                    f"fold-{i + 1}-train-start": _index_at(X, train[0]),
                    f"fold-{i + 1}-train-end": _index_at(X, train[-1]),
                    f"fold-{i + 1}-test-start": _index_at(X, test[0]),
                    f"fold-{i + 1}-test-end": _index_at(X, test[-1]),
                }
            )
        return split_metadata

    @staticmethod
    def metrics_from_list(metric_names: Optional[List[str]] = None) -> List[Callable]:
        """
        Resolve metric names (e.g. ``explained_variance_score``,
        ``sklearn.metrics.r2_score``) to callables; defaults to the
        reference's four (normalized_config.py:95-107).
        """
        default = [
            metrics.explained_variance_score,
            metrics.r2_score,
            metrics.mean_squared_error,
            metrics.mean_absolute_error,
        ]
        if not metric_names:
            return default
        resolved = []
        for name in metric_names:
            if callable(name):
                resolved.append(name)
            elif "." in name:
                from ..serializer.import_utils import import_location

                resolved.append(import_location(name))
            else:
                resolved.append(getattr(metrics, name))
        return resolved

    @staticmethod
    def build_metrics_dict(
        metrics_list: list,
        y: pd.DataFrame,
        scaler: Optional[Union[TransformerMixin, str, dict]] = None,
    ) -> dict:
        """
        Scorers keyed ``{score}-{tag}`` per target tag plus ``{score}`` for
        the all-tag aggregate; metric names are dashed, tags have spaces
        dashed (reference: build_model.py:377-446).
        """
        if scaler:
            if isinstance(scaler, (str, dict)):
                scaler = serializer.from_definition(scaler)
            scaler.fit(y)

        def _score_factory(metric_func, col_index):
            def _score_per_tag(y_true, y_pred):
                y_true = getattr(y_true, "values", y_true)
                y_pred = getattr(y_pred, "values", y_pred)
                return metric_func(y_true[:, col_index], y_pred[:, col_index])

            return _score_per_tag

        metrics_dict = {}
        for metric in metrics_list:
            metric_str = metric.__name__.replace("_", "-")
            for index, col in enumerate(y.columns):
                scorer_key = f"{metric_str}-{str(col).replace(' ', '-')}"
                metrics_dict[scorer_key] = metrics.make_scorer(
                    metric_wrapper(
                        _score_factory(metric_func=metric, col_index=index),
                        scaler=scaler,
                    )
                )
            metrics_dict[metric_str] = metrics.make_scorer(
                metric_wrapper(metric, scaler=scaler)
            )
        return metrics_dict

    @staticmethod
    def _determine_offset(model: BaseEstimator, X: Union[np.ndarray, pd.DataFrame]) -> int:
        """len(X) - len(model output): the LSTM lookback offset."""
        X = getattr(X, "values", X)
        out = model.predict(X) if hasattr(model, "predict") else model.transform(X)
        return len(X) - len(out)

    @staticmethod
    def _extract_metadata_from_model(
        model: BaseEstimator, metadata: Optional[dict] = None
    ) -> dict:
        """
        Recursively dig ``GordoBase.get_metadata()`` out of nested
        pipelines/estimators (reference: build_model.py:515-569).
        """
        metadata = metadata if metadata is not None else {}
        if isinstance(model, Pipeline):
            final = model.steps[-1][1]
            return ModelBuilder._extract_metadata_from_model(final, metadata)
        if isinstance(model, GordoBase):
            metadata.update(model.get_metadata())
            base = getattr(model, "base_estimator", None)
            if isinstance(base, BaseEstimator) and base is not model:
                ModelBuilder._extract_metadata_from_model(base, metadata)
            return metadata
        for attr_name in ("base_estimator", "estimator"):
            nested = getattr(model, attr_name, None)
            if isinstance(nested, BaseEstimator):
                ModelBuilder._extract_metadata_from_model(nested, metadata)
        return metadata

    @staticmethod
    def calculate_cache_key(machine: Machine) -> str:
        """
        Content hash over (name, model config, dataset config, evaluation
        config, framework major.minor — full version for unstable builds);
        reference: build_model.py:575-631.
        """
        dataset = machine.dataset
        dataset_config = (
            dataset.to_dict() if hasattr(dataset, "to_dict") else dataset
        )
        if gordo_tpu.version_is_stable():
            version = f"{gordo_tpu.MAJOR_VERSION}.{gordo_tpu.MINOR_VERSION}"
        else:
            version = gordo_tpu.__version__
        payload = json.dumps(
            {
                "name": machine.name,
                "model_config": machine.model,
                "data_config": dataset_config,
                "evaluation_config": machine.evaluation,
                "gordo-major-version": gordo_tpu.MAJOR_VERSION,
                "gordo-minor-version": gordo_tpu.MINOR_VERSION,
                "version": version,
            },
            sort_keys=True,
            default=str,
        )
        return hashlib.sha3_256(payload.encode()).hexdigest()

    @staticmethod
    def _cache_entry_valid(path: str) -> bool:
        """The ONE definition of a loadable cache entry — shared by the
        coordinator's check_cache and the read-only probe_cache mirror so
        multi-host processes can never disagree on cache hits."""
        return os.path.isdir(path) and os.path.isfile(
            os.path.join(path, "model.pkl")
        )

    @classmethod
    def probe_cache(
        cls, machine: Machine, model_register_dir: Union[os.PathLike, str]
    ) -> Optional[str]:
        """Read-only cache probe: like :meth:`check_cache` but with NO
        stale-key cleanup, so non-coordinator SPMD processes can mirror
        the coordinator's cache-hit machine filter without writing to the
        shared registry."""
        path = disk_registry.get_value(
            model_register_dir, cls.calculate_cache_key(machine)
        )
        if path is None or not cls._cache_entry_valid(path):
            return None
        return path

    def check_cache(self, model_register_dir: Union[os.PathLike, str]) -> Optional[str]:
        """Return the cached model path for this machine, if valid."""
        path = disk_registry.get_value(model_register_dir, self.cache_key)
        if path is None:
            return None
        if not self._cache_entry_valid(path):
            logger.warning("Registry key %s points at missing dir %s", self.cache_key, path)
            disk_registry.delete_value(model_register_dir, self.cache_key)
            return None
        return path

    def delete_cached_model(self, model_register_dir: Union[os.PathLike, str]):
        disk_registry.delete_value(model_register_dir, self.cache_key)

    @staticmethod
    def _save_model(
        model: BaseEstimator,
        machine: Union[Machine, dict],
        output_dir: Union[os.PathLike, str],
    ) -> str:
        output_dir = str(output_dir)
        metadata = machine.to_dict() if isinstance(machine, Machine) else machine
        # Atomic (staging dir + rename): a crash mid-save can never leave
        # a half-written model.pkl where the registry or a resume pass
        # would find it — same contract as the fleet builder's dumps.
        serializer.dump_atomic(model, output_dir, metadata=metadata)
        return output_dir


def _index_at(X, position: int):
    index = getattr(X, "index", None)
    if index is None:
        return int(position)
    value = index[position]
    return value.isoformat() if hasattr(value, "isoformat") else value
