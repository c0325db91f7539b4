"""
Build-metadata schema.

Reference parity: gordo/machine/metadata/metadata.py:16-55 — the dataclass
tree recorded per build and served from ``/metadata``:
``Metadata{user_defined, build_metadata}`` with model/dataset build records.
"""

import copy
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

try:
    from dataclasses_json import dataclass_json
except ImportError:  # container without dataclasses_json
    from ..utils.dataclasses_compat import dataclass_json


@dataclass_json
@dataclass
class CrossValidationMetaData:
    scores: Dict[str, Any] = field(default_factory=dict)
    cv_duration_sec: Optional[float] = None
    splits: Dict[str, Any] = field(default_factory=dict)


@dataclass_json
@dataclass
class TrainingSummaryMetadata:
    """Per-member training-history summary, captured from the fit's
    ``History`` carry: final/best losses, how many epochs actually ran
    vs were configured, and where early stopping cut in (``None`` when
    the fit ran to its configured epoch count)."""

    final_loss: Optional[float] = None
    best_loss: Optional[float] = None
    final_val_loss: Optional[float] = None
    best_val_loss: Optional[float] = None
    epochs_run: int = 0
    epochs_configured: int = 0
    early_stop_epoch: Optional[int] = None

    @classmethod
    def from_history(cls, history) -> "TrainingSummaryMetadata":
        """Summarize a Keras-History-shaped fit record (duck-typed:
        ``.history`` dict of loss lists, ``.params`` dict, ``.epoch``
        list) — shared by the fleet builder and the sequential
        ModelBuilder so both artifact paths carry the same fields."""
        losses = [float(l) for l in history.history.get("loss") or []]
        val = [float(l) for l in history.history.get("val_loss") or []]
        epochs_run = len(history.epoch)
        configured = int(
            history.params.get("epochs", epochs_run) or epochs_run
        )
        early = epochs_run < configured
        return cls(
            final_loss=losses[-1] if losses else None,
            best_loss=min(losses) if losses else None,
            final_val_loss=val[-1] if val else None,
            best_val_loss=min(val) if val else None,
            epochs_run=epochs_run,
            epochs_configured=configured,
            early_stop_epoch=epochs_run if early else None,
        )


@dataclass_json
@dataclass
class ModelBuildMetadata:
    model_offset: int = 0
    model_creation_date: Optional[str] = None
    model_builder_version: Optional[str] = None
    cross_validation: CrossValidationMetaData = field(
        default_factory=CrossValidationMetaData
    )
    model_training_duration_sec: Optional[float] = None
    model_meta: Dict[str, Any] = field(default_factory=dict)
    training: TrainingSummaryMetadata = field(
        default_factory=TrainingSummaryMetadata
    )
    #: where the model was trained, as JAX reported it: ``platform``,
    #: ``device_kind`` and device ``count``
    #: (``telemetry.device_identity``)
    device: Dict[str, Any] = field(default_factory=dict)


@dataclass_json
@dataclass
class DatasetBuildMetadata:
    query_duration_sec: Optional[float] = None
    dataset_meta: Dict[str, Any] = field(default_factory=dict)


@dataclass_json
@dataclass
class DriftBaselineMetadata:
    """Training-data distribution baseline the lifecycle drift monitor
    (``gordo_tpu.lifecycle.drift``) tests scored serving data against:
    per-tag means/stds of the RAW input frame (the same space serving
    requests arrive in — host transformers run after this point) plus
    the sample count behind them. Residual (reconstruction-error)
    baselines are calibrated online by the monitor from the first scored
    window, because training loss lives in the estimator's scaled space
    while serving residuals are raw-target-space mse."""

    tags: List[str] = field(default_factory=list)
    feature_means: List[float] = field(default_factory=list)
    feature_stds: List[float] = field(default_factory=list)
    n_samples: int = 0

    @classmethod
    def from_frame(cls, X) -> "DriftBaselineMetadata":
        """Baseline from a training DataFrame (raw, pre-transform).
        NaN-aware: sensor frames carry NaN rows, and a NaN mean/std
        would silently disable the monitor's feature test for that
        tag (an all-NaN column stays NaN → serialized null → the
        monitor treats the tag as unmeasurable)."""
        import warnings

        import numpy as np

        values = np.asarray(X.to_numpy(), dtype=float)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)  # all-NaN cols
            means = np.nanmean(values, axis=0)
            stds = np.nanstd(values, axis=0)
        return cls(
            tags=[str(c) for c in X.columns],
            feature_means=[round(float(v), 8) for v in means],
            feature_stds=[round(float(v), 8) for v in stds],
            n_samples=int(len(values)),
        )


@dataclass_json
@dataclass
class RobustnessMetadata:
    """Per-machine fleet-build robustness counters: diverged-member
    reseed retries, bucket bisection (split-retry) events the machine's
    members rode through, and data-fetch retry total."""

    fleet_retries: int = 0
    bucket_bisects: int = 0
    data_fetch_retries: int = 0


@dataclass_json
@dataclass
class BuildMetadata:
    model: ModelBuildMetadata = field(default_factory=ModelBuildMetadata)
    dataset: DatasetBuildMetadata = field(default_factory=DatasetBuildMetadata)
    robustness: RobustnessMetadata = field(default_factory=RobustnessMetadata)
    drift_baseline: DriftBaselineMetadata = field(
        default_factory=DriftBaselineMetadata
    )


@dataclass_json
@dataclass
class Metadata:
    user_defined: Dict[str, Any] = field(default_factory=dict)
    build_metadata: BuildMetadata = field(default_factory=BuildMetadata)


def _metadata_to_dict(self: Metadata, **_kwargs) -> Dict[str, Any]:
    """
    Snapshot of the tree as plain dicts (independent copies, like the
    dataclasses_json walk it replaces). Hand-rolled because the schema
    is fixed and small while the ``Dict[str, Any]`` leaves (CV scores,
    model_meta) hold hundreds of entries: the generic walk's
    per-value typing introspection was ~20ms per machine — a real cost
    when dumping a thousand-machine fleet's metadata.
    """
    model = self.build_metadata.model
    dataset = self.build_metadata.dataset
    robustness = self.build_metadata.robustness
    baseline = self.build_metadata.drift_baseline
    training = model.training
    return {
        "user_defined": copy.deepcopy(self.user_defined),
        "build_metadata": {
            "model": {
                "model_offset": model.model_offset,
                "model_creation_date": model.model_creation_date,
                "model_builder_version": model.model_builder_version,
                "cross_validation": {
                    "scores": copy.deepcopy(model.cross_validation.scores),
                    "cv_duration_sec": model.cross_validation.cv_duration_sec,
                    "splits": copy.deepcopy(model.cross_validation.splits),
                },
                "model_training_duration_sec": model.model_training_duration_sec,
                "model_meta": copy.deepcopy(model.model_meta),
                "training": {
                    "final_loss": training.final_loss,
                    "best_loss": training.best_loss,
                    "final_val_loss": training.final_val_loss,
                    "best_val_loss": training.best_val_loss,
                    "epochs_run": training.epochs_run,
                    "epochs_configured": training.epochs_configured,
                    "early_stop_epoch": training.early_stop_epoch,
                },
                "device": dict(model.device),
            },
            "dataset": {
                "query_duration_sec": dataset.query_duration_sec,
                "dataset_meta": copy.deepcopy(dataset.dataset_meta),
            },
            "robustness": {
                "fleet_retries": robustness.fleet_retries,
                "bucket_bisects": robustness.bucket_bisects,
                "data_fetch_retries": robustness.data_fetch_retries,
            },
            "drift_baseline": {
                "tags": list(baseline.tags),
                "feature_means": list(baseline.feature_means),
                "feature_stds": list(baseline.feature_stds),
                "n_samples": baseline.n_samples,
            },
        },
    }


# Installed AFTER decoration: @dataclass_json unconditionally assigns
# cls.to_dict = DataClassJsonMixin.to_dict, so a to_dict defined in the
# class body is silently clobbered by the decorator.
Metadata.to_dict = _metadata_to_dict  # type: ignore[method-assign]
