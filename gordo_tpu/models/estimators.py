"""
Scikit-learn-compatible JAX estimators — the drop-in replacements for the
reference's Keras wrappers (gordo/machine/model/models.py:36-710).

API parity: ``kind`` factory resolution (registered name or dotted path),
``from_definition``/``into_definition`` hooks, ``supported_fit_args``
filtering, fit-history metadata, pickling of a *fitted* model, and the LSTM
output-offset contract. Engine: specs + the fused JAX training program in
models/training.py — there is no per-model Python training loop to port.
"""

import abc
import importlib
import logging
from copy import copy, deepcopy
from importlib.util import find_spec
from pprint import pformat
from typing import Any, Callable, Dict, Optional, Tuple, Union

import numpy as np
import pandas as pd
from sklearn.base import BaseEstimator, TransformerMixin
from sklearn.exceptions import NotFittedError
from sklearn.metrics import explained_variance_score

from .. import serializer
from ..ops.windows import sliding_windows, window_targets
from .base import GordoBase
from .in_flight import for_pickling
from .register import register_model_builder
from .spec import ModelSpec, Sequential
from .training import (
    History,
    fit_config_from_kwargs,
    fit_single,
    predict_fn,
    split_fit_kwargs,
    windowed_loss_and_grad_norms,
)

logger = logging.getLogger(__name__)


class JaxBaseEstimator(GordoBase, BaseEstimator):
    """
    Base estimator: resolves ``kind`` to an architecture factory, trains via
    the fused JAX engine, and exposes the GordoBase + sklearn surface.
    """

    # Keras fit args honored by configs written for the reference
    # (gordo/machine/model/models.py:37-51). Args that have no JAX analog
    # (workers, multiprocessing, queue sizes) are accepted and ignored.
    supported_fit_args = [
        "batch_size",
        "epochs",
        "verbose",
        "callbacks",
        "validation_split",
        "shuffle",
        "class_weight",
        "initial_epoch",
        "steps_per_epoch",
        "validation_batch_size",
        "max_queue_size",
        "workers",
        "use_multiprocessing",
    ]

    def __init__(self, kind: Union[str, Callable, dict], **kwargs) -> None:
        self.kind = self.load_kind(kind)
        self.kwargs: Dict[str, Any] = kwargs
        self._history: Optional[History] = None
        self.params_ = None
        self.spec_: Optional[ModelSpec] = None

    # -- kind resolution ----------------------------------------------------

    @staticmethod
    def parse_module_path(module_path: str) -> Tuple[Optional[str], str]:
        parts = module_path.split(".")
        if len(parts) == 1:
            return None, parts[0]
        return ".".join(parts[:-1]), parts[-1]

    def _factory_registry_type(self) -> str:
        for klass in type(self).__mro__:
            if klass.__name__ in register_model_builder.factories:
                return klass.__name__
        return type(self).__name__

    def load_kind(self, kind):
        if callable(kind):
            register_model_builder(type=type(self).__name__)(kind)
            return kind.__name__
        module_name, attr_name = self.parse_module_path(kind)
        if module_name is None:
            registry = register_model_builder.factories.get(
                self._factory_registry_type(), {}
            )
            if attr_name not in registry:
                raise ValueError(
                    f"kind: {kind} is not an available model for type: "
                    f"{type(self).__name__}!"
                )
        else:
            try:
                found = find_spec(module_name)
            except ModuleNotFoundError:
                found = None
            if not found:
                raise ValueError(f"kind: {kind}, unable to find module: {module_name!r}")
        return kind

    def _resolve_factory(self) -> Callable:
        module_name, attr_name = self.parse_module_path(self.kind)
        if module_name is None:
            return register_model_builder.factories[self._factory_registry_type()][
                self.kind
            ]
        module = importlib.import_module(module_name)
        if not hasattr(module, attr_name):
            raise ValueError(
                f"kind: {self.kind}, unable to find {attr_name} in module "
                f"{module_name!r}"
            )
        return getattr(module, attr_name)

    # -- serializer hooks ---------------------------------------------------

    @classmethod
    def from_definition(cls, definition: dict):
        definition = copy(definition)
        kind = definition.pop("kind")
        return cls(kind, **definition)

    def into_definition(self) -> dict:
        definition = copy(self.kwargs)
        definition["kind"] = self.kind
        return definition

    @classmethod
    def extract_supported_fit_args(cls, kwargs: dict) -> dict:
        return {k: kwargs[k] for k in cls.supported_fit_args if k in kwargs}

    @property
    def sk_params(self) -> dict:
        """kwargs with any definition-form fit args (e.g. callbacks) built."""
        fit_args = self.extract_supported_fit_args(self.kwargs)
        if fit_args:
            kwargs = deepcopy(self.kwargs)
            kwargs.update(serializer.load_params_from_definition(fit_args))
            return kwargs
        return self.kwargs

    # -- fitting ------------------------------------------------------------

    @staticmethod
    def get_n_features(X) -> int:
        if X.ndim < 2:
            raise ValueError(f"Unsupported input dimensionality {X.ndim}")
        return X.shape[-1]

    def _build_spec(self, factory_kwargs: dict) -> ModelSpec:
        factory = self._resolve_factory()
        spec = factory(**factory_kwargs)
        if not isinstance(spec, ModelSpec):
            raise TypeError(
                f"Factory {self.kind!r} returned {type(spec).__name__}, "
                "expected a ModelSpec"
            )
        return spec

    def fit(self, X, y, **kwargs):
        target_is_input = y is X  # fit_single asks it of the arrays it is handed
        if isinstance(y, np.ndarray) and y.ndim == 1:
            y = y.reshape(-1, 1)
        X = X.values if isinstance(X, (pd.DataFrame, pd.Series)) else np.asarray(X)
        y = y.values if isinstance(y, (pd.DataFrame, pd.Series)) else np.asarray(y)

        self.kwargs.update(
            {"n_features": self.get_n_features(X), "n_features_out": self.get_n_features(y)}
        )

        all_kwargs = {**self.sk_params, **kwargs}
        fit_kwargs, factory_kwargs = split_fit_kwargs(all_kwargs)
        self.spec_ = self._build_spec(factory_kwargs)
        config, host_callbacks = fit_config_from_kwargs(fit_kwargs)
        seed = int(fit_kwargs.get("seed", 42))
        X = np.asarray(X, np.float32)
        self.params_, self._history = fit_single(
            self.spec_,
            X,
            X if target_is_input else np.asarray(y, np.float32),
            config,
            seed=seed,
            host_callbacks=host_callbacks,
        )
        return self

    def predict(self, X, **kwargs) -> np.ndarray:
        if self.params_ is None:
            raise NotFittedError(f"This {type(self).__name__} has not been fitted yet.")
        X = X.values if isinstance(X, pd.DataFrame) else np.asarray(X)
        out = predict_fn(self.spec_)(self.params_, np.asarray(X, np.float32))
        return np.asarray(out)

    def score(self, X, y, sample_weight=None, **kwargs) -> float:
        out = self.predict(X)
        y = y.values if isinstance(y, pd.DataFrame) else np.asarray(y)
        return explained_variance_score(y[-len(out):], out)

    # -- params / metadata / pickling --------------------------------------

    def get_params(self, deep: bool = False) -> dict:
        params = {"kind": self.kind}
        params.update(self.kwargs)
        if params.get("callbacks") and any(
            isinstance(cb, dict) for cb in params["callbacks"]
        ):
            params["callbacks"] = serializer.build_callbacks(params["callbacks"])
        return params

    def get_metadata(self) -> dict:
        if self._history is not None:
            history: Dict[str, Any] = dict(self._history.history)
            history["params"] = self._history.params
            return {"history": history}
        return {}

    def __getstate__(self):
        state = self.__dict__.copy()
        if state.get("params_") is not None:
            state["params_"] = for_pickling(state["params_"])
        return state

    def __setstate__(self, state):
        self.__dict__ = state
        return self

    def __repr__(self):
        return f"{type(self).__name__}(kind={self.kind!r})"


class JaxAutoEncoder(JaxBaseEstimator, TransformerMixin):
    """
    Feedforward autoencoder: fits X→y (usually y=X); scores with explained
    variance of the reconstruction (reference:
    gordo/machine/model/models.py:360-398).
    """

    def score(self, X, y, sample_weight=None, **kwargs) -> float:
        if self.params_ is None:
            raise NotFittedError(f"This {type(self).__name__} has not been fitted yet.")
        out = self.predict(X)
        y = y.values if isinstance(y, pd.DataFrame) else np.asarray(y)
        return explained_variance_score(y, out)

    def transform(self, X) -> np.ndarray:
        return self.predict(X)


class JaxWindowedBaseEstimator(
    JaxBaseEstimator, TransformerMixin, metaclass=abc.ABCMeta
):
    """
    Many-to-one model over sliding windows, whatever runs inside the
    window (stacked LSTMs, a backbone's layers: the ``kind`` factory's
    spec says). Output is ``lookback_window + lookahead - 1`` rows
    shorter than the input — the model-offset contract that threads
    through builder metadata and server alignment (reference:
    gordo/machine/model/models.py:463-698).
    """

    def __init__(
        self,
        kind: Union[Callable, str],
        lookback_window: int = 1,
        batch_size: int = 32,
        **kwargs,
    ) -> None:
        kwargs["lookback_window"] = lookback_window
        kwargs["batch_size"] = batch_size
        self.lookback_window = lookback_window
        self.batch_size = batch_size
        super().__init__(kind, **kwargs)

    @property
    @abc.abstractmethod
    def lookahead(self) -> int:
        """Steps ahead in y the model targets."""

    def get_metadata(self) -> dict:
        metadata = super().get_metadata()
        metadata.update({"forecast_steps": self.lookahead})
        return metadata

    def _validate_and_fix_size_of_X(self, X: np.ndarray) -> np.ndarray:
        if X.ndim == 1:
            X = X.reshape(len(X), 1)
        if self.lookback_window >= X.shape[0]:
            raise ValueError(
                f"For {type(self).__name__} lookback_window must be < size of X"
            )
        return X

    def fit(self, X, y, **kwargs):
        X = X.values if isinstance(X, pd.DataFrame) else np.asarray(X)
        y = y.values if isinstance(y, pd.DataFrame) else np.asarray(y)
        if y.ndim == 1:
            y = y.reshape(-1, 1)
        X = self._validate_and_fix_size_of_X(X)

        targets = window_targets(y, self.lookback_window, self.lookahead)

        self.kwargs.update(
            {"n_features": X.shape[1], "n_features_out": y.shape[1]}
        )
        all_kwargs = {**self.sk_params, **kwargs}
        # Time-series training never shuffles between epochs (reference fits
        # its generator with shuffle=False — models.py:613-615).
        all_kwargs["shuffle"] = False
        fit_kwargs, factory_kwargs = split_fit_kwargs(all_kwargs)
        self.spec_ = self._build_spec(factory_kwargs)
        config, host_callbacks = fit_config_from_kwargs(fit_kwargs)
        seed = int(fit_kwargs.get("seed", 42))

        windows = sliding_windows(X, self.lookback_window, self.lookahead)
        self.params_, self._history = fit_single(
            self.spec_,
            np.asarray(windows, np.float32),
            np.asarray(targets, np.float32),
            config,
            seed=seed,
            host_callbacks=host_callbacks,
        )
        return self

    def predict(self, X, **kwargs) -> np.ndarray:
        if self.params_ is None:
            raise NotFittedError(f"This {type(self).__name__} has not been fitted yet.")
        X = X.values if isinstance(X, pd.DataFrame) else np.asarray(X)
        X = self._validate_and_fix_size_of_X(X)

        from ..parallel.sequence import ring_predict_enabled, ring_windowed_predict

        if ring_predict_enabled(len(X)):
            # Long series: shard the time axis over the devices and exchange
            # window halos over ICI (parallel/sequence.py) — the host never
            # materializes the lookback× window blowup.
            return ring_windowed_predict(
                predict_fn(self.spec_),
                self.params_,
                np.asarray(X, np.float32),
                self.lookback_window,
                self.lookahead,
            )
        windows = sliding_windows(X, self.lookback_window, self.lookahead)
        out = predict_fn(self.spec_)(self.params_, np.asarray(windows, np.float32))
        return np.asarray(out)

    def score(self, X, y, sample_weight=None, **kwargs) -> float:
        if self.params_ is None:
            raise NotFittedError(f"This {type(self).__name__} has not been fitted yet.")
        out = self.predict(X)
        y = y.values if isinstance(y, pd.DataFrame) else np.asarray(y)
        return explained_variance_score(y[-len(out):], out)

    def training_loss_and_grad_norms(self, X, y) -> Tuple[float, Any]:
        """
        The training loss of the windows of ``(X, y)``, taken as one
        batch, at the fitted parameters, and the norm of its gradient for
        each parameter leaf (a tree of floats shaped like ``params_``).
        Computed by the function the windowed fit program differentiates
        (``training.windowed_batch_loss_fn``: the same forward, loss,
        ``compute_dtype`` and rematerialisation), so it says what one
        more training step on these rows would see: how far from settled
        the model is on new data, and, held against a plain
        reimplementation, whether the step computes what it should (the
        chip benchmark's backbone reference does that).
        """
        if self.params_ is None:
            raise NotFittedError(f"This {type(self).__name__} has not been fitted yet.")
        X = X.values if isinstance(X, pd.DataFrame) else np.asarray(X)
        y = y.values if isinstance(y, pd.DataFrame) else np.asarray(y)
        if y.ndim == 1:
            y = y.reshape(-1, 1)
        X = self._validate_and_fix_size_of_X(X)
        return windowed_loss_and_grad_norms(
            self.spec_,
            self.params_,
            X,
            window_targets(y, self.lookback_window, self.lookahead),
        )

    def transform(self, X) -> np.ndarray:
        return self.predict(X)


#: the base's name from when every windowed model was an LSTM; pickled
#: artifacts and callers keep it
JaxLSTMBaseEstimator = JaxWindowedBaseEstimator


class JaxLSTMForecast(JaxLSTMBaseEstimator):
    @property
    def lookahead(self) -> int:
        return 1


class JaxLSTMAutoEncoder(JaxLSTMBaseEstimator):
    @property
    def lookahead(self) -> int:
        return 0


class JaxBackboneForecast(JaxWindowedBaseEstimator):
    """A backbone (``kind: lfm2_moe``) over a window of sensor rows,
    predicting the next row (``KerasLSTMForecast`` semantics)."""

    @property
    def lookahead(self) -> int:
        return 1


class JaxRawModelRegressor(JaxAutoEncoder):
    """
    Estimator from a raw ``{spec: ..., compile: ...}`` config — the analog of
    KerasRawModelRegressor (gordo/machine/model/models.py:401-460): ``spec``
    holds a Sequential layer-list definition, ``compile`` the loss/optimizer.
    """

    _expected_keys = ("spec", "compile")

    def load_kind(self, kind):
        return kind

    def __repr__(self):
        return f"{type(self).__name__}(kind: {pformat(self.kind)})"

    def _build_spec(self, factory_kwargs: dict) -> ModelSpec:
        if not all(k in self.kind for k in self._expected_keys):
            raise ValueError(
                f"Expected spec to have keys: {self._expected_keys}, "
                f"but found {list(self.kind)}"
            )
        sequential = serializer.from_definition(self.kind["spec"])
        if not isinstance(sequential, Sequential):
            raise ValueError(
                f"Raw spec must describe a Sequential stack, got {type(sequential)}"
            )
        compile_kwargs = dict(self.kind.get("compile") or {})
        sequential.loss = compile_kwargs.get("loss", sequential.loss)
        optimizer = compile_kwargs.get("optimizer", sequential.optimizer)
        sequential.optimizer = (
            optimizer.capitalize() if isinstance(optimizer, str) else optimizer
        )
        return sequential.compile_spec(n_features=factory_kwargs["n_features"])
