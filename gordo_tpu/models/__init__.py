from . import factories  # noqa: F401  (populates the factory registry)
from .base import GordoBase
from .callbacks import Callback, EarlyStopping
from .estimators import (
    JaxAutoEncoder,
    JaxBackboneForecast,
    JaxBaseEstimator,
    JaxLSTMAutoEncoder,
    JaxLSTMBaseEstimator,
    JaxLSTMForecast,
    JaxRawModelRegressor,
    JaxWindowedBaseEstimator,
)
from .register import register_model_builder
from .spec import (
    BackboneSpec,
    Dense,
    FeedForwardSpec,
    LSTMSpec,
    ModelSpec,
    OptimizerSpec,
    Sequential,
)

# Migration aliases: reference configs name the Keras classes; resolving them
# here lets `gordo.machine.model.models.Keras*` paths rewritten by the
# serializer's COMPAT_LOCATIONS (and direct `gordo_tpu.models.Keras*` paths)
# work unchanged.
KerasAutoEncoder = JaxAutoEncoder
KerasLSTMAutoEncoder = JaxLSTMAutoEncoder
KerasLSTMForecast = JaxLSTMForecast
KerasRawModelRegressor = JaxRawModelRegressor

__all__ = [
    "GordoBase",
    "register_model_builder",
    "JaxBaseEstimator",
    "JaxAutoEncoder",
    "JaxWindowedBaseEstimator",
    "JaxLSTMBaseEstimator",
    "JaxBackboneForecast",
    "JaxLSTMAutoEncoder",
    "JaxLSTMForecast",
    "JaxRawModelRegressor",
    "KerasAutoEncoder",
    "KerasLSTMAutoEncoder",
    "KerasLSTMForecast",
    "KerasRawModelRegressor",
    "ModelSpec",
    "FeedForwardSpec",
    "LSTMSpec",
    "BackboneSpec",
    "OptimizerSpec",
    "Sequential",
    "Dense",
    "Callback",
    "EarlyStopping",
]
