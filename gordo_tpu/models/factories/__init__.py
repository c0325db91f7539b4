from . import backbone, feedforward_autoencoder, lstm_autoencoder  # noqa: F401  (registration)
from .backbone import kanana, keye_vl2, laguna, lfm2_moe, phi4flash, smallthinker
from .feedforward_autoencoder import (
    feedforward_hourglass,
    feedforward_model,
    feedforward_symmetric,
)
from .lstm_autoencoder import lstm_hourglass, lstm_model, lstm_symmetric

__all__ = [
    "feedforward_model",
    "feedforward_symmetric",
    "feedforward_hourglass",
    "lstm_model",
    "lstm_symmetric",
    "lstm_hourglass",
    "lfm2_moe",
    "keye_vl2",
    "laguna",
    "smallthinker",
    "kanana",
    "phi4flash",
]
