"""
Plain float32 reference of the stacked many-to-one LSTM autoencoder:
the forward pass in numpy on the host (Keras semantics: gates in the
order input, forget, cell, output; sigmoid gates; the configured
activation on the candidate and on the output transform; zero initial
state; a dense head on the last hidden state). Nothing here comes from
``gordo_tpu.models`` or ``gordo_tpu.ops``.

There is no reference fit: back-propagation through 60 timesteps of
six layers (1.1 M weights) in numpy costs about a minute an init seed,
which no run can pay. The build check holds an LSTM build to its epoch
and step counts and to this forward instead (PERF.md, open questions).
"""

from typing import Any, Dict, List, Tuple

import numpy as np

ACTIVATIONS = {
    "tanh": np.tanh,
    "linear": lambda x: x,
    "relu": lambda x: np.maximum(x, 0.0),
    "sigmoid": lambda x: 1.0 / (1.0 + np.exp(-x)),
}

Layer = Tuple[np.ndarray, np.ndarray, np.ndarray, str]  # Wx, Wh, b, activation


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-x))


def layers_of(estimator: Any) -> Dict[str, Any]:
    spec, params = estimator.spec_, estimator.params_
    lstm: List[Layer] = [
        (
            np.asarray(params[f"lstm_{i}"]["Wx"], np.float32),
            np.asarray(params[f"lstm_{i}"]["Wh"], np.float32),
            np.asarray(params[f"lstm_{i}"]["b"], np.float32),
            spec.activations[i],
        )
        for i in range(len(spec.dims))
    ]
    head = (
        np.asarray(params["out"]["W"], np.float32),
        np.asarray(params["out"]["b"], np.float32),
        spec.out_activation,
    )
    return {"lstm": lstm, "head": head, "lookback": int(spec.lookback_window)}


def model_input(estimator: Any, X_scaled: np.ndarray) -> np.ndarray:
    """Every window of ``lookback`` consecutive scaled rows, lookahead 0:
    window ``j`` covers rows ``j .. j+lookback-1`` and predicts row
    ``j+lookback-1``."""
    X = np.asarray(X_scaled, np.float32)
    lookback = int(estimator.spec_.lookback_window)
    index = np.arange(len(X) - lookback + 1)[:, None] + np.arange(lookback)[None, :]
    return X[index]


def forward(layers: Dict[str, Any], windows: np.ndarray) -> np.ndarray:
    """``windows [n, lookback, tags]`` -> ``[n, tags]``."""
    sequence = np.asarray(windows, np.float32).transpose(1, 0, 2)  # time first
    for Wx, Wh, b, activation in layers["lstm"]:
        act = ACTIVATIONS[activation]
        units = Wh.shape[0]
        h = np.zeros((sequence.shape[1], units), np.float32)
        c = np.zeros_like(h)
        outputs = []
        for x_t in sequence:
            gates = x_t @ Wx + b + h @ Wh
            i, f, g, o = (gates[:, k * units : (k + 1) * units] for k in range(4))
            c = _sigmoid(f) * c + _sigmoid(i) * act(g)
            h = (_sigmoid(o) * act(c)).astype(np.float32)
            outputs.append(h)
        sequence = np.stack(outputs)
    W, b, activation = layers["head"]
    return ACTIVATIONS[activation](sequence[-1] @ W + b).astype(np.float32)
