"""
Plain float32 reference of the dense autoencoder: forward, and a fit
(mean-squared error, Adam, mini-batches in order) in numpy on the host.
Nothing here comes from ``gordo_tpu.models`` or ``gordo_tpu.ops``.

It is Keras's ``fit`` written out: rows shuffled anew each epoch, every
batch of 32 real rows one Adam step, Glorot-uniform kernels and zero
biases. Departures from the program, each deliberate: no L1 activity
penalty (the hourglass factory puts 1e-4 on two layers; that is 1e-3 of
a loss of order 10-1000); and the program pads a member's rows to a
power of two and scans every batch of the padded axis, so it takes up
to a quarter more, smaller steps an epoch than the plain algorithm and
converges faster for it. While the loss still halves every epoch that
is worth a factor of two, which is why the band is wide on its lower
side.
"""

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

ACTIVATIONS = {
    "tanh": np.tanh,
    "linear": lambda x: x,
    "relu": lambda x: np.maximum(x, 0.0),
    "sigmoid": lambda x: 1.0 / (1.0 + np.exp(-x)),
}
Layer = Tuple[np.ndarray, np.ndarray, str]  # W [in, out], b [out], activation


def layers_of(estimator: Any) -> List[Layer]:
    """The artifact's own weights as plain float32 layers."""
    spec, params = estimator.spec_, estimator.params_
    names = [f"dense_{i}" for i in range(len(spec.dims))] + ["out"]
    activations = list(spec.activations) + [spec.out_activation]
    return [
        (
            np.asarray(params[name]["W"], np.float32),
            np.asarray(params[name]["b"], np.float32),
            activation,
        )
        for name, activation in zip(names, activations)
    ]


def forward(layers: Sequence[Layer], X: np.ndarray) -> np.ndarray:
    """``X [rows, tags]`` through the stack, float32 throughout."""
    h = np.asarray(X, np.float32)
    for W, b, activation in layers:
        h = ACTIVATIONS[activation](h @ W + b).astype(np.float32)
    return h


def model_input(estimator: Any, X_scaled: np.ndarray) -> np.ndarray:
    """What the estimator's network sees for scaled rows: the rows."""
    return np.asarray(X_scaled, np.float32)


def _init(widths: Sequence[int], activations: Sequence[str], rng) -> List[Layer]:
    layers = []
    for fan_in, fan_out, activation in zip(widths, widths[1:], activations):
        limit = np.sqrt(6.0 / (fan_in + fan_out))
        W = rng.uniform(-limit, limit, (fan_in, fan_out)).astype(np.float32)
        layers.append((W, np.zeros(fan_out, np.float32), activation))
    return layers


def _gradients(layers: Sequence[Layer], X: np.ndarray, y: np.ndarray):
    """Loss (mean over rows and tags of the squared error) and its
    gradients; only ``tanh`` and ``linear`` are differentiated here."""
    hs = [X]
    for W, b, activation in layers:
        hs.append(ACTIVATIONS[activation](hs[-1] @ W + b))
    diff = hs[-1] - y
    loss = float(np.mean(diff * diff))
    delta = 2.0 * diff / diff.size
    grads = []
    for (W, _, activation), h_in, h_out in zip(layers[::-1], hs[-2::-1], hs[::-1]):
        if activation == "tanh":
            delta = delta * (1.0 - h_out * h_out)
        elif activation != "linear":
            raise ValueError(f"no reference gradient for {activation}")
        grads.append((h_in.T @ delta, delta.sum(axis=0)))
        delta = delta @ W.T
    return loss, grads[::-1]


def fit_final_loss(
    X: np.ndarray,
    y: np.ndarray,
    dims: Sequence[int],
    activations: Sequence[str],
    epochs: int,
    batch_size: int,
    learning_rate: float,
    seed: int,
) -> float:
    """Mean training loss of the last epoch of a plain Adam fit (Keras
    defaults: beta 0.9 / 0.999, epsilon 1e-7) from a Glorot init drawn
    from ``seed``."""
    X, y = np.asarray(X, np.float32), np.asarray(y, np.float32)
    rng = np.random.RandomState(seed)
    layers = _init([X.shape[1], *dims, y.shape[1]], activations, rng)
    m = [[np.zeros_like(W), np.zeros_like(b)] for W, b, _ in layers]
    v = [[np.zeros_like(W), np.zeros_like(b)] for W, b, _ in layers]
    step, epoch_loss = 0, float("nan")
    for _ in range(epochs):
        total, rows = 0.0, 0
        order = rng.permutation(len(X))
        for start in range(0, len(X), batch_size):
            batch = order[start : start + batch_size]
            xb, yb = X[batch], y[batch]
            loss, grads = _gradients(layers, xb, yb)
            total, rows = total + loss * len(xb), rows + len(xb)
            step += 1
            scale = learning_rate * np.sqrt(1 - 0.999**step) / (1 - 0.9**step)
            updated = []
            for i, ((W, b, activation), (gW, gb)) in enumerate(zip(layers, grads)):
                new = []
                for j, (p, g) in enumerate(((W, gW), (b, gb))):
                    m[i][j] = 0.9 * m[i][j] + 0.1 * g
                    v[i][j] = 0.999 * v[i][j] + 0.001 * g * g
                    new.append(
                        (p - scale * m[i][j] / (np.sqrt(v[i][j]) + 1e-7)).astype(np.float32)
                    )
                updated.append((new[0], new[1], activation))
            layers = updated
        epoch_loss = total / rows
    return epoch_loss


def loss_band(
    X: np.ndarray, y: np.ndarray, config: Dict[str, Any], seeds: Sequence[int] = (0, 1, 2)
) -> Tuple[float, float]:
    """The band a correct build's final training loss lies in: from a
    quarter of the least to 1.5 x the largest of plain fits from
    ``seeds``. The upper side catches a build that trained less (fewer
    epochs, fewer rows, a smaller rate); the lower side is wide because
    the program's padded scan takes more steps than the plain algorithm
    (see the module's docstring) and only has to catch a loss that no
    fit of this model reaches."""
    dims = config["layer_dims"]
    activations = [config["activation"]] * len(dims) + [config["out_activation"]]
    losses = [
        fit_final_loss(
            X, y, dims, activations, config["epochs"], config["batch_size"],
            config["learning_rate"], seed,
        )
        for seed in seeds
    ]
    return min(losses) / 4.0, max(losses) * 1.5
