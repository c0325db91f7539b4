"""
Sequence (time-axis) parallelism for long-series scoring.

The reference handles sequence length purely by *windowing* on one CPU
(``create_keras_timeseriesgenerator``, gordo/machine/model/models.py:713-793);
a decade-long 10-minute-resolution series (~500k rows) would be scored row
by row through a single process. Here the time axis itself becomes a mesh
axis: each device holds a contiguous chunk of the series, pulls the
``lookback + lookahead - 1`` halo rows it needs from its right-hand
neighbor over ICI with one ``jax.lax.ppermute``, builds its windows
locally, and runs the forward pass — so scoring an N-row series on D chips
touches N/D rows per chip and one tiny collective, instead of an N-row
gather on one device.

This is the ring/halo-exchange pattern of context parallelism specialised
to finite windows: because gordo models have no attention (SURVEY.md §5
"Long-context"), the dependency footprint of output row k is exactly rows
``[k, k + lookback + lookahead)`` — a fixed halo, not the whole sequence —
so a single neighbor exchange replaces the full ring rotation.

Works on any 1-D slice of a mesh; the fleet's ``data`` axis is the natural
choice. All shapes are static: the series is padded to a multiple of the
axis size, every device computes the same number of windows, and the
(globally meaningless) tail windows computed from padding are trimmed on
the host.
"""

import logging
from functools import lru_cache
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..ops.windows import model_offset, sliding_windows
from .mesh import DATA_AXIS

logger = logging.getLogger(__name__)

#: Row threshold above which windowed estimators route prediction through
#: the ring (time-sharded) path instead of host-materializing windows.
#: Overridable via the env var; <= 0 disables the ring path entirely.
RING_PREDICT_ROWS_ENV = "GORDO_TPU_RING_PREDICT_ROWS"
DEFAULT_RING_PREDICT_ROWS = 65_536


def ring_predict_enabled(n_rows: int) -> bool:
    """
    Whether a windowed predict over ``n_rows`` should take the ring path:
    the series is long enough that the host-side ``lookback×`` window
    materialization hurts (threshold rows), and there is more than one
    device to shard the time axis over.
    """
    from ..utils.env import env_int

    threshold = env_int(RING_PREDICT_ROWS_ENV, DEFAULT_RING_PREDICT_ROWS)
    if threshold <= 0:
        return False
    return n_rows >= threshold and len(jax.devices()) > 1


def _right_halo(local: jnp.ndarray, halo: int, axis_name: str, axis_size: int):
    """
    The first ``halo`` rows of the right-hand neighbor's chunk (device i
    receives from device i+1; the last device receives device 0's head,
    which only ever feeds trimmed tail windows).
    """
    head = local[:halo]
    perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]
    return jax.lax.ppermute(head, axis_name, perm)


def ring_windowed_predict(
    predict_fn: Callable,
    params,
    X: np.ndarray,
    lookback: int,
    lookahead: int = 0,
    mesh: Optional[Mesh] = None,
    axis_name: str = DATA_AXIS,
) -> np.ndarray:
    """
    Score a long series with a windowed model, sharded over the time axis.

    Equivalent to ``predict_fn(params, sliding_windows(X, lookback,
    lookahead))`` but with ``X`` split across the ``axis_name`` devices of
    ``mesh`` and halos exchanged via ``ppermute``.

    Parameters
    ----------
    predict_fn
        ``(params, windows[k, lookback, F]) -> out[k, F_out]`` — a jittable
        forward (e.g. ``models.training.predict_fn(spec)`` for LSTM specs).
    X
        The full series ``[n, F]`` (host array).
    lookback, lookahead
        Window geometry; output has ``n - (lookback + lookahead - 1)`` rows.
    mesh
        Mesh whose ``axis_name`` axis shards time. Every other mesh axis
        must have size 1 for this entry point (fleet scoring composes the
        model axis separately).
    """
    if mesh is None:
        dev = jax.devices()
        mesh = Mesh(np.array(dev).reshape(len(dev)), (axis_name,))
    axis_size = mesh.shape[axis_name]
    offset = model_offset(lookback, lookahead)
    halo = offset

    X = np.asarray(X, np.float32)
    n = X.shape[0]
    n_windows = n - offset
    if n_windows <= 0:
        raise ValueError(
            f"Series of length {n} too short for lookback={lookback}, "
            f"lookahead={lookahead}"
        )
    # Pad the time axis to a multiple of the mesh axis; every chunk must
    # also be at least one halo long so the neighbor exchange suffices.
    chunk = -(-n // axis_size)
    if chunk < halo:
        chunk = halo
    total = chunk * axis_size
    if total != n:
        Xp = np.zeros((total,) + X.shape[1:], X.dtype)
        Xp[:n] = X
    else:
        Xp = X

    other_axes = [a for a in mesh.axis_names if a != axis_name]
    for a in other_axes:
        if mesh.shape[a] != 1:
            raise ValueError(
                f"ring_windowed_predict shards only {axis_name!r}; mesh axis "
                f"{a!r} has size {mesh.shape[a]} != 1"
            )

    fn = _ring_program(predict_fn, lookback, lookahead, mesh, axis_name)
    with mesh:
        out = fn(
            params, jax.device_put(Xp, NamedSharding(mesh, PartitionSpec(axis_name)))
        )
    return np.asarray(out)[:n_windows]


@lru_cache(maxsize=None)
def _ring_program(
    predict_fn: Callable, lookback: int, lookahead: int, mesh: Mesh, axis_name: str
):
    """The jitted halo-exchange scoring program for a (geometry, mesh) key —
    cached so repeated scoring (a serving loop) traces/compiles once, like
    the sibling ``training.predict_fn`` / ``fleet._fleet_fit_program``."""
    axis_size = mesh.shape[axis_name]
    halo = model_offset(lookback, lookahead)
    in_spec = PartitionSpec(axis_name)
    rep = PartitionSpec()

    def local_score(params, xs):
        # xs: [chunk, F] — this device's contiguous slice of the series.
        halo_rows = _right_halo(xs, halo, axis_name, axis_size)
        ext = jnp.concatenate([xs, halo_rows], axis=0)  # [chunk + halo, F]
        if halo:
            windows = sliding_windows(ext, lookback, lookahead)  # [chunk, L, F]
        else:
            # lookback=1, lookahead=0: windows are the rows themselves.
            windows = ext[:, None, :]
        return predict_fn(params, windows)

    return jax.jit(
        shard_map(
            local_score,
            mesh=mesh,
            in_specs=(rep, in_spec),
            out_specs=in_spec,
            # The LSTM scan carry starts replicated (zeros) and becomes
            # device-varying after consuming the sharded sequence; vma
            # checking rejects that mixed carry, so it is disabled here.
            check_vma=False,
        )
    )


def ring_windowed_anomaly_scores(
    predict_fn: Callable,
    params,
    X: np.ndarray,
    y: Optional[np.ndarray],
    lookback: int,
    lookahead: int = 0,
    mesh: Optional[Mesh] = None,
    axis_name: str = DATA_AXIS,
) -> np.ndarray:
    """
    Per-row squared reconstruction/forecast error over a time-sharded
    series: ``((predict(windows) - y_aligned) ** 2)`` with the same halo
    exchange as :func:`ring_windowed_predict`. ``y`` defaults to ``X``.
    Returns ``[n - offset, F_out]`` squared errors (host array).
    """
    y = np.asarray(X if y is None else y, np.float32)
    out = ring_windowed_predict(
        predict_fn, params, X, lookback, lookahead, mesh, axis_name
    )
    offset = model_offset(lookback, lookahead)
    aligned = y[offset:]
    return (out - aligned[: len(out)]) ** 2
