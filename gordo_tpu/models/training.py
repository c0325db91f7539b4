"""
The JAX training engine: spec + arrays → trained params + history.

This replaces the reference's ``keras.Model.fit`` call inside its estimator
wrapper (gordo/machine/model/models.py:243-287). Design is TPU-first:

- **One device program per fit.** When callbacks can be compiled in (the
  common case — EarlyStopping becomes masked updates), the entire
  epochs×batches loop is a nested ``lax.scan`` under one ``jit``; the host
  dispatches once and reads back final params + per-epoch losses. No
  per-batch (or even per-epoch) host↔device ping-pong.
- **Static shapes.** Data is padded host-side to a whole number of batches
  with a weight mask; shuffling is a device-side ``jax.random.permutation``
  per epoch, an index vector of which each step gathers its batch. The
  dense fit keeps its samples for that as rows ``[X | y | w]`` packed
  whole into vector rows of 128 floats, since a gather on the chip moves
  a vector row at once and an array with rows in lanes an element at a
  time. The compiled program is reused across epochs and across models
  with the same (spec, shape).
- **Keras-compatible semantics** where they matter for parity: the
  validation split is the *last* fraction of the data (taken before
  shuffling), shuffle applies to the training portion only, epoch "loss" is
  the sample-weighted mean, Adam defaults match Keras.

The fleet path (gordo_tpu/parallel/fleet.py) vmaps `_train_step`/`_epoch`
logic over a stacked model axis; both paths share these functions.
"""

import logging
import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import telemetry
from ..ops.losses import resolve_loss, weighted_mean_loss
from .callbacks import Callback, EarlyStopping
from .nn import forward_fn_for, init_fn_for
from .spec import ModelSpec

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class FitConfig:
    """Static (hashable) fit configuration — part of the compilation key."""

    epochs: int = 1
    batch_size: int = 32
    validation_split: float = 0.0
    shuffle: bool = True
    # (monitor, patience, min_delta, restore_best_weights) or None
    early_stopping: Optional[Tuple[str, int, float, bool]] = None


@dataclass
class History:
    """Keras-History-shaped fit record (consumed by get_metadata)."""

    history: Dict[str, List[float]]
    params: Dict[str, Any]
    epoch: List[int]


def split_fit_kwargs(kwargs: dict) -> Tuple[dict, dict]:
    """Split estimator kwargs into (fit-related, factory-related)."""
    fit_keys = {
        "epochs",
        "batch_size",
        "validation_split",
        "shuffle",
        "callbacks",
        "verbose",
        "initial_epoch",
        "seed",
    }
    fit_args = {k: v for k, v in kwargs.items() if k in fit_keys}
    rest = {k: v for k, v in kwargs.items() if k not in fit_keys}
    return fit_args, rest


def fit_config_from_kwargs(kwargs: dict) -> Tuple[FitConfig, List[Callback]]:
    """
    Build a FitConfig from Keras-style fit kwargs. EarlyStopping callbacks
    compile into the config; any other callbacks are returned for the
    host-loop path.
    """
    callbacks = list(kwargs.get("callbacks") or [])
    early_stopping = None
    early_stoppers: List[Callback] = []
    host_callbacks: List[Callback] = []
    for cb in callbacks:
        if isinstance(cb, EarlyStopping):
            early_stoppers.append(cb)
            early_stopping = (
                cb.monitor,
                cb.patience,
                cb.min_delta,
                cb.restore_best_weights,
            )
        elif isinstance(cb, Callback):
            host_callbacks.append(cb)
        else:
            raise TypeError(f"Unsupported callback: {cb!r}")
    if host_callbacks:
        # The host loop runs all callbacks; EarlyStopping must ride along
        # rather than being compiled into a program that never runs.
        host_callbacks = early_stoppers + host_callbacks
        early_stopping = None
    config = FitConfig(
        epochs=int(kwargs.get("epochs", 1)),
        batch_size=int(kwargs.get("batch_size", 32)),
        validation_split=float(kwargs.get("validation_split", 0.0)),
        shuffle=bool(kwargs.get("shuffle", True)),
        early_stopping=early_stopping,
    )
    return config, host_callbacks


#: ``jax.named_scope`` names inside every fused fit program: each
#: operation's metadata in the HLO (and in a profiler trace) then says
#: which part of an epoch it belongs to, whatever XLA numbers the fusion
SHUFFLE_SCOPE = "epoch_shuffle"  # the per-epoch permutation, and each batch's gather
STEPS_SCOPE = "optimizer_steps"  # the scan over an epoch's batches
VALIDATION_SCOPE = "validation"  # the end-of-epoch validation pass


#: floats of one TPU vector row. The dense fit packs whole samples into
#: rows of this width, because a row of it is what a gather moves at once
LANES = 128


def _tree_where(flag, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(flag, x, y), a, b
    )


def _skipping_padding(step):
    """``step(carry, (starts, weights)) -> (carry, out)`` of a scan over
    batches, behind a branch: a batch whose weights are all zero leaves
    the carry as it was and gives zeros for ``out``, without running
    ``step``. What the masked steps compute for such a batch (a no-op
    update, a zero contribution) at none of the cost."""

    def branching(carry, batch):
        def skip(carry, batch):
            out = jax.eval_shape(step, carry, batch)[1]
            return carry, jax.tree_util.tree_map(
                lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), out
            )

        return jax.lax.cond(jnp.sum(batch[1]) > 0, step, skip, carry, batch)

    return branching


def validation_inputs(wval: np.ndarray, *arrays: np.ndarray, axis: int = 0):
    """The host's side of the one rule of validation: a fit program holds
    a validation pass only if the bucket it is compiled for has a
    validation row. ``wval`` holds the validation weights as stacked on
    the host, ``axis`` its validation axis (1 behind a member axis) and
    ``arrays`` whatever else a program reads along that axis alone
    (``Xval``, ``yval``).

    Returns ``(validation_slots, wval, *arrays)``: the number of slots
    with a validation weight and, where that is 0, every array cut to
    length zero along ``axis``, which is what :func:`validation_pass`
    reads inside the program. A bucket in which any member has such a
    slot keeps its arrays whole: a stacked program can only mask. An
    array that is None (no target array: the target is the input) stays so.
    """
    slots = int(np.count_nonzero(wval))
    if slots:
        return (slots, wval, *arrays)
    empty = (slice(None),) * axis + (slice(0, 0),)
    return (0, wval[empty], *(None if a is None else a[empty] for a in arrays))


def validation_pass(wval, evaluate):
    """``evaluate_val`` for a fit program handed ``wval``: ``evaluate``
    where the validation axis has a length, else the constant NaN
    (what ``evaluate`` answers for weights that are all zero:
    see weighted_mean_loss), with no ``validation`` scope traced, lowered
    or run. The length of an axis is static, so this is decided when the
    program is traced: :func:`validation_inputs` hands over length zero
    when nothing would be validated."""
    if wval.shape[0] > 0:
        return evaluate
    return lambda params: jnp.full((), jnp.nan, jnp.float32)


def _make_fit_loop(config: FitConfig, train_epoch, evaluate_val):
    """
    The shared epochs×early-stopping scaffold of every fused fit program
    (dense and windowed): scans ``train_epoch`` over per-epoch RNG keys
    with EarlyStopping compiled in as masked updates.

    ``train_epoch(params, opt_state, erng) -> (params, opt_state, loss,
    *extras)`` and ``evaluate_val(params) -> val_loss`` (NaN when there
    is no validation data — see weighted_mean_loss) close over the
    training arrays; this function owns everything else. ``extras`` are
    a model's own per-epoch counters (router counts); they are stacked
    over epochs like the losses.

    Returns ``fit_tail(params, opt_state, rng) -> (params, opt_state,
    losses[epochs], val_losses[epochs], epochs_ran, *extras[epochs])``.

    Without early stopping nothing can stop an epoch, so the loop carries
    the state once: no ``stopped`` mask over the update (which keeps the
    epoch's input state alive beside its output, a second copy of
    parameters and moments) and no ``best_params``. The masked form
    computes the same numbers; it is kept for early stopping only.
    """
    es = config.early_stopping
    monitor_val = es is not None and es[0] == "val_loss"

    def fit_plain(params, opt_state, rng):
        def epoch_body(carry, erng):
            params, opt_state, loss, *extras = train_epoch(*carry, erng)
            return (params, opt_state), (loss, evaluate_val(params), *extras)

        (params, opt_state), (losses, val_losses, *extras) = jax.lax.scan(
            epoch_body, (params, opt_state), jax.random.split(rng, config.epochs)
        )
        ran = jnp.array(config.epochs, jnp.int32)
        return (params, opt_state, losses, val_losses, ran, *extras)

    def fit_tail(params, opt_state, rng):
        def epoch_body(carry, erng):
            params, opt_state, best, best_params, wait, stopped = carry
            stopped_at_start = stopped
            new_params, new_opt, loss, *extras = train_epoch(params, opt_state, erng)
            # When already stopped, freeze state (masked update keeps one
            # compiled program; tiny models make the dead compute negligible).
            params = _tree_where(stopped, params, new_params)
            opt_state = _tree_where(stopped, opt_state, new_opt)
            val_loss = evaluate_val(params)
            if monitor_val:
                # Per-member fallback: a fleet member with no validation
                # rows gets NaN val_loss; monitor train loss instead.
                monitor = jnp.where(jnp.isnan(val_loss), loss, val_loss)
            else:
                monitor = loss
            improved = monitor < best - es[2]
            best = jnp.where(~stopped & improved, monitor, best)
            if es[3]:
                best_params = _tree_where(
                    ~stopped & improved, params, best_params
                )
            wait = jnp.where(stopped, wait, jnp.where(improved, 0, wait + 1))
            stopped = stopped | (wait >= jnp.maximum(es[1], 1))
            return (params, opt_state, best, best_params, wait, stopped), (
                loss,
                val_loss,
                ~stopped_at_start,
                *extras,
            )

        rngs = jax.random.split(rng, config.epochs)
        init_carry = (
            params,
            opt_state,
            jnp.array(jnp.inf, jnp.float32),
            params,
            jnp.array(0, jnp.int32),
            jnp.array(False),
        )
        (params, opt_state, _, best_params, _, _), (
            losses, val_losses, ran, *extras
        ) = jax.lax.scan(epoch_body, init_carry, rngs)
        if es[3]:
            params = best_params
        return (
            params, opt_state, losses, val_losses,
            jnp.sum(ran.astype(jnp.int32)), *extras,
        )

    return fit_plain if es is None else fit_tail


def _pad_to_batches(
    X: np.ndarray, y: Optional[np.ndarray], batch_size: int
) -> Tuple[np.ndarray, Optional[np.ndarray], np.ndarray, int]:
    """Pad to a whole number of batches; returns (X, y, weights, steps).
    ``y`` None (the target is the input) stays None."""
    n = X.shape[0]
    steps = max(1, -(-n // batch_size))
    total = steps * batch_size
    pad = total - n
    if pad:
        X = np.concatenate([X, np.repeat(X[-1:], pad, axis=0)], axis=0)
        if y is not None:
            y = np.concatenate([y, np.repeat(y[-1:], pad, axis=0)], axis=0)
    weights = np.concatenate(
        [np.ones(n, dtype=X.dtype), np.zeros(pad, dtype=X.dtype)]
    )
    return X, y, weights, steps


@lru_cache(maxsize=None)
def _eval_fn(spec: ModelSpec):
    forward = forward_fn_for(spec)
    per_sample = resolve_loss(spec.loss)

    @jax.jit
    def evaluate(params, X, y, w):
        out, _ = forward(spec, params, X)
        return weighted_mean_loss(per_sample(out, X if y is None else y), w)

    return evaluate


@lru_cache(maxsize=None)
def predict_fn(spec: ModelSpec):
    """Jitted forward pass for a spec (used by estimator.predict and server)."""
    forward = forward_fn_for(spec)

    @jax.jit
    def predict(params, X):
        return forward(spec, params, X)[0]

    return predict


def shuffle_columns(config: FitConfig, X_row: Tuple[int, ...], y_row=None) -> int:
    """Columns of the one float32 row a sample that the dense fit packs
    and its steps gather (the ``shuffle_columns`` of its
    ``device_program`` span): the inputs' ``X_row`` elements, the
    targets' ``y_row`` where they are an array of their own (None: the
    target is the input) and the weight; 0 for a fit that does not
    shuffle."""
    if not config.shuffle:
        return 0
    return math.prod(X_row) + (0 if y_row is None else math.prod(y_row)) + 1


@lru_cache(maxsize=None)
def build_raw_fit_fn(spec: ModelSpec, config: FitConfig):
    """
    The *unjitted* fused fit function for (spec, config):
    (params, opt_state, Xtr, ytr, wtr, Xval, yval, wval, rng) ->
    (params, opt_state, losses[epochs], val_losses[epochs], epochs_ran).

    ``ytr`` and ``yval`` are None where the target is the input (a bare
    autoencoder; behind a scaler ``X`` is scaled and ``y`` is not): the
    program then holds and gathers one array where a second parameter,
    which XLA cannot know to be the same, would be a second copy.

    Everything — ragged lengths, validation split, fold boundaries — is
    expressed through the weight vectors, so the same function serves the
    single-model path (jit) and the fleet path (jit∘vmap over a stacked
    model axis, sharded across the mesh). Two things are read from what
    it is handed: validation arrays of no rows mean no validation pass
    (:func:`validation_pass`), and no target array means a sample's row
    is ``[X | w]`` and not ``[X | y | w]``.

    Inputs (or targets) of more than two dimensions join that row
    flattened and get their shape back after the gather. A fit that does
    not shuffle takes the same path with its indices in order.
    """
    forward = forward_fn_for(spec)
    per_sample = resolve_loss(spec.loss)
    tx = spec.optimizer.to_optax()
    compute_dtype = jnp.dtype(spec.compute_dtype)

    def batch_loss(params, xb, yb, wb):
        out, penalty = forward(spec, params, xb)
        # Keras adds activity-regularization losses as the raw batch sum, not
        # averaged; padding rows (duplicates of the last sample) inflate the
        # final partial batch's penalty slightly — negligible at l1≈1e-4.
        return weighted_mean_loss(per_sample(out, yb), wb) + penalty

    grad_fn = jax.value_and_grad(batch_loss)

    def epoch_rows(X, y, w):
        """The training data as an epoch reads it: ``[X | y | w]``, one
        float32 row a sample (``y`` left out where it is None), as many
        whole rows to a vector of ``LANES`` floats as fit, behind the
        function that takes a batch of them by its indices:
        ``take_batch(idx) -> (xb, yb, wb)`` with ``xb`` and ``yb``
        in the compute dtype and ``yb`` being ``xb`` where there was no
        target array. Which samples a batch holds is the only thing the
        packing does not change."""
        n = X.shape[0]
        arrays = [X] if y is None else [X, y]
        flat = jnp.concatenate(
            [a.reshape(n, -1) for a in arrays] + [w[:, None]], axis=1
        )
        columns = flat.shape[1]
        ends = np.cumsum([math.prod(a.shape[1:]) for a in arrays])
        per_vector = max(1, LANES // columns)
        vectors = -(-n // per_vector)
        flat = jnp.pad(flat, ((0, vectors * per_vector - n), (0, 0)))
        flat = flat.reshape(vectors, per_vector * columns)
        rows = jnp.pad(flat, ((0, 0), (0, -flat.shape[1] % LANES)))

        def take_batch(idx):
            with jax.named_scope(SHUFFLE_SCOPE):
                vector = jnp.take(rows, idx // per_vector, axis=0)
                slot = (idx % per_vector)[:, None]
                batch = vector[:, :columns]
                for j in range(1, per_vector):
                    here = vector[:, j * columns : (j + 1) * columns]
                    batch = jnp.where(slot == j, here, batch)
            *parts, wb = jnp.split(batch, ends, axis=1)
            parts = [
                part.reshape(idx.shape + a.shape[1:]).astype(compute_dtype)
                for part, a in zip(parts, arrays)
            ]
            return parts[0], parts[-1], wb.reshape(idx.shape)

        return take_batch

    def train_epoch(params, opt_state, take_batch, weight, erng):
        # The epoch's order is an index vector and each step gathers its
        # own batch of vector rows (features in lanes: what XLA:TPU moves
        # 128 floats at a time). The arrays themselves XLA:TPU keeps
        # with rows in lanes, since 20 features in lanes would pad to
        # 128, and a gather across lanes costs by the element, about one
        # a nanosecond: on a v5e at [480, 16384] a take an array (20, 20
        # and 1 columns) cost 61 ns a row an epoch and one take of the
        # 41 as one row 54, a batch at a time or all at once alike; the
        # packed rows cost 12, steps included (PERF.md 6, PR 30).
        n_total = weight.shape[0]
        steps = n_total // config.batch_size
        with jax.named_scope(SHUFFLE_SCOPE):
            if config.shuffle:
                order = jax.random.permutation(erng, n_total)
            else:
                order = jnp.arange(n_total)

        def step(carry, idx):
            params, opt_state = carry
            xb, yb, wb = take_batch(idx)
            loss, grads = grad_fn(params, xb, yb, wb)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            # An all-padding batch (possible for short members of a padded
            # fleet bucket) must be a true no-op: zero grads would still
            # advance Adam momentum and drift the params, and its NaN loss
            # must not poison the epoch sum.
            has_data = jnp.sum(wb) > 0
            params = _tree_where(
                has_data, optax.apply_updates(params, updates), params
            )
            opt_state = _tree_where(has_data, new_opt_state, opt_state)
            contribution = jnp.where(has_data, loss * jnp.sum(wb), 0.0)
            return (params, opt_state), contribution

        with jax.named_scope(STEPS_SCOPE):
            (params, opt_state), weighted_losses = jax.lax.scan(
                step, (params, opt_state), order.reshape(steps, config.batch_size)
            )
        epoch_loss = jnp.sum(weighted_losses) / jnp.maximum(jnp.sum(weight), 1.0)
        return params, opt_state, epoch_loss

    def evaluate(params, X, y, w):
        with jax.named_scope(VALIDATION_SCOPE):
            out, _ = forward(spec, params, X)
            return weighted_mean_loss(per_sample(out, y), w)

    def fit(params, opt_state, Xtr, ytr, wtr, Xval, yval, wval, rng):
        take_batch = epoch_rows(Xtr, ytr, wtr)
        # one on-device cast up front: every epoch's validation pass
        # re-reads the half-width copy, not the f32 staging buffer
        Xval = Xval.astype(compute_dtype)
        yval = Xval if yval is None else yval.astype(compute_dtype)
        fit_tail = _make_fit_loop(
            config,
            train_epoch=lambda p, o, erng: train_epoch(p, o, take_batch, wtr, erng),
            evaluate_val=validation_pass(
                wval, lambda p: evaluate(p, Xval, yval, wval)
            ),
        )
        return fit_tail(params, opt_state, rng)

    return fit


@lru_cache(maxsize=None)
def windowed_batch_loss_fn(spec: ModelSpec):
    """
    The loss a windowed fit step differentiates: ``(params, series[n, F],
    ytgt[nw, F], starts[B], weights[B]) -> (loss, aux)``, windows gathered
    on device from the raw series (``starts[:, None] + arange(lookback)``),
    ``aux`` the forward's own counters (``spec.forward_aux_fn()``) or
    None. One function for the fit program
    (:func:`build_raw_windowed_fit_fn`) and for the loss and gradient
    norms an estimator reports of itself
    (:func:`windowed_loss_and_grad_norms_program`): what the second reads
    is what the first trains on.
    """
    forward = forward_fn_for(spec)
    # a forward with counters of its own (router counts): summed over an
    # epoch's steps and returned beside the losses; None for the others
    forward_aux = spec.forward_aux_fn()
    per_sample = resolve_loss(spec.loss)
    lookback = spec.lookback_window

    def batch_loss(params, series, ytgt, starts, wb):
        xb = gather_windows(series, starts, lookback)
        yb = jnp.take(ytgt, starts, axis=0)
        if forward_aux is None:
            (out, penalty), aux = forward(spec, params, xb), None
        else:
            # a slot of padding (weight 0) is no sample of this step: a
            # forward that counts its own work leaves it out of the count
            out, penalty, aux = forward_aux(spec, params, xb, active=wb > 0)
        return weighted_mean_loss(per_sample(out, yb), wb) + penalty, aux

    return batch_loss


def gather_windows(series, starts, lookback: int):
    """``series[n, F]`` -> ``[B, lookback, F]``: the windows that start
    at ``starts``."""
    idx = starts[:, None] + jnp.arange(lookback)[None, :]
    return series[idx]


@lru_cache(maxsize=None)
def windowed_loss_and_grad_norms_program(spec: ModelSpec):
    """Jitted ``(params, series, ytgt, starts, weights) -> (loss, norms)``:
    the fit step's own loss of one batch (:func:`windowed_batch_loss_fn`,
    in the spec's ``compute_dtype`` like the fit) and the Euclidean norm
    of its gradient, a number a parameter leaf."""
    grad_fn = jax.value_and_grad(windowed_batch_loss_fn(spec), has_aux=True)
    compute_dtype = jnp.dtype(spec.compute_dtype)

    def loss_and_grad_norms(params, series, ytgt, starts, wb):
        if compute_dtype != jnp.float32:
            series, ytgt = series.astype(compute_dtype), ytgt.astype(compute_dtype)
        (loss, _), grads = grad_fn(params, series, ytgt, starts, wb)
        return loss, jax.tree_util.tree_map(
            lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads
        )

    return jax.jit(loss_and_grad_norms)


def windowed_loss_and_grad_norms(
    spec: ModelSpec, params, series: np.ndarray, targets: np.ndarray
) -> Tuple[float, Any]:
    """Every window of ``series[n, F]`` with its aligned ``targets[nw,
    F]`` (ops.windows.window_targets) as one batch through
    :func:`windowed_loss_and_grad_norms_program`, fetched: the loss as a
    float, the norms as a tree of floats shaped like ``params``."""
    series = np.asarray(series, np.float32)
    targets = np.asarray(targets, np.float32)
    with telemetry.program_span(
        "windowed_loss_and_grad_norms",
        (spec, series.shape, targets.shape),
        shape=str(tuple(series.shape)),
        spec=type(spec).__name__,
    ):
        loss, norms = jax.device_get(
            windowed_loss_and_grad_norms_program(spec)(
                params,
                series,
                targets,
                np.arange(len(targets), dtype=np.int32),
                np.ones(len(targets), np.float32),
            )
        )
    return float(loss), jax.tree_util.tree_map(float, norms)


@lru_cache(maxsize=None)
def build_raw_windowed_fit_fn(spec: ModelSpec, config: FitConfig):
    """
    The fused fit for windowed (LSTM) models with windows gathered ON
    DEVICE from the raw series, per batch:

    ``(params, opt_state, series[n, F], ytgt[nw, F], order[nv], wtr[nv],
    wval[nv], rng) -> (params, opt_state, losses, val_losses, epochs_ran)``
    and, for a spec whose forward has counters of its own
    (``spec.forward_aux_fn()``), a sixth output: those counters summed
    over each epoch's steps, ``[epochs, ...]``.

    The dense path pre-materializes ``[n_windows, lookback, F]`` windows —
    a ``lookback×`` HBM blowup that caps LSTM fleet size (1000 machines at
    lookback 120 ≈ 13 GB for the windows alone, over a v5e chip's HBM).
    Here only the ``[n, F]`` series and the ``[nw, F]`` aligned targets
    stay resident; each training step gathers its batch of windows from
    the series (``starts[:, None] + arange(lookback)``).

    - ``ytgt`` is aligned host-side via ``ops.windows.window_targets`` (so
      lookahead is already folded in): window ``j`` covers
      ``series[j : j+lookback]`` with target ``ytgt[j]``.
    - ``order`` maps virtual training slots to original window starts
      (the detector-level shuffle of fleet_build, plus padding slots that
      point at window 0 with zero weight).
    - ``wtr``/``wval`` are per-VIRTUAL-slot weights, exactly like the
      dense path's masks; a ``wval`` of length zero means no validation
      pass (:func:`validation_pass`).

    Given the same virtual ordering and batch geometry, this trains
    bit-for-bit like the dense path on pre-materialized windows
    (tests/parallel/test_fleet_windowed.py asserts it).
    """
    forward = forward_fn_for(spec)
    batch_loss = windowed_batch_loss_fn(spec)
    per_sample = resolve_loss(spec.loss)
    tx = spec.optimizer.to_optax()
    lookback = spec.lookback_window
    # a spec without a member axis runs one member a program and not
    # under ``vmap``, so a branch there is a branch: a batch of padding
    # alone is skipped, where a stacked program can only mask it (its
    # members' batches differ, and ``cond`` under ``vmap`` runs both sides)
    skip_padding = not spec.member_axis

    grad_fn = jax.value_and_grad(batch_loss, has_aux=True)

    def train_epoch(params, opt_state, series, ytgt, order, wtr, erng):
        nv = order.shape[0]
        steps = nv // config.batch_size
        if config.shuffle:
            with jax.named_scope(SHUFFLE_SCOPE):
                perm = jax.random.permutation(erng, nv)
                order_e = jnp.take(order, perm)
                wtr_e = jnp.take(wtr, perm)
        else:
            order_e, wtr_e = order, wtr
        starts_b = order_e.reshape(steps, config.batch_size)
        w_b = wtr_e.reshape(steps, config.batch_size)

        def masked_step(carry, batch):
            params, opt_state = carry
            starts, wb = batch
            (loss, aux), grads = grad_fn(params, series, ytgt, starts, wb)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            has_data = jnp.sum(wb) > 0
            params = _tree_where(
                has_data, optax.apply_updates(params, updates), params
            )
            opt_state = _tree_where(has_data, new_opt_state, opt_state)
            contribution = jnp.where(has_data, loss * jnp.sum(wb), 0.0)
            if aux is not None:
                # a fit with counters of its own also counts the steps
                # that held data: a skipped step counts none
                aux = {**aux, "steps_run": has_data.astype(jnp.int32)}
            return (params, opt_state), (contribution, aux)

        step = _skipping_padding(masked_step) if skip_padding else masked_step

        with jax.named_scope(STEPS_SCOPE):
            (params, opt_state), (weighted_losses, aux) = jax.lax.scan(
                step, (params, opt_state), (starts_b, w_b)
            )
        epoch_loss = jnp.sum(weighted_losses) / jnp.maximum(jnp.sum(wtr), 1.0)
        if aux is None:
            return params, opt_state, epoch_loss
        return params, opt_state, epoch_loss, jax.tree_util.tree_map(
            lambda a: jnp.sum(a, axis=0), aux
        )

    def evaluate(params, series, ytgt, order, w):
        # Batched scan, not one full-window forward: validation memory must
        # stay bounded for the same reason training's does.
        nv = order.shape[0]
        steps = nv // config.batch_size

        def scored_step(acc, batch):
            starts, wb = batch
            xb = gather_windows(series, starts, lookback)
            yb = jnp.take(ytgt, starts, axis=0)
            out, _ = forward(spec, params, xb)
            losses = per_sample(out, yb)
            return (acc[0] + jnp.sum(losses * wb), acc[1] + jnp.sum(wb)), None

        step = _skipping_padding(scored_step) if skip_padding else scored_step

        with jax.named_scope(VALIDATION_SCOPE):
            (total, wsum), _ = jax.lax.scan(
                step,
                (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                (
                    order.reshape(steps, config.batch_size),
                    w.reshape(steps, config.batch_size),
                ),
            )
        return jnp.where(wsum > 0, total / wsum, jnp.nan)

    compute_dtype = jnp.dtype(spec.compute_dtype)

    def fit(params, opt_state, series, ytgt, order, wtr, wval, rng):
        if compute_dtype != jnp.float32:
            series, ytgt = series.astype(compute_dtype), ytgt.astype(compute_dtype)
        fit_tail = _make_fit_loop(
            config,
            train_epoch=lambda p, o, erng: train_epoch(
                p, o, series, ytgt, order, wtr, erng
            ),
            evaluate_val=validation_pass(
                wval, lambda p: evaluate(p, series, ytgt, order, wval)
            ),
        )
        return fit_tail(params, opt_state, rng)

    return fit


@lru_cache(maxsize=None)
def _fit_program(spec: ModelSpec, config: FitConfig):
    """Jitted single-model fused fit program for (spec, config)."""
    return jax.jit(build_raw_fit_fn(spec, config))


def fit_single(
    spec: ModelSpec,
    X: np.ndarray,
    y: np.ndarray,
    config: FitConfig,
    seed: int = 42,
    host_callbacks: Optional[List[Callback]] = None,
    initial_params=None,
) -> Tuple[Any, History]:
    """
    Train one model described by ``spec`` on host arrays ``(X, y)``.
    Where ``y is X`` the fit program is handed no target array
    (:func:`build_raw_fit_fn`).

    Returns (params pytree, History). ``host_callbacks`` forces the per-epoch
    host loop; otherwise the whole fit is a single device program.
    """
    n = X.shape[0]
    n_val = int(n * config.validation_split)

    def tail_split(a):
        """(training rows, validation rows) as float32; no array, none"""
        if a is None:
            return None, None
        a = np.asarray(a, np.float32)
        return a[: n - n_val], a[n - n_val :]

    Xtr, Xval = tail_split(X)
    ytr, yval = tail_split(None if y is X else y)

    batch_size = min(config.batch_size, max(1, len(Xtr)))
    if batch_size != config.batch_size:
        config = FitConfig(
            epochs=config.epochs,
            batch_size=batch_size,
            validation_split=config.validation_split,
            shuffle=config.shuffle,
            early_stopping=config.early_stopping,
        )

    Xtr, ytr, wtr, _ = _pad_to_batches(Xtr, ytr, batch_size)
    wval = np.ones(len(Xval), np.float32)

    rng = jax.random.PRNGKey(seed)
    rng, init_rng = jax.random.split(rng)
    params = (
        initial_params
        if initial_params is not None
        else init_fn_for(spec)(init_rng, spec)
    )
    tx = spec.optimizer.to_optax()
    opt_state = tx.init(params)

    if host_callbacks:
        return _fit_host_loop(
            spec, config, params, opt_state, Xtr, ytr, wtr, Xval, yval, wval,
            rng, host_callbacks,
        )

    fit = _fit_program(spec, config)
    y_row = None if ytr is None else ytr.shape[1:]
    with telemetry.program_span(
        "fit_single",
        (spec, config, Xtr.shape, y_row, Xval.shape),
        shape=str(tuple(Xtr.shape)),
        spec=type(spec).__name__,
        validation_slots=n_val,
        shuffle_columns=shuffle_columns(config, Xtr.shape[1:], y_row),
        fit_counters=["shuffle_columns"],
    ):
        params, _, losses, val_losses, epochs_ran = fit(
            params, opt_state, Xtr, ytr, wtr, Xval, yval, wval, rng
        )
        # one coalesced d2h readback — per-element float() would pay the
        # fixed per-transfer latency once PER EPOCH. Inside the span:
        # the readback waits on the program, so the span times real
        # device work, not dispatch.
        losses, val_losses, epochs_ran = jax.device_get(
            (losses, val_losses, epochs_ran)
        )
    epochs_ran = int(epochs_ran)
    history = {"loss": [float(l) for l in losses[:epochs_ran]]}
    if n_val:
        history["val_loss"] = [float(l) for l in val_losses[:epochs_ran]]
    return params, History(
        history=history,
        params={
            "epochs": config.epochs,
            "steps": len(Xtr) // batch_size,
            "verbose": 0,
            "metrics": list(history),
        },
        epoch=list(range(epochs_ran)),
    )


def _fit_host_loop(
    spec, config, params, opt_state, Xtr, ytr, wtr, Xval, yval, wval, rng, callbacks
):
    """Per-epoch host loop for custom callbacks: one jitted epoch at a
    time. Callbacks may stop training (on_epoch_end -> True) or request a
    learning-rate change (``consume_lr_request`` protocol —
    ReduceLROnPlateau); an LR change swaps in the one-epoch program
    compiled for the new rate (lru-cached per rate) while Adam's moment
    state carries over unchanged."""
    from dataclasses import replace as dc_replace

    single_epoch_config = FitConfig(
        epochs=1,
        batch_size=config.batch_size,
        validation_split=0.0,
        shuffle=config.shuffle,
        early_stopping=None,
    )
    evaluate = _eval_fn(spec)
    empty = np.zeros((0,) + Xtr.shape[1:], np.float32)
    empty_y = None if ytr is None else np.zeros((0,) + ytr.shape[1:], np.float32)
    empty_w = np.zeros((0,), np.float32)

    history: Dict[str, List[float]] = {"loss": []}
    if len(Xval):
        history["val_loss"] = []
    for cb in callbacks:
        cb.on_train_begin()
    epochs_ran = 0
    current_spec = spec
    for epoch in range(config.epochs):
        fit_one = _fit_program(current_spec, single_epoch_config)
        rng, erng = jax.random.split(rng)
        params, opt_state, losses, _, _ = fit_one(
            params, opt_state, Xtr, ytr, wtr, empty, empty_y, empty_w, erng
        )
        logs = {
            "loss": float(losses[0]),
            "lr": current_spec.optimizer.learning_rate,
        }
        if len(Xval):
            logs["val_loss"] = float(evaluate(params, Xval, yval, wval))
            history["val_loss"].append(logs["val_loss"])
        history["loss"].append(logs["loss"])
        epochs_ran += 1
        # run every callback (Keras semantics), then stop/LR decisions
        stop_requests = [cb.on_epoch_end(epoch, logs) for cb in callbacks]
        new_lr = None
        for cb in callbacks:
            request = getattr(cb, "consume_lr_request", None)
            if callable(request):
                requested = request()
                if requested is not None:
                    new_lr = requested
        if new_lr is not None and new_lr != current_spec.optimizer.learning_rate:
            logger.info("Host loop: learning rate -> %g (epoch %d)", new_lr, epoch)
            current_spec = dc_replace(
                current_spec,
                optimizer=dc_replace(
                    current_spec.optimizer, learning_rate=float(new_lr)
                ),
            )
        if any(stop_requests):
            break
    return params, History(
        history=history,
        params={
            "epochs": config.epochs,
            "steps": len(Xtr) // config.batch_size,
            "verbose": 0,
            "metrics": list(history),
        },
        epoch=list(range(epochs_ran)),
    )
