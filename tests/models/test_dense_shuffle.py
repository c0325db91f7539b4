"""
The dense fit's epoch shuffle (models/training.py: build_raw_fit_fn):
the samples as rows ``[X | w]`` or ``[X | y | w]`` packed into vector
rows, an index vector an epoch and one gather a step, held on the CPU to
the plain epoch it replaced, which is written out here: the same
permutation and one whole-array ``take`` an array. Parameters, optimizer
state and ``epochs_ran`` agree **bit for bit**; an epoch's reported loss
to a few units in the last place, because XLA:CPU contracts the loss's
last multiply-adds differently when the step's inputs come from
another producer (the gradients do not pass through them).
Tier-1 at small dims.
"""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from gordo_tpu import telemetry
from gordo_tpu.models.factories import (
    feedforward_hourglass,
    feedforward_symmetric,
    lstm_model,
)
from gordo_tpu.models.nn import forward_fn_for, init_fn_for
from gordo_tpu.models.training import (
    LANES,
    SHUFFLE_SCOPE,
    FitConfig,
    _make_fit_loop,
    _tree_where,
    build_raw_fit_fn,
    fit_single,
    shuffle_columns,
    validation_inputs,
    validation_pass,
)
from gordo_tpu.ops.losses import resolve_loss, weighted_mean_loss
from gordo_tpu.parallel import host_blocks
from gordo_tpu.parallel import FleetMember, FleetTrainer

TAGS, ROWS, BATCH = 3, 64, 16
SPEC = feedforward_symmetric(TAGS, dims=(6, 3), funcs=("tanh", "tanh"))
DTYPES = ("float32", "bfloat16")


def plain_fit(spec, config):
    """The dense fit as it was before the one-row shuffle: every array a
    parameter of its own, cast to the compute dtype up front, and an
    epoch that gathers each with its own ``take``."""
    forward = forward_fn_for(spec)
    per_sample = resolve_loss(spec.loss)
    tx = spec.optimizer.to_optax()
    dtype = jnp.dtype(spec.compute_dtype)

    def batch_loss(params, xb, yb, wb):
        out, penalty = forward(spec, params, xb)
        return weighted_mean_loss(per_sample(out, yb), wb) + penalty

    grad_fn = jax.value_and_grad(batch_loss)

    def train_epoch(params, opt_state, Xtr, ytr, wtr, erng):
        n_total = Xtr.shape[0]
        steps = n_total // config.batch_size
        if config.shuffle:
            perm = jax.random.permutation(erng, n_total)
            Xtr = jnp.take(Xtr, perm, axis=0)
            ytr = jnp.take(ytr, perm, axis=0)
            wtr = jnp.take(wtr, perm, axis=0)
        batches = (
            Xtr.reshape((steps, config.batch_size) + Xtr.shape[1:]),
            ytr.reshape((steps, config.batch_size) + ytr.shape[1:]),
            wtr.reshape(steps, config.batch_size),
        )

        def step(carry, batch):
            params, opt_state = carry
            xb, yb, wb = batch
            loss, grads = grad_fn(params, xb, yb, wb)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            has_data = jnp.sum(wb) > 0
            params = _tree_where(has_data, optax.apply_updates(params, updates), params)
            opt_state = _tree_where(has_data, new_opt_state, opt_state)
            return (params, opt_state), jnp.where(has_data, loss * jnp.sum(wb), 0.0)

        (params, opt_state), weighted = jax.lax.scan(step, (params, opt_state), batches)
        return params, opt_state, jnp.sum(weighted) / jnp.maximum(jnp.sum(wtr), 1.0)

    def evaluate(params, X, y, w):
        out, _ = forward(spec, params, X)
        return weighted_mean_loss(per_sample(out, y), w)

    def fit(params, opt_state, Xtr, ytr, wtr, Xval, yval, wval, rng):
        Xtr, ytr, Xval, yval = (a.astype(dtype) for a in (Xtr, ytr, Xval, yval))
        loop = _make_fit_loop(
            config,
            train_epoch=lambda p, o, erng: train_epoch(p, o, Xtr, ytr, wtr, erng),
            evaluate_val=validation_pass(wval, lambda p: evaluate(p, Xval, yval, wval)),
        )
        return loop(params, opt_state, rng)

    return fit


def _rows(seed, rows=ROWS, shape=(TAGS,)):
    return np.random.RandomState(seed).rand(rows, *shape).astype(np.float32)


def _state(spec, seeds):
    """Stacked parameters, optimizer state and fit keys of one member a
    seed, derived as ``fit_single`` derives them."""
    keys = jax.vmap(jax.random.split)(
        jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    )
    params = jax.vmap(lambda k: init_fn_for(spec)(k, spec))(keys[:, 1])
    opt_state = jax.vmap(spec.optimizer.to_optax().init)(params)
    return params, opt_state, keys[:, 0]


def _assert_same_bits(a, b):
    leaves_a, leaves_b = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(leaves_a) == len(leaves_b)
    for leaf_a, leaf_b in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


def _assert_same_losses(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.array_equal(np.isnan(a), np.isnan(b))
    np.testing.assert_array_max_ulp(a[~np.isnan(a)], b[~np.isnan(b)], maxulp=4)


def _assert_same_fit(plain, packed):
    """``(params, opt_state, losses, val_losses, epochs_ran)`` of the two
    fits: the state and the epochs bit for bit, the losses to 4 ulp."""
    _assert_same_bits(plain[:2], packed[:2])
    _assert_same_bits(plain[4], packed[4])
    _assert_same_losses(plain[2], packed[2])
    _assert_same_losses(plain[3], packed[3])


def _both_fits(spec, config, X, y, wtr, wval):
    """``(plain, one-row)`` outputs of the stacked fit of the members
    ``X[i]``; ``y`` None hands the plain fit ``X`` again as its targets
    and the one-row fit no target array."""
    seeds = list(range(3, 3 + len(X)))
    _, wval_in, Xval, yval = validation_inputs(wval, X, y, axis=1)
    plain = jax.jit(jax.vmap(plain_fit(spec, config)))(
        *_state(spec, seeds)[:2], X, X if y is None else y, wtr,
        Xval, Xval if y is None else yval, wval_in, _state(spec, seeds)[2],
    )
    one_row = jax.jit(jax.vmap(build_raw_fit_fn(spec, config)))(
        *_state(spec, seeds)[:2], X, y, wtr, Xval, yval, wval_in, _state(spec, seeds)[2],
    )
    return plain, one_row


def _ragged_weights(members, rows, validation_split):
    """Member 0 fills its rows; member 1 has 20 rows, so its last two
    batches of 16 are padding alone in an unshuffled epoch and some
    batches are likely to be in a shuffled one; with a split each
    validates on the tail of its own rows."""
    wtr = np.zeros((members, rows), np.float32)
    wval = np.zeros((members, rows), np.float32)
    for i, n in enumerate([rows, 20][:members]):
        n_val = int(n * validation_split)
        wtr[i, : n - n_val] = 1.0
        wval[i, n - n_val : n] = 1.0
    return wtr, wval


# -- (1) the same bits as three takes -------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shuffle", [True, False], ids=["shuffled", "in-order"])
@pytest.mark.parametrize("validation_split", [0.0, 0.2])
@pytest.mark.parametrize("target", ["input", "own"])
def test_one_row_epoch_trains_the_bits_of_three_takes(
    target, validation_split, shuffle, dtype
):
    spec = dataclasses.replace(SPEC, compute_dtype=dtype)
    config = FitConfig(epochs=3, batch_size=BATCH, shuffle=shuffle)
    X = np.stack([_rows(1), _rows(2)])
    X[1, 20:] = 0.0  # a ragged member: rows of padding behind its 20
    y = None if target == "input" else np.stack([_rows(11), _rows(12)])
    wtr, wval = _ragged_weights(2, ROWS, validation_split)
    plain, one_row = _both_fits(spec, config, X, y, wtr, wval)
    _assert_same_fit(plain, one_row)
    params, _, losses, val_losses, epochs_ran = one_row
    assert np.isfinite(np.asarray(losses)).all() and list(epochs_ran) == [3, 3]
    assert np.isnan(np.asarray(val_losses)).all() == (validation_split == 0.0)
    # ... and it trained: the bits are not those of the initial state
    initial = _state(spec, [3, 4])[0]
    assert not np.array_equal(
        jax.tree_util.tree_leaves(initial)[0], jax.tree_util.tree_leaves(params)[0]
    )


def test_a_batch_of_padding_alone_leaves_the_state_as_it_was():
    """A member of no rows at all: every batch is padding, whichever
    rows the permutation brings."""
    config = FitConfig(epochs=2, batch_size=BATCH, shuffle=True)
    X = np.stack([_rows(1), np.zeros((ROWS, TAGS), np.float32)])
    wtr = np.stack([np.ones(ROWS, np.float32), np.zeros(ROWS, np.float32)])
    plain, one_row = _both_fits(SPEC, config, X, None, wtr, np.zeros_like(wtr))
    _assert_same_fit(plain, one_row)
    initial = _state(SPEC, [3, 4])[0]
    for before, after in zip(
        jax.tree_util.tree_leaves(initial), jax.tree_util.tree_leaves(one_row[0])
    ):
        np.testing.assert_array_equal(np.asarray(before)[1], np.asarray(after)[1])
    assert list(np.asarray(one_row[2])[1]) == [0.0, 0.0]


@pytest.mark.parametrize("target", ["input", "own"])
def test_the_hourglass_of_the_cell_agrees_to_the_last_place(target):
    """The published model at its 20 tags (41 or 21 columns: three or
    six samples a vector row). Six layers and an activity penalty give
    XLA:CPU enough to fuse differently around another producer, so the
    weights agree to a unit in the last place and not to the bit."""
    spec = feedforward_hourglass(20)
    config = FitConfig(epochs=3, batch_size=32, shuffle=True)
    X = np.stack([_rows(1, rows=96, shape=(20,)), _rows(2, rows=96, shape=(20,))])
    y = None if target == "input" else np.stack(
        [_rows(3, rows=96, shape=(20,)), _rows(4, rows=96, shape=(20,))]
    )
    wtr, wval = _ragged_weights(2, 96, 0.0)
    plain, packed = _both_fits(spec, config, X, y, wtr, wval)
    for a, b in zip(
        jax.tree_util.tree_leaves(plain[:3]), jax.tree_util.tree_leaves(packed[:3])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-7)
    _assert_same_bits(plain[4], packed[4])


def test_early_stopping_runs_the_same_epochs():
    config = FitConfig(
        epochs=6, batch_size=BATCH, shuffle=True,
        early_stopping=("val_loss", 1, 0.5, True),
    )
    X = np.stack([_rows(1), _rows(2)])
    wtr, wval = _ragged_weights(2, ROWS, 0.2)
    plain, one_row = _both_fits(SPEC, config, X, None, wtr, wval)
    _assert_same_fit(plain, one_row)
    assert (np.asarray(one_row[4]) < 6).all()


def test_inputs_of_more_dimensions_join_the_row_flattened():
    """Windows ``[n, 4, 3]`` in, rows ``[n, 3]`` out (the windowed dense
    estimators' fit): twelve and three columns of the row beside the
    weight, and ``[batch, 4, 3]`` again before the forward sees them."""
    spec = lstm_model(
        TAGS, lookback_window=4, encoding_dim=(5,), encoding_func=("tanh",),
        decoding_dim=(5,), decoding_func=("tanh",),
    )
    config = FitConfig(epochs=2, batch_size=BATCH, shuffle=True)
    X, y = np.stack([_rows(5, shape=(4, TAGS))]), np.stack([_rows(6)])
    wtr = np.ones((1, ROWS), np.float32)
    plain, one_row = _both_fits(spec, config, X, y, wtr, np.zeros_like(wtr))
    # (an LSTM's own fusions move with their surroundings: the last place)
    for a, b in zip(
        jax.tree_util.tree_leaves(plain[:3]), jax.tree_util.tree_leaves(one_row[:3])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
    assert shuffle_columns(config, X.shape[2:], y.shape[2:]) == 16


# -- (2) fit_single, and a one-member bucket of the trainer ---------------------------------------

SPLITS = pytest.mark.parametrize("validation_split", [0.0, 0.2])
TARGETS = pytest.mark.parametrize("target", ["input", "own"])


def _sixty_rows(target):
    """60 rows, so that 60 or 48 training rows end in a batch that is
    part padding; the targets the same array or one of their own."""
    X = _rows(7, rows=60)
    return X, X if target == "input" else _rows(8, rows=60)


@SPLITS
@TARGETS
def test_fit_single_trains_the_plain_fits_bits(target, validation_split):
    config = FitConfig(
        epochs=3, batch_size=BATCH, shuffle=True, validation_split=validation_split
    )
    X, y = _sixty_rows(target)
    params, history = fit_single(SPEC, X, y, config, seed=9)

    n_train = 60 - int(60 * validation_split)
    pad = -n_train % BATCH

    def padded(a):  # as _pad_to_batches: the last training row again
        return np.concatenate([a[:n_train], np.repeat(a[n_train - 1 : n_train], pad, 0)])

    wtr = np.concatenate([np.ones(n_train, np.float32), np.zeros(pad, np.float32)])
    init_params, opt_state, rng = jax.tree_util.tree_map(lambda a: a[0], _state(SPEC, [9]))
    plain = jax.jit(plain_fit(SPEC, config))(
        init_params, opt_state, padded(X), padded(y), wtr,
        X[n_train:], y[n_train:], np.ones(60 - n_train, np.float32), rng,
    )
    _assert_same_bits(plain[0], params)
    _assert_same_losses(plain[2], history.history["loss"])
    if validation_split:
        _assert_same_losses(plain[3], history.history["val_loss"])


@SPLITS
@TARGETS
def test_a_one_member_bucket_trains_the_plain_fits_bits(target, validation_split):
    config = FitConfig(
        epochs=3, batch_size=BATCH, shuffle=True, validation_split=validation_split
    )
    X, y = _sixty_rows(target)
    member = FleetMember(name="m", spec=SPEC, X=X, y=y, seed=9)
    trainer = FleetTrainer()
    (result,) = trainer.train([member], config)

    (Xs, ys, wtr, Xval, yval, wval, rngs), _ = trainer._stack_bucket(
        SPEC, 60, [member], config, host_blocks.Lease()
    )
    assert (ys is None) == (yval is None) == (target == "input")
    params, opt_state, rngs = trainer._init_bucket_params(SPEC, rngs)
    plain = jax.jit(jax.vmap(plain_fit(SPEC, config)))(
        params, opt_state, Xs, Xs if ys is None else ys, wtr,
        Xval, Xval if yval is None else yval, wval, rngs,
    )
    _assert_same_bits(jax.tree_util.tree_map(lambda a: a[0], plain[0]), result.params)
    _assert_same_losses(plain[2][0], result.history.history["loss"])
    # ... and the one-model program agrees with it as closely as it did
    single, _ = fit_single(SPEC, X, y, config, seed=9)
    if not validation_split:  # (with a split the two shuffle other rows)
        for a, b in zip(
            jax.tree_util.tree_leaves(single), jax.tree_util.tree_leaves(result.params)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


# -- (3) one gather a step, and the span says how wide ------------------------------------------


def _gathers(target, shuffle=True):
    """The ``gather`` operations of the lowered stacked fit, two members
    of 64 rows (the permutation itself is a sort and holds none)."""
    config = FitConfig(epochs=2, batch_size=BATCH, shuffle=shuffle)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32)  # noqa: E731
    y = None if target == "input" else f32(2, ROWS, TAGS)
    yval = None if target == "input" else f32(2, 0, TAGS)
    params, opt_state, rngs = jax.eval_shape(lambda: _state(SPEC, [1, 2]))
    lowered = jax.jit(jax.vmap(build_raw_fit_fn(SPEC, config))).lower(
        params, opt_state, f32(2, ROWS, TAGS), y, f32(2, ROWS),
        f32(2, 0, TAGS), yval, f32(2, 0), rngs,
    )
    text = lowered.as_text(debug_info=True)
    assert f'{SHUFFLE_SCOPE}/' in text
    return [l for l in text.splitlines() if re.search(r"stablehlo\.(dynamic_)?gather", l)]


@pytest.mark.parametrize("target, columns", [("input", TAGS + 1), ("own", 2 * TAGS + 1)])
def test_a_step_gathers_once(target, columns):
    """So that a later edit cannot quietly bring the copies back: the
    program lowers to ONE gather, a batch of vector rows in the step,
    shuffled or not."""
    (gather,) = _gathers(target)
    assert f"-> tensor<2x{BATCH}x{LANES}xf32>" in gather  # a batch of vector rows
    assert len(_gathers(target, shuffle=False)) == 1
    config = FitConfig(epochs=2, batch_size=BATCH, shuffle=True)
    assert shuffle_columns(config, (TAGS,), None if target == "input" else (TAGS,)) == columns


@pytest.mark.parametrize("target, columns", [("input", TAGS + 1), ("own", 2 * TAGS + 1)])
def test_fit_spans_carry_shuffle_columns(target, columns, tmp_path):
    config = FitConfig(epochs=1, batch_size=BATCH, shuffle=True)
    X = _rows(1)
    y = X if target == "input" else _rows(2)
    sink = tmp_path / "trace.jsonl"
    recorder = telemetry.SpanRecorder(sink_path=str(sink))
    with telemetry.activate(recorder):
        FleetTrainer().train([FleetMember(name="m", spec=SPEC, X=X, y=y, seed=1)], config)
        fit_single(SPEC, X, y, config, seed=1)
        fit_single(SPEC, X, y, dataclasses.replace(config, shuffle=False), seed=1)
    recorder.close()
    spans = [json.loads(line) for line in sink.read_text().splitlines()]
    fits = [
        s["attributes"] for s in spans
        if s["name"] == "device_program" and "fit" in s["attributes"]["program"]
    ]
    assert [f["program"] for f in fits] == ["fleet_fit", "fit_single", "fit_single"]
    assert [f["shuffle_columns"] for f in fits] == [columns, columns, 0]
    assert all(f["fit_counters"] == ["shuffle_columns"] for f in fits)
