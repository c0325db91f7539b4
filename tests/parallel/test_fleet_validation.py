"""
The one rule of validation in the fused fit programs: a program holds a
validation pass only if the bucket it is compiled for has a validation
row, decided from the length of an input's axis
(models/training.py: validation_inputs on the host, validation_pass in
the program). Tier-1 at small dims, over the dense and the windowed
stacked fit.
"""

import json

import jax
import numpy as np
import pytest

from gordo_tpu import telemetry
from gordo_tpu.models.factories import feedforward_symmetric, lstm_model
from gordo_tpu.models.training import (
    STEPS_SCOPE,
    VALIDATION_SCOPE,
    FitConfig,
    fit_single,
    validation_inputs,
)
from gordo_tpu.ops.windows import sliding_windows, window_targets
from gordo_tpu.parallel import host_blocks
from gordo_tpu.parallel import (
    FleetMember,
    FleetTrainer,
    WindowedFleetMember,
    make_mesh,
)
from gordo_tpu.parallel.fleet import (
    _fleet_fit_program,
    _fleet_init_program,
    _fleet_windowed_fit_program,
    _optimizer_init_program,
)

LOOKBACK = 4
ROWS = 67  # 64 windows of 4: two batches of 32, four of 16
DENSE_SPEC = feedforward_symmetric(3, dims=(6, 3), funcs=("tanh", "tanh"))
LSTM_SPEC = lstm_model(
    3,
    lookback_window=LOOKBACK,
    encoding_dim=(5,),
    encoding_func=("tanh",),
    decoding_dim=(5,),
    decoding_func=("tanh",),
)
KINDS = ("dense", "windowed")


def _series(seed):
    return np.random.RandomState(seed).rand(ROWS, 3).astype(np.float32)


def _member(kind, name, seed, **weights):
    """One member of 64 samples: the dense path's rows, or the windowed
    path's 64 windows of the same 67-row series."""
    series = _series(seed)
    if kind == "dense":
        X = series[:64]
        return FleetMember(
            name=name, spec=DENSE_SPEC, X=X, y=X.copy(), seed=seed, **weights
        )
    return WindowedFleetMember(
        name=name,
        spec=LSTM_SPEC,
        series=series,
        targets=window_targets(series, LOOKBACK, 0),
        seed=seed,
        **weights,
    )


def _tail_weights(n_val):
    """Explicit weights of a member that validates on its last ``n_val``
    samples and trains on the rest."""
    train, val = np.ones(64, np.float32), np.zeros(64, np.float32)
    train[64 - n_val :] = 0.0
    val[64 - n_val :] = 1.0
    return {"train_weights": train, "val_weights": val}


def _assert_same_bits(a, b):
    leaves_a, leaves_b = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(leaves_a) == len(leaves_b)
    for leaf_a, leaf_b in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


def _lowered_text(kind, config, validation_axis):
    """The lowered text, with each operation's scope, of the stacked fit
    program for two members of 64 samples whose validation axis has the
    given length."""
    members, nv = 2, 64
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32)  # noqa: E731
    rngs = jax.ShapeDtypeStruct((members, 2), np.uint32)
    if kind == "dense":
        spec, program = DENSE_SPEC, _fleet_fit_program(DENSE_SPEC, config)
        data = (
            f32(members, nv, 3), f32(members, nv, 3), f32(members, nv),
            f32(members, validation_axis, 3), f32(members, validation_axis, 3),
            f32(members, validation_axis),
        )
    else:
        spec, program = LSTM_SPEC, _fleet_windowed_fit_program(LSTM_SPEC, config)
        data = (
            f32(members, ROWS, 3), f32(members, nv, 3),
            jax.ShapeDtypeStruct((members, nv), np.int32),
            f32(members, nv), f32(members, validation_axis),
        )
    params = jax.eval_shape(_fleet_init_program(spec), rngs)
    opt_state = jax.eval_shape(_optimizer_init_program(spec), params)
    lowered = program.lower(params, opt_state, *data, rngs)
    text = lowered.as_text(debug_info=True)
    assert f'"{STEPS_SCOPE}/' in text  # scopes are in the text, so named
    return text


# -- (1) same bits alone and beside a member that validates ---------------------


@pytest.mark.parametrize("kind", KINDS)
def test_member_without_validation_trains_the_same_bits_in_either_program(kind):
    config = FitConfig(epochs=3, batch_size=16, shuffle=True)
    alone = FleetTrainer().train([_member(kind, "m", 3)], config)[0]
    beside = FleetTrainer().train(
        [_member(kind, "m", 3), _member(kind, "v", 4, **_tail_weights(16))], config
    )
    _assert_same_bits(alone.params, beside[0].params)
    assert alone.history.history["loss"] == beside[0].history.history["loss"]
    assert len(alone.history.history["loss"]) == 3
    assert "val_loss" not in alone.history.history
    assert "val_loss" not in beside[0].history.history
    # the pass was compiled in for the second bucket, and ran
    assert np.isfinite(beside[1].history.history["val_loss"]).all()


# -- (2) the lowered program ------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_validation_scope_is_lowered_only_for_a_validation_axis(kind):
    config = FitConfig(epochs=2, batch_size=16, shuffle=False)
    assert f'"{VALIDATION_SCOPE}/' not in _lowered_text(kind, config, 0)
    assert f'"{VALIDATION_SCOPE}/' in _lowered_text(kind, config, 64)


@pytest.mark.parametrize("kind", KINDS)
def test_validation_split_hands_the_program_a_validation_axis(kind):
    """``validation_split=0.25`` through the trainer compiles the pass
    in; without it the trainer hands over an axis of length zero."""
    trainer = FleetTrainer()
    stack = (
        (
            lambda c: trainer._stack_bucket(
                DENSE_SPEC, 64, [_member(kind, "m", 1)], c, host_blocks.Lease()
            )
        )
        if kind == "dense"
        else (
            lambda c: trainer._stack_windowed_bucket(
                LSTM_SPEC, ROWS, LOOKBACK - 1, [_member(kind, "m", 1)], c,
                host_blocks.Lease(),
            )
        )
    )
    arrays, slots = stack(FitConfig(epochs=1, batch_size=16))
    wval = arrays[-2]
    assert slots == 0 and wval.shape[1] == 0
    arrays, slots = stack(
        FitConfig(epochs=1, batch_size=16, validation_split=0.25)
    )
    wval = arrays[-2]
    assert slots == 16 and wval.shape[1] == 64


def test_validation_inputs_cuts_only_where_nothing_validates():
    wval = np.zeros((2, 8), np.float32)
    X = np.ones((2, 8, 3), np.float32)
    slots, cut, Xval = validation_inputs(wval, X, axis=1)
    assert slots == 0 and cut.shape == (2, 0) and Xval.shape == (2, 0, 3)
    wval[1, 5:] = 0.5
    slots, kept, Xval = validation_inputs(wval, X, axis=1)
    assert slots == 3 and kept is wval and Xval is X
    slots, cut = validation_inputs(np.zeros(8, np.float32))
    assert slots == 0 and cut.shape == (0,)


# -- (3) a validated fit gives the numbers it gave ---------------------------------


def test_dense_and_windowed_validated_fits_agree():
    """The cross-check of the slow suite, at small dims: a windowed member
    with ``validation_split`` and early stopping on ``val_loss`` trains
    like the dense member over the same windows, materialised."""
    config = FitConfig(
        epochs=4,
        batch_size=16,
        validation_split=0.25,
        shuffle=False,
        early_stopping=("val_loss", 2, 0.0, True),
    )
    series = _series(5)
    windows = sliding_windows(series, LOOKBACK, 0)
    targets = window_targets(series, LOOKBACK, 0)
    dense = FleetTrainer().train(
        [FleetMember(name="m", spec=LSTM_SPEC, X=windows, y=targets, seed=5)], config
    )[0]
    windowed = FleetTrainer().train([_member("windowed", "m", 5)], config)[0]
    for key in ("loss", "val_loss"):
        assert len(dense.history.history[key]) >= 1
        np.testing.assert_allclose(
            windowed.history.history[key], dense.history.history[key], rtol=1e-5
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(dense.params),
        jax.tree_util.tree_leaves(windowed.params),
    ):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-6)


def test_validated_dense_fleet_fit_matches_fit_single():
    """``validation_split=0.25`` and early stopping on ``val_loss`` in a
    stacked bucket against the one-model program on the same rows."""
    config = FitConfig(
        epochs=6,
        batch_size=16,
        validation_split=0.25,
        shuffle=False,
        early_stopping=("val_loss", 2, 0.0, False),
    )
    member = _member("dense", "m", 6)
    fleet = FleetTrainer().train([member], config)[0]
    _, single = fit_single(DENSE_SPEC, member.X, member.y, config, seed=6)
    for key in ("loss", "val_loss"):
        np.testing.assert_allclose(
            fleet.history.history[key], single.history[key], rtol=2e-4
        )


# -- (4) early stopping on val_loss with no validation row --------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_early_stopping_on_val_loss_falls_back_to_the_training_loss(kind):
    """No validation row: NaN in, so ``val_loss`` monitors the training
    loss, epoch for epoch like ``loss`` itself."""
    stops = {}
    for monitor in ("val_loss", "loss"):
        config = FitConfig(
            epochs=8,
            batch_size=16,
            shuffle=False,
            # an improvement no epoch reaches: the fit stops after `patience`
            early_stopping=(monitor, 2, 10.0, True),
        )
        stops[monitor] = FleetTrainer().train([_member(kind, "m", 7)], config)[0]
    on_val, on_loss = stops["val_loss"], stops["loss"]
    assert "val_loss" not in on_val.history.history
    assert len(on_val.history.history["loss"]) == 3  # the first epoch, then 2 waits
    assert on_val.history.history["loss"] == on_loss.history.history["loss"]
    _assert_same_bits(on_val.params, on_loss.params)


# -- (5) the zero-length axis on a (models, data) mesh ---------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_zero_length_validation_axis_on_a_models_by_data_mesh(kind):
    mesh = make_mesh(jax.devices()[:4], data_parallelism=2)
    assert mesh.devices.shape == (2, 2)
    config = FitConfig(epochs=2, batch_size=16, shuffle=False)
    members = [_member(kind, f"m{i}", i) for i in range(2)]
    sharded = FleetTrainer(mesh=mesh).train(members, config)
    single = FleetTrainer(mesh=make_mesh(jax.devices()[:1])).train(members, config)
    for a, b in zip(sharded, single):
        assert "val_loss" not in a.history.history
        np.testing.assert_allclose(
            a.history.history["loss"], b.history.history["loss"], rtol=1e-5
        )
        for leaf_a, leaf_b in zip(
            jax.tree_util.tree_leaves(a.params), jax.tree_util.tree_leaves(b.params)
        ):
            np.testing.assert_allclose(leaf_a, leaf_b, rtol=1e-4, atol=1e-6)


# -- the span: validation_slots, and compile once a variant ---------------------------------


@pytest.mark.parametrize(
    "kind, program", [("dense", "fleet_fit"), ("windowed", "fleet_windowed_fit")]
)
def test_fit_span_carries_validation_slots_and_compiles_once_a_variant(
    kind, program, tmp_path
):
    # a config of this test's own: the span's key is new to the process
    config = FitConfig(epochs=1, batch_size=32, shuffle=False)
    sink = tmp_path / "trace.jsonl"
    recorder = telemetry.SpanRecorder(sink_path=str(sink))
    trainer = FleetTrainer()
    with telemetry.activate(recorder):
        for _ in range(2):
            trainer.train([_member(kind, "m", 9)], config)
            trainer.train([_member(kind, "m", 9, **_tail_weights(24))], config)
    recorder.close()
    spans = [json.loads(line) for line in sink.read_text().splitlines()]
    fits = [
        s["attributes"]
        for s in spans
        if s["name"] == "device_program" and s["attributes"]["program"] == program
    ]
    assert [f["validation_slots"] for f in fits] == [0, 24, 0, 24]
    assert [f["compile"] for f in fits] == [True, True, False, False]
