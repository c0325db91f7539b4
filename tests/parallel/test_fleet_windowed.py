"""On-device windowing parity: WindowedFleetMember (raw series resident,
windows gathered per batch) must train exactly like the dense path on
pre-materialized windows."""

import jax
import numpy as np
import pytest

from gordo_tpu.models.factories import lstm_model
from gordo_tpu.models.training import FitConfig
from gordo_tpu.ops.windows import sliding_windows, window_targets
from gordo_tpu.parallel import FleetMember, FleetTrainer, WindowedFleetMember
from gordo_tpu.parallel.fleet import (
    fleet_windowed_predict_program,
    stack_member_params,
)

LOOKBACK = 8


def _series(n, f, seed):
    return np.random.RandomState(seed).rand(n, f).astype(np.float32)


def _members(n_rows, n_members, lookahead=0, order=None):
    spec = lstm_model(3, lookback_window=LOOKBACK)
    dense, windowed = [], []
    for i in range(n_members):
        X = _series(n_rows, 3, seed=i)
        wins = sliding_windows(X, LOOKBACK, lookahead)
        tgts = window_targets(X, LOOKBACK, lookahead)
        virt = wins if order is None else wins[order]
        virt_t = tgts if order is None else tgts[order]
        dense.append(
            FleetMember(name=f"m{i}", spec=spec, X=np.ascontiguousarray(virt),
                        y=np.ascontiguousarray(virt_t), seed=i)
        )
        windowed.append(
            WindowedFleetMember(
                name=f"m{i}", spec=spec, series=X, targets=tgts,
                order=order, seed=i,
            )
        )
    return spec, dense, windowed


@pytest.mark.parametrize("lookahead", [0, 1])
def test_windowed_matches_dense_no_shuffle(lookahead):
    spec, dense, windowed = _members(70, 2, lookahead=lookahead)
    config = FitConfig(epochs=3, batch_size=16, validation_split=0.25, shuffle=False)
    trainer = FleetTrainer()
    dense_res = trainer.train(dense, config)
    win_res = trainer.train(windowed, config)
    for d, w in zip(dense_res, win_res):
        np.testing.assert_allclose(
            w.history.history["loss"], d.history.history["loss"], rtol=1e-5
        )
        assert ("val_loss" in d.history.history) == ("val_loss" in w.history.history)
        if "val_loss" in d.history.history:
            np.testing.assert_allclose(
                w.history.history["val_loss"], d.history.history["val_loss"], rtol=1e-4
            )
        for a, b in zip(
            jax.tree_util.tree_leaves(d.params), jax.tree_util.tree_leaves(w.params)
        ):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-6)


def test_windowed_with_order_permutation():
    rng = np.random.RandomState(0)
    # lookahead=0 -> n_windows = 70 - 8 + 1 = 63
    order = rng.permutation(63).astype(np.int32)
    spec, dense, windowed = _members(70, 1, order=order)
    config = FitConfig(epochs=2, batch_size=16, shuffle=False)
    trainer = FleetTrainer()
    dense_res = trainer.train(dense, config)
    win_res = trainer.train(windowed, config)
    np.testing.assert_allclose(
        win_res[0].history.history["loss"],
        dense_res[0].history.history["loss"],
        rtol=1e-5,
    )


def test_windowed_shuffle_trains_finite():
    spec, _, windowed = _members(70, 2)
    config = FitConfig(epochs=3, batch_size=16, shuffle=True)
    results = FleetTrainer().train(windowed, config)
    for r in results:
        assert np.all(np.isfinite(r.history.history["loss"]))
        assert len(r.history.history["loss"]) == 3


def test_windowed_mixed_with_dense_members():
    spec, dense, windowed = _members(70, 2)
    # same names would collide; rename the dense ones
    for i, m in enumerate(dense):
        m.name = f"d{i}"
    config = FitConfig(epochs=1, batch_size=16, shuffle=False)
    results = FleetTrainer().train(dense + windowed, config)
    assert [r.name for r in results] == ["d0", "d1", "m0", "m1"]


def test_windowed_predict_program_matches_dense():
    spec, dense, windowed = _members(70, 2)
    config = FitConfig(epochs=1, batch_size=16, shuffle=False)
    trainer = FleetTrainer()
    results = trainer.train(windowed, config)
    stacked = stack_member_params(results)

    batch = 16
    nv = windowed[0].n_windows
    nv_pad = -(-nv // batch) * batch
    order = np.zeros((2, nv_pad), np.int32)
    order[:, :nv] = np.arange(nv)
    series = np.stack([m.series for m in windowed])
    out = np.asarray(
        fleet_windowed_predict_program(spec, batch)(stacked, series, order)
    )[:, :nv]

    expected = trainer.predict_bucket(
        spec, stacked, np.stack([sliding_windows(m.series, LOOKBACK) for m in windowed])
    )
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)


def test_windowed_too_short_series_raises():
    spec = lstm_model(3, lookback_window=LOOKBACK)
    with pytest.raises(ValueError, match="too short"):
        WindowedFleetMember(
            name="x", spec=spec, series=_series(5, 3, 0),
            targets=np.zeros((0, 3), np.float32),
        )
