"""Pallas fused fleet forward vs the reference jnp forward (interpret mode)."""

import jax
import numpy as np
import pytest

from gordo_tpu.models.factories import feedforward_hourglass, feedforward_model
from gordo_tpu.models.nn import forward_feedforward, init_feedforward
from gordo_tpu.ops.activations import _ACTIVATIONS
from gordo_tpu.ops.pallas_dense import (
    fleet_anomaly_scores_pallas,
    fleet_feedforward_pallas,
)


def _stacked(spec, m, rng):
    keys = jax.random.split(jax.random.PRNGKey(rng), m)
    return jax.vmap(lambda k: init_feedforward(k, spec))(keys)


@pytest.mark.parametrize("m,b", [(1, 8), (4, 32)])
def test_pallas_forward_matches_jnp(m, b):
    spec = feedforward_hourglass(12)
    params = _stacked(spec, m, 0)
    X = np.random.RandomState(0).rand(m, b, 12).astype(np.float32)

    expected = jax.vmap(lambda p, x: forward_feedforward(spec, p, x)[0])(params, X)
    got = fleet_feedforward_pallas(spec, params, X, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-5, atol=1e-6)


def test_pallas_forward_explicit_dims_relu():
    spec = feedforward_model(6, 6, encoding_dim=(8, 4), decoding_dim=(4, 8),
                             encoding_func=("relu", "relu"), decoding_func=("relu", "relu"))
    params = _stacked(spec, 3, 1)
    X = np.random.RandomState(1).rand(3, 16, 6).astype(np.float32)
    expected = jax.vmap(lambda p, x: forward_feedforward(spec, p, x)[0])(params, X)
    got = fleet_feedforward_pallas(spec, params, X, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-5, atol=1e-6)


def test_pallas_anomaly_scores():
    spec = feedforward_hourglass(5)
    params = _stacked(spec, 2, 2)
    X = np.random.RandomState(2).rand(2, 10, 5).astype(np.float32)
    out, err = fleet_anomaly_scores_pallas(spec, params, X, X, interpret=True)
    expected_out = jax.vmap(lambda p, x: forward_feedforward(spec, p, x)[0])(params, X)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected_out), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(err),
        ((np.asarray(expected_out) - X) ** 2).mean(-1),
        rtol=1e-5, atol=1e-6,
    )


def test_pallas_forward_large_batch_blocked(monkeypatch):
    """B larger than the row-block tile: the batch grid axis + padding must
    keep parity (this bounds VMEM for big serving requests)."""
    import gordo_tpu.ops.pallas_dense as pallas_dense

    monkeypatch.setattr(pallas_dense, "BLOCK_B", 16)
    spec = feedforward_hourglass(7)
    params = _stacked(spec, 2, 3)
    # 50 rows: 3 full 16-row blocks + a 2-row tail forcing padding
    X = np.random.RandomState(3).rand(2, 50, 7).astype(np.float32)
    expected = jax.vmap(lambda p, x: forward_feedforward(spec, p, x)[0])(params, X)
    got = pallas_dense.fleet_feedforward_pallas(spec, params, X, interpret=True)
    assert got.shape == (2, 50, 7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-5, atol=1e-6)


def _activation_spec(name):
    return feedforward_model(
        6, 6, encoding_dim=(8, 4), decoding_dim=(4, 8),
        encoding_func=(name, name), decoding_func=(name, name),
    )


@pytest.mark.parametrize("name", sorted(_ACTIVATIONS))
def test_every_activation_lowers_for_tpu(name):
    """The kernel is the default f32 serving program on a TPU, so every
    Keras-valid activation must lower through Mosaic — checked on the
    CPU host by lowering for the ``tpu`` platform (``elu``/``selu`` were
    written with expm1, which Mosaic does not implement)."""
    spec = _activation_spec(name)
    params = _stacked(spec, 2, 4)
    X = np.zeros((2, 32, 6), np.float32)
    jax.jit(lambda p, x: fleet_feedforward_pallas(spec, p, x)).trace(
        params, X
    ).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("name", sorted(_ACTIVATIONS))
def test_every_activation_matches_jnp(name):
    """The kernel's spelling of each activation (``kernel_activation``)
    computes what ``ops.activations`` does."""
    spec = _activation_spec(name)
    params = _stacked(spec, 2, 5)
    X = (np.random.RandomState(5).rand(2, 16, 6).astype(np.float32) - 0.5) * 6
    expected = jax.vmap(lambda p, x: forward_feedforward(spec, p, x)[0])(params, X)
    got = fleet_feedforward_pallas(spec, params, X, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-5, atol=1e-6)


def test_no_package_module_interprets_the_kernel():
    """``interpret=`` is a test-only argument: a serving path that passed
    it would run the Pallas interpreter in place of the compiled kernel
    and still answer 200."""
    import pathlib
    import re

    import gordo_tpu

    root = pathlib.Path(gordo_tpu.__file__).parent
    offenders = [
        str(path.relative_to(root))
        for path in root.rglob("*.py")
        if re.search(r"interpret\s*=\s*True", path.read_text())
    ]
    assert offenders == []
