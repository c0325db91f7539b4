"""
The hand-written backward of the LSTM recurrence (models/nn.py
``_lstm_recurrence``) against autodiff of a plain ``lax.scan`` copy kept
here: same forward bit for bit, same gradients up to summation order,
and no backward scan that carries a ``Wh``-shaped accumulator.

Tolerances, set before the first run from the dtypes: float32 1e-5 of a
gradient leaf's largest magnitude; bfloat16 3e-2 (eight ulps of 2**-8:
the plain copy accumulates ``dWh`` in bfloat16 over the timesteps, the
new backward in float32).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import nn
from gordo_tpu.models.spec import LSTMSpec
from gordo_tpu.ops.activations import resolve_activation

TOLERANCE = {"float32": 1e-5, "bfloat16": 3e-2}
BATCH, FEATURES, MEMBERS = 5, 3, 3


def plain_lstm_layer(layer, x_seq, activation, unroll):
    """``_lstm_layer`` as it was before the custom VJP: ``Wh`` closed over
    by the scan step, so autodiff accumulates its cotangent in the
    backward scan's carry. The reference; do not optimise."""
    act = resolve_activation(activation)
    dtype = x_seq.dtype
    Wx, Wh = layer["Wx"].astype(dtype), layer["Wh"].astype(dtype)
    b = layer["b"].astype(dtype)
    units = layer["Wh"].shape[0]
    batch = x_seq.shape[1]
    h0 = jnp.zeros((batch, units), x_seq.dtype)
    c0 = jnp.zeros((batch, units), x_seq.dtype)
    x_proj = x_seq @ Wx + b

    def step(carry, xp_t):
        h, c = carry
        gates = xp_t + h @ Wh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        c_new = f * c + i * act(g)
        h_new = o * act(c_new)
        return (h_new, c_new), h_new

    _, h_seq = jax.lax.scan(step, (h0, c0), x_proj, unroll=unroll)
    return h_seq


def stacked(layer_fn, spec, params, x):
    """Two stacked layers and a squared-error loss on the last hidden
    state; returns ``(loss, hidden sequence)``."""
    h_seq = jnp.transpose(x.astype(spec.compute_dtype), (1, 0, 2))
    for i in range(len(spec.dims)):
        h_seq = layer_fn(params[f"lstm_{i}"], h_seq, spec.activations[i])
    return jnp.mean(h_seq[-1].astype(jnp.float32) ** 2), h_seq


def make_case(units, lookback, dtype, activation="tanh", members=None):
    spec = LSTMSpec(
        n_features=FEATURES,
        n_features_out=FEATURES,
        lookback_window=lookback,
        dims=(units, units),
        activations=(activation, activation),
        compute_dtype=dtype,
    )
    shape = (BATCH, lookback, FEATURES)
    if members is None:
        params = nn.init_lstm(jax.random.PRNGKey(0), spec)
    else:
        keys = jax.random.split(jax.random.PRNGKey(0), members)
        params = jax.vmap(lambda k: nn.init_lstm(k, spec))(keys)
        shape = (members,) + shape
    params = {k: v for k, v in params.items() if k != "out"}
    return spec, params, jax.random.normal(jax.random.PRNGKey(1), shape)


def both_sides(spec, params, x, unroll, vmapped):
    """``((loss, h_seq), grads)`` of the plain copy and of nn._lstm_layer."""

    def plain(layer, x_seq, activation):
        return plain_lstm_layer(layer, x_seq, activation, unroll)

    results = []
    for layer_fn in (plain, nn._lstm_layer):
        fn = jax.value_and_grad(
            lambda p, xx, layer_fn=layer_fn: stacked(layer_fn, spec, p, xx),
            has_aux=True,
        )
        results.append(jax.jit(jax.vmap(fn) if vmapped else fn)(params, x))
    return results


def assert_same_gradients(expected, got, tolerance):
    for (path, want), have in zip(
        jax.tree_util.tree_leaves_with_path(expected),
        jax.tree_util.tree_leaves(got),
    ):
        assert have.dtype == want.dtype == jnp.float32
        want, have = np.asarray(want, np.float64), np.asarray(have, np.float64)
        # with one timestep h_prev is all zero, and so is dWh: absolute then
        error = np.abs(want - have).max() / (np.abs(want).max() or 1.0)
        assert error <= tolerance, (jax.tree_util.keystr(path), error)


@pytest.mark.parametrize("vmapped", [False, True], ids=["single", "vmap3"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("unroll", [1, 4])
@pytest.mark.parametrize("lookback", [1, 7])
@pytest.mark.parametrize("units", [4, 64])
def test_gradients_match_autodiff_of_plain_scan(
    monkeypatch, units, lookback, unroll, dtype, vmapped
):
    monkeypatch.setattr(nn, "LSTM_SCAN_UNROLL", unroll)
    spec, params, x = make_case(
        units, lookback, dtype, members=MEMBERS if vmapped else None
    )
    ((loss_p, h_p), grads_p), ((loss_n, h_n), grads_n) = both_sides(
        spec, params, x, unroll, vmapped
    )
    # the forward is the same program: bit-identical, not merely close
    assert h_n.dtype == h_p.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(
        np.asarray(h_n, np.float32), np.asarray(h_p, np.float32)
    )
    np.testing.assert_array_equal(np.asarray(loss_n), np.asarray(loss_p))
    assert_same_gradients(grads_p, grads_n, TOLERANCE[dtype])


@pytest.mark.parametrize("activation", ["relu", "linear", "softmax", "elu"])
def test_gradients_match_for_any_activation(activation):
    """The candidate's and the cell's activation are arbitrary functions
    (softmax is not even elementwise): their derivative is taken by
    ``jax.vjp``, not from a table."""
    spec, params, x = make_case(4, 5, "float32", activation=activation)
    (_, grads_p), (_, grads_n) = both_sides(spec, params, x, 4, False)
    assert_same_gradients(grads_p, grads_n, TOLERANCE["float32"])


def test_undifferentiated_forward_is_bit_identical():
    """Predict paths never differentiate: forward_lstm through the custom
    VJP's primal answers exactly what the plain layers answer."""
    spec, params, x = make_case(64, 7, "float32")
    plain = jax.jit(
        lambda p, xx: stacked(
            lambda *a: plain_lstm_layer(*a, nn.LSTM_SCAN_UNROLL), spec, p, xx
        )[1]
    )
    new = jax.jit(lambda p, xx: stacked(nn._lstm_layer, spec, p, xx)[1])
    np.testing.assert_array_equal(
        np.asarray(new(params, x)), np.asarray(plain(params, x))
    )


def scan_carries(jaxpr):
    """Avals of every ``scan`` carry in ``jaxpr``, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            first = eqn.params["num_consts"]
            carries = body.invars[first : first + eqn.params["num_carry"]]
            found += [v.aval for v in carries]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += scan_carries(sub)
    return found


@pytest.mark.parametrize("vmapped", [False, True], ids=["single", "vmap3"])
def test_no_backward_scan_carries_a_recurrent_weight_accumulator(vmapped):
    units = 8
    spec, params, x = make_case(
        units, 7, "float32", members=MEMBERS if vmapped else None
    )

    def weight_shaped_carries(layer_fn):
        grad = jax.grad(lambda p, xx: stacked(layer_fn, spec, p, xx)[0])
        jaxpr = jax.make_jaxpr(jax.vmap(grad) if vmapped else grad)(params, x)
        carries = scan_carries(jaxpr.jaxpr)
        assert carries, "the walk found no scan at all"
        return [a for a in carries if a.shape[-2:] == (units, 4 * units)]

    # the walk can see one: autodiff of the plain copy has it in both layers
    assert len(weight_shaped_carries(lambda *a: plain_lstm_layer(*a, 4))) == 2
    assert weight_shaped_carries(nn._lstm_layer) == []


def test_recurrence_is_built_once_per_activation_so_jit_caches_hit():
    assert nn._lstm_recurrence("tanh") is nn._lstm_recurrence("tanh")
    assert nn._lstm_recurrence("tanh") is not nn._lstm_recurrence("relu")
    spec, params, x = make_case(4, 3, "float32")
    traces = []

    @jax.jit
    def grad(p, xx):
        traces.append(1)
        return jax.grad(lambda q: stacked(nn._lstm_layer, spec, q, xx)[0])(p)

    grad(params, x)
    grad(params, x + 1)
    assert len(traces) == 1
