"""
Network init/forward as pure JAX functions over explicit param pytrees.

Rather than translating Keras ``Sequential`` objects, each
:mod:`gordo_tpu.models.spec` ModelSpec maps to an ``(init, forward)`` pair of
pure functions. Everything is vmap/shard_map-friendly: the fleet trainer
vmaps ``init`` over per-model RNG keys and ``forward`` over stacked param
pytrees with zero code changes here.

Initialization parity with Keras (so reference configs converge the same
way): Dense kernels glorot_uniform + zero bias; LSTM input kernels
glorot_uniform, recurrent kernels orthogonal, zero bias with unit forget
gate bias.

Dtype contract (``spec.compute_dtype``): mixed precision in the standard
sense — parameters and optimizer moments always live in float32 (Adam
updates are ~1e-4 of the param magnitude, far below bf16's 8-bit
mantissa ULP; storing params in bf16 silently drops most updates and
stalls training — measured: EV −0.02 vs 0.70 on the bf16 test fixture),
while matmuls/activations cast to the compute dtype per use and the
OUTPUT, losses and thresholds are always float32.

The LSTM recurrence has a hand-written backward (``jax.custom_vjp``):
the recurrent weight's gradient is one product over all timesteps after
the backward time scan, not an accumulator carried through it; the
undifferentiated forward is the plain ``lax.scan``. See ``_lstm_layer``.
"""

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.activations import resolve_activation
from .spec import FeedForwardSpec, LSTMSpec, ModelSpec

Params = Dict[str, Dict[str, jnp.ndarray]]

_glorot = jax.nn.initializers.glorot_uniform()
_orthogonal = jax.nn.initializers.orthogonal()


#: Timesteps a scan iteration of the recurrent scans, forward and backward:
#: several in one iteration, without changing the math. What a timestep costs
#: on the chip, by layer width, is in docs/architecture.md.
LSTM_SCAN_UNROLL = 4


def init_feedforward(rng: jax.Array, spec: FeedForwardSpec) -> Params:
    """Initialize params for a FeedForwardSpec (always float32 — see the
    module docstring's dtype contract)."""
    dtype = jnp.float32
    params: Params = {}
    in_dim = spec.n_features
    for i, units in enumerate(spec.dims):
        rng, key = jax.random.split(rng)
        params[f"dense_{i}"] = {
            "W": _glorot(key, (in_dim, units), dtype),
            "b": jnp.zeros((units,), dtype),
        }
        in_dim = units
    rng, key = jax.random.split(rng)
    params["out"] = {
        "W": _glorot(key, (in_dim, spec.n_features_out), dtype),
        "b": jnp.zeros((spec.n_features_out,), dtype),
    }
    return params


def forward_feedforward(
    spec: FeedForwardSpec, params: Params, x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """
    Forward pass on ``x`` of shape ``[batch, n_features]``.

    Returns ``(output, activity_penalty)`` where the penalty is the summed L1
    activity regularization (zero when the spec has none) to be added to the
    training loss. XLA fuses the elementwise activations into the matmuls, so
    the whole stack is a handful of MXU ops.

    Dtype contract: compute runs in ``spec.compute_dtype`` (bf16 halves
    the HBM traffic the tiny-model regime is bound by — see
    docs/architecture.md roofline); the OUTPUT and the penalty are always
    float32, so losses, thresholds and the sklearn-facing predict keep
    full precision regardless of compute dtype.
    """
    dtype = jnp.dtype(spec.compute_dtype)

    def cast(leaf) -> jnp.ndarray:
        return leaf.astype(dtype) if leaf.dtype != dtype else leaf

    penalty = jnp.zeros((), jnp.float32)
    h = cast(x)
    for i in range(len(spec.dims)):
        layer = params[f"dense_{i}"]
        h = resolve_activation(spec.activations[i])(
            h @ cast(layer["W"]) + cast(layer["b"])
        )
        if spec.l1_activity and spec.l1_activity[i]:
            penalty = penalty + spec.l1_activity[i] * jnp.sum(
                jnp.abs(h), dtype=jnp.float32
            )
    out = h @ cast(params["out"]["W"]) + cast(params["out"]["b"])
    return resolve_activation(spec.out_activation)(out).astype(jnp.float32), penalty


def init_lstm(rng: jax.Array, spec: LSTMSpec) -> Params:
    """Initialize params for an LSTMSpec (stacked LSTM + Dense head);
    always float32 like init_feedforward."""
    dtype = jnp.float32
    params: Params = {}
    in_dim = spec.n_features
    for i, units in enumerate(spec.dims):
        rng, kx, kh = jax.random.split(rng, 3)
        bias = jnp.zeros((4 * units,), dtype)
        # Unit forget-gate bias (Keras unit_forget_bias=True); gate order is
        # (input, forget, cell, output).
        bias = bias.at[units : 2 * units].set(1.0)
        params[f"lstm_{i}"] = {
            "Wx": _glorot(kx, (in_dim, 4 * units), dtype),
            "Wh": _orthogonal(kh, (units, 4 * units), dtype),
            "b": bias,
        }
        in_dim = units
    rng, key = jax.random.split(rng)
    params["out"] = {
        "W": _glorot(key, (in_dim, spec.n_features_out), dtype),
        "b": jnp.zeros((spec.n_features_out,), dtype),
    }
    return params


def _lstm_cell(act, Wh, carry, xp_t):
    """One timestep: ``(h, c)`` and the projected input to the activated
    gates ``(i, f, o)``, the candidate's pre-activation ``g`` and the new
    ``(h, c)``."""
    h, c = carry
    gates = xp_t + h @ Wh
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
    c_new = f * c + i * act(g)
    h_new = o * act(c_new)
    return (i, f, g, o), (h_new, c_new)


@functools.lru_cache(maxsize=None)
def _lstm_recurrence(activation):
    """
    The recurrence of one LSTM layer, ``(Wh, x_proj) -> h_seq``, with a
    hand-written backward; built once per activation so that ``jit``
    caches hit. :func:`_lstm_layer` documents the backward's shape.
    """
    act = resolve_activation(activation)

    def zero_state(Wh, x_proj):
        zeros = jnp.zeros((x_proj.shape[1], Wh.shape[0]), x_proj.dtype)
        return zeros, zeros

    def scan_forward(Wh, x_proj, keep):
        """The forward time scan, stacking ``keep(gates, (h, c))``."""
        Wh_c = Wh.astype(x_proj.dtype)

        def step(carry, xp_t):
            gates, carry = _lstm_cell(act, Wh_c, carry, xp_t)
            return carry, keep(gates, carry)

        _, kept = jax.lax.scan(
            step, zero_state(Wh, x_proj), x_proj, unroll=LSTM_SCAN_UNROLL
        )
        return kept

    @jax.custom_vjp
    def recurrence(Wh, x_proj):
        return scan_forward(Wh, x_proj, lambda gates, carry: carry[0])

    def forward(Wh, x_proj):
        h_seq, c_seq, gates_seq = scan_forward(
            Wh,
            x_proj,
            lambda gates, carry: (*carry, jnp.concatenate(gates, axis=-1)),
        )
        return h_seq, (Wh, h_seq, c_seq, gates_seq)

    def backward(residuals, dh_seq):
        Wh, h_seq, c_seq, gates_seq = residuals
        Wh_c = Wh.astype(h_seq.dtype)
        h0, c0 = zero_state(Wh, h_seq)
        h_prev_seq = jnp.concatenate([h0[None], h_seq[:-1]])
        c_prev_seq = jnp.concatenate([c0[None], c_seq[:-1]])

        def step(carry, inputs):
            dh, dc = carry  # cotangents of h_t, c_t from the timesteps after t
            dh_out, gates, c_prev, c_new = inputs
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            a, a_vjp = jax.vjp(act, g)
            ac, ac_vjp = jax.vjp(act, c_new)
            dh = dh + dh_out
            dc = dc + ac_vjp(dh * o)[0]
            dgates = jnp.concatenate(
                [
                    dc * a * i * (1 - i),
                    dc * c_prev * f * (1 - f),
                    a_vjp(dc * i)[0],
                    dh * ac * o * (1 - o),
                ],
                axis=-1,
            )
            # the one product left in the loop: contract Wh's gate axis
            dh_prev = jnp.einsum("bg,hg->bh", dgates, Wh_c)
            return (dh_prev, dc * f), dgates

        _, dgates_seq = jax.lax.scan(
            step,
            (h0, c0),
            (dh_seq, gates_seq, c_prev_seq, c_seq),
            reverse=True,
            unroll=LSTM_SCAN_UNROLL,
        )
        with jax.named_scope("lstm_weight_grad"):
            dWh = jnp.einsum(
                "tbh,tbg->hg",
                h_prev_seq,
                dgates_seq,
                preferred_element_type=jnp.promote_types(h_seq.dtype, jnp.float32),
            )
        # x_proj enters the gates by addition: its cotangent is dgates_seq
        return dWh.astype(Wh.dtype), dgates_seq

    recurrence.defvjp(forward, backward)
    return recurrence


def _lstm_layer(
    layer: Dict[str, jnp.ndarray], x_seq: jnp.ndarray, activation: str
) -> jnp.ndarray:
    """
    Run one LSTM layer over ``x_seq`` of shape ``[time, batch, features]``,
    returning the full hidden sequence ``[time, batch, units]``.

    The configured ``activation`` applies to both the candidate cell update
    and the output transform (Keras LSTM semantics); gates use sigmoid.
    Compute dtype follows ``x_seq`` (the caller casts); f32 master params
    are cast at use.

    Backward (hand-written, :func:`_lstm_recurrence`): the recurrent
    weight's gradient is NOT accumulated in the backward time scan, so
    no scan carries an array of ``Wh``'s shape. The reverse scan carries
    only ``(dh, dc)``, keeps one product a timestep (``dgates_t`` against
    ``Wh``'s gate axis) and stacks ``dgates_t``; after it, ``dWh`` is one
    product over the whole ``[time * batch]`` axis, accumulated in
    float32 (scope ``lstm_weight_grad``). ``dgates_seq`` is also the
    cotangent of the hoisted input projection, so ``dWx``, ``db`` and
    ``dx_seq`` come from ordinary autodiff of ``x_seq @ Wx + b``. Saved
    from the forward: ``h_seq``, ``c_seq`` and the gates (``i, f, o``
    after the sigmoid, the candidate before ``activation``, whose
    derivative an arbitrary function only gives from its input); no
    product is recomputed. Same math as autodiff of the plain scan,
    another summation order for ``dWh``.
    """
    dtype = x_seq.dtype
    Wx, b = layer["Wx"].astype(dtype), layer["b"].astype(dtype)

    # Hoist the input projection out of the scan: one big [T*B, F] @ [F, 4H]
    # matmul keeps the MXU busy instead of T small ones.
    x_proj = x_seq @ Wx + b
    return _lstm_recurrence(activation)(layer["Wh"], x_proj)


def forward_lstm(
    spec: LSTMSpec, params: Params, x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """
    Forward pass on windows ``x`` of shape ``[batch, lookback, n_features]``
    → ``[batch, n_features_out]`` (many-to-one: last timestep's hidden state
    feeds the Dense head). Returns ``(output, activity_penalty=0)``.
    Same dtype contract as :func:`forward_feedforward`: compute in
    ``spec.compute_dtype``, float32 out.
    """
    dtype = jnp.dtype(spec.compute_dtype)
    if x.dtype != dtype:
        x = x.astype(dtype)
    h_seq = jnp.transpose(x, (1, 0, 2))  # [time, batch, features] for scan
    for i in range(len(spec.dims)):
        with jax.named_scope(f"lstm_{i}"):  # one scope a layer, as in params
            h_seq = _lstm_layer(params[f"lstm_{i}"], h_seq, spec.activations[i])
    last_h = h_seq[-1]
    out = last_h @ params["out"]["W"].astype(dtype) + params["out"]["b"].astype(
        dtype
    )
    return (
        resolve_activation(spec.out_activation)(out).astype(jnp.float32),
        jnp.zeros((), jnp.float32),
    )


def init_fn_for(spec) -> "object":
    """The spec's ``(rng, spec) -> params``: the spec answers for itself."""
    if not isinstance(spec, ModelSpec):
        raise TypeError(f"No init function for spec type {type(spec).__name__}")
    return spec.init_fn()


def forward_fn_for(spec) -> "object":
    """The spec's ``(spec, params, x) -> (output, penalty)``."""
    if not isinstance(spec, ModelSpec):
        raise TypeError(f"No forward function for spec type {type(spec).__name__}")
    return spec.forward_fn()
