"""
Pallas TPU kernel: the fleet feedforward-AE batch as ONE fused kernel.

The serving hot loop (reference call stack §3.3: ``model.anomaly`` →
``self.predict(X)``, gordo/machine/model/anomaly/diff.py:310-458) for a
feedforward AE is a stack of small dense layers. Model dims are tiny
(hourglass of a ~20-tag asset), so when a fleet of M models scores a batch
at once, XLA's batched-matmul path emits one kernel per layer and streams
the [M, B, hidden] activations through HBM between them. This kernel
instead walks the whole stack for one model per grid step with every
activation resident in VMEM: grid = (M,), each step loads the model's
weights + its row block, applies all L layers and the output head, and
writes only the final reconstruction back to HBM.

The layer walk is unrolled at trace time from the spec (static), so the
kernel is recompiled per architecture — exactly like the XLA path, which
is cached per (spec, shape) too.

CPU tests run the kernel through the Pallas interpreter (the
``interpret`` argument — test-only, no serving path passes it); numerical parity with
:func:`gordo_tpu.models.nn.forward_feedforward` is asserted in
tests/ops/test_pallas_dense.py, and on the chip by ``chip_smoke.py``.
"""

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.spec import FeedForwardSpec
from .activations import resolve_activation

Params = Dict[str, Dict[str, jnp.ndarray]]

# jax.nn.elu / jax.nn.selu are written with expm1, which Mosaic does not
# lower ("Unimplemented primitive in Pallas TPU lowering"). In the kernel
# they are spelled with exp instead: exp(x) - 1 differs from expm1(x) by
# at most one float32 rounding of a value near 1 (~6e-8 absolute), far
# inside the kernel's tolerance against the XLA forward. min(x, 0) keeps
# exp from overflowing in the branch ``where`` discards.
_SELU_ALPHA = 1.6732632423543772848170429916717
_SELU_SCALE = 1.0507009873554804934193349852946


def _elu(x):
    return jnp.where(x > 0, x, jnp.exp(jnp.minimum(x, 0.0)) - 1.0)


def _selu(x):
    return _SELU_SCALE * jnp.where(
        x > 0, x, _SELU_ALPHA * (jnp.exp(jnp.minimum(x, 0.0)) - 1.0)
    )


_KERNEL_ACTIVATIONS = {"elu": _elu, "selu": _selu}


def kernel_activation(name: str):
    """The activation as the kernel computes it: the ``ops.activations``
    function wherever Mosaic lowers it, an expm1-free spelling where it
    does not. tests/ops/test_pallas_dense.py lowers every name for the
    TPU on the CPU host."""
    return _KERNEL_ACTIVATIONS.get(name) or resolve_activation(name)


def _layer_names(spec: FeedForwardSpec) -> List[Tuple[str, str]]:
    """[(param key, activation name), ...] in forward order."""
    names = [(f"dense_{i}", spec.activations[i]) for i in range(len(spec.dims))]
    names.append(("out", spec.out_activation))
    return names


# Row-block size of the batch grid axis. Bounds VMEM residency per grid
# step to ~BLOCK_B × max(width) activations regardless of request size —
# without it a large B (e.g. a year of 10-min rows ≈ 52k) would try to
# hold the whole [B, F] block in VMEM and fail to compile.
BLOCK_B = 512

#: the kernel's name in the HLO and in a profiler trace (the custom
#: call is otherwise told apart only by ``tpu_custom_call`` in its text)
KERNEL_NAME = "fleet_dense_forward"


def fleet_feedforward_pallas(
    spec: FeedForwardSpec,
    stacked_params: Params,
    X: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """
    Fused forward for a stacked fleet: ``X[M, B, F] -> [M, B, F_out]``.

    ``stacked_params`` is the fleet pytree (leading model axis on every
    leaf), as produced by ``parallel.fleet.stack_member_params``.

    Semantically identical to ``vmap(forward_feedforward)`` without the
    activity-penalty output (inference only). The grid is (models,
    row-blocks): each step walks the whole layer stack for one model's
    ``BLOCK_B`` rows with activations resident in VMEM.
    """
    names = _layer_names(spec)
    M, B, F = X.shape
    f_out = spec.n_features_out

    block_b = min(B, BLOCK_B)
    b_pad = -(-B // block_b) * block_b
    if b_pad != B:
        X = jnp.pad(X, ((0, 0), (0, b_pad - B), (0, 0)))

    # Flatten params into the pallas_call argument list, layer order.
    # Biases ride as [M, 1, d_out]: a (1, d_out) block of an [M, d_out]
    # array violates the TPU tiling rule (second-to-last block dim must
    # divide 8 or equal the array dim); a trailing-(1, d_out) block of an
    # [M, 1, d_out] array satisfies it exactly.
    flat: List[jnp.ndarray] = []
    for key, _ in names:
        flat.append(stacked_params[key]["W"])
        flat.append(stacked_params[key]["b"][:, None, :])

    def kernel(x_ref, *refs):
        out_ref = refs[-1]
        param_refs = refs[:-1]
        h = x_ref[0]  # [block_b, F] this model's row block, in VMEM
        for li, (_, act_name) in enumerate(names):
            w = param_refs[2 * li][0]  # [d_in, d_out]
            b = param_refs[2 * li + 1][0, 0]  # [d_out]
            # the chip's default matmul precision: float32 operands are
            # rounded to bf16 for the MXU and preferred_element_type sets
            # only the accumulator — on a v5e the kernel and the XLA
            # default-precision forward sit at the same distance from a
            # "highest"-precision reference (PERF.md, PR 22)
            h = jnp.dot(h, w, preferred_element_type=jnp.float32) + b
            h = kernel_activation(act_name)(h)
        out_ref[0] = h

    mem = {"memory_space": pltpu.VMEM}
    in_specs = [pl.BlockSpec((1, block_b, F), lambda m, bi: (m, bi, 0), **mem)]
    for key, _ in names:
        w = stacked_params[key]["W"]
        d_in, d_out = w.shape[-2], w.shape[-1]
        in_specs.append(pl.BlockSpec((1, d_in, d_out), lambda m, bi: (m, 0, 0), **mem))
        in_specs.append(pl.BlockSpec((1, 1, d_out), lambda m, bi: (m, 0, 0), **mem))

    out = pl.pallas_call(
        kernel,
        grid=(M, b_pad // block_b),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_b, f_out), lambda m, bi: (m, bi, 0), **mem),
        out_shape=jax.ShapeDtypeStruct((M, b_pad, f_out), jnp.float32),
        interpret=interpret,
        name=KERNEL_NAME,
    )(X.astype(jnp.float32), *flat)
    return out[:, :B]


@partial(jax.jit, static_argnums=(0,), static_argnames=("interpret",))
def fleet_anomaly_scores_pallas(
    spec: FeedForwardSpec,
    stacked_params: Params,
    X: jnp.ndarray,
    y: jnp.ndarray,
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """
    Fused fleet scoring: ``(reconstruction[M, B, F_out], mse[M, B])``.

    The per-row mean-squared error is the ``total-anomaly-unscaled``
    column of the anomaly response (diff.py:387-415 semantics); the
    reconstruction feeds the ``model-output`` columns.
    """
    out = fleet_feedforward_pallas(spec, stacked_params, X, interpret=interpret)
    err = ((out - y.astype(jnp.float32)) ** 2).mean(axis=-1)
    return out, err
