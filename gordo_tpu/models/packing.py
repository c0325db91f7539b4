"""
Packed fleet training: G tiny models as ONE block-diagonal supermodel.

The fleet's models are hourglass MLPs a few tens of units wide, but the
TPU MXU multiplies 128×128 tiles — a vmapped ``[B, 17] @ [17, 13]`` fleet
spends one systolic pass per model with ~1% of each tile doing work.
Packing G models into block-diagonal weights turns G passes into one:
``[B, G·17] @ (G·17, G·13 block-diag)`` fills the tile laterally.

What that buys in practice: the MXU-pass count drops ~G×, but the fleet
regime is NOT matmul-bound — per training step the chip moves the f32
params + Adam moments + gradients and the batch through HBM, and that
elementwise/optimizer traffic is identical packed or unpacked (compact
``[G, d_in, d_out]`` parameters, by design). Measured on a v5e, packing
is worth ~1.1× end to end, consistent with the roofline arithmetic in
docs/architecture.md — it is the matmul share of the step, not the whole
step, that scales with G. The block-diagonal trick would approach its
ideal ~G× only for compute-bound workloads (wider layers, bigger
batches), which these fleet models deliberately are not.

Parameters stay COMPACT: each layer's weights live as ``[G, d_in, d_out]``
stacks (exactly a vmapped ``init_feedforward``), and the block-diagonal
``[G·d_in, G·d_out]`` matrix is materialized *inside* the step, only for
the matmul. This keeps the matmul win without a G× optimizer tax — Adam's
moments, the gradients it consumes, and every elementwise update touch
``G·d_in·d_out`` elements, not the ``G²·d_in·d_out`` of a dense packed
weight. (An earlier dense-parameter formulation lost on real TPUs for
exactly that reason: these models are so small that training is
elementwise/HBM-bound, not matmul-bound.)

Per-model math is EXACTLY preserved:

- off-diagonal blocks are structural zeros (built by construction, not
  masked), so cross-model terms are exact float zeros and each model's
  output matches its unpacked forward to within dot-product summation
  order;
- autodiff through the block-diagonal construction returns gradients in
  the compact ``[G, d_in, d_out]`` layout — each member's block, nothing
  else — so per-member gradients equal separate-training gradients;
- the training loss is the SUM of per-model weighted means (not a mean
  over the concatenated feature axis), so each model's parameter gradients
  equal its separate-training gradients;
- per-model "empty batch" guards become per-member update masks over the
  leading G axis, keeping the no-op contract of the unpacked engine
  (models/training.py).

The one intentional departure: members of a pack share the per-epoch
shuffle permutation (one ``jax.random.permutation`` per pack instead of
per member). With ``shuffle=False`` packed training reproduces unpacked
training to float summation order; with shuffling it is statistically
equivalent.

Early stopping is not supported in packed mode — callers fall back to the
unpacked program when ``config.early_stopping`` is set.

One more ragged-bucket caveat: Adam's step count is shared across a
pack. A batch that is padding for only SOME members masks their updates
and moments, but the shared count still advances, so their later
bias-correction factors differ slightly from separate training (order
1e-3 over a few epochs). Members of equal length are unaffected.
"""

from dataclasses import dataclass
from functools import lru_cache
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..ops.activations import resolve_activation
from ..ops.losses import resolve_loss
from .nn import init_feedforward
from .spec import FeedForwardSpec, ModelSpec
from .training import validation_pass

Params = Dict[str, Dict[str, jnp.ndarray]]

#: MXU lane width — packing beyond this stops helping and starts hurting.
MXU_LANES = 128


@dataclass(frozen=True)
class PackedFeedForwardSpec(ModelSpec):
    """G copies of ``base`` fused into block-diagonal layers."""

    base: FeedForwardSpec
    g: int

    @property
    def layer_dims(self) -> Tuple[Tuple[int, int], ...]:
        """Per-layer (d_in, d_out) of the BASE model, output layer last."""
        dims = []
        d_in = self.base.n_features
        for units in self.base.dims:
            dims.append((d_in, units))
            d_in = units
        dims.append((d_in, self.base.n_features_out))
        return tuple(dims)

    @property
    def layer_keys(self) -> Tuple[str, ...]:
        return tuple(f"dense_{i}" for i in range(len(self.base.dims))) + ("out",)


def auto_packing(spec: FeedForwardSpec, n_members: int) -> int:
    """
    A packing factor that fills (but does not overflow) the MXU lane
    width: ``G = 128 // widest layer``, capped by the member count.
    """
    widest = max((spec.n_features, spec.n_features_out) + tuple(spec.dims))
    g = max(1, MXU_LANES // max(widest, 1))
    return max(1, min(g, n_members, 16))


def _block_diag(W: jnp.ndarray) -> jnp.ndarray:
    """
    ``W[G, d_in, d_out] -> [G·d_in, G·d_out]`` with member ``gi``'s matrix
    on diagonal block ``gi`` and structural zeros elsewhere. Differentiable:
    the backward pass is the block-extraction, so gradients arrive compact.
    """
    g, d_in, d_out = W.shape
    eye = jnp.eye(g, dtype=W.dtype)
    # [G(row-block), d_in, G(col-block), d_out] -> flatten pairwise
    blocks = W[:, :, None, :] * eye[:, None, :, None]
    return blocks.reshape(g * d_in, g * d_out)


def init_packed(member_keys: jnp.ndarray, spec: PackedFeedForwardSpec) -> Params:
    """
    Compact packed params from G per-member PRNG keys: each member
    initializes through the exact ``init_feedforward`` chain (same glorot
    draws as unpacked training); leaves carry a leading member axis
    (``W[G, d_in, d_out]``, ``b[G, d_out]``).
    """
    return jax.vmap(lambda k: init_feedforward(k, spec.base))(member_keys)


def unpack_params(packed: Params, spec: PackedFeedForwardSpec, gi: int) -> Params:
    """Member ``gi``'s standalone param pytree (leading-axis slice)."""
    return jax.tree_util.tree_map(lambda leaf: leaf[gi], packed)


def forward_packed(
    spec: PackedFeedForwardSpec, params: Params, x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """
    ``x[B, G*F] -> (out[B, G*F_out], penalties[G])`` — the packed
    equivalent of ``forward_feedforward`` with per-model activity
    penalties (L1 over each member's block).
    """
    base = spec.base
    dtype = jnp.dtype(base.compute_dtype)

    def cast(leaf):
        return leaf.astype(dtype) if leaf.dtype != dtype else leaf

    penalties = jnp.zeros((spec.g,), jnp.float32)
    h = cast(x)
    for i in range(len(base.dims)):
        layer = params[f"dense_{i}"]
        pre = h @ _block_diag(cast(layer["W"])) + cast(layer["b"]).reshape(-1)
        h = resolve_activation(base.activations[i])(pre)
        if base.l1_activity and base.l1_activity[i]:
            per_member = jnp.sum(
                jnp.abs(h).reshape(h.shape[0], spec.g, base.dims[i]),
                axis=(0, 2),
                dtype=jnp.float32,
            )
            penalties = penalties + base.l1_activity[i] * per_member
    out = h @ _block_diag(cast(params["out"]["W"])) + cast(
        params["out"]["b"]
    ).reshape(-1)
    # float32 out regardless of compute dtype (models/nn.py dtype contract)
    return resolve_activation(base.out_activation)(out).astype(jnp.float32), penalties


def _per_model_losses(
    spec: PackedFeedForwardSpec, out: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """
    ``(weighted per-model means [G], per-model weight totals [G])`` from
    packed outputs. ``w[B, G]`` carries each member's sample weights.
    """
    base = spec.base
    # resolve_loss gives the per-sample loss (mean over the trailing
    # feature axis); reshaping to [B, G, F_out] yields the [B, G]
    # per-member matrix with the same registry as the unpacked engine.
    per_sample_fn = resolve_loss(base.loss)
    shape = (out.shape[0], spec.g, base.n_features_out)
    per_sample = per_sample_fn(out.reshape(shape), y.reshape(shape))
    totals = jnp.sum(w, axis=0)
    means = jnp.sum(per_sample * w, axis=0) / jnp.maximum(totals, 1.0)
    return means, totals


def _per_member_select(g: int, new, old, keep: jnp.ndarray):
    """
    ``where(keep[member], new, old)`` over every leaf whose leading axis is
    the member axis (compact params and optimizer moments all carry it);
    scalar leaves (Adam's shared step count) advance unconditionally.
    """

    def select(new_leaf, old_leaf):
        shape = tuple(np.shape(new_leaf))
        if len(shape) >= 2 and shape[0] == g:
            cond = keep.reshape((g,) + (1,) * (len(shape) - 1))
            return jnp.where(cond, new_leaf, old_leaf)
        return new_leaf

    return jax.tree_util.tree_map(select, new, old)


@lru_cache(maxsize=None)
def build_packed_fit_fn(spec: PackedFeedForwardSpec, config):
    """
    The unjitted packed fused fit:

    ``(params, opt_state, Xtr[n, G·F], ytr[n, G·Fo], wtr[n, G],
    Xval, yval, wval[nv, G], rng) ->
    (params, opt_state, losses[epochs, G], val_losses[epochs, G])``

    Mirrors ``models.training.build_raw_fit_fn`` with per-model loss
    vectors and per-model empty-batch update masks. No early stopping.
    """
    if config.early_stopping is not None:
        raise ValueError("Packed training does not support early stopping")
    tx = spec.base.optimizer.to_optax()

    def batch_loss(params, xb, yb, wb):
        out, penalties = forward_packed(spec, params, xb)
        means, totals = _per_model_losses(spec, out, yb, wb)
        has_data = totals > 0
        # Penalties for empty members are pure padding artifacts and would
        # leak gradients into their biases.
        losses_g = means + jnp.where(has_data, penalties, 0.0)
        return jnp.sum(losses_g), (losses_g, totals)

    grad_fn = jax.value_and_grad(batch_loss, has_aux=True)

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
            wtr.reshape((steps, config.batch_size) + wtr.shape[1:]),
        )

        def step(carry, batch):
            params, opt_state = carry
            xb, yb, wb = batch
            (_, (losses_g, totals)), grads = grad_fn(params, xb, yb, wb)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            has_data = totals > 0
            # A batch that is padding for EVERY member is a true no-op —
            # Adam's shared step count must not advance (matches the
            # unpacked engine's has_data skip exactly). A batch that is
            # padding for only SOME members masks their updates/moments,
            # but the shared count still advances for them — the one
            # bias-correction divergence of packed ragged buckets.
            any_data = jnp.any(has_data)
            new_params = optax.apply_updates(params, updates)
            new_params = _per_member_select(spec.g, new_params, params, has_data)
            new_opt_state = _per_member_select(
                spec.g, new_opt_state, opt_state, has_data
            )
            params = jax.tree_util.tree_map(
                lambda n, o: jnp.where(any_data, n, o), new_params, params
            )
            opt_state = jax.tree_util.tree_map(
                lambda n, o: jnp.where(any_data, n, o), new_opt_state, opt_state
            )
            contribution = jnp.where(has_data, losses_g * totals, 0.0)
            return (params, opt_state), (contribution, totals)

        (params, opt_state), (weighted, batch_totals) = jax.lax.scan(
            step, (params, opt_state), batches
        )
        member_totals = jnp.sum(batch_totals, axis=0)
        epoch_losses = jnp.sum(weighted, axis=0) / jnp.maximum(member_totals, 1.0)
        epoch_losses = jnp.where(member_totals > 0, epoch_losses, jnp.nan)
        return params, opt_state, epoch_losses

    def evaluate(params, X, y, w):
        out, _ = forward_packed(spec, params, X)
        means, totals = _per_model_losses(spec, out, y, w)
        return jnp.where(totals > 0, means, jnp.nan)

    compute_dtype = jnp.dtype(spec.base.compute_dtype)

    def fit(params, opt_state, Xtr, ytr, wtr, Xval, yval, wval, rng):
        if compute_dtype != jnp.float32:
            Xtr, ytr = Xtr.astype(compute_dtype), ytr.astype(compute_dtype)
            Xval, yval = Xval.astype(compute_dtype), yval.astype(compute_dtype)
        evaluate_val = validation_pass(
            wval, lambda p: evaluate(p, Xval, yval, wval), shape=(spec.g,)
        )

        def epoch_body(carry, erng):
            params, opt_state = carry
            params, opt_state, losses_g = train_epoch(
                params, opt_state, Xtr, ytr, wtr, erng
            )
            return (params, opt_state), (losses_g, evaluate_val(params))

        rngs = jax.random.split(rng, config.epochs)
        (params, opt_state), (losses, val_losses) = jax.lax.scan(
            epoch_body, (params, opt_state), rngs
        )
        return params, opt_state, losses, val_losses

    return fit
