"""
Init and forward of :class:`~gordo_tpu.models.spec.BackboneSpec`: the
four layer kinds of the LFM2-MoE family (HF ``modeling_lfm2_moe``) as
pure functions over an explicit parameter tree, like :mod:`.nn`.

``u`` is the ``[batch, T, hidden]`` sequence of a batch of windows.

- RMSNorm ``y = x / sqrt(mean(x^2) + eps) * g``; block ``h = x +
  Op(RMSNorm(x))``, ``out = h + FFN(RMSNorm(h))``; no bias anywhere.
- ``conv`` (gated short convolution): ``[B, C, X] = split(u W_in)``,
  ``z = B * X``, ``c_t = sum_k w[:, k] * z_{t-(L-1)+k}`` (depthwise,
  causal, zeros before the window), ``y = (C * c) W_out``.
- ``full_attention``: grouped-query causal attention with an RMSNorm
  over each head of ``q`` and ``k`` and a rotary embedding in the
  half-rotation layout at positions ``0..T-1``.
- dense feed-forward ``W_2(silu(u W_1) * (u W_3))``.
- routed experts (:func:`moe_ffn`): the guide's *share* layer. The
  router scores all published experts (sigmoid), the ``k`` largest
  ``score + bias`` are chosen, the chosen scores, normalised, weigh.
  This holder keeps the (token, expert) pairs whose expert it holds,
  sorts them by expert, runs the three products as grouped products
  (``jax.lax.ragged_dot``; XLA:TPU lowers it to a tiled grouped kernel
  that visits the rows each group has) and scatters the weighted
  results back. No pair is dropped at any routing: the pair buffer holds
  the worst case, ``tokens * k`` rows. What absent experts would add is
  left out; nothing stands in for the other holders. A window that a
  fit step marks as padding (weight 0 in its loss: ``active`` False)
  is no token of the step: it routes nothing and is counted nowhere.
  On a TPU the operands of the grouped products are rounded to
  bfloat16 before the kernel and not inside it
  (:func:`_mxu_operand_dtype`): the same products, bit for bit.

Dtype contract as :mod:`.nn`: float32 parameters, compute in
``spec.compute_dtype``, float32 out. The expert bias is a buffer: it
enters the choice under ``stop_gradient``, so its gradient and its Adam
update are zero and it keeps its seeded value.

Layers are rematerialised in the backward pass (``jax.checkpoint`` a
block) when the parameters are large enough that a step's activations
compete with them for the device's memory: decided at trace time from
the bytes of ``params``, :data:`REMAT_MIN_PARAM_BYTES`. A routed layer
then still keeps :data:`SAVED_PRODUCTS`.
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .spec import BackboneSpec

#: ``jax.named_scope`` names of the four mechanisms, for an operator's xprof
CONV_SCOPE = "short_conv"
ATTENTION_SCOPE = "gqa_attention"
ROUTE_SCOPE = "moe_route"
EXPERTS_SCOPE = "moe_experts"

#: what a rematerialised routed layer keeps for its backward pass: the two
#: grouped products that feed the gate. They are the part of a step whose
#: cost follows the routing, and two of the eleven grouped products a
#: pair would take forward, rematerialised and backward; kept, they are
#: two ``[tokens * k, moe_intermediate_size]`` buffers a routed layer (at
#: the published widths 2.6 GiB more scratch over four layers: 13.0 of
#: 15.75 GiB by the compiler's count for a v5e)
SAVED_PRODUCTS = ("moe_h1", "moe_h3")

#: parameters of at least this many bytes rematerialise their layers in
#: the backward pass: below it a step's saved activations are small
#: beside the chip's memory, above it they are what runs it out
REMAT_MIN_PARAM_BYTES = 1 << 30


def _mxu_operand_dtype(dtype):
    """The dtype the grouped products' operands are handed over in. A
    float32 product at XLA's default precision is one bfloat16 pass on
    the TPU's matrix unit: the operands are rounded on their way in.
    Rounding them first gives the same bits (forward and gradients;
    the accumulator stays float32) and the grouped kernel loads half
    the bytes: 0.88 -> 0.70 us a pair forward and backward on a v5e.
    Any other backend multiplies float32 as float32, and keeps it."""
    if dtype == jnp.float32 and jax.default_backend() == "tpu":
        return jnp.bfloat16
    return dtype


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(float(fan_in))


def init_backbone(rng: jax.Array, spec: BackboneSpec) -> Dict:
    """Seeded float32 parameters: matrices normal with variance 1 /
    fan-in (the config states no initialiser), norm gains one, the conv
    taps uniform in +-1/sqrt(L), the expert bias a small seeded buffer."""
    h, dh = spec.hidden_size, spec.head_dim
    kv = spec.num_key_value_heads * dh
    keys = iter(jax.random.split(rng, 2 + 8 * len(spec.layer_ops)))
    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params: Dict = {
        "embed": {
            "W": _normal(next(keys), (spec.n_features, h), spec.n_features),
            "b": jnp.zeros((h,), jnp.float32),
        }
    }
    for i, (op, ffn) in enumerate(zip(spec.layer_ops, spec.layer_ffns)):
        layer: Dict = {"operator_norm": ones(h), "ffn_norm": ones(h)}
        if op == "conv":
            bound = 1.0 / jnp.sqrt(float(spec.conv_L_cache))
            layer["conv"] = {
                "in_proj": _normal(next(keys), (h, 3 * h), h),
                "kernel": jax.random.uniform(
                    next(keys), (h, spec.conv_L_cache), jnp.float32, -bound, bound
                ),
                "out_proj": _normal(next(keys), (h, h), h),
            }
        else:
            layer["attn"] = {
                "wq": _normal(next(keys), (h, h), h),
                "wk": _normal(next(keys), (h, kv), h),
                "wv": _normal(next(keys), (h, kv), h),
                "wo": _normal(next(keys), (h, h), h),
                "q_norm": ones(dh),
                "k_norm": ones(dh),
            }
        if ffn == "dense":
            width = spec.intermediate_size
            layer["ffn"] = {
                "w1": _normal(next(keys), (h, width), h),
                "w3": _normal(next(keys), (h, width), h),
                "w2": _normal(next(keys), (width, h), width),
            }
        else:
            width, held = spec.moe_intermediate_size, spec.experts_held
            layer["moe"] = {
                "router": _normal(next(keys), (h, spec.num_experts), h),
                "expert_bias": 0.01
                * jax.random.normal(next(keys), (spec.num_experts,), jnp.float32),
                "w1": _normal(next(keys), (held, h, width), h),
                "w3": _normal(next(keys), (held, h, width), h),
                "w2": _normal(next(keys), (held, width, h), width),
            }
        params[f"layer_{i}"] = layer
    params["head"] = {
        "norm": ones(h),
        "W": _normal(next(keys), (h, spec.n_features_out), h),
        "b": jnp.zeros((spec.n_features_out,), jnp.float32),
    }
    return params


def trained_param_count(params: Dict) -> int:
    """The program's own count of what it trains: every leaf but the
    expert-bias buffers."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if not any(getattr(key, "key", None) == "expert_bias" for key in path):
            total += int(leaf.size)
    return total


def rms_norm(x, gain, eps):
    variance = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(variance + eps).astype(x.dtype)) * gain.astype(x.dtype)


def short_conv(spec: BackboneSpec, w: Dict, u: jnp.ndarray) -> jnp.ndarray:
    dtype = u.dtype
    taps, length = spec.conv_L_cache, u.shape[1]
    with jax.named_scope(CONV_SCOPE):
        b, c, x = jnp.split(u @ w["in_proj"].astype(dtype), 3, axis=-1)
        z = jnp.pad(b * x, ((0, 0), (taps - 1, 0), (0, 0)))  # zero state before the window
        kernel = w["kernel"].astype(dtype)
        conv = sum(kernel[:, k] * z[:, k : k + length] for k in range(taps))
        return (c * conv) @ w["out_proj"].astype(dtype)


def rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """``x [B, T, heads, d]`` at positions ``0..T-1``, half-rotation layout."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def gqa_attention(spec: BackboneSpec, w: Dict, u: jnp.ndarray) -> jnp.ndarray:
    dtype = u.dtype
    batch, length, _ = u.shape
    heads, kv_heads, dh = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    group = heads // kv_heads
    with jax.named_scope(ATTENTION_SCOPE):
        q = (u @ w["wq"].astype(dtype)).reshape(batch, length, heads, dh)
        k = (u @ w["wk"].astype(dtype)).reshape(batch, length, kv_heads, dh)
        v = (u @ w["wv"].astype(dtype)).reshape(batch, length, kv_heads, dh)
        q = rotary(rms_norm(q, w["q_norm"], spec.norm_eps), spec.rope_theta)
        k = rotary(rms_norm(k, w["k_norm"], spec.norm_eps), spec.rope_theta)
        # each key/value head serves `group` query heads: no repeat of k, v
        q = q.reshape(batch, length, kv_heads, group, dh)
        scores = jnp.einsum("bqngd,bknd->bngqk", q, k) * (1.0 / jnp.sqrt(float(dh))).astype(dtype)
        causal = jnp.tril(jnp.ones((length, length), bool))
        scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out = jnp.einsum("bngqk,bknd->bqngd", weights, v)
        return out.reshape(batch, length, heads * dh) @ w["wo"].astype(dtype)


def dense_ffn(w: Dict, u: jnp.ndarray) -> jnp.ndarray:
    dtype = u.dtype
    gate = jax.nn.silu(u @ w["w1"].astype(dtype)) * (u @ w["w3"].astype(dtype))
    return gate @ w["w2"].astype(dtype)


def route(spec: BackboneSpec, w: Dict, tokens: jnp.ndarray):
    """``tokens [N, hidden]`` -> ``(chosen [N, k], weights [N, k])``:
    the experts each token goes to and what each weighs."""
    # the choice is discrete: the router's one small product runs at full
    # float32 precision, so that its own rounding moves no token between
    # experts (2 * hidden * experts a token: nothing beside a layer)
    logits = jnp.dot(
        tokens.astype(jnp.float32), w["router"], precision=jax.lax.Precision.HIGHEST
    )
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(w["expert_bias"])  # a buffer: chooses, takes no gradient
    _, chosen = jax.lax.top_k(scores + bias, spec.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return chosen, weights * spec.routed_scaling_factor


def moe_ffn(
    spec: BackboneSpec, w: Dict, u: jnp.ndarray, active: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The held experts' share of the routed layer over ``u [B, T,
    hidden]``: ``(output, tokens routed to each published expert
    [num_experts], pairs computed here)``. ``active [B]`` (None: all):
    the windows that are tokens of this step; the others are padding,
    route nothing and get no expert's output."""
    dtype = u.dtype
    tokens = u.reshape(-1, u.shape[-1])
    k, held, offset = spec.num_experts_per_tok, spec.experts_held, spec.expert_offset
    with jax.named_scope(ROUTE_SCOPE):
        chosen, weights = route(spec, w, tokens)
        flat = chosen.reshape(-1)  # pair p is (token p // k, expert flat[p])
        local = flat - offset
        is_local = (local >= 0) & (local < held)
        if active is None:
            routed = jnp.zeros((spec.num_experts,), jnp.int32).at[flat].add(1)
        else:
            pair_active = jnp.repeat(active, u.shape[1] * k)
            routed = jnp.zeros((spec.num_experts,), jnp.int32).at[flat].add(
                pair_active.astype(jnp.int32)
            )
            is_local = is_local & pair_active
        # pairs sorted by held expert; pairs of absent experts last
        order = jnp.argsort(jnp.where(is_local, local, held), stable=True)
        group_sizes = jax.lax.dynamic_slice_in_dim(routed, offset, held)
        pairs_here = jnp.sum(group_sizes)
        token_of = order // k
        pair_weight = jnp.where(is_local, weights.reshape(-1), 0.0)[order]
    with jax.named_scope(EXPERTS_SCOPE):
        # rows past the last group belong to no expert held here. A
        # grouped product writes the rows of its groups only: the rest
        # is whatever the buffer held, forward and backward, so those
        # rows are zeroed wherever they enter or leave a product (each
        # ``where`` zeroes the same rows of the cotangent)
        valid = (jnp.arange(tokens.shape[0] * k) < pairs_here)[:, None]
        operand = _mxu_operand_dtype(dtype)

        def grouped(rows, matrices):
            return jax.lax.ragged_dot(
                rows.astype(operand),
                matrices.astype(operand),
                group_sizes,
                preferred_element_type=dtype,
            )

        x = jnp.where(valid, tokens[token_of], 0.0)  # [N * k, hidden]: the worst case, nothing dropped
        h1 = checkpoint_name(jnp.where(valid, grouped(x, w["w1"]), 0.0), SAVED_PRODUCTS[0])
        h3 = checkpoint_name(jnp.where(valid, grouped(x, w["w3"]), 0.0), SAVED_PRODUCTS[1])
        y = grouped(jax.nn.silu(h1) * h3, w["w2"])
        y = jnp.where(valid, y, 0.0) * pair_weight[:, None].astype(dtype)
        out = jnp.zeros_like(tokens).at[token_of].add(y)
    return out.reshape(u.shape), routed, pairs_here


def block(spec: BackboneSpec, op: str, ffn: str, w: Dict, h: jnp.ndarray, active=None):
    """One pre-norm residual block; returns ``(h, counts)`` with
    ``counts = (routed, pairs_here)`` of a routed block, else None.
    ``active``: as :func:`moe_ffn`."""
    normed = rms_norm(h, w["operator_norm"], spec.norm_eps)
    if op == "conv":
        h = h + short_conv(spec, w["conv"], normed)
    else:
        h = h + gqa_attention(spec, w["attn"], normed)
    normed = rms_norm(h, w["ffn_norm"], spec.norm_eps)
    if ffn == "dense":
        return h + dense_ffn(w["ffn"], normed), None
    out, routed, pairs_here = moe_ffn(spec, w["moe"], normed, active)
    return h + out, (routed, pairs_here)


def _param_bytes(params: Dict) -> int:
    return sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(params))


def forward_backbone_aux(
    spec: BackboneSpec,
    params: Dict,
    x: jnp.ndarray,
    remat: Optional[bool] = None,
    active: Optional[jnp.ndarray] = None,
):
    """
    Windows ``x [batch, lookback, n_features]`` -> ``(output [batch,
    n_features_out], penalty=0, aux)``; ``aux`` holds, a row per expert
    layer, ``router_tokens [layers, num_experts]`` (tokens routed to
    each published expert), ``pairs_here [layers]`` (pairs whose expert
    is held here) and ``pairs_total [layers]`` (tokens x k).

    ``remat``: rematerialise each block in the backward pass; None
    decides from the bytes of ``params`` (module docstring).
    ``active [batch]`` (None: all): the windows that count in the
    caller's loss. The rest are a step's padding: the expert layers
    route and count none of their tokens, and their rows of the output
    mean nothing.
    """
    dtype = jnp.dtype(spec.compute_dtype)
    if remat is None:
        remat = _param_bytes(params) >= REMAT_MIN_PARAM_BYTES
    h = x.astype(dtype) @ params["embed"]["W"].astype(dtype) + params["embed"]["b"].astype(dtype)
    routed_rows, pairs_rows = [], []
    for i, (op, ffn) in enumerate(zip(spec.layer_ops, spec.layer_ffns)):
        run = lambda w, h, a, _op=op, _ffn=ffn: block(spec, _op, _ffn, w, h, a)  # noqa: E731
        if remat:
            run = jax.checkpoint(
                run, policy=jax.checkpoint_policies.save_only_these_names(*SAVED_PRODUCTS)
            )
        with jax.named_scope(f"layer_{i}"):  # one scope a layer, as in params
            h, counts = run(params[f"layer_{i}"], h, active)
        if counts is not None:
            routed_rows.append(counts[0])
            pairs_rows.append(counts[1])
    last = rms_norm(h[:, -1], params["head"]["norm"], spec.norm_eps)
    out = last @ params["head"]["W"].astype(dtype) + params["head"]["b"].astype(dtype)
    aux = None
    if routed_rows:
        windows = x.shape[0] if active is None else jnp.sum(active)
        aux = {
            "router_tokens": jnp.stack(routed_rows),
            "pairs_here": jnp.stack(pairs_rows).astype(jnp.int32),
            "pairs_total": jnp.full(
                (len(routed_rows),), windows * x.shape[1] * spec.num_experts_per_tok, jnp.int32
            ),
        }
    return out.astype(jnp.float32), jnp.zeros((), jnp.float32), aux


def forward_backbone(spec: BackboneSpec, params: Dict, x: jnp.ndarray):
    """``(output, penalty=0)``: :func:`forward_backbone_aux` without its
    counters, the signature every spec's forward has."""
    out, penalty, _ = forward_backbone_aux(spec, params, x)
    return out, penalty
