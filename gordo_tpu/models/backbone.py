"""
Init and forward of :class:`~gordo_tpu.models.spec.BackboneSpec`: the
layer kinds of the LFM2-MoE family (HF ``modeling_lfm2_moe``), of
``kind: keye_vl2`` (the ``qwen3_moe`` shape with DeepSeek-Sparse-
Attention's indexer), of ``kind: laguna`` (window and full attention
mixed, gated heads, a shared expert) and of ``kind: smallthinker``
(attention without positions and window attention with rotary mixed, a
router that reads the layer's input before its attention, experts
gated by ``relu``) and of ``kind: kanana`` (``deepseek_v3``'s latent
attention in every layer, two shared experts as one) as pure functions
and of ``kind: phi4flash`` (a selective state-space scan, differential
attention in a window and in full, gated memory units and attention
that read an earlier layer's tensors) as pure functions over an explicit
parameter tree, like :mod:`.nn`.

``u`` is the ``[batch, T, hidden]`` sequence of a batch of windows.

- RMSNorm ``y = x / sqrt(mean(x^2) + eps) * g``; block ``h = x +
  Op(RMSNorm(x))``, ``out = h + FFN(RMSNorm(h))``; no bias anywhere.
- ``conv`` (gated short convolution): ``[B, C, X] = split(u W_in)``,
  ``z = B * X``, ``c_t = sum_k w[:, k] * z_{t-(L-1)+k}`` (depthwise,
  causal, zeros before the window), ``y = (C * c) W_out``.
- ``full_attention``: grouped-query causal attention with an RMSNorm
  over each head of ``q`` and ``k`` (``spec.qk_norm``) and a rotary
  embedding in the half-rotation layout at positions ``0..T-1``
  (``spec.rope_of``: plain, or YaRN's frequencies over the leading part
  of a head, or none at all: ``rope_type: none``, an operator whose
  ``q`` and ``k`` carry no position). A layer has its own number of
  query heads (the width of
  its ``wq``). With ``spec.attention_gate`` each head's output is
  multiplied by ``sigmoid(u Wg)`` before ``wo``.
- latent attention (``spec.kv_lora_rank`` above 0; :func:`_latent_heads`):
  ``full_attention`` whose heads come another way. ``q = u W_q``, each
  head ``[q_nope | q_rope]``; ``[c | r] = u W_kva``, ``c`` the latent,
  normed (``kv_norm``), ``r`` one rotary key that every head shares,
  not normed; ``[k_nope | v] = RMSNorm(c) W_kvb`` a head; the rotary
  embedding turns ``q_rope`` and ``r`` alone (the trailing part of a
  head, in interleaved pairs under ``spec.rope_interleave``); a head's
  key is ``[k_nope | r]``. Scores are ``qk_nope_head_dim +
  qk_rope_head_dim`` wide and scaled by that width, values
  ``v_head_dim`` wide. Every head has a key and a value of its own
  (groups of one): the shared key is broadcast into them, and the
  attention is the one every kind runs, in tiles or in one piece.
- ``sliding_attention``: the same under the mask ``s <= t and t - s <
  sliding_window``. It, and ``full_attention`` over a window longer
  than :data:`ATTENTION_TILE` rows, run in square tiles under a
  running softmax (:func:`_banded_attention`): a block of queries
  visits the tiles its mask reaches and no other (all up to the
  diagonal, or the diagonal tile and ``ceil((sliding_window - 1) /
  tile)`` before it), with its own derivative rule, so no score
  outlives its tile. A shorter ``full_attention`` window holds every
  score at once (:func:`gqa_attention`), as it always did.
- ``sparse_attention`` (:func:`sparse_attention`): the same q, k, v,
  but query ``t`` attends to ``S_t``, the ``min(t + 1, index_topk)``
  causal keys of largest index score ``I[t, s] = sum_j w[t, j] *
  relu(qI[t, j] . kI[s])`` (a small indexer of its own heads over
  ``stop_gradient(u)``). ``S_t`` is what a top-k of the row takes, ties
  to the earlier key, and no row is sorted for it: a block of queries
  finds each query's k-th largest score by a search over the bits of
  the score, a compare and a row sum a bit (:func:`kth_largest`), and
  keeps what lies above it. Computed in square tiles of ``index_chunk``
  queries by ``index_chunk`` keys: a block of queries against the tiles
  up to its diagonal under a running softmax, the keys outside ``S_t``
  masked. No score outlives its tile, forward or backward, nothing
  above the diagonal is computed, and the loops over blocks and tiles
  have one body each whatever the window's length: sixteen blocks of
  sixteen different lengths were sixteen programs to compile, four
  minutes of them at 8,192 rows. The indexer learns from ``KL(p_t ||
  r_t)``, ``p`` the head-mean of the attention weights (detached), ``r``
  the softmax of ``I`` over ``S_t``: the forward's ``penalty``. The
  forecast loss gives the indexer no gradient and the penalty gives
  nothing else any (:func:`_selected_attention` has the derivative rule).
- ``mamba`` (:func:`mamba`): ``[xs | z] = u W_in``; ``c = silu(conv(xs)
  + b_conv)``, the causal depthwise convolution ``conv`` shares with the
  gated one above (:func:`causal_conv`), ``ssm_conv`` taps; ``[r | B |
  C] = c W_x``; ``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * c_t) (x) B_t``, ``s`` a
  float32 ``[ssm_inner, ssm_state]`` state that is zero before the
  window; ``y_t = s_t C_t + D * c_t``; the output ``(y * silu(z))
  W_out``. The scan (:func:`selective_scan`) runs a chunk of
  :data:`SCAN_CHUNK` rows at a time under its own derivative rule: the
  forward keeps the state each chunk starts from and no other, the
  backward computes a chunk's states again from that one and walks the
  chunk back; state and arithmetic are :data:`SCAN_DTYPE` whatever the
  layer's dtype. The layer a ``gmu`` reads hands on ``y``: the scan
  output before the gate, with the ``D`` term.
- ``gmu`` (:func:`gated_memory`): ``(M * silu(u W_g)) W_o``, ``M`` the
  ``y`` of the last ``mamba`` layer before it.
- differential attention (``spec.differential``;
  :func:`differential_attention`): the projections carry a bias
  (``spec.attention_bias``); query heads ``2j`` and ``2j + 1`` are the
  two maps of differential head ``j``, key heads ``2p`` and ``2p + 1``
  those of pair ``p``, whose value is ``[v_2p | v_2p+1]``, twice a head
  wide; head ``j`` reads pair ``j // (heads / kv_heads)``. ``O_j =
  RMSNorm(A1_j - lam A2_j; g) * (1 - lam_0)``, ``A1``, ``A2`` the two
  causal softmax maps over that value, ``lam = exp(lq1 . lk1) - exp(lq2
  . lk2) + lam_0``, ``lam_0 = 0.8 - 0.6 exp(-0.3 l)`` at layer ``l``.
  Each map is an attention of its own over the pair's value (``heads /
  kv_heads`` query heads a key head, as any grouped attention), in
  tiles (:func:`_banded_attention`) or, ``full_attention`` and
  ``cross_attention`` over a window of a tile at most, with every score
  held at once. ``cross_attention`` projects queries alone and takes the
  keys and values of the last ``full_attention`` layer before it.
- a block of those operators is every kind's (:func:`block`): ``h = x
  + Op(Norm(x))``, ``out = h + FFN(Norm(h))`` with ``spec.norm``'s norm
  (here a LayerNorm with a bias) and the dense feed-forward. The layer loop
  hands ``M`` and ``(k, v)`` from the layer that makes them to the
  layers that read them beside the residual, as arguments and results
  of each block's ``jax.checkpoint``.
- dense feed-forward ``W_2(silu(u W_1) * (u W_3))``; an expert is the
  same at its own width, its gate ``spec.expert_activation`` (``silu``
  or ``relu``).
- routed experts (:func:`moe_ffn`): the guide's *share* layer. The
  router scores all published experts (sigmoid), the ``k`` largest
  ``score + bias`` are chosen, the chosen scores, normalised, weigh
  (``router: softmax``: a softmax over all logits, its ``k`` largest
  renormalised to sum 1, no bias; ``router: softmax_of_chosen``: the
  ``k`` largest logits, a softmax over those). A shared expert
  (``spec.shared_expert_intermediate_size``) is a dense feed-forward
  that every token takes beside them: every holder computes it whole.
  The router reads the normed tensor the experts read, or, under
  ``spec.router_input: layer_input``, the block's input before its
  operator and its norm: :func:`block` then makes the routing plan
  (:func:`routing_plan`: the choice, the sort, the groups) first, and
  nothing the operator computes enters it.
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
the bytes of ``params``, :data:`REMAT_MIN_PARAM_BYTES`. A rematerialised
layer still keeps four sets of values by name, each what its backward
pass would otherwise compute a second time: a routed layer
:data:`SAVED_PRODUCTS` (the two grouped products that feed the gate), a
``sparse_attention`` layer :data:`SAVED_SELECTION` (the indexer's choice:
no second search), a ``mamba`` layer :data:`SAVED_SCAN` (the scan's output
and its chunks' states: no second scan), and a layer that attends in
tiles :data:`SAVED_TILES` (the tile loops' output and normalisers, which
their own derivative rule asks for: the second forward projects ``q``,
``k``, ``v`` again and runs no tile loop), but a sliding layer whose band
is no wider than a tile (:func:`keeps_tile_outputs`, from the operator
and the shapes alone).
"""

import math
from functools import lru_cache, partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from .spec import BackboneSpec

#: ``jax.named_scope`` names of the four mechanisms, for an operator's xprof
CONV_SCOPE = "short_conv"
ATTENTION_SCOPE = "gqa_attention"
ROUTE_SCOPE = "moe_route"
EXPERTS_SCOPE = "moe_experts"
#: ... and of sparse attention's three parts: the index scores (and the
#: indexer's objective), the search for each query's k-th largest score,
#: the attention's tiles under the selection
INDEX_SCOPE = "sparse_index"
SELECT_SCOPE = "sparse_select"
SPARSE_ATTENTION_SCOPE = "sparse_attention"
#: ... of attention in tiles under a mask by position, by operator, of
#: the gate on its heads and of the shared expert
TILES_SCOPES = {
    "full_attention": "full_attention_tiles", "sliding_attention": "sliding_attention_tiles",
    "cross_attention": "cross_attention_tiles",
}
GATE_SCOPE = "attention_gate"
SHARED_SCOPE = "moe_shared"
#: ... and of a latent attention's way to its heads: the projection to
#: the latent and the shared rotary key, the latent's norm, the
#: expansion, the rotary parts
LATENT_SCOPE = "latent_kv"
#: ... of a state-space layer (the operator, its convolution, its scan),
#: of a gated memory unit, and of what makes a differential head of two
#: maps (the pairing before the attention; the weight, the difference
#: and its norm after it)
MAMBA_SCOPE = "mamba"
SCAN_CONV_SCOPE = "scan_conv"
SCAN_SCOPE = "selective_scan"
MEMORY_SCOPE = "gated_memory"
DIFFERENTIAL_SCOPE = "differential_heads"

#: what a rematerialised routed layer keeps for its backward pass: the two
#: grouped products that feed the gate. They are the part of a step whose
#: cost follows the routing, and two of the eleven grouped products a
#: pair would take forward, rematerialised and backward; kept, they are
#: two ``[tokens * k, moe_intermediate_size]`` buffers a routed layer (at
#: the published widths 2.6 GiB more scratch over four layers: 13.0 of
#: 15.75 GiB by the compiler's count for a v5e)
SAVED_PRODUCTS = ("moe_h1", "moe_h3")

#: what a rematerialised ``sparse_attention`` layer keeps besides: the
#: selection, a bit a (query, key) pair (8 MB a window of 8,192 rows).
#: With it the rematerialised forward masks and does not select a second
#: time, and what it masks is what the first forward masked, to the bit
SAVED_SELECTION = "sparse_selection"

#: what a rematerialised ``mamba`` layer keeps: its scan's output and the
#: state each chunk of it starts from (168 + 10 MB a window of 8,192
#: rows). With them the rematerialised forward runs no scan: of the four
#: passes a row a step would take (forward, forward again, the chunk's
#: states again, the walk back) three are left
SAVED_SCAN = "selective_scan_output"

#: what a rematerialised layer that attends in tiles keeps
#: (:func:`keeps_tile_outputs`): what the tile loops' own derivative rule
#: hands their backward pass beside ``q``, ``k``, ``v``: the output and
#: its log-normalisers (134 + 1 MB a window of 8,192 rows a layer of 32
#: heads 128 wide), and of a ``sparse_attention`` layer the objective's
#: terms, the indexer's normalisers and the weights' sums besides (0.07
#: MB). With them the rematerialised forward runs no tile loop: of the
#: three passes over a layer's tiles a step would take (forward, forward
#: again, backward) two are left
SAVED_TILES = "attention_tiles_output"

#: parameters of at least this many bytes rematerialise their layers in
#: the backward pass: below it a step's saved activations are small
#: beside the chip's memory, above it they are what runs it out
REMAT_MIN_PARAM_BYTES = 1 << 30

#: rows of a square tile of attention under a mask by position
#: (:func:`banded_attention`), and the longest window whose
#: ``full_attention`` still holds every score at once
#: (:func:`gqa_attention`): blocks of the computation, not of a model
#: (any tile gives the same numbers)
ATTENTION_TILE = 512

#: rows of a chunk of the selective scan (:func:`selective_scan`): the
#: forward keeps one state a chunk, the backward holds one chunk's states
#: (84 MB at 5,120 x 16 and 256 rows). A block of the computation, as
#: the tile is: any chunk gives the same numbers up to rounding
SCAN_CHUNK = 256
#: rows of a chunk that one trip of the scan's loops takes: the rows
#: follow one another an operation each, and what they read out and the
#: gradients of a trip are computed for a trip's rows at once. Timed on
#: the chip beside a loop of single rows and trips of 16 and 64 (PERF.md
#: 6, PR 45)
SCAN_UNROLL = 32
#: the scan's state and arithmetic, whatever ``compute_dtype`` says: it
#: integrates over every row of a window
SCAN_DTYPE = jnp.float32


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
    # a split of another length is other keys: the kinds that were here
    # before the indexer keep their 8 a layer, and so their weights; those
    # before the gate and the shared expert their 8 or 12
    a_layer = 12 if "sparse_attention" in spec.layer_ops else 8
    if spec.attention_gate or spec.shared_expert_intermediate_size or spec.differential:
        a_layer = 16
    keys = iter(jax.random.split(rng, 2 + a_layer * len(spec.layer_ops)))
    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    zeros = lambda n: jnp.zeros((n,), jnp.float32)  # noqa: E731
    # a LayerNorm has a bias beside its gain
    norm = (lambda: ones(h)) if spec.norm == "rms" else (lambda: {"gain": ones(h), "bias": zeros(h)})
    params: Dict = {
        "embed": {
            "W": _normal(next(keys), (spec.n_features, h), spec.n_features),
            "b": jnp.zeros((h,), jnp.float32),
        }
    }
    for i, (op, ffn) in enumerate(zip(spec.layer_ops, spec.layer_ffns)):
        layer: Dict = {"operator_norm": norm(), "ffn_norm": norm()}
        heads = spec.heads_by_layer[i]
        qo = heads * dh
        if op == "mamba":
            layer["mamba"] = _init_mamba(keys, spec)
        elif op == "gmu":
            layer["gmu"] = {
                "in_proj": _normal(next(keys), (h, spec.ssm_inner), h),
                "out_proj": _normal(next(keys), (spec.ssm_inner, h), spec.ssm_inner),
            }
        elif spec.differential:
            # a cross layer projects queries alone
            names = (("q", qo),) if op == "cross_attention" else (("q", qo), ("k", kv), ("v", kv))
            attn = {f"w{name}": _normal(next(keys), (h, width), h) for name, width in names}
            attn["wo"] = _normal(next(keys), (qo, h), qo)
            if spec.attention_bias:
                attn.update({f"b{name}": zeros(width) for name, width in names + (("o", h),)})
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                attn[name] = 0.1 * jax.random.normal(next(keys), (dh,), jnp.float32)
            attn["sub_norm"] = ones(2 * dh)
            layer["attn"] = attn
        elif op == "conv":
            bound = 1.0 / jnp.sqrt(float(spec.conv_L_cache))
            layer["conv"] = {
                "in_proj": _normal(next(keys), (h, 3 * h), h),
                "kernel": jax.random.uniform(
                    next(keys), (h, spec.conv_L_cache), jnp.float32, -bound, bound
                ),
                "out_proj": _normal(next(keys), (h, h), h),
            }
        elif spec.kv_lora_rank:
            rank = spec.kv_lora_rank
            layer["attn"] = {
                "wq": _normal(next(keys), (h, qo), h),
                "wkv_a": _normal(next(keys), (h, rank + spec.qk_rope_head_dim), h),
                "kv_norm": ones(rank),
                "wkv_b": _normal(next(keys), (rank, spec.kv_expanded_dim), rank),
                "wo": _normal(next(keys), (heads * spec.v_head_dim, h), heads * spec.v_head_dim),
            }
        else:
            layer["attn"] = {
                "wq": _normal(next(keys), (h, qo), h),
                "wk": _normal(next(keys), (h, kv), h),
                "wv": _normal(next(keys), (h, kv), h),
                "wo": _normal(next(keys), (qo, h), qo),
            }
            if spec.qk_norm:
                layer["attn"].update(q_norm=ones(dh), k_norm=ones(dh))
            if spec.attention_gate:
                layer["attn"]["gate"] = _normal(next(keys), (h, heads), h)
        if op == "sparse_attention":
            heads, width = spec.index_n_heads, spec.index_head_dim
            layer["indexer"] = {
                "wq": _normal(next(keys), (h, heads * width), h),
                "wk": _normal(next(keys), (h, width), h),
                "k_norm": {"gain": ones(width), "bias": jnp.zeros((width,), jnp.float32)},
                "w": _normal(next(keys), (h, heads), h),
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
            layer["moe"] = {"router": _normal(next(keys), (h, spec.num_experts), h)}
            if spec.router == "sigmoid_bias":
                layer["moe"]["expert_bias"] = 0.01 * jax.random.normal(
                    next(keys), (spec.num_experts,), jnp.float32
                )
            layer["moe"].update(
                w1=_normal(next(keys), (held, h, width), h),
                w3=_normal(next(keys), (held, h, width), h),
                w2=_normal(next(keys), (held, width, h), width),
            )
            if spec.shared_expert_intermediate_size:
                width = spec.shared_expert_intermediate_size
                layer["moe"]["shared"] = {
                    "w1": _normal(next(keys), (h, width), h),
                    "w3": _normal(next(keys), (h, width), h),
                    "w2": _normal(next(keys), (width, h), width),
                }
        params[f"layer_{i}"] = layer
    params["head"] = {
        "norm": norm(),
        "W": _normal(next(keys), (h, spec.n_features_out), h),
        "b": jnp.zeros((spec.n_features_out,), jnp.float32),
    }
    return params


def _init_mamba(keys, spec: BackboneSpec) -> Dict:
    """One ``mamba`` operator, seeded as the family seeds it where a
    seeded matrix would make rates no trained model has: ``A_log =
    log(1..ssm_state)`` in every channel, ``D`` one, the step's bias the
    inverse softplus of steps log-uniform in [1e-3, 1e-1]; the matrices
    and the taps as the other kinds', the convolution's bias zero."""
    h, d, n, rank, taps = spec.hidden_size, spec.ssm_inner, spec.ssm_state, spec.ssm_dt_rank, spec.ssm_conv
    bound = 1.0 / jnp.sqrt(float(taps))
    w = {
        "in_proj": _normal(next(keys), (h, 2 * d), h),
        "conv_kernel": jax.random.uniform(next(keys), (d, taps), jnp.float32, -bound, bound),
        "conv_bias": jnp.zeros((d,), jnp.float32),
        "x_proj": _normal(next(keys), (d, rank + 2 * n), d),
        "dt_proj": _normal(next(keys), (rank, d), rank),
    }
    steps = jnp.exp(
        jax.random.uniform(next(keys), (d,), jnp.float32) * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3)
    )
    w["dt_bias"] = steps + jnp.log(-jnp.expm1(-steps))
    w["A_log"] = jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (d, n))
    w["D"] = jnp.ones((d,), jnp.float32)
    w["out_proj"] = _normal(next(keys), (d, h), d)
    return w


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


def block_norm(spec: BackboneSpec, x, w):
    """``spec.norm``'s norm of a block's input or of the head's: an
    RMSNorm under a gain, or a LayerNorm (statistics in float32) under
    a gain and a bias."""
    if spec.norm == "rms":
        return rms_norm(x, w, spec.norm_eps)
    return layer_norm(x.astype(jnp.float32), w["gain"], w["bias"], spec.norm_eps).astype(x.dtype)


def causal_conv(x: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """``x [B, T, C]`` -> ``sum_k kernel[:, k] * x_{t-(L-1)+k}``:
    depthwise and causal, zeros before the window. The one convolution
    under the gated three taps of ``conv`` and the four taps, bias and
    ``silu`` of ``mamba``: the two differ in what goes in and what is
    done to what comes out, not in this sum."""
    taps, length = kernel.shape[1], x.shape[1]
    z = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))  # zero state before the window
    kernel = kernel.astype(x.dtype)
    return sum(kernel[:, k] * z[:, k : k + length] for k in range(taps))


def short_conv(spec: BackboneSpec, w: Dict, u: jnp.ndarray) -> jnp.ndarray:
    dtype = u.dtype
    with jax.named_scope(CONV_SCOPE):
        b, c, x = jnp.split(u @ w["in_proj"].astype(dtype), 3, axis=-1)
        return (c * causal_conv(b * x, w["kernel"])) @ w["out_proj"].astype(dtype)


def rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """``x [B, T, heads, d]`` at positions ``0..T-1``, half-rotation layout."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def yarn_inverse_frequencies(rope: Dict[str, Any], rotated: int) -> np.ndarray:
    """YaRN's ``rotated // 2`` inverse frequencies (``transformers``'
    ``_compute_yarn_parameters``): those of ``rope_theta`` as they are
    (extrapolated) for the fast dimensions before the ramp, divided by
    ``factor`` (interpolated) for the slow ones after it, blended
    linearly between the two correction dimensions: those that turn
    ``beta_fast`` and ``beta_slow`` times over
    ``original_max_position_embeddings`` positions, rounded outwards.
    Computed in float64 and rounded to float32 once."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    positions = float(rope["original_max_position_embeddings"])

    def correction(rotations):
        return rotated * math.log(positions / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(rope.get("beta_slow", 1))), rotated - 1)
    high = high + 0.001 if high == low else high
    plain = 1.0 / base ** (np.arange(0, rotated, 2, dtype=np.float64) / rotated)
    ramp = np.clip((np.arange(rotated // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return ((plain / factor) * ramp + plain * (1.0 - ramp)).astype(np.float32)


def scaled_rotary(x: jnp.ndarray, rope: Dict[str, Any]) -> jnp.ndarray:
    """:func:`rotary` of the leading ``partial_rotary_factor`` of each
    head of ``x [B, T, heads, d]``, the rest as it is; under ``rope_type:
    yarn`` at :func:`yarn_inverse_frequencies`, ``cos`` and ``sin``
    multiplied by ``attention_factor`` (so the rotated part of ``q`` and
    of ``k`` alone is scaled)."""
    rotated = int(x.shape[-1] * rope["partial_rotary_factor"])
    half = rotated // 2
    if rope["rope_type"] == "yarn":
        inv_freq = jnp.asarray(yarn_inverse_frequencies(rope, rotated))
        factor = float(rope["attention_factor"])
    else:
        inv_freq = 1.0 / (rope["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
        factor = 1.0
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angles) * factor)[None, :, None, :].astype(x.dtype)
    sin = (jnp.sin(angles) * factor)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:rotated]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotated:]], axis=-1)


def _heads(spec: BackboneSpec, w: Dict, u: jnp.ndarray, op: str = "full_attention", kv=None):
    """``u [B, T, hidden]`` -> ``(q [B, T, heads, dh], k, v [B, T,
    kv_heads, dh])``: the projections (``heads`` is the layer's own: the
    width of its ``wq``) with a bias where the spec has one
    (``attention_bias``), the per-head RMSNorm of ``q`` and ``k`` where
    the spec has one, and the rotary embedding of operator ``op``, as
    every attention takes them. ``kv``: the ``(k, v)`` of an earlier
    layer, as that layer placed them; the layer then projects ``q``
    alone (``cross_attention``). A latent attention's come the other
    way (:func:`_latent_heads`): ``k`` and ``q`` of one width, ``v`` of
    its own."""
    if spec.kv_lora_rank:
        return _latent_heads(spec, w, u)
    dtype = u.dtype
    batch, length, _ = u.shape
    kv_heads, dh = spec.num_key_value_heads, spec.head_dim

    def projected(name, heads):
        out = u @ w["w" + name].astype(dtype)
        if spec.attention_bias:
            out = out + w["b" + name].astype(dtype)
        return out.reshape(batch, length, heads, dh)

    q = projected("q", -1)
    k, v = (projected("k", kv_heads), projected("v", kv_heads)) if kv is None else kv
    rope = spec.rope_of(op)
    plain = rope["rope_type"] == "default" and rope["partial_rotary_factor"] == 1

    def placed(x, gain):
        if spec.qk_norm:
            x = rms_norm(x, w[gain], spec.norm_eps)
        if rope["rope_type"] == "none":  # no position encoding: the projection as it is
            return x
        return rotary(x, rope["rope_theta"]) if plain else scaled_rotary(x, rope)

    return placed(q, "q_norm"), placed(k, "k_norm") if kv is None else k, v


def interleaved_rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """``x [B, T, heads, d]`` at positions ``0..T-1``, dimensions ``(2i,
    2i + 1)`` turning together at ``theta ** (-2i / d)``: the pairs
    parted into their first and their second members, which is
    :func:`rotary`'s layout (``deepseek_v3`` does the same: a ``q`` and
    a ``k`` permuted alike give the scores they gave)."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    return rotary(jnp.concatenate([pairs[..., 0], pairs[..., 1]], axis=-1), theta)


def _latent_heads(spec: BackboneSpec, w: Dict, u: jnp.ndarray):
    """``u [B, T, hidden]`` -> ``(q, k [B, T, heads, nope + rope], v [B,
    T, heads, dv])`` of a latent attention (module docstring): the
    other way to what :func:`_heads` gives. The shared rotary key is
    broadcast into every head's key."""
    dtype = u.dtype
    batch, length, _ = u.shape
    rank, nope, rope = spec.kv_lora_rank, spec.qk_nope_head_dim, spec.qk_rope_head_dim
    turn = interleaved_rotary if spec.rope_interleave else rotary
    with jax.named_scope(LATENT_SCOPE):
        q = (u @ w["wq"].astype(dtype)).reshape(batch, length, -1, nope + rope)
        down = u @ w["wkv_a"].astype(dtype)
        latent = rms_norm(down[..., :rank], w["kv_norm"], spec.norm_eps)  # the shared key is not normed
        up = (latent @ w["wkv_b"].astype(dtype)).reshape(batch, length, q.shape[2], -1)
        shared = turn(down[:, :, None, rank:], spec.rope_theta)
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:], spec.rope_theta)], axis=-1)
        k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(shared, q.shape[:3] + (rope,))], axis=-1)
    return q, k, up[..., nope:]


def _gated(spec: BackboneSpec, w: Dict, u: jnp.ndarray, out: jnp.ndarray) -> jnp.ndarray:
    """``out [B, T, ..., dh]``, the heads' outputs of the attention over
    ``u``: each head times ``sigmoid(u Wg)`` where the spec gates its
    heads, then ``wo``."""
    dtype = u.dtype
    batch, length, _ = u.shape
    if spec.attention_gate:
        with jax.named_scope(GATE_SCOPE):
            gate = jax.nn.sigmoid(u @ w["gate"].astype(dtype))
            out = out.reshape(batch, length, -1, out.shape[-1]) * gate[..., None]
    return out.reshape(batch, length, -1) @ w["wo"].astype(dtype)


def _attend_in_one_piece(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Causal attention with every score of a window held at once: ``q
    [B, T, n, g, dh]`` (each key/value head serves ``g`` query heads: no
    repeat of ``k``, ``v``), ``k [B, T, n, dh]``, ``v [B, T, n, dv]`` ->
    ``[B, T, n, g, dv]``."""
    dtype = q.dtype
    length, dh = q.shape[1], q.shape[-1]
    scores = jnp.einsum("bqngd,bknd->bngqk", q, k) * (1.0 / jnp.sqrt(float(dh))).astype(dtype)
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bngqk,bknd->bqngd", weights, v)


def gqa_attention(spec: BackboneSpec, w: Dict, u: jnp.ndarray) -> jnp.ndarray:
    """``full_attention`` with every score of a window held at once."""
    batch, length, _ = u.shape
    kv_heads, dh = spec.num_key_value_heads, spec.head_dim
    with jax.named_scope(ATTENTION_SCOPE):
        q, k, v = _heads(spec, w, u)
        q = q.reshape(batch, length, kv_heads, -1, dh)
        return _gated(spec, w, u, _attend_in_one_piece(q, k, v))


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    variance = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(variance + eps) * gain + bias


def indexer_inputs(spec: BackboneSpec, w: Dict, u: jnp.ndarray):
    """``u [B, T, hidden]`` -> the indexer's ``(qI [B, T, heads, d], kI
    [B, T, d], wI [B, T, heads])``, the two scales folded into ``wI``.
    The indexer sees its input detached and computes in float32 at full
    precision whatever the layer's dtype: what it feeds is a choice."""
    heads, width = spec.index_n_heads, spec.index_head_dim
    batch, length, _ = u.shape
    x = jax.lax.stop_gradient(u).astype(jnp.float32)
    project = lambda m: jnp.dot(x, m, precision=jax.lax.Precision.HIGHEST)  # noqa: E731
    qi = rotary(project(w["wq"]).reshape(batch, length, heads, width), spec.rope_theta)
    ki = layer_norm(project(w["wk"]), w["k_norm"]["gain"], w["k_norm"]["bias"], spec.norm_eps)
    ki = rotary(ki[:, :, None, :], spec.rope_theta)[:, :, 0, :]
    wi = project(w["w"]) * (float(heads) ** -0.5 * float(width) ** -0.5)
    return qi, ki, wi


def index_scores(qi: jnp.ndarray, ki: jnp.ndarray, wi: jnp.ndarray) -> jnp.ndarray:
    """``I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s])``: ``qI [Q,
    heads, d]``, ``kI [S, d]``, ``wI [Q, heads]`` -> ``[Q, S]`` float32.
    At full float32 precision, as the router's logits are: the scores
    choose, and a product of bfloat16-rounded operands would put about
    one key in a thousand on the other side of the k-th largest than
    the reference's float32 has it."""
    dots = jnp.einsum("qjd,sd->qjs", qi, ki, precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(wi[:, :, None] * jax.nn.relu(dots), axis=1)


def _blocked(a: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """``a [T, ...]`` -> ``[blocks, chunk, ...]``, zeros after row ``T``."""
    blocks = -(-a.shape[0] // chunk)
    a = jnp.pad(a, ((0, blocks * chunk - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))
    return a.reshape((blocks, chunk) + a.shape[1:])


def _pack_bits(keep: jnp.ndarray) -> jnp.ndarray:
    """``keep [Q, tiles, chunk]`` bool -> ``[Q, tiles, ceil(chunk / 32)]``
    uint32, key ``s`` of a tile in bit ``s % 32`` of word ``s // 32``."""
    rows, tiles, chunk = keep.shape
    words = -(-chunk // 32)
    padded = jnp.pad(keep, ((0, 0), (0, 0), (0, words * 32 - chunk))).reshape(rows, tiles, words, 32)
    return jnp.sum(padded.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32), axis=-1)


def _unpack_bits(packed: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """:func:`_pack_bits` back, a tile or all of them: ``[..., words]``
    uint32 -> ``[..., chunk]`` bool."""
    bits = (packed[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    return bits.reshape(packed.shape[:-1] + (-1,))[..., :chunk].astype(bool)


def _tile(a: jnp.ndarray, j, axis: int = 0) -> jnp.ndarray:
    return jax.lax.dynamic_index_in_dim(a, j, axis, keepdims=False)


def _add_tile(total: jnp.ndarray, j, tile: jnp.ndarray) -> jnp.ndarray:
    """``total`` with ``tile`` added to its tile ``j`` (a backward pass's
    running ``d_k``, ``d_v``)."""
    return jax.lax.dynamic_update_index_in_dim(total, _tile(total, j) + tile, j, 0)


def _tile_gradients(j, q_i, k_j, v_j, d_out_i, weights, delta, scale, d_q, d_k, d_v):
    """Tile ``j`` of keys' part of a block of queries' gradients, from
    the tile's recomputed softmax ``weights [n, g, q, k]`` and ``delta``,
    the block's sum over the keys of weights x their cotangent: the
    running ``(d_q, d_k, d_v)`` with the tile's terms added. The one
    body of both tiled attentions' backward loops."""
    dtype = q_i.dtype
    d_v = _add_tile(d_v, j, jnp.einsum(
        "ngqk,qngd->knd", weights.astype(dtype), d_out_i
    ).astype(jnp.float32))
    d_weights = jnp.einsum("qngd,knd->ngqk", d_out_i, v_j).astype(jnp.float32)
    d_scores = (weights * (d_weights - delta[..., None])).astype(dtype) * scale.astype(dtype)
    d_q = d_q + jnp.einsum("ngqk,knd->qngd", d_scores, k_j).astype(jnp.float32)
    d_k = _add_tile(d_k, j, jnp.einsum("ngqk,qngd->knd", d_scores, q_i).astype(jnp.float32))
    return d_q, d_k, d_v


def searches_selection(spec: BackboneSpec, last_row) -> bool:
    """Whether a block of queries that ends at row ``last_row`` (a
    number or a traced one) holds a query with more causal keys than
    ``index_topk``: such a block searches for its queries' k-th largest
    score (:func:`kth_largest`), any other keeps its causal keys as
    they are."""
    return spec.index_topk < last_row


def selection_blocks_searched(spec: BackboneSpec) -> int:
    """The blocks of ``index_chunk`` queries a window a
    ``sparse_attention`` layer whose selection runs the search, by the
    function :func:`select_keys` decides with
    (:func:`searches_selection`): 12 of 16 at 8,192 rows that keep 2,048
    in blocks of 512, 0 where ``index_topk`` is at least the window."""
    chunk = spec.index_chunk
    blocks = -(-spec.lookback_window // chunk)
    return sum(searches_selection(spec, (i + 1) * chunk) for i in range(blocks))


def _ordered(bits: jnp.ndarray) -> jnp.ndarray:
    """A float32's bits as int32 <-> the key that compares as
    ``jax.lax.top_k`` compares the floats (its own inverse): by sign and
    magnitude, so ``-0.0`` is below ``0.0`` and a NaN beyond the
    infinity of its sign."""
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def kth_largest(scores: jnp.ndarray, k: int, group: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``scores [Q, S]`` float32, ``k <= S`` -> the k-th entry of
    ``jax.lax.top_k(scores, k)``, ``(value [Q, 1], position [Q, 1])``,
    to the bit and for any input, without sorting a row: that top-k
    lists by value (in the order of :func:`_ordered`) and equal values
    by position.

    The value is the largest key that at least ``k`` of the row's keys
    reach: a loop of 32 passes settles a bit of it a pass, from the top
    (from the lowest key up; the addition wraps at bit 31). A pass is a
    compare and a row sum over the block, and nothing moves; a bit stays
    where ``k`` keys still reach the candidate, and the last count that
    fell short is of the keys ``above`` what is found. The position is
    that of the ``k - above``-th key equal to it, in groups of ``group``
    positions (``S`` a multiple of it): the group from the groups'
    counts, the place in it from a running count over that group alone."""
    rows, width = scores.shape
    keys = _ordered(jax.lax.bitcast_convert_type(scores, jnp.int32))

    def settle(step, carry):
        found, above = carry
        candidate = found + (jnp.int32(1) << (31 - step))
        reach = jnp.sum(keys >= candidate, axis=-1, keepdims=True, dtype=jnp.int32)
        stays = reach >= k
        return jnp.where(stays, candidate, found), jnp.where(stays, above, reach)

    lowest = jnp.full((rows, 1), jnp.iinfo(jnp.int32).min, jnp.int32)
    found, above = jax.lax.fori_loop(0, 32, settle, (lowest, jnp.zeros((rows, 1), jnp.int32)))
    equal = (keys == found).reshape(rows, width // group, group)
    wanted = k - above
    upto = jnp.cumsum(jnp.sum(equal, axis=-1, dtype=jnp.int32), axis=-1)  # equal keys up to each group's end
    at_group = jnp.sum(upto < wanted, axis=-1, keepdims=True)
    wanted = wanted - jnp.take_along_axis(jnp.pad(upto, ((0, 0), (1, 0))), at_group, axis=1)
    inside = jnp.sum(
        equal & (jnp.arange(width // group)[None, :, None] == at_group[:, :, None]), axis=1, dtype=jnp.int32
    )
    at = at_group * group + jnp.sum(jnp.cumsum(inside, axis=-1) < wanted, axis=-1, keepdims=True)
    return jax.lax.bitcast_convert_type(_ordered(found), jnp.float32), at


def select_keys(spec: BackboneSpec, qi, ki, wi) -> jnp.ndarray:
    """One window's selection ``S_t`` of every query, a bit a key, in
    tiles of ``index_chunk`` queries by ``index_chunk`` keys: blocked
    inputs (:func:`_blocked`) -> ``[blocks, chunk, blocks, words]``
    uint32 (:func:`_pack_bits`; 8 MB for 8,192 rows where the scores it
    is taken from are 268). ``S_t`` is what ``jax.lax.top_k`` of the
    causal index scores takes: the ``index_topk`` largest, of equal
    scores the earlier key (a relu makes exact ties: every head's
    product negative is a score of 0). Found from the k-th entry of that
    top-k alone, its value and its position, which a search over the
    value's bits gives without sorting the row (:func:`kth_largest`): a
    key is kept if its score is larger, or equal and its position no
    later. One loop over the blocks of queries; a block's
    scores are computed a tile at a time up to its diagonal, the tiles
    above it never, and a block that ends at or before row
    ``index_topk`` searches nothing (:func:`searches_selection`)."""
    blocks, chunk = qi.shape[:2]
    keys = blocks * chunk
    qi, ki, wi = jax.lax.stop_gradient((qi, ki, wi))
    positions = jnp.arange(keys)

    def block_of(i):
        causal = positions[None, :] <= (i * chunk + jnp.arange(chunk))[:, None]

        def chosen():
            def write(j, scores):
                with jax.named_scope(INDEX_SCOPE):
                    tile = index_scores(_tile(qi, i), _tile(ki, j), _tile(wi, i))
                return jax.lax.dynamic_update_slice(scores, tile, (0, j * chunk))

            scores = jnp.full((chunk, keys), -jnp.inf, jnp.float32)
            scores = jax.lax.fori_loop(0, i + 1, write, scores)
            with jax.named_scope(SELECT_SCOPE):
                scores = jnp.where(causal, scores, -jnp.inf)
                # the k-th largest is -inf for a query with fewer causal
                # keys than that, which keeps them all
                kth, kth_at = kth_largest(scores, spec.index_topk, chunk)
                keep = (scores > kth) | ((scores == kth) & (positions[None, :] <= kth_at))
                return keep & causal

        if not searches_selection(spec, keys):  # no query has more causal keys than it may keep
            keep = causal
        else:  # nor has one of a block that ends at or before row top-k
            keep = jax.lax.cond(searches_selection(spec, (i + 1) * chunk), chosen, lambda: causal)
        return _pack_bits(keep.reshape(chunk, blocks, chunk))

    return jax.lax.map(block_of, jnp.arange(blocks))


def _tile_scores(q, k, keep, scale):
    """A tile of the attention's logits under the selection: ``q [Q, n,
    g, dh]``, ``k [S, n, dh]``, ``keep [Q, S]`` -> ``[n, g, Q, S]``
    float32, ``-inf`` outside the selection."""
    scores = jnp.einsum("qngd,knd->ngqk", q, k) * scale.astype(q.dtype)
    return jnp.where(keep, scores.astype(jnp.float32), -jnp.inf)


def _log_sum_exp_step(top, total, scores):
    """One step of a running ``log(sum(exp))`` over the last axis:
    ``(top, total)`` so far and a tile of scores -> ``(top, total, the
    tile's exp(scores - top), what the sums so far scale by)``; rows that
    have seen nothing yet stay at ``(-inf, 0)``."""
    new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
    safe = jnp.where(jnp.isfinite(new_top), new_top, 0.0)
    weights = jnp.exp(scores - safe[..., None])
    scale = jnp.exp(top - safe)
    return new_top, total * scale + jnp.sum(weights, axis=-1), weights, scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _selected_attention(length: int, q, k, v, qi, ki, wi, selected):
    """One window's attention under its selection and the indexer's
    objective, blocked inputs (:func:`_blocked`; ``length`` rows of them
    are the window's): ``(out [blocks, chunk, n, g, dh], sum over t of
    KL(p_t || r_t))``. A block of queries at a time against the tiles
    of keys up to its diagonal, in loops (one body whatever the length):
    the running softmax for the output and its log-normaliser, then a
    second pass for ``p``, the head-mean of the attention's weights,
    which needs that normaliser. Its own derivative rule: the backward
    pass recomputes a tile's weights from the normalisers kept, so no
    score outlives its tile; the output's cotangent reaches ``q``, ``k``,
    ``v`` alone and the objective's the indexer alone (``p`` is a
    constant there): the two gradients are disjoint by construction."""
    return _selected_attention_fwd(length, q, k, v, qi, ki, wi, selected)[0]


def _selected_attention_fwd(length, q, k, v, qi, ki, wi, selected):
    blocks, chunk, kv_heads, group, dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))

    def block_of(i):
        q_i, qi_i, wi_i, selected_i = _tile(q, i), _tile(qi, i), _tile(wi, i), _tile(selected, i)
        valid = (i * chunk + jnp.arange(chunk) < length)[:, None]

        def attend(j, carry):
            top, total, acc = carry
            keep = _unpack_bits(_tile(selected_i, j, 1), chunk)
            with jax.named_scope(SPARSE_ATTENTION_SCOPE):
                scores = _tile_scores(q_i, _tile(k, j), keep, scale)
                top, total, weights, rescale = _log_sum_exp_step(top, total, scores)
                acc = acc * rescale[..., None] + jnp.einsum(
                    "ngqk,knd->ngqd", weights.astype(q.dtype), _tile(v, j)
                ).astype(jnp.float32)
            return top, total, acc

        rows = (kv_heads, group, chunk)
        top, total, acc = jax.lax.fori_loop(
            0, i + 1, attend,
            (jnp.full(rows, -jnp.inf, jnp.float32), jnp.zeros(rows, jnp.float32),
             jnp.zeros(rows + v.shape[-1:], jnp.float32)),
        )
        out = jnp.transpose(acc / total[..., None], (2, 0, 1, 3)).astype(q.dtype)
        log_z = top + jnp.log(total)

        def objective(j, carry):
            # KL(p || r) = sum p log p - sum p I + log(sum exp I) sum p,
            # the sums over the keys kept: one pass
            top_i, total_i, p_log_p, p_index, p_sum = carry
            keep = _unpack_bits(_tile(selected_i, j, 1), chunk) & valid
            with jax.named_scope(SPARSE_ATTENTION_SCOPE):
                scores = _tile_scores(q_i, _tile(k, j), keep, scale)
                p = jnp.mean(jnp.exp(scores - log_z[..., None]), axis=(0, 1))
            with jax.named_scope(INDEX_SCOPE):
                index = jnp.where(keep, index_scores(qi_i, _tile(ki, j), wi_i), -jnp.inf)
                top_i, total_i, _, _ = _log_sum_exp_step(top_i, total_i, index)
                seen = keep & (p > 0)
                p_log_p = p_log_p + jnp.sum(jnp.where(seen, p * jnp.log(jnp.where(seen, p, 1.0)), 0.0), -1)
                p_index = p_index + jnp.sum(jnp.where(seen, p * jnp.where(seen, index, 0.0), 0.0), -1)
                p_sum = p_sum + jnp.sum(jnp.where(seen, p, 0.0), -1)
            return top_i, total_i, p_log_p, p_index, p_sum

        zeros = jnp.zeros((chunk,), jnp.float32)
        top_i, total_i, p_log_p, p_index, p_sum = jax.lax.fori_loop(
            0, i + 1, objective, (jnp.full((chunk,), -jnp.inf, jnp.float32), zeros, zeros, zeros, zeros)
        )
        # a row of padding has kept nothing: (-inf, 0) -> 0, and adds nothing
        log_z_i = jnp.where(total_i > 0, top_i + jnp.log(jnp.where(total_i > 0, total_i, 1.0)), 0.0)
        kl = jnp.sum(p_log_p - p_index + log_z_i * p_sum)
        return out, kl, log_z, log_z_i, p_sum

    # what a rematerialised layer keeps: with all five its second forward runs neither loop
    out, kl, log_z, log_z_i, p_sum = (
        checkpoint_name(a, SAVED_TILES) for a in jax.lax.map(block_of, jnp.arange(blocks))
    )
    return (out, jnp.sum(kl)), (q, k, v, qi, ki, wi, selected, out, log_z, log_z_i, p_sum)


def _selected_attention_bwd(length, kept, cotangents):
    q, k, v, qi, ki, wi, selected, out, log_z, log_z_i, p_sum = kept
    d_out, d_kl = cotangents
    blocks, chunk, kv_heads, group, dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))

    def block_of(carry, i):
        q_i, qi_i, wi_i, selected_i = _tile(q, i), _tile(qi, i), _tile(wi, i), _tile(selected, i)
        d_out_i = _tile(d_out, i)
        log_z_b, log_z_i_b, p_sum_b = _tile(log_z, i), _tile(log_z_i, i), _tile(p_sum, i)
        valid = (i * chunk + jnp.arange(chunk) < length)[:, None]
        # sum over the keys of weights x their cotangent, from the output
        delta = jnp.einsum(
            "qngd,qngd->ngq", d_out_i.astype(jnp.float32), _tile(out, i).astype(jnp.float32)
        )

        def tile_of(j, carry):
            d_q, d_qi, d_wi, d_k, d_v, d_ki = carry
            keep = _unpack_bits(_tile(selected_i, j, 1), chunk)
            k_j, v_j, ki_j = _tile(k, j), _tile(v, j), _tile(ki, j)
            with jax.named_scope(SPARSE_ATTENTION_SCOPE):
                weights = jnp.exp(_tile_scores(q_i, k_j, keep, scale) - log_z_b[..., None])
                d_q, d_k, d_v = _tile_gradients(j, q_i, k_j, v_j, d_out_i, weights, delta, scale, d_q, d_k, d_v)
            with jax.named_scope(INDEX_SCOPE):
                # d KL / d I[t, s] = r[t, s] sum_s' p[t, s'] - p[t, s]
                counted = keep & valid
                p = jnp.where(counted, jnp.mean(weights, axis=(0, 1)), 0.0)
                index, back = jax.vjp(index_scores, qi_i, ki_j, wi_i)
                r = jnp.where(counted, jnp.exp(jnp.where(counted, index, -jnp.inf) - log_z_i_b[:, None]), 0.0)
                add_qi, add_ki, add_wi = back(d_kl * (r * p_sum_b[:, None] - p))
            return d_q, d_qi + add_qi, d_wi + add_wi, d_k, d_v, _add_tile(d_ki, j, add_ki)

        d_k, d_v, d_ki = carry
        d_q, d_qi, d_wi, d_k, d_v, d_ki = jax.lax.fori_loop(
            0, i + 1, tile_of,
            (jnp.zeros(q_i.shape, jnp.float32), jnp.zeros_like(qi_i), jnp.zeros_like(wi_i), d_k, d_v, d_ki),
        )
        return (d_k, d_v, d_ki), (d_q, d_qi, d_wi)

    zeros = (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32), jnp.zeros_like(ki))
    (d_k, d_v, d_ki), (d_q, d_qi, d_wi) = jax.lax.scan(block_of, zeros, jnp.arange(blocks))
    return d_q.astype(q.dtype), d_k.astype(k.dtype), d_v.astype(v.dtype), d_qi, d_ki, d_wi, None


_selected_attention.defvjp(_selected_attention_fwd, _selected_attention_bwd)


def sparse_attention(
    spec: BackboneSpec, w: Dict, wi: Dict, u: jnp.ndarray, active: Optional[jnp.ndarray] = None
):
    """Grouped-query attention over the keys the indexer ``wi`` selects
    (module docstring), over ``u [B, T, hidden]``: ``(output, the
    indexer's objective, counts)``. The objective is the mean over the
    windows that count (``active``; None: all) of the mean over ``t``
    of ``KL(p_t || r_t)``; ``counts`` are float32 ``(keys_selected,
    keys_causal)`` of those windows: a window's 33.6 M causal pairs at
    8,192 rows fit an int32, an epoch's do not."""
    dtype = u.dtype
    batch, length, _ = u.shape
    kv_heads, dh = spec.num_key_value_heads, spec.head_dim
    heads = w["wq"].shape[1] // dh
    blocked = jax.vmap(lambda a: _blocked(a, spec.index_chunk))
    with jax.named_scope(SPARSE_ATTENTION_SCOPE):
        q, k, v = _heads(spec, w, u, "sparse_attention")
        q = q.reshape(batch, length, kv_heads, heads // kv_heads, dh)
        q, k, v = blocked(q), blocked(k), blocked(v)
    with jax.named_scope(INDEX_SCOPE):
        index_in = tuple(blocked(a) for a in indexer_inputs(spec, wi, u))
    # the choice, once: it carries no gradient, and a layer
    # rematerialised in the backward pass is handed it back by name
    selected = jax.lax.map(lambda one: select_keys(spec, *one), index_in)
    selected = checkpoint_name(selected, SAVED_SELECTION)
    out, kl = jax.lax.map(
        lambda one: _selected_attention(length, *one), (q, k, v, *index_in, selected)
    )
    with jax.named_scope(SPARSE_ATTENTION_SCOPE):
        out = out.reshape(batch, -1, heads * dh)[:, :length] @ w["wo"].astype(dtype)
    # the pairs kept, off the bits of the window's own rows
    rows = (jnp.arange(selected.shape[1] * selected.shape[2]) < length).reshape(selected.shape[1:3])
    kept = jnp.sum(
        jax.lax.population_count(selected).astype(jnp.int32) * rows[None, :, :, None, None],
        axis=(1, 2, 3, 4),
    )
    counted = jnp.ones((batch,), jnp.float32) if active is None else active.astype(jnp.float32)
    windows = jnp.maximum(jnp.sum(counted), 1.0)
    objective = jnp.sum(kl * counted) / (windows * length)
    counts = (
        jnp.sum(kept.astype(jnp.float32) * counted),
        jnp.sum(counted) * (length * (length + 1) / 2.0),
    )
    return out, objective, counts


def _band_tiles(chunk: int, window: int, i):
    """``(first, stop)``: block ``i`` of queries visits the tiles of keys
    ``first .. stop - 1``, up to its diagonal one from the tile that its
    earliest query ``i * chunk`` sees back to, row ``i * chunk - (window
    - 1)``. The bounds of the forward's and the backward's loops, and
    what ``pairs_multiplied`` counts."""
    back = -(-(window - 1) // chunk)
    return jnp.maximum(i - back, 0), i + 1


def _band_mask(chunk: int, window: int, i, j) -> jnp.ndarray:
    """Tile ``(i, j)`` of the mask by position: ``s <= t and t - s < window``."""
    t = (i * chunk + jnp.arange(chunk))[:, None]
    s = (j * chunk + jnp.arange(chunk))[None, :]
    return (s <= t) & (t - s < window)


def band_pairs(length: int, window: int, chunk: int):
    """Of one window of ``length`` rows: the (query, key) pairs inside
    the mask, ``sum over t of min(t + 1, window)``, and the pairs of the
    tiles :func:`_banded_attention` visits for them (float32: the loops'
    own bounds, summed over the blocks)."""
    reach = min(length, window)
    attended = reach * (reach + 1) / 2.0 + (length - reach) * reach
    first, stop = _band_tiles(chunk, window, jnp.arange(-(-length // chunk)))
    return attended, jnp.sum(stop - first).astype(jnp.float32) * (chunk * chunk)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _banded_attention(scope: str, window: int, q, k, v):
    """One window's causal attention limited to the ``window`` rows up
    to the query (``window`` at least the length: every causal key),
    blocked inputs (:func:`_blocked`: ``q [blocks, chunk, n, g, dh]``,
    ``k [blocks, chunk, n, dh]``, ``v [blocks, chunk, n, dv]``: a head's
    values have a width of their own) -> ``out [blocks, chunk, n, g,
    dv]``. A block of
    queries at a time against the tiles of keys that
    :func:`_band_tiles` names under a running softmax, in
    loops of one body whatever the length and the mask. Rows of padding
    after the window's own lie after every key they could hide: they
    are computed, and mean nothing. Its own derivative rule, as
    :func:`_selected_attention`'s: the backward pass recomputes a
    tile's weights from the normalisers kept."""
    return _banded_attention_fwd(scope, window, q, k, v)[0]


def _banded_attention_fwd(scope, window, q, k, v):
    blocks, chunk, kv_heads, group, dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))

    def block_of(i):
        q_i = _tile(q, i)

        def attend(j, carry):
            top, total, acc = carry
            with jax.named_scope(scope):
                scores = _tile_scores(q_i, _tile(k, j), _band_mask(chunk, window, i, j), scale)
                top, total, weights, rescale = _log_sum_exp_step(top, total, scores)
                acc = acc * rescale[..., None] + jnp.einsum(
                    "ngqk,knd->ngqd", weights.astype(q.dtype), _tile(v, j)
                ).astype(jnp.float32)
            return top, total, acc

        rows = (kv_heads, group, chunk)
        top, total, acc = jax.lax.fori_loop(
            *_band_tiles(chunk, window, i), attend,
            (jnp.full(rows, -jnp.inf, jnp.float32), jnp.zeros(rows, jnp.float32),
             jnp.zeros(rows + v.shape[-1:], jnp.float32)),
        )
        # every query sees itself: no row's total is 0
        out = jnp.transpose(acc / total[..., None], (2, 0, 1, 3)).astype(q.dtype)
        return out, top + jnp.log(total)

    out, log_z = jax.lax.map(block_of, jnp.arange(blocks))
    # what a rematerialised layer keeps: with them its second forward runs no tile loop
    out, log_z = checkpoint_name(out, SAVED_TILES), checkpoint_name(log_z, SAVED_TILES)
    return out, (q, k, v, out, log_z)


def _banded_attention_bwd(scope, window, kept, d_out):
    q, k, v, out, log_z = kept
    blocks, chunk, kv_heads, group, dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))

    def block_of(carry, i):
        q_i, d_out_i, log_z_i = _tile(q, i), _tile(d_out, i), _tile(log_z, i)
        # sum over the keys of weights x their cotangent, from the output
        delta = jnp.einsum(
            "qngd,qngd->ngq", d_out_i.astype(jnp.float32), _tile(out, i).astype(jnp.float32)
        )

        def tile_of(j, carry):
            d_q, d_k, d_v = carry
            k_j, v_j = _tile(k, j), _tile(v, j)
            with jax.named_scope(scope):
                scores = _tile_scores(q_i, k_j, _band_mask(chunk, window, i, j), scale)
                weights = jnp.exp(scores - log_z_i[..., None])
                d_q, d_k, d_v = _tile_gradients(j, q_i, k_j, v_j, d_out_i, weights, delta, scale, d_q, d_k, d_v)
            return d_q, d_k, d_v

        d_q, d_k, d_v = jax.lax.fori_loop(
            *_band_tiles(chunk, window, i), tile_of, (jnp.zeros(q_i.shape, jnp.float32),) + carry
        )
        return (d_k, d_v), d_q

    zeros = (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    (d_k, d_v), d_q = jax.lax.scan(block_of, zeros, jnp.arange(blocks))
    return d_q.astype(q.dtype), d_k.astype(k.dtype), d_v.astype(v.dtype)


_banded_attention.defvjp(_banded_attention_fwd, _banded_attention_bwd)


def banded_attention(
    spec: BackboneSpec, op: str, w: Dict, u: jnp.ndarray, active: Optional[jnp.ndarray] = None
):
    """``full_attention`` or ``sliding_attention`` over ``u [B, T,
    hidden]`` in tiles of :data:`ATTENTION_TILE` rows (module docstring):
    ``(output, counts)``, ``counts`` float32 ``(pairs_attended,
    pairs_multiplied)`` of the windows that count (``active``; None:
    all): the pairs inside the mask and the pairs of the tiles visited
    (:func:`band_pairs`: the mask is by position, the tiles are the
    loops' bounds)."""
    batch, length, _ = u.shape
    kv_heads, dh = spec.num_key_value_heads, spec.head_dim
    chunk = min(ATTENTION_TILE, length)
    window = min(spec.sliding_window, length) if op == "sliding_attention" else length
    scope = TILES_SCOPES[op]
    blocked = jax.vmap(lambda a: _blocked(a, chunk))
    with jax.named_scope(scope):
        q, k, v = _heads(spec, w, u, op)
        q = q.reshape(batch, length, kv_heads, -1, dh)
        q, k, v = blocked(q), blocked(k), blocked(v)
    out = jax.lax.map(lambda one: _banded_attention(scope, window, *one), (q, k, v))
    with jax.named_scope(scope):
        out = _gated(spec, w, u, out.reshape((batch, -1) + out.shape[3:])[:, :length])
    windows = jnp.float32(batch) if active is None else jnp.sum(active.astype(jnp.float32))
    attended, multiplied = band_pairs(length, window, chunk)
    return out, (windows * attended, windows * multiplied)


def _scan_states(state, a, dt, dtx, b):
    """``s_r = exp(dt_r (x) a) * s_{r-1} + dtx_r (x) b_r`` down a trip's
    rows from ``state``: every ``s_r``, stacked ``[rows, N, d]`` (the
    channels last: they fill a register's lanes). The sequential part of
    the scan: a row is one operation on the device, which forms what it
    multiplies and adds from the row's own vectors and writes nothing
    else (held as arrays of all rows they were four times the states'
    bytes, and the loop ran at the memory's pace: PERF.md 6, PR 45)."""
    states = []
    for row in range(dt.shape[0]):
        state = jnp.exp(dt[row][None, :] * a) * state + dtx[row][None, :] * b[row][:, None]
        states.append(state)
    return jnp.stack(states)


def _trips(t: jnp.ndarray) -> jnp.ndarray:
    """A chunk's rows ``[rows, ...]`` as the trips of its loop, ``[trips,
    SCAN_UNROLL rows, ...]`` (``_selective_scan`` takes chunks that divide)."""
    rows = min(SCAN_UNROLL, t.shape[0])
    return t.reshape((t.shape[0] // rows, rows) + t.shape[1:])


@jax.custom_vjp
def _selective_scan(x, dt, a, b, c):
    """One window's scan, in chunks (:func:`_blocked`: ``x``, ``dt``
    ``[chunks, rows, d]``, ``b``, ``c`` ``[chunks, rows, N]``; rows of
    padding after the window's own carry ``dt = 0``: they leave the
    state as it is) under the rates ``a [N, d]``: ``y [chunks, rows, d]``,
    ``y_t = sum_n s_t[n] c_t[n]``, ``s_t = exp(dt_t (x) a) * s_{t-1} +
    (dt_t * x_t) (x) b_t``, ``s`` zero before the first row. A chunk is
    a loop of trips of :data:`SCAN_UNROLL` rows: the rows follow one
    another in :func:`_scan_states`, an operation a row, and what a
    trip's rows read out is computed for all of them at once. Its own
    derivative rule: the forward keeps the state each chunk starts from
    (``chunks`` states where a window has ``chunks x rows``), the
    backward computes a chunk's states again from it, holds that one
    chunk's, and walks it back."""
    return _selective_scan_fwd(x, dt, a, b, c)[0]


def _selective_scan_fwd(x, dt, a, b, c):
    def chunk_of(state, rows):
        def trip(state, rows):
            x_r, dt_r, b_r, c_r = rows
            states = _scan_states(state, a, dt_r, dt_r * x_r, b_r)
            return states[-1], jnp.sum(states * c_r[:, :, None], axis=1)

        with jax.named_scope(SCAN_SCOPE):
            end, y = jax.lax.scan(trip, state, tuple(_trips(t) for t in rows))
        return end, (y.reshape(rows[0].shape), state)

    _, (y, starts) = jax.lax.scan(chunk_of, jnp.zeros(a.shape, a.dtype), (x, dt, b, c))
    # what a rematerialised layer keeps: with them its second forward runs no scan
    y, starts = checkpoint_name(y, SAVED_SCAN), checkpoint_name(starts, SAVED_SCAN)
    return y, (x, dt, a, b, c, starts)


def _selective_scan_bwd(kept, d_y):
    x, dt, a, b, c, starts = kept

    def chunk_of(carry, rows):
        *rows, start = rows
        x_c, dt_c, b_c, c_c, d_y_c = (_trips(t) for t in rows)

        def again(state, rows):  # the state before each row of the trip
            x_r, dt_r, b_r = rows
            states = _scan_states(state, a, dt_r, dt_r * x_r, b_r)
            return states[-1], jnp.concatenate([state[None], states[:-1]])

        def back(carry, rows):
            flowing, d_a = carry  # into the trip's last state from the rows after it; of the rates so far
            x_r, dt_r, b_r, c_r, d_y_r, before = rows
            dtx = dt_r * x_r
            decay = jnp.exp(dt_r[:, None, :] * a[None])
            states = decay * before + dtx[:, None, :] * b_r[:, :, None]
            # of each state: what its own row reads out, and what flows
            # back from the row after it, last row first
            d_states = []
            for row in reversed(range(dt_r.shape[0])):
                d_states.append(flowing + c_r[row][:, None] * d_y_r[row][None, :])
                flowing = jnp.exp(dt_r[row][None, :] * a) * d_states[-1]
            d_states = jnp.stack(d_states[::-1])
            d_exponent = d_states * before * decay  # of dt_t (x) a
            entering = jnp.sum(d_states * b_r[:, :, None], axis=1)  # of dt_t * x_t
            grads = (
                entering * dt_r,
                jnp.sum(d_exponent * a[None], axis=1) + entering * x_r,
                jnp.sum(d_states * dtx[:, None, :], axis=2),
                jnp.sum(states * d_y_r[:, None, :], axis=2),
            )
            return (flowing, d_a + jnp.sum(d_exponent * dt_r[:, None, :], axis=0)), grads

        with jax.named_scope(SCAN_SCOPE):
            _, befores = jax.lax.scan(again, start, (x_c, dt_c, b_c))
            carry, grads = jax.lax.scan(back, carry, (x_c, dt_c, b_c, c_c, d_y_c, befores), reverse=True)
        return carry, tuple(g.reshape(like.shape) for g, like in zip(grads, rows))

    zero = jnp.zeros(a.shape, a.dtype)
    (_, d_a), (d_x, d_dt, d_b, d_c) = jax.lax.scan(
        chunk_of, (zero, zero), (x, dt, b, c, d_y, starts), reverse=True
    )
    return d_x, d_dt, d_a, d_b, d_c


_selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)


def scan_chunk_rows(length: int) -> int:
    """Rows of a chunk of the scan over a window of ``length`` rows:
    :data:`SCAN_CHUNK`, or the window where that is shorter, in whole
    trips of the chunk's loop."""
    trip = min(SCAN_UNROLL, SCAN_CHUNK, length)
    return -(-min(SCAN_CHUNK, length) // trip) * trip


def selective_scan(x, dt, a, b, c, d_skip):
    """The selective scan over each window of ``x``, ``dt [B, T, d]``,
    ``b``, ``c [B, T, N]`` under the rates ``a [d, N]`` (negative) and
    the skip ``d_skip [d]``: ``y [B, T, d]``, ``y_t = s_t c_t + d_skip *
    x_t`` (module docstring). In :data:`SCAN_DTYPE`, in chunks of
    :data:`SCAN_CHUNK` rows, a window after another."""
    length = x.shape[1]
    chunk = scan_chunk_rows(length)
    x, dt, b, c = (t.astype(SCAN_DTYPE) for t in (x, dt, b, c))
    rates = a.T.astype(SCAN_DTYPE)
    blocked = lambda t: _blocked(t, chunk)  # noqa: E731
    y = jax.lax.map(
        lambda one: _selective_scan(blocked(one[0]), blocked(one[1]), rates, blocked(one[2]), blocked(one[3])),
        (x, dt, b, c),
    )
    return y.reshape(x.shape[0], -1, x.shape[2])[:, :length] + d_skip.astype(SCAN_DTYPE) * x


def mamba(spec: BackboneSpec, w: Dict, u: jnp.ndarray, active: Optional[jnp.ndarray] = None):
    """A selective state-space layer over ``u [B, T, hidden]`` (module
    docstring): ``(output, y, scan_steps)``, ``y [B, T, ssm_inner]`` the
    scan output before the gate and with the skip term (what a ``gmu``
    reads), ``scan_steps`` float32 the rows of the windows that count
    (``active``; None: all)."""
    dtype = u.dtype
    batch, length, _ = u.shape
    d, n, rank = spec.ssm_inner, spec.ssm_state, spec.ssm_dt_rank
    with jax.named_scope(MAMBA_SCOPE):
        stream = u @ w["in_proj"].astype(dtype)
        xs, z = stream[..., :d], stream[..., d:]
        with jax.named_scope(SCAN_CONV_SCOPE):
            conv = jax.nn.silu(causal_conv(xs, w["conv_kernel"]) + w["conv_bias"].astype(dtype))
        # what sets the scan's step and its two matrices a row: two small
        # products at full precision in the scan's own dtype, as a
        # router's logits are (an error here is integrated over a window)
        project = lambda t, m: jnp.dot(  # noqa: E731
            t.astype(SCAN_DTYPE), m.astype(SCAN_DTYPE), precision=jax.lax.Precision.HIGHEST
        )
        row = project(conv, w["x_proj"])
        dt = jax.nn.softplus(project(row[..., :rank], w["dt_proj"]) + w["dt_bias"].astype(SCAN_DTYPE))
        y = selective_scan(
            conv, dt, -jnp.exp(w["A_log"]), row[..., rank : rank + n], row[..., rank + n :], w["D"]
        ).astype(dtype)
        out = (y * jax.nn.silu(z)) @ w["out_proj"].astype(dtype)
    windows = jnp.float32(batch) if active is None else jnp.sum(active.astype(jnp.float32))
    return out, y, windows * length


def gated_memory(w: Dict, u: jnp.ndarray, memory: jnp.ndarray) -> jnp.ndarray:
    """A gated memory unit: ``memory [B, T, ssm_inner]``, an earlier
    layer's scan output, gated by a projection of this layer's input."""
    dtype = u.dtype
    with jax.named_scope(MEMORY_SCOPE):
        return (memory * jax.nn.silu(u @ w["in_proj"].astype(dtype))) @ w["out_proj"].astype(dtype)


def differential_weight(w: Dict, index: int) -> Tuple[jnp.ndarray, float]:
    """``(lam, lam_0)`` of the differential heads of layer ``index``."""
    start = 0.8 - 0.6 * math.exp(-0.3 * index)
    first = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
    second = jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"]))
    return first - second + start, start


def differential_attention(
    spec: BackboneSpec, op: str, index: int, w: Dict, u: jnp.ndarray,
    active: Optional[jnp.ndarray] = None, kv=None,
):
    """Differential attention (module docstring) of operator ``op`` at
    layer ``index`` over ``u [B, T, hidden]``: ``(output, band, (k,
    v))``; ``band`` as :func:`banded_attention`'s counts (the pairs of
    the mask, once: both maps read the same mask), None where every
    score is held at once; ``(k, v)`` the keys and values as projected,
    for a later ``cross_attention``, which is handed an earlier layer's
    as ``kv`` and projects none."""
    dtype = u.dtype
    batch, length, _ = u.shape
    kv_heads, dh = spec.num_key_value_heads, spec.head_dim
    pairs = kv_heads // 2
    tiled = attends_in_tiles(op, length)
    scope = TILES_SCOPES[op] if tiled else ATTENTION_SCOPE
    with jax.named_scope(scope):
        q, k, v = _heads(spec, w, u, op, kv)
    with jax.named_scope(DIFFERENTIAL_SCOPE):
        # query head 2j + s is map s of differential head j = p * group +
        # g; key head 2p + s is map s of pair p, whose value is the two
        # heads' side by side
        group = q.shape[2] // kv_heads
        maps = q.reshape(batch, length, pairs, group, 2, dh)
        keys = k.reshape(batch, length, pairs, 2, dh)
        value = v.reshape(batch, length, pairs, 2 * dh)
    band = None
    if tiled:
        chunk = min(ATTENTION_TILE, length)
        window = min(spec.sliding_window, length) if op == "sliding_attention" else length
        blocked = jax.vmap(lambda a: _blocked(a, chunk))
        value = blocked(value)

        def attend(queries, keys):
            out = jax.lax.map(
                lambda one: _banded_attention(scope, window, *one), (blocked(queries), blocked(keys), value)
            )
            return out.reshape((batch, -1) + out.shape[3:])[:, :length]

        windows = jnp.float32(batch) if active is None else jnp.sum(active.astype(jnp.float32))
        attended, multiplied = band_pairs(length, window, chunk)
        band = (windows * attended, windows * multiplied)
    else:
        def attend(queries, keys):
            with jax.named_scope(scope):
                return _attend_in_one_piece(queries, keys, value)

    # a map a call: the two over stacked heads as one call ran its
    # backward twice as long on the chip (PERF.md 6, PR 45)
    first, second = (attend(maps[:, :, :, :, s], keys[:, :, :, s]) for s in (0, 1))
    with jax.named_scope(DIFFERENTIAL_SCOPE):
        lam, start = differential_weight(w, index)
        heads = rms_norm(first - lam.astype(dtype) * second, w["sub_norm"], spec.norm_eps) * (1.0 - start)
    with jax.named_scope(scope):
        out = heads.reshape(batch, length, -1) @ w["wo"].astype(dtype)
        if spec.attention_bias:
            out = out + w["bo"].astype(dtype)
    return out, band, (k, v)


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
    if spec.router == "softmax":
        picked, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), spec.num_experts_per_tok)
        return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)
    if spec.router == "softmax_of_chosen":
        picked, chosen = jax.lax.top_k(logits, spec.num_experts_per_tok)
        return chosen, jax.nn.softmax(picked, axis=-1)
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(w["expert_bias"])  # a buffer: chooses, takes no gradient
    _, chosen = jax.lax.top_k(scores + bias, spec.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return chosen, weights * spec.routed_scaling_factor


def routing_plan(
    spec: BackboneSpec, w: Dict, tokens: jnp.ndarray, rows: int, active: Optional[jnp.ndarray] = None
) -> Dict[str, jnp.ndarray]:
    """What a routed layer does with ``tokens [N, hidden]`` (windows of
    ``rows`` tokens) before any expert runs: the choice, the pairs sorted
    by held expert, the groups' sizes. ``token_of [N * k]`` (the token of
    each sorted pair), ``pair_weight [N * k]`` (its weight; 0 for a pair
    of an absent expert), ``group_sizes [experts_held]``, ``pairs_here``
    and ``routed [num_experts]`` (tokens to each published expert).
    ``active``: as :func:`moe_ffn`. The tensor the router reads need not
    be the experts' (``spec.router_input``)."""
    k, held, offset = spec.num_experts_per_tok, spec.experts_held, spec.expert_offset
    with jax.named_scope(ROUTE_SCOPE):
        chosen, weights = route(spec, w, tokens)
        flat = chosen.reshape(-1)  # pair p is (token p // k, expert flat[p])
        local = flat - offset
        is_local = (local >= 0) & (local < held)
        if active is None:
            routed = jnp.zeros((spec.num_experts,), jnp.int32).at[flat].add(1)
        else:
            pair_active = jnp.repeat(active, rows * k)
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
    return {
        "token_of": token_of, "pair_weight": pair_weight, "group_sizes": group_sizes,
        "pairs_here": pairs_here, "routed": routed,
    }


def moe_ffn(
    spec: BackboneSpec,
    w: Dict,
    u: jnp.ndarray,
    active: Optional[jnp.ndarray] = None,
    plan: Optional[Dict[str, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """The held experts' share of the routed layer over ``u [B, T,
    hidden]``: ``(output, tokens routed to each published expert
    [num_experts], pairs computed here, gate)``. ``active [B]`` (None:
    all): the windows that are tokens of this step; the others are
    padding, route nothing and get no expert's output. ``plan``: the
    :func:`routing_plan` of these tokens, made before from another
    tensor; None: made here from ``u``. ``gate``: under a ``relu`` gate
    float32 ``(gate_active, gate_total)``, the gate units of the pairs
    computed here that are above zero and all of them; else None."""
    dtype = u.dtype
    tokens = u.reshape(-1, u.shape[-1])
    k = spec.num_experts_per_tok
    if plan is None:
        plan = routing_plan(spec, w, tokens, u.shape[1], active)
    token_of, pair_weight, group_sizes = plan["token_of"], plan["pair_weight"], plan["group_sizes"]
    pairs_here = plan["pairs_here"]
    relu = spec.expert_activation == "relu"
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
        y = grouped((jax.nn.relu(h1) if relu else jax.nn.silu(h1)) * h3, w["w2"])
        y = jnp.where(valid, y, 0.0) * pair_weight[:, None].astype(dtype)
        out = jnp.zeros_like(tokens).at[token_of].add(y)
        gate = None
        if relu:  # the zeroed rows of no group are not above zero
            gate = (
                jnp.sum(jax.lax.stop_gradient(h1) > 0).astype(jnp.float32),
                pairs_here.astype(jnp.float32) * h1.shape[1],
            )
    return out.reshape(u.shape), plan["routed"], pairs_here, gate


def block(
    spec: BackboneSpec, op: str, ffn: str, w: Dict, h: jnp.ndarray, active=None, index: int = 0, read=None,
):
    """One pre-norm residual block, ``h = x + Op(Norm(x))``, ``out = h +
    FFN(Norm(h))`` under ``spec.norm``'s norm, at layer ``index``;
    returns ``(h, counts, selection, band, scan_steps, made)`` with
    ``counts = (routed, pairs_here, gate)`` of a routed block
    (:func:`moe_ffn`'s), ``selection = (objective, (keys_selected,
    keys_causal))`` of a ``sparse_attention`` block, ``band =
    (pairs_attended, pairs_multiplied)`` of an attention computed in
    tiles under a mask by position and ``scan_steps`` of a ``mamba``
    block, else None. ``read``: what the layer reads of an earlier one
    (``spec.layer_sources``): a ``gmu`` that layer's scan output, a
    ``cross_attention`` its ``(k, v)``. ``made``: what this layer could
    hand on in turn, a ``mamba``'s scan output or a differential
    attention's ``(k, v)``, else None. ``active``: as :func:`moe_ffn`.
    Where the router reads the layer's input (``spec.router_input``)
    the routing plan is made first, from ``h`` as it comes in: nothing
    the operator computes enters it."""
    plan = None
    if ffn == "moe" and spec.router_input == "layer_input":
        plan = routing_plan(spec, w["moe"], h.reshape(-1, h.shape[-1]), h.shape[1], active)
    normed = block_norm(spec, h, w["operator_norm"])
    selection = band = steps = made = None
    if op == "conv":
        out = short_conv(spec, w["conv"], normed)
    elif op == "mamba":
        out, made, steps = mamba(spec, w["mamba"], normed, active)
    elif op == "gmu":
        out = gated_memory(w["gmu"], normed, read)
    elif spec.differential:
        out, band, made = differential_attention(spec, op, index, w["attn"], normed, active, read)
    elif op == "sparse_attention":
        out, objective, keys = sparse_attention(spec, w["attn"], w["indexer"], normed, active)
        selection = (objective, keys)
    elif not attends_in_tiles(op, h.shape[1]):
        out = gqa_attention(spec, w["attn"], normed)
    else:
        out, band = banded_attention(spec, op, w["attn"], normed, active)
    h = h + out
    normed = block_norm(spec, h, w["ffn_norm"])
    if ffn == "dense":
        return h + dense_ffn(w["ffn"], normed), None, selection, band, steps, made
    out, *counts = moe_ffn(spec, w["moe"], normed, active, plan)
    if "shared" in w["moe"]:
        with jax.named_scope(SHARED_SCOPE):
            out = out + dense_ffn(w["moe"]["shared"], normed)
    return h + out, tuple(counts), selection, band, steps, made


def _param_bytes(params: Dict) -> int:
    return sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(params))


@lru_cache(maxsize=8)
def _spec_param_bytes(spec: BackboneSpec) -> int:
    """:func:`_param_bytes` of what :func:`init_backbone` makes for
    ``spec``, from the shapes alone: nothing runs on a device (tens of
    milliseconds of tracing at the published widths, once a spec: a
    job's fit spans ask four times)."""
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)  # a raw key's shape: making one is a device program
    return _param_bytes(jax.eval_shape(partial(init_backbone, spec=spec), key))


def attends_in_tiles(op: str, length: int) -> bool:
    """Whether a layer of operator ``op`` over windows of ``length``
    rows attends in tile loops (:func:`_selected_attention`,
    :func:`_banded_attention`): a selection and a sliding window always,
    any other attention where the window is longer than a tile (up to
    :data:`ATTENTION_TILE` rows every score is held at once)."""
    if op in ("sparse_attention", "sliding_attention"):
        return True
    return op in TILES_SCOPES and length > ATTENTION_TILE


def keeps_tile_outputs(spec: BackboneSpec, op: str, length: int) -> bool:
    """Whether a rematerialised layer of operator ``op`` over windows of
    ``length`` rows keeps :data:`SAVED_TILES`: every layer that attends
    in tiles but a sliding one whose band is no wider than a tile. A
    block of such a layer's queries visits two tiles, so its second
    forward is short where its output is as large as any layer's (0.43 s
    a GB kept in ``laguna_swa_build`` against 0.9-2.5 for a band of nine
    to sixteen tiles), and with its three such layers kept that cell's
    fit is 17.10 GiB by the compiler's count for a v5e, of 15.75
    (PERF.md 6, PR 47)."""
    if op == "sliding_attention" and min(spec.sliding_window, length) <= ATTENTION_TILE:
        return False
    return attends_in_tiles(op, length)


def tile_outputs_kept(spec: BackboneSpec, remat: Optional[bool] = None) -> int:
    """The layers of a fit over ``spec``'s windows whose tile loops'
    output and normalisers the backward pass is handed by name
    (:func:`keeps_tile_outputs`) and does not compute again; 0 where the
    program rematerialises nothing (nothing is computed twice there) or
    no layer runs a tile loop. ``remat``: as
    :func:`forward_backbone_aux`'s, None decided from the bytes of the
    spec's parameters."""
    if remat is None:
        remat = _spec_param_bytes(spec) >= REMAT_MIN_PARAM_BYTES
    return sum(keeps_tile_outputs(spec, op, spec.lookback_window) for op in spec.layer_ops) if remat else 0


def forward_backbone_aux(
    spec: BackboneSpec,
    params: Dict,
    x: jnp.ndarray,
    remat: Optional[bool] = None,
    active: Optional[jnp.ndarray] = None,
):
    """
    Windows ``x [batch, lookback, n_features]`` -> ``(output [batch,
    n_features_out], penalty, aux)``; ``aux`` holds, a row per expert
    layer, ``router_tokens [layers, num_experts]`` (tokens routed to
    each published expert), ``pairs_here [layers]`` (pairs whose expert
    is held here) and ``pairs_total [layers]`` (tokens x k); and, a row
    per ``sparse_attention`` layer, float32 ``keys_selected`` and
    ``keys_causal`` (query-key pairs kept and possible) and
    ``indexer_kl`` (the layer's term of the indexer's objective); and,
    a row per attention layer computed in tiles under a mask by
    position, float32 ``pairs_attended`` and ``pairs_multiplied``
    (query-key pairs inside the mask and of the tiles visited); and, a
    row per expert layer whose gate is a ``relu``, float32
    ``gate_active`` and ``gate_total`` (the gate units of the pairs
    computed here that are above zero, and all of them); and, a row per
    ``mamba`` layer, float32 ``scan_steps`` (rows its scan stepped
    over). Without a routed layer the router's three are absent.
    ``penalty`` is the sum of those terms, 0 without such a layer.

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
    routed_rows, pairs_rows, gates, selections, bands, scans = [], [], [], [], [], []
    # what a layer hands the layers that read it, beside the residual:
    # through each block's checkpoint as an argument and a result
    handed: Dict[int, Any] = {}
    for i, (op, ffn) in enumerate(zip(spec.layer_ops, spec.layer_ffns)):
        # its place among the layers to a differential attention alone
        # (``lam_0``), ``read`` to a layer that reads an earlier one's
        place = {"index": i} if spec.differential else {}
        read = {} if spec.layer_sources[i] is None else {"read": handed[spec.layer_sources[i]]}
        run = lambda w, h, a, read, _op=op, _ffn=ffn, _place=place: block(spec, _op, _ffn, w, h, a, **_place, **read)  # noqa: E731
        if remat:
            saved = SAVED_PRODUCTS + {"sparse_attention": (SAVED_SELECTION,), "mamba": (SAVED_SCAN,)}.get(op, ())
            if keeps_tile_outputs(spec, op, x.shape[1]):
                saved += (SAVED_TILES,)
            run = jax.checkpoint(
                run, policy=jax.checkpoint_policies.save_only_these_names(*saved)
            )
        with jax.named_scope(f"layer_{i}"):  # one scope a layer, as in params
            h, counts, selection, band, steps, made = run(params[f"layer_{i}"], h, active, read)
        if i in spec.layer_sources:
            handed[i] = made
        if counts is not None:
            routed_rows.append(counts[0])
            pairs_rows.append(counts[1])
            if counts[2] is not None:
                gates.append(counts[2])
        if selection is not None:
            selections.append(selection)
        if band is not None:
            bands.append(band)
        if steps is not None:
            scans.append(steps)
    last = block_norm(spec, h[:, -1], params["head"]["norm"])
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
    if gates:
        aux["gate_active"] = jnp.stack([active_units for active_units, _ in gates])
        aux["gate_total"] = jnp.stack([units for _, units in gates])
    penalty = jnp.zeros((), jnp.float32)
    if selections:
        objectives = jnp.stack([objective for objective, _ in selections])
        penalty = jnp.sum(objectives)
        aux = {
            **(aux or {}),
            "keys_selected": jnp.stack([keys[0] for _, keys in selections]),
            "keys_causal": jnp.stack([keys[1] for _, keys in selections]),
            "indexer_kl": objectives,
        }
    if bands:
        aux = {
            **(aux or {}),
            "pairs_attended": jnp.stack([attended for attended, _ in bands]),
            "pairs_multiplied": jnp.stack([multiplied for _, multiplied in bands]),
        }
    if scans:
        aux = {**(aux or {}), "scan_steps": jnp.stack(scans)}
    return out.astype(jnp.float32), penalty, aux


def forward_backbone(spec: BackboneSpec, params: Dict, x: jnp.ndarray):
    """``(output, penalty)``: :func:`forward_backbone_aux` without its
    counters, the signature every spec's forward has."""
    out, penalty, _ = forward_backbone_aux(spec, params, x)
    return out, penalty
