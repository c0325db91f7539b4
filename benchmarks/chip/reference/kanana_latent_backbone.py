"""
Plain float32 reference of the kanana-2-30b-a3b-instruct-2601 sensor
backbone (kakaocorp/kanana-2-30b-a3b-instruct-2601, ``model_type:
deepseek_v3``: latent attention in every layer, a 512-wide key/value
latent and one rotary key shared by 32 heads, scores 192 wide and values
128 wide; a leading dense layer; then 128 routed experts of 768 under a
scaled sigmoid router whose bias buffer chooses, 6 a token, beside two
shared experts built as one feed-forward of twice the width): the
forward pass, the loss and its gradients, in straightforward
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, the
equations as ISSUE 41 writes them out:

    u            = RMSNorm(x; g_1)
    q            = u W_q                  a head [q_nope 128 | q_rope 64]
    [c | r]      = u W_kva                the latent 512 and ONE rotary key 64
    c            = RMSNorm(c; g_kv)       r is not normed
    [k_nope | v] = c W_kvb                a head [128 | 128]
    q_rope, r   <- rotary, theta 1e6, dims (2i, 2i + 1) turning together
    k            = [k_nope | r]           r broadcast over the heads
    a            = softmax(q k^T * 192 ** -0.5 + causal) v
    h            = x + a W_o

The key is built by broadcasting ``r`` over the heads; the mask is built
from positions (``s <= t``) over every key of the window: no tile is
skipped, no running softmax, no grouped product, no sort. The rotary
embedding is written out from ``cos`` and ``sin`` on the interleaved
pairs, in place. The router is one plain product, the six largest of
``s + b`` are found by counting, for each expert, the experts that beat
it. The experts are a loop with a mask over the share held; the shared
expert is computed once. Nothing here comes from ``gordo_tpu``: the
artifact's weights and the spec's sizes are read off the estimator as
plain arrays and numbers; the helpers that have nothing of this model in
them (norm, the gated feed-forward, the windows, a piece of masked
attention, the readings) are the sibling references', loaded by path.

What ``configs/kanana-2-30b-a3b-50tag-lb8192.json`` lists under
``assumed`` is marked "assumed" where it enters here.

Two departures from "plain" that are none from the mathematics, as the
sibling ``laguna_banded_backbone`` has them: the attention runs
:data:`QUERY_ROWS` queries at a time against every key of the window
(an 8,192-row window's ``[32, T, T]`` scores are 8.6 GB a layer), each
such piece and each layer under ``jax.checkpoint``; and consecutive
layers of one kind and shape (the cut's four routed layers) are one loop
of one body over their weights laid side by side, for the machine's
compile cache of 192 MiB. The jitted functions take their batch as
arguments: a run's data are its seed's, and a program that held them
would be another to compile in every run.

What ``harness/correct.py`` holds a build to with it: the artifact's
prediction against :func:`forward` of the artifact's own weights, and
one training step at those weights (:func:`loss_band`, the hook the
harness has; ``lfm2_moe_backbone``'s docstring says why a step and why
through that hook): outputs, loss and every leaf's gradient norm of
:data:`STEP_WINDOWS` whole window against :func:`loss_and_grads` at
"highest" on the device the process holds, under :data:`STEP_LIMITS`.

Loaded by the child that is about to build the configuration
(``procs/build_worker.py``) in a checkout whose program has no
``kind: kanana`` (every commit before PR 41), this module ends that
child at once with exit code 5, as the siblings do, and for the reason
written in ``lfm2_moe_backbone``.
"""

import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))
_MAIN = os.path.basename(getattr(sys.modules.get("__main__"), "__file__", "") or "")


def _program_has_the_kind() -> bool:
    try:
        with open(os.path.join(_CHECKOUT, "gordo_tpu", "models", "factories", "backbone.py")) as f:
            return "def kanana(" in f.read()
    except OSError:
        return False


if _MAIN == "build_worker.py" and not _program_has_the_kind():
    print(
        "chipbench: this checkout's program has no kind kanana "
        "(gordo_tpu/models/factories/backbone.py): it cannot build this configuration",
        file=sys.stderr,
    )
    sys.exit(5)


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_reference_{name}", os.path.join(_HERE, f"{name}.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_shared = _sibling("lfm2_moe_backbone")
rms_norm, dense_ffn = _shared.rms_norm, _shared.dense_ffn
model_input, HIGHEST = _shared.model_input, _shared.HIGHEST
#: a piece of queries against every key under a mask from positions (its
#: scores are as wide as ``q`` and scaled by that width, its values as
#: wide as ``v``), and the layers in order as runs of like ones
_banded = _sibling("laguna_banded_backbone")
attend, like_layers = _banded.attend, _banded.like_layers
#: the leaves :func:`step_readings` reads over the sibling's floor (a
#: hundredth of the whole gradient's norm): what reaches a softmax that
#: smooth rows leave nearly flat (``wq``; ``wkv_a``, whose last columns
#: are the shared key), and a router
FLOORED_LEAVES = ("['wq']", "['wkv_a']", "['router']")

#: what of the spec the forward needs, read by name
SIZES = (
    "layer_ops", "layer_ffns", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rope_theta", "num_experts", "experts_held", "expert_offset",
    "num_experts_per_tok", "routed_scaling_factor", "norm_eps", "lookback_window",
)

#: queries a piece of the attention (module docstring)
QUERY_ROWS = 512

#: the step check: one whole window of the machine's history (8,192
#: tokens at the published lookback: every layer's far keys act only in
#: a window longer than a tile)
STEP_WINDOWS = 1
#: limits of the step check, each between two readings on the v5e
#: (PERF.md, section 6, PR 41, has every one): the largest of seven
#: sound builds of seven seeds, and the smallest of two builds with
#: ``compute_dtype: bfloat16``, the nearest precision below, which the
#: cell has to read as not correct (both do, by each of the four). Each
#: limit stands near the geometric mean of its two readings.
#: ``output``: the window's outputs, max |program - reference| over
#: max(1, max |reference|): sound 3.2e-5 to 6.1e-5, bfloat16 2.7e-3 and
#: 3.8e-3.
#: ``leaf``: the worst gradient norm, of the reference's, as
#: :func:`step_readings` reads it (most often the shared expert's
#: ``w3`` in a late layer, once layer 0's ``wo``): sound 4.7e-4 to
#: 3.1e-3, bfloat16 1.5e-1 (layer 0's ``wkv_b``) and 1.7e-1 (layer 0's
#: ``wo``).
#: ``loss``: |program - reference| of the reference's: sound 1.2e-7 to
#: 6.0e-6, bfloat16 7.3e-4 and 2.3e-3.
#: ``grad_norm``: the whole gradient's norm, likewise: sound 1.4e-7 to
#: 3.6e-6, bfloat16 2.0e-4 and 3.9e-4.
STEP_LIMITS = {"output": 4e-4, "leaf": 2e-2, "loss": 7e-5, "grad_norm": 3e-5}

_LAST: Dict[str, Any] = {}


def layers_of(estimator: Any) -> Dict[str, Any]:
    """The artifact's own weights as float32 arrays, with the sizes of
    its spec: ``{"weights": <the parameter tree>, "sizes": {...}}``."""
    spec = estimator.spec_
    weights = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float32), estimator.params_)
    layers = {"weights": weights, "sizes": {key: getattr(spec, key) for key in SIZES}}
    _LAST.update(estimator=estimator, layers=layers)
    return layers


def interleaved_rotary(x, theta: float):
    """``x [B, T, heads, d]`` at positions 0..T-1, ``rope_interleave``:
    dimensions ``(2i, 2i + 1)`` turn together by ``t * theta ** (-2i /
    d)``, written out from ``cos`` and ``sin``, each pair where it was."""
    width = x.shape[-1]
    inverse = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    first, second = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([first * cos - second * sin, second * cos + first * sin], axis=-1)
    return turned.reshape(x.shape)


def attention(u, w, sizes, query_rows: int = QUERY_ROWS):
    """``u [B, T, H]`` -> ``(output [B, T, H], pairs attended [B])``."""
    batch, length, _ = u.shape
    heads, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, theta = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["rope_theta"]
    # q_lora_rank null: one projection, no norm; assumed: no bias, no q/k norm
    q = (u @ w["wq"]).reshape(batch, length, heads, nope + rope)
    down = u @ w["wkv_a"]  # kv_a_proj_with_mqa: the latent and ONE rotary key
    # assumed (deepseek_v3): kv_a_layernorm over the latent alone, the rotary key as projected
    latent, shared_key = rms_norm(down[..., :rank], w["kv_norm"], sizes["norm_eps"]), down[..., rank:]
    up = (latent @ w["wkv_b"]).reshape(batch, length, heads, -1)  # kv_b_proj: a head's [k_nope | v]
    k_nope, v = up[..., :nope], up[..., nope:]
    # assumed: rotary on the trailing rope dims of q and on the shared key
    # alone, interleaved pairs, theta as published, rope_scaling null
    q = jnp.concatenate([q[..., :nope], interleaved_rotary(q[..., nope:], theta)], axis=-1)
    shared_key = interleaved_rotary(shared_key[:, :, None, :], theta)  # one head ...
    k = jnp.concatenate([k_nope, jnp.broadcast_to(shared_key, k_nope.shape[:3] + (rope,))], axis=-1)  # ... for all
    query_rows = min(query_rows, length)
    pieces = -(-length // query_rows)
    padded = jnp.pad(q, ((0, 0), (0, pieces * query_rows - length), (0, 0), (0, 0)))
    in_pieces = jnp.moveaxis(padded.reshape(batch, pieces, query_rows, heads, nope + rope), 1, 0)
    # the scale is (nope + rope) ** -0.5, the width of q: no mscale (rope_scaling null)
    piece = jax.checkpoint(lambda one: attend(one[0], k, v, one[1], length))
    out, pairs = jax.lax.map(piece, (in_pieces, jnp.arange(pieces) * query_rows))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, pieces * query_rows, heads, -1)[:, :length]
    return out.reshape(batch, length, -1) @ w["wo"], jnp.sum(pairs, axis=0)


def router_gates(n, w, sizes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``n [B, T, H]``, the normed tensor the experts read -> ``(gates,
    chosen) [B, T, experts]``: each chosen expert's weight, 0 for the
    others, and which are chosen."""
    scores = jax.nn.sigmoid(n @ w["router"])
    # noaux_tc: the bias buffer chooses and does not weigh; n_group 1,
    # topk_group 1: no group limit. Brute force: an expert is chosen if
    # fewer than k experts beat it (a larger biased score, or an equal
    # one of a lower index)
    biased = scores + w["expert_bias"]
    index = jnp.arange(biased.shape[-1])
    beats = (biased[..., None, :] > biased[..., :, None]) | (
        (biased[..., None, :] == biased[..., :, None]) & (index[None, :] < index[:, None])
    )
    chosen = jnp.sum(beats, axis=-1) < sizes["num_experts_per_tok"]
    picked = jnp.where(chosen, scores, 0.0)
    # departure from deepseek_v3, which adds 1e-20: the program's
    # sigmoid router adds 1e-6 (as laguna's; a sum of six scores)
    gates = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return gates * sizes["routed_scaling_factor"], chosen


def moe_ffn(n, w, sizes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The shared expert, and the share of the routed experts' sum that
    the experts held give; and the tokens routed to each of the
    published experts."""
    gates, chosen = router_gates(n, w, sizes)
    counts = jnp.sum(chosen, axis=(0, 1)).astype(jnp.int32)
    # departure from the published model: only the experts held here add
    # to the result; what the absent experts would add lies on other chips
    first = sizes["expert_offset"]
    held_gates = jnp.moveaxis(gates[..., first : first + sizes["experts_held"]], -1, 0)

    def add_expert(out, expert):  # one expert after another: one loop of one body
        w1, w3, w2, expert_gate = expert
        hidden = jax.nn.silu(n @ w1) * (n @ w3)
        return out + expert_gate[..., None] * (hidden @ w2), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(n), (w["w1"], w["w3"], w["w2"], held_gates))
    # assumed (deepseek_v3): n_shared_experts 2 is one feed-forward of twice the width
    return dense_ffn(n, w["shared"]) + routed, counts


def block(h, w, ffn: str, sizes):
    """One layer; returns ``(h, pairs attended [B], router counts or None)``."""
    out, pairs = attention(rms_norm(h, w["operator_norm"], sizes["norm_eps"]), w["attn"], sizes)
    h = h + out
    normed = rms_norm(h, w["ffn_norm"], sizes["norm_eps"])
    if ffn == "dense":
        return h + dense_ffn(normed, w["ffn"]), pairs, None
    out, counts = moe_ffn(normed, w["moe"], sizes)
    return h + out, pairs, counts


def _forward(weights, sizes, windows):
    # departure from the published model: a linear projection of sensor
    # rows stands where the token embedding stood (vocab_size replaced)
    h = windows @ weights["embed"]["W"] + weights["embed"]["b"]
    attended, routed = [], []
    for _, ffn, names in like_layers(weights, sizes):
        # each layer under ``jax.checkpoint``; a run of like layers as
        # one loop of one body over their weights laid side by side
        layer = jax.checkpoint(lambda h, w, _ffn=ffn: block(h, w, _ffn, sizes))

        def one_more(h, w, _layer=layer):
            h, pairs, counts = _layer(h, w)
            return h, (pairs, counts)

        side_by_side = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *[weights[name] for name in names]
        )
        h, (pairs, counts) = jax.lax.scan(one_more, h, side_by_side)
        attended.extend(pairs)
        if counts is not None:
            routed.extend(counts)
    # departure: the final norm and a linear head to the tags, read at
    # the window's last position, stand where the LM head stood
    last = rms_norm(h[:, -1], weights["head"]["norm"], sizes["norm_eps"])
    out = last @ weights["head"]["W"] + weights["head"]["b"]
    return out, {"attended": jnp.stack(attended), "routed": jnp.stack(routed) if routed else None}


def forward(layers: Dict[str, Any], windows: np.ndarray, block_windows: int = 1) -> np.ndarray:
    """``windows [n, lookback, tags]`` -> ``[n, tags]``, computed in
    blocks of ``block_windows`` so that the published widths fit."""
    weights, sizes = layers["weights"], layers["sizes"]
    windows = np.asarray(windows, np.float32)
    outs = []
    with jax.default_matmul_precision(HIGHEST):
        run = jax.jit(lambda tree, block: _forward(tree, sizes, block)[0])
        for start in range(0, len(windows), block_windows):
            outs.append(np.asarray(run(weights, jnp.asarray(windows[start : start + block_windows])), np.float32))
    if not outs:
        return np.zeros((0, weights["head"]["W"].shape[1]), np.float32)
    return np.concatenate(outs)


def counters(layers: Dict[str, Any], windows: np.ndarray) -> Dict[str, np.ndarray]:
    """Of one batch: ``routed [expert layers, experts]`` (tokens to each
    published expert) and ``attended [layers]`` (query-key pairs inside
    the mask)."""
    with jax.default_matmul_precision(HIGHEST):
        _, found = _forward(layers["weights"], layers["sizes"], jnp.asarray(windows, jnp.float32))
    return {
        "routed": np.asarray(found["routed"]),
        "attended": np.asarray(found["attended"]).sum(axis=1),
    }


def loss_and_grads(layers: Dict[str, Any], windows, targets, weights=None):
    """The weighted mean squared error of a batch and its gradient with
    respect to every weight (the expert bias is a buffer: its gradient is
    zero by construction; assumed: no load-balancing term acts)."""
    return _loss_grads_outputs(layers, windows, targets, weights)[:2]


def _loss_grads_outputs(layers: Dict[str, Any], windows, targets, weights=None):
    windows = jnp.asarray(windows, jnp.float32)
    targets = jnp.asarray(targets, jnp.float32)
    w = jnp.ones(len(windows), jnp.float32) if weights is None else jnp.asarray(weights, jnp.float32)

    # the batch is an argument and no constant of the program (module docstring)
    def loss_of(tree, windows, targets, w):
        out, _ = _forward(_banded._frozen_bias(tree), layers["sizes"], windows)
        per_sample = jnp.mean((out - targets) ** 2, axis=-1)
        return jnp.sum(per_sample * w) / jnp.sum(w), out

    with jax.default_matmul_precision(HIGHEST):
        (loss, out), grads = jax.jit(jax.value_and_grad(loss_of, has_aux=True))(
            jax.tree_util.tree_map(jnp.asarray, layers["weights"]), windows, targets, w
        )
    return float(loss), jax.tree_util.tree_map(np.asarray, grads), np.asarray(out)


def blockwise_step(
    layers: Dict[str, Any], windows: np.ndarray, targets: np.ndarray, block: int
) -> Tuple[float, Any, np.ndarray]:
    """:func:`loss_and_grads` of one batch computed ``block`` windows at
    a time: the batch's loss, the norm of its gradient for each weight (a
    tree of floats), and the batch's outputs."""
    loss, grads, outputs = 0.0, None, []
    for start in range(0, len(windows), block):
        share = len(windows[start : start + block]) / len(windows)
        block_loss, block_grads, block_out = _loss_grads_outputs(
            layers, windows[start : start + block], targets[start : start + block]
        )
        loss += share * block_loss
        outputs.append(block_out)
        if share == 1.0:  # one block: the gradient as it is, no second copy of 2.04 GB
            grads = block_grads
        else:
            scaled = jax.tree_util.tree_map(lambda g: share * g, block_grads)
            grads = scaled if grads is None else jax.tree_util.tree_map(np.add, grads, scaled)
    norms = jax.tree_util.tree_map(
        lambda g: float(np.sqrt(np.sum(np.square(g, dtype=np.float64)))), grads
    )
    return loss, norms, np.concatenate(outputs)


def step_readings(loss: float, norms: Any, ref_loss: float, ref_norms: Any) -> Dict[str, Any]:
    """The sibling ``lfm2_moe_backbone``'s readings, with ``leaf`` read
    otherwise for :data:`FLOORED_LEAVES`, as the siblings
    ``laguna_banded_backbone`` (``wq``, ``wk``) and
    ``smallthinker_prerouted_backbone`` (a router) read theirs and for
    the reasons written there: ``|program ** 2 - reference ** 2| / (2
    (reference ** 2 + floor ** 2))``, ``floor`` a hundredth of the whole
    gradient's norm. Well above the floor that is the norm's relative
    error, as for every other leaf; below it what the leaf adds to the
    whole's square. ``router_leaf``, the worst router's error as a
    fraction of its own norm, is printed beside the readings and held to
    nothing."""
    readings = _shared.step_readings(loss, norms, ref_loss, ref_norms)
    whole = readings["reference"]["grad_norm"]
    worst, worst_router = (0.0, ""), 0.0
    for (path, value), ref in zip(
        jax.tree_util.tree_flatten_with_path(norms)[0], jax.tree_util.tree_leaves(ref_norms)
    ):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router']") and ref > 0.0:
            worst_router = max(worst_router, abs(value - ref) / ref)
        if name.endswith(FLOORED_LEAVES):
            off = abs(value * value - ref * ref) / (2.0 * (ref * ref + (_banded.QK_FLOOR * whole) ** 2))
        elif ref > 1e-3 * whole:  # the siblings' "a leaf that carries gradient"
            off = abs(value - ref) / ref
        else:
            continue
        worst = max(worst, (off, name))
    readings["leaf"], readings["worst_leaf"] = worst
    readings["router_leaf"] = worst_router
    return readings


def loss_band(
    X_scaled: np.ndarray, y: np.ndarray, config: Dict[str, Any],
    limits: Optional[Dict[str, float]] = None,
) -> Tuple[float, float]:
    """The step check (module docstring): the first :data:`STEP_WINDOWS`
    window of the machine's history and the row it predicts, as one
    batch at the artifact's weights, through the program's own training
    loss and through :func:`loss_and_grads`. Returns the band the
    artifact's final training loss must lie in: every finite loss where
    each reading is within its limit, none (``nan, nan``) where one is
    not. The readings go to the child's output as one line,
    ``chipbench step check: {...}``."""
    estimator, layers = _LAST["estimator"], _LAST["layers"]
    lookback = int(config["lookback_window"])
    rows = lookback + STEP_WINDOWS
    X = np.asarray(X_scaled[:rows], np.float32)
    targets = np.asarray(y[lookback:rows], np.float32)  # lookahead 1
    loss, norms = estimator.training_loss_and_grad_norms(X, np.asarray(y[:rows], np.float32))
    outputs = np.asarray(estimator.predict(X), np.float64)
    # the reference's side on the device the process holds, at "highest",
    # compiled as one program (the sibling says why)
    ref_loss, ref_norms, ref_outputs = blockwise_step(
        layers, model_input(estimator, X), targets, block=1
    )
    readings = step_readings(loss, norms, ref_loss, ref_norms)
    readings["output"] = float(np.max(np.abs(outputs - ref_outputs))) / max(
        1.0, float(np.max(np.abs(ref_outputs)))
    )
    limits = STEP_LIMITS if limits is None else limits
    over = [key for key, limit in limits.items() if not readings[key] <= limit]
    print(
        "chipbench step check: "
        + json.dumps({
            **readings, "limits": limits, "windows": STEP_WINDOWS, "over": over,
            "reference_on": str(jax.devices()[0]),
        }),
        flush=True,
    )
    return (math.nan, math.nan) if over else (0.0, sys.float_info.max)
