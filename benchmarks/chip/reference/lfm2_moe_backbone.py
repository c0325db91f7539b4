"""
Plain float32 reference of the LFM2-MoE sensor backbone (LiquidAI
LFM2-8B-A1B, ``model_type: lfm2_moe``; HF ``modeling_lfm2_moe``): the
forward pass, the loss and its gradients, in straightforward
``jax.numpy`` under ``jax.default_matmul_precision("highest")``. No
kernel, no sort, no grouped product, no rematerialisation; experts are a
loop with a mask. Nothing here comes from ``gordo_tpu``: the artifact's
weights and the spec's sizes are read off the estimator as plain arrays
and numbers.

What ``harness/correct.py`` holds a build to with it:

- the artifact's prediction against :func:`forward` of the artifact's
  own weights (``check_artifact_forward``, the harness's tolerance);
- one training step (:func:`loss_band`): a reference fit of 474 M
  weights fits no run, so there is no band of fitted losses. Instead the
  work the cell times is held at the artifact's weights: the program's
  own outputs, loss and gradient norms of one small batch
  (``estimator.predict`` and ``estimator.training_loss_and_grad_norms``,
  the function its fit program differentiates) against
  :func:`loss_and_grads` here, computed on the host's CPU, under
  :data:`STEP_LIMITS`. ``loss_band`` is the one hook through which
  ``correct.py`` takes a limit that a reference brings
  (``check_loss_band``: the final training loss must lie in what it
  returns), so the step check answers through it: every finite loss
  where the step holds, no loss at all where it does not. PERF.md,
  section 7, says what a hook of its own would read better.

Loaded by the child that is about to build the configuration
(``procs/build_worker.py``) in a checkout whose program has no backbone
(``gordo_tpu/models/backbone.py``: every commit before PR 27), this
module ends that child at once with exit code 5, which ``run.py``
reports as its exit code 3 with the child's last lines. ``build-fleet``
there refuses every machine in a second ("Could not locate path
gordo_tpu.models.JaxBackboneForecast"), the worker has no rule that
stops on a failed warm-up job, and it would hold the chip for a window
of failed jobs and print a result of none built: the driver takes a
parent that cannot run a new configuration by its exit code, soon, not
by a result. Anywhere else (``run.py``'s own checks, the tests) it
loads.
"""

import json
import math
import os
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
_MAIN = os.path.basename(getattr(sys.modules.get("__main__"), "__file__", "") or "")
if _MAIN == "build_worker.py" and not os.path.isfile(
    os.path.join(_CHECKOUT, "gordo_tpu", "models", "backbone.py")
):
    print(
        "chipbench: this checkout's program has no gordo_tpu/models/backbone.py: "
        "it cannot build a backbone configuration",
        file=sys.stderr,
    )
    sys.exit(5)

HIGHEST = "highest"

#: what of the spec the forward needs, read by name
SIZES = (
    "layer_ops", "layer_ffns", "num_attention_heads", "num_key_value_heads",
    "conv_L_cache", "num_experts", "experts_held", "expert_offset",
    "num_experts_per_tok", "routed_scaling_factor", "rope_theta", "norm_eps",
    "lookback_window",
)


#: the step check: windows of the machine's own history taken as one
#: batch (2,048 tokens at the published lookback: every layer and every
#: router gets gradient; the reference's half-minute on the host's cores
#: is what sizes it)
STEP_WINDOWS = 4
#: limits of the step check (PERF.md, section 6, has every reading).
#: ``output``: the batch's outputs, max |program - reference| over
#: max(1, max |reference|), as the harness compares a forward. It lies
#: between two readings on the v5e: the largest a sound build gave over
#: its seeds (4.0e-4), and the smallest the program gave with
#: ``compute_dtype: bfloat16``, the nearest precision below the
#: configuration's float32 activations (2.1e-3).
#: ``leaf``: the worst parameter leaf's gradient norm, of the reference's,
#: among the leaves that carry gradient. It is there for a gradient at
#: fault (this PR met one of 2.4e5 x whose loss agreed to 1.5e-4; a toy's
#: doubled cotangent reads 1.0), not for the precision, which moves it
#: too little to tell from seeds (sound to 1.6e-3, bfloat16 from 4.0e-3):
#: its limit lies above both. The loss and the whole gradient's norm are
#: read and printed and not limited, for the same reason (seeds to 2.7e-4
#: and 4.8e-4, bfloat16 from 5.3e-4 and 3.3e-4): a loss at fault is an
#: output or a gradient at fault.
STEP_LIMITS = {"output": 9e-4, "leaf": 7e-3}

#: the estimator whose layers the harness asked for last, and those
#: layers: ``loss_band`` is given a machine's data but no artifact, and
#: the step it checks is the same program at any built weights
_LAST: Dict[str, Any] = {}


def layers_of(estimator: Any) -> Dict[str, Any]:
    """The artifact's own weights as float32 arrays, with the sizes of
    its spec: ``{"weights": <the parameter tree>, "sizes": {...}}``."""
    spec = estimator.spec_
    weights = jax.tree_util.tree_map(
        lambda leaf: np.asarray(leaf, np.float32), estimator.params_
    )
    layers = {"weights": weights, "sizes": {key: getattr(spec, key) for key in SIZES}}
    _LAST.update(estimator=estimator, layers=layers)
    return layers


def model_input(estimator: Any, X_scaled: np.ndarray) -> np.ndarray:
    """Every window of ``lookback`` consecutive scaled rows, lookahead 1
    (the reference's ``KerasLSTMForecast`` semantics): window ``j``
    covers rows ``j .. j+lookback-1`` and predicts row ``j+lookback``."""
    X = np.asarray(X_scaled, np.float32)
    lookback = int(estimator.spec_.lookback_window)
    index = np.arange(len(X) - lookback)[:, None] + np.arange(lookback)[None, :]
    return X[index]


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def short_conv(u, w, sizes):
    """Gated short convolution over ``u [B, T, H]``."""
    taps = sizes["conv_L_cache"]
    b, c, x = jnp.split(u @ w["in_proj"], 3, axis=-1)
    z = b * x
    # assumed: zero state before a window (a deployment carries the
    # conv cache of the stream; a window starts cold here)
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    length = u.shape[1]
    conv = sum(
        w["kernel"][:, k] * padded[:, k : k + length] for k in range(taps)
    )  # depthwise, causal: c_t = sum_k kernel[:, k] * z_{t-(taps-1)+k}
    return (c * conv) @ w["out_proj"]


def rotary(x, theta):
    """``x [B, T, heads, d]`` rotated at positions 0..T-1."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    # assumed: the half-rotation layout (HF rotate_half), not interleaved
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(u, w, sizes):
    """Grouped-query causal attention over ``u [B, T, H]``."""
    batch, length, _ = u.shape
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    q = (u @ w["wq"]).reshape(batch, length, heads, -1)
    k = (u @ w["wk"]).reshape(batch, length, kv_heads, -1)
    v = (u @ w["wv"]).reshape(batch, length, kv_heads, -1)
    q = rotary(rms_norm(q, w["q_norm"], sizes["norm_eps"]), sizes["rope_theta"])
    k = rotary(rms_norm(k, w["k_norm"], sizes["norm_eps"]), sizes["rope_theta"])
    # each key/value head serves heads // kv_heads query heads
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(batch, length, -1) @ w["wo"]


def dense_ffn(u, w):
    return (jax.nn.silu(u @ w["w1"]) * (u @ w["w3"])) @ w["w2"]


def moe_ffn(u, w, sizes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The share of the routed expert layer that the experts held give,
    and the tokens routed to each of the published experts."""
    experts, top_k = sizes["num_experts"], sizes["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ w["router"])  # [B, T, experts]
    # the bias chooses, the unbiased score weighs; assumed: the bias is
    # held at its seeded value (its update rule is not in the config)
    _, chosen = jax.lax.top_k(scores + w["expert_bias"], top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    # assumed: 1e-6 in the normalisation (norm_topk_prob), as HF has it
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    weights = weights * sizes["routed_scaling_factor"]
    one_hot = jax.nn.one_hot(chosen, experts, dtype=u.dtype)  # [B, T, k, E]
    gate = jnp.einsum("btk,btke->bte", weights, one_hot)
    counts = jnp.sum(one_hot, axis=(0, 1, 2)).astype(jnp.int32)
    out = jnp.zeros_like(u)
    # departure from the published model: only the experts held here
    # (expert_offset .. expert_offset + experts_held - 1) add to the
    # result; what the absent experts would add lies on other chips
    for local in range(sizes["experts_held"]):
        expert = sizes["expert_offset"] + local
        hidden = jax.nn.silu(u @ w["w1"][local]) * (u @ w["w3"][local])
        out = out + gate[..., expert : expert + 1] * (hidden @ w["w2"][local])
    return out, counts


def block(h, w, op, ffn, sizes):
    normed = rms_norm(h, w["operator_norm"], sizes["norm_eps"])
    mixed = short_conv(normed, w["conv"], sizes) if op == "conv" else attention(
        normed, w["attn"], sizes
    )
    h = h + mixed
    normed = rms_norm(h, w["ffn_norm"], sizes["norm_eps"])
    if ffn == "dense":
        return h + dense_ffn(normed, w["ffn"]), None
    out, counts = moe_ffn(normed, w["moe"], sizes)
    return h + out, counts


def _forward(weights, sizes, windows):
    # departure from the published model: a linear projection of sensor
    # rows stands where the token embedding stood (vocab_size replaced)
    h = windows @ weights["embed"]["W"] + weights["embed"]["b"]
    counts = []
    for i, (op, ffn) in enumerate(zip(sizes["layer_ops"], sizes["layer_ffns"])):
        h, routed = block(h, weights[f"layer_{i}"], op, ffn, sizes)
        if routed is not None:
            counts.append(routed)
    # departure: the final norm and a linear head to the tags, read at
    # the window's last position, stand where the LM head stood
    last = rms_norm(h[:, -1], weights["head"]["norm"], sizes["norm_eps"])
    return last @ weights["head"]["W"] + weights["head"]["b"], counts


def forward(layers: Dict[str, Any], windows: np.ndarray, block_windows: int = 4) -> np.ndarray:
    """``windows [n, lookback, tags]`` -> ``[n, tags]``, computed in
    blocks of ``block_windows`` so that the published widths fit."""
    weights, sizes = layers["weights"], layers["sizes"]
    windows = np.asarray(windows, np.float32)
    outs = []
    with jax.default_matmul_precision(HIGHEST):
        for start in range(0, len(windows), block_windows):
            out, _ = _forward(weights, sizes, jnp.asarray(windows[start : start + block_windows]))
            outs.append(np.asarray(out, np.float32))
    if not outs:
        return np.zeros((0, weights["head"]["W"].shape[1]), np.float32)
    return np.concatenate(outs)


def router_counts(layers: Dict[str, Any], windows: np.ndarray) -> np.ndarray:
    """Tokens routed to each published expert, a row per expert layer."""
    with jax.default_matmul_precision(HIGHEST):
        _, counts = _forward(layers["weights"], layers["sizes"], jnp.asarray(windows, jnp.float32))
    return np.stack([np.asarray(c) for c in counts])


def loss_and_grads(layers: Dict[str, Any], windows, targets, weights=None):
    """The weighted mean squared error of a batch and its gradient with
    respect to every weight (the expert bias is a buffer: its gradient is
    zero by construction, as the published model trains none into it)."""
    return _loss_grads_outputs(layers, windows, targets, weights)[:2]


def _loss_grads_outputs(layers: Dict[str, Any], windows, targets, weights=None):
    """:func:`loss_and_grads`, and the outputs the loss was taken of."""
    windows = jnp.asarray(windows, jnp.float32)
    targets = jnp.asarray(targets, jnp.float32)
    w = jnp.ones(len(windows), jnp.float32) if weights is None else jnp.asarray(weights, jnp.float32)

    def loss_of(tree):
        out, _ = _forward(tree, layers["sizes"], windows)
        per_sample = jnp.mean((out - targets) ** 2, axis=-1)
        return jnp.sum(per_sample * w) / jnp.sum(w), out

    def freeze_bias(tree):
        return {
            name: (
                {**group, "moe": {**group["moe"], "expert_bias": jax.lax.stop_gradient(group["moe"]["expert_bias"])}}
                if isinstance(group, dict) and "moe" in group
                else group
            )
            for name, group in tree.items()
        }

    with jax.default_matmul_precision(HIGHEST):
        (loss, out), grads = jax.value_and_grad(
            lambda tree: loss_of(freeze_bias(tree)), has_aux=True
        )(jax.tree_util.tree_map(jnp.asarray, layers["weights"]))
    return float(loss), jax.tree_util.tree_map(np.asarray, grads), np.asarray(out)


def blockwise_step(
    layers: Dict[str, Any], windows: np.ndarray, targets: np.ndarray, block: int
) -> Tuple[float, Any, np.ndarray]:
    """:func:`loss_and_grads` of one batch computed ``block`` windows at
    a time (so that the published widths fit a chip's memory beside
    their gradients): the batch's loss, the norm of its gradient for
    each weight (a tree of floats), and the batch's outputs."""
    loss, grads, outputs = 0.0, None, []
    for start in range(0, len(windows), block):
        share = len(windows[start : start + block]) / len(windows)
        block_loss, block_grads, block_out = _loss_grads_outputs(
            layers, windows[start : start + block], targets[start : start + block]
        )
        loss += share * block_loss
        outputs.append(block_out)
        scaled = jax.tree_util.tree_map(lambda g: share * g.astype(np.float64), block_grads)
        grads = scaled if grads is None else jax.tree_util.tree_map(np.add, grads, scaled)
    norms = jax.tree_util.tree_map(lambda g: float(np.sqrt(np.sum(g * g))), grads)
    return loss, norms, np.concatenate(outputs)


def step_readings(loss: float, norms: Any, ref_loss: float, ref_norms: Any) -> Dict[str, Any]:
    """The program's loss and gradient norms of a batch beside the
    reference's, as fractions of the reference's."""
    got = jax.tree_util.tree_flatten_with_path(norms)[0]
    want = jax.tree_util.tree_leaves(ref_norms)
    total = math.sqrt(sum(v * v for _, v in got))
    ref_total = math.sqrt(sum(v * v for v in want))
    worst, worst_name = 0.0, ""
    for (path, value), ref_value in zip(got, want):
        if ref_value > 1e-3 * ref_total:  # a leaf that carries gradient
            off = abs(value - ref_value) / ref_value
            if off > worst:
                worst, worst_name = off, jax.tree_util.keystr(path)
    return {
        "loss": abs(loss - ref_loss) / abs(ref_loss),
        "grad_norm": abs(total - ref_total) / ref_total,
        "leaf": worst,
        "worst_leaf": worst_name,
        "program": {"loss": loss, "grad_norm": total},
        "reference": {"loss": ref_loss, "grad_norm": ref_total},
    }


def loss_band(
    X_scaled: np.ndarray, y: np.ndarray, config: Dict[str, Any],
    limits: Optional[Dict[str, float]] = None,
) -> Tuple[float, float]:
    """The step check (module docstring): the first
    :data:`STEP_WINDOWS` windows of the machine's history and the rows
    they predict, as one batch at the artifact's weights, through the
    program's own training loss and through :func:`loss_and_grads`.
    Returns the band the artifact's final training loss must lie in:
    every finite loss where each reading is within its limit, none
    (``nan, nan``) where one is not. The readings go to the child's
    output as one line, ``chipbench step check: {...}``."""
    estimator, layers = _LAST["estimator"], _LAST["layers"]
    lookback = int(config["lookback_window"])
    rows = lookback + STEP_WINDOWS
    X = np.asarray(X_scaled[:rows], np.float32)
    targets = np.asarray(y[lookback:rows], np.float32)  # lookahead 1
    loss, norms = estimator.training_loss_and_grad_norms(X, np.asarray(y[:rows], np.float32))
    outputs = np.asarray(estimator.predict(X), np.float64)
    # the reference's side on the host's CPU, where JAX has it beside the
    # accelerator: true float32, and no weights, gradients and saved
    # activations of a second model on the chip, whose peak the run
    # reports as the build's (on the chip they read 11.1 GB live where
    # the build's own peak is 6.5)
    device = _host_device()
    with jax.default_device(device):
        ref_loss, ref_norms, ref_outputs = blockwise_step(
            layers, model_input(estimator, X), targets, block=STEP_WINDOWS
        )
    readings = step_readings(loss, norms, ref_loss, ref_norms)
    # the batch's outputs as the harness compares a forward: of the scale
    readings["output"] = float(np.max(np.abs(outputs - ref_outputs))) / max(
        1.0, float(np.max(np.abs(ref_outputs)))
    )
    limits = STEP_LIMITS if limits is None else limits
    over = [key for key, limit in limits.items() if not readings[key] <= limit]
    print(
        "chipbench step check: "
        + json.dumps({
            **readings, "limits": limits, "windows": STEP_WINDOWS, "over": over,
            "reference_on": str(device or jax.devices()[0]),
        }),
        flush=True,
    )
    return (math.nan, math.nan) if over else (0.0, sys.float_info.max)


def _host_device():
    """The host's CPU as a JAX device, or None (the default device)
    where this process was given the accelerator alone."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None
