"""
Plain float32 reference of the Laguna-XS.2 sensor backbone
(poolside/Laguna-XS.2, ``model_type: laguna``: window-512 and full
attention mixed three to one, layers of unlike head counts, a sigmoid
gate on every head, a shared expert beside 256 routed ones under a
scaled sigmoid router): the forward pass, the loss and its gradients, in
straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. Every mask is built from
positions (``s <= t``, and ``t - s < sliding_window`` in a sliding
layer) over every key of the window: no tile is skipped, no band is cut
out, no running softmax, no grouped product. The experts are a loop with
a mask over the share held; the shared expert is computed once. Nothing
here comes from ``gordo_tpu``: the artifact's weights and the spec's
sizes are read off the estimator as plain arrays and numbers; the
helpers that have nothing of a model in them (norm, plain rotary, the
gated feed-forward, the windows, the readings) are the sibling
reference's, loaded by path.

What ``configs/laguna-xs2-50tag-lb8192.json`` lists under ``assumed`` is
marked "assumed" where it enters here.

One departure from "plain" that is no departure from the mathematics,
as the sibling ``keye_sparse_backbone`` has it: an 8,192-row window's
``[64, T, T]`` scores are 17 GB a layer, so the attention runs
:data:`QUERY_ROWS` queries at a time against every key of the window
(one loop of one body), each such piece and each layer around them
under ``jax.checkpoint``; the step and the forward are compiled and run
on the device the process holds. And one for the machine's compile
cache, which keeps 192 MiB: consecutive layers of one kind and shape
(the cut's three sliding layers) are one loop of one body over their
weights laid side by side (:func:`like_layers`), so the step's
executable is 40 MB there and not 66, the forward's 10 and not 15, and
the cell's programs fit it together (PERF.md, section 6).

What ``harness/correct.py`` holds a build to with it: the artifact's
prediction against :func:`forward` of the artifact's own weights, and
one training step at those weights (:func:`loss_band`, the hook the
harness has; ``lfm2_moe_backbone``'s docstring says why a step and why
through that hook): outputs, loss and every leaf's gradient norm of
:data:`STEP_WINDOWS` whole window against :func:`loss_and_grads` at
"highest" on the device the process holds, under :data:`STEP_LIMITS`.

Loaded by the child that is about to build the configuration
(``procs/build_worker.py``) in a checkout whose program has no
``kind: laguna`` (every commit before PR 33), this module ends that
child at once with exit code 5, as the siblings do, and for the reason
written in ``lfm2_moe_backbone``.
"""

import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))
_MAIN = os.path.basename(getattr(sys.modules.get("__main__"), "__file__", "") or "")


def _program_has_the_kind() -> bool:
    try:
        with open(os.path.join(_CHECKOUT, "gordo_tpu", "models", "factories", "backbone.py")) as f:
            return "def laguna(" in f.read()
    except OSError:
        return False


if _MAIN == "build_worker.py" and not _program_has_the_kind():
    print(
        "chipbench: this checkout's program has no kind laguna "
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
rms_norm, rotary, dense_ffn = _shared.rms_norm, _shared.rotary, _shared.dense_ffn
model_input, HIGHEST = _shared.model_input, _shared.HIGHEST

#: what of the spec the forward needs, read by name (a layer's number of
#: query heads is the width of its ``wq``)
SIZES = (
    "layer_ops", "layer_ffns", "num_key_value_heads", "head_dim", "num_experts", "experts_held",
    "expert_offset", "num_experts_per_tok", "routed_scaling_factor", "norm_eps",
    "lookback_window", "sliding_window", "rope_parameters",
)

#: queries a piece of the attention (module docstring)
QUERY_ROWS = 512

#: the step check: one whole window of the machine's history (8,192
#: tokens at the published lookback: a full layer's far keys and a
#: sliding layer's edge both act only in a window longer than 512 rows)
STEP_WINDOWS = 1
#: limits of the step check, each between two readings on the v5e
#: (PERF.md, section 6, has every one): the largest of the sound
#: builds, a seed each, and the builds with ``compute_dtype: bfloat16``,
#: the nearest precision below, which the cell has to read as not
#: correct.
#: ``output``: the window's outputs, max |program - reference| over
#: max(1, max |reference|): sound 2.9e-5 to 6.5e-5 (the outputs of a
#: build are some 15 wide), bfloat16 2.4e-3 to 5.0e-3;
#: keye_sparse_backbone's.
#: ``leaf``: the worst gradient norm, of the reference's, as
#: :func:`step_readings` reads it (most often layer 0's gate, whose
#: input is the sensor projection's 50 columns under one norm): sound
#: 7.8e-4 to 6.2e-3 in twenty builds (seventeen of them read before
#: ``wq`` and ``wk`` had their floor, with another leaf the worst) and
#: 1.6e-3 to 4.7e-3 over nine windows of one, bfloat16 5.1e-2 to 1.2e-1
#: in four builds and 3.1e-2 to 3.2e-1 over nine windows of one.
#: ``loss``: |program - reference| of the reference's: sound 3.5e-7 to
#: 1.0e-5, bfloat16 3.9e-4 to 4.5e-3.
#: ``grad_norm``: the whole gradient's norm, likewise: sound 1.2e-7 to
#: 6.1e-6, bfloat16 2.9e-4 to 4.9e-4.
STEP_LIMITS = {"output": 1.5e-4, "leaf": 2e-2, "loss": 1e-4, "grad_norm": 5e-5}

#: the leaves :func:`step_readings` reads over a floor, and the floor as a
#: share of the whole gradient's norm
QK_LEAVES = ("['wq']", "['wk']")
QK_FLOOR = 1e-2

_LAST: Dict[str, Any] = {}


def layers_of(estimator: Any) -> Dict[str, Any]:
    """The artifact's own weights as float32 arrays, with the sizes of
    its spec: ``{"weights": <the parameter tree>, "sizes": {...}}``."""
    spec = estimator.spec_
    weights = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float32), estimator.params_)
    sizes = {key: getattr(spec, key) for key in SIZES}
    sizes["rope_parameters"] = {op: dict(pairs) for op, pairs in sizes["rope_parameters"]}
    layers = {"weights": weights, "sizes": sizes}
    _LAST.update(estimator=estimator, layers=layers)
    return layers


def yarn_frequencies(rope: Dict[str, Any], rotated: int) -> np.ndarray:
    """The ``rotated // 2`` inverse frequencies of YaRN, after
    ``transformers``' ``_compute_yarn_parameters``: plain ones for the
    dimensions that turn more than ``beta_fast`` times over the original
    context, plain ones divided by ``factor`` for those that turn fewer
    than ``beta_slow`` times, a linear ramp between the two."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def dimension_of(rotations: float) -> float:
        return (rotated * math.log(original / (rotations * 2.0 * math.pi))) / (2.0 * math.log(base))

    # assumed (transformers): the two dimensions are rounded outwards
    low = max(math.floor(dimension_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dimension_of(float(rope["beta_slow"]))), rotated - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / (base ** (np.arange(0, rotated, 2, dtype=np.float64) / rotated))
    ramp = np.clip((np.arange(rotated // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    # assumed: computed in float64 and rounded to float32 once
    return ((plain / factor) * (1.0 - extrapolation) + plain * extrapolation).astype(np.float32)


def placed(x, rope: Dict[str, Any]):
    """``x [B, T, heads, d]`` under the rotary embedding ``rope`` at
    positions 0..T-1, half-rotation layout."""
    if rope["rope_type"] == "default":
        return rotary(x, float(rope["rope_theta"]))  # over the whole head
    rotated = int(x.shape[-1] * rope["partial_rotary_factor"])
    half = rotated // 2
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * yarn_frequencies(rope, rotated)[None, :]
    # assumed (transformers): attention_factor multiplies cos and sin, so
    # the rotated part of q and of k alone
    cos = (jnp.cos(angles) * rope["attention_factor"])[None, :, None, :]
    sin = (jnp.sin(angles) * rope["attention_factor"])[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotated], x[..., rotated:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attend(q, k, v, first_row, window: int):
    """Queries ``q [B, Q, heads, d]`` from row ``first_row`` against
    every key ``k``, ``v [B, T, heads, d]``, the mask from positions:
    ``(out [B, Q, heads, d], pairs inside the mask of the window's own
    rows [B])``."""
    t = (first_row + jnp.arange(q.shape[1]))[:, None]
    s = jnp.arange(k.shape[1])[None, :]
    mask = (s <= t) & (t - s < window)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    weights = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
    pairs = jnp.sum(mask & (t < k.shape[1]))
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v), jnp.full((q.shape[0],), pairs)


def attention(u, w, op: str, sizes, query_rows: int = QUERY_ROWS):
    """``u [B, T, H]`` -> ``(output [B, T, H], pairs attended [B])``."""
    batch, length, _ = u.shape
    kv_heads, width = sizes["num_key_value_heads"], sizes["head_dim"]
    rope = sizes["rope_parameters"][op]
    # assumed: no RMSNorm of q and k (the config names none)
    q = placed((u @ w["wq"]).reshape(batch, length, -1, width), rope)
    k = placed((u @ w["wk"]).reshape(batch, length, kv_heads, width), rope)
    v = (u @ w["wv"]).reshape(batch, length, kv_heads, width)
    heads = q.shape[2]
    k, v = jnp.repeat(k, heads // kv_heads, axis=2), jnp.repeat(v, heads // kv_heads, axis=2)
    # a sliding layer's query sees itself and the window - 1 rows before it
    window = sizes["sliding_window"] if op == "sliding_attention" else length
    query_rows = min(query_rows, length)
    pieces = -(-length // query_rows)
    padded = jnp.pad(q, ((0, 0), (0, pieces * query_rows - length), (0, 0), (0, 0)))
    in_pieces = jnp.moveaxis(padded.reshape(batch, pieces, query_rows, heads, width), 1, 0)
    piece = jax.checkpoint(lambda one: attend(one[0], k, v, one[1], window))
    out, pairs = jax.lax.map(piece, (in_pieces, jnp.arange(pieces) * query_rows))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, pieces * query_rows, heads, width)[:, :length]
    # assumed (Laguna-S-2.1's gating: "per-head"): a sigmoid gate a head,
    # read off the layer's normed input
    out = out * jax.nn.sigmoid(u @ w["gate"])[..., None]
    return out.reshape(batch, length, -1) @ w["wo"], jnp.sum(pairs, axis=0)


def moe_ffn(u, w, sizes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The shared expert, and the share of the routed experts' sum that
    the experts held give; and the tokens routed to each of the
    published experts."""
    experts, top_k = sizes["num_experts"], sizes["num_experts_per_tok"]
    # assumed: DeepSeek-V3's router: sigmoid scores, the k largest of
    # score + bias chosen (the bias a buffer at its seeded value), the
    # chosen scores normalised to sum 1 (1e-6 in the normaliser, as the
    # program's sigmoid router has it) and scaled; no groups
    scores = jax.nn.sigmoid(u @ w["router"])
    _, chosen = jax.lax.top_k(scores + w["expert_bias"], top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    weights = weights * sizes["routed_scaling_factor"]
    one_hot = jax.nn.one_hot(chosen, experts, dtype=u.dtype)  # [B, T, k, E]
    gate = jnp.einsum("btk,btke->bte", weights, one_hot)
    counts = jnp.sum(one_hot, axis=(0, 1, 2)).astype(jnp.int32)
    # departure from the published model: only the experts held here add
    # to the result; what the absent experts would add lies on other chips
    first = sizes["expert_offset"]
    held_gates = jnp.moveaxis(gate[..., first : first + sizes["experts_held"]], -1, 0)

    def add_expert(out, expert):  # one expert after another: one loop of one body
        w1, w3, w2, expert_gate = expert
        hidden = jax.nn.silu(u @ w1) * (u @ w3)
        return out + expert_gate[..., None] * (hidden @ w2), None

    # moe_apply_router_weight_on_input false: the weights are on the outputs
    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (w["w1"], w["w3"], w["w2"], held_gates))
    return dense_ffn(u, w["shared"]) + routed, counts


def block(h, w, op: str, ffn: str, sizes):
    """One layer; returns ``(h, pairs attended [B], router counts or None)``."""
    out, pairs = attention(rms_norm(h, w["operator_norm"], sizes["norm_eps"]), w["attn"], op, sizes)
    h = h + out
    normed = rms_norm(h, w["ffn_norm"], sizes["norm_eps"])
    if ffn == "dense":
        return h + dense_ffn(normed, w["ffn"]), pairs, None
    out, counts = moe_ffn(normed, w["moe"], sizes)
    return h + out, pairs, counts


def like_layers(weights, sizes) -> List[Tuple[str, str, List[str]]]:
    """The layers in order as runs of like ones: ``(operator,
    feed-forward, names)`` of consecutive layers of one kind whose
    weights have one shape (the cut: layer 0; layers 1-3; layer 4)."""
    runs: List[Tuple[str, str, List[str]]] = []
    shapes = None
    for i, kind in enumerate(zip(sizes["layer_ops"], sizes["layer_ffns"])):
        name = f"layer_{i}"
        found = jax.tree_util.tree_map(np.shape, weights[name])
        if runs and runs[-1][:2] == kind and found == shapes:
            runs[-1][2].append(name)
        else:
            runs.append((*kind, [name]))
        shapes = found
    return runs


def _forward(weights, sizes, windows):
    # departure from the published model: a linear projection of sensor
    # rows stands where the token embedding stood (vocab_size replaced)
    h = windows @ weights["embed"]["W"] + weights["embed"]["b"]
    attended, routed = [], []
    for op, ffn, names in like_layers(weights, sizes):
        # each layer under ``jax.checkpoint``; layers that differ in shape
        # (heads, feed-forward) one after another, a run of like layers
        # as one loop of one body over their weights laid side by side
        layer = jax.checkpoint(lambda h, w, _op=op, _ffn=ffn: block(h, w, _op, _ffn, sizes))
        if len(names) == 1:
            h, pairs, counts = layer(h, weights[names[0]])
            pairs, counts = pairs[None], None if counts is None else counts[None]
        else:
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
    zero by construction)."""
    return _loss_grads_outputs(layers, windows, targets, weights)[:2]


def _frozen_bias(tree):
    return {
        name: (
            {**group, "moe": {**group["moe"], "expert_bias": jax.lax.stop_gradient(group["moe"]["expert_bias"])}}
            if isinstance(group, dict) and "moe" in group
            else group
        )
        for name, group in tree.items()
    }


def _loss_grads_outputs(layers: Dict[str, Any], windows, targets, weights=None):
    windows = jnp.asarray(windows, jnp.float32)
    targets = jnp.asarray(targets, jnp.float32)
    w = jnp.ones(len(windows), jnp.float32) if weights is None else jnp.asarray(weights, jnp.float32)

    # the batch is an argument and no constant of the program: a run's
    # data are its seed's, and a program that held them would be another
    # to compile, and to keep in the machine's compile cache, every run
    def loss_of(tree, windows, targets, w):
        out, _ = _forward(_frozen_bias(tree), layers["sizes"], windows)
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
        if share == 1.0:  # one block: the gradient as it is, no second copy of 1.76 GB
            grads = block_grads
        else:
            scaled = jax.tree_util.tree_map(lambda g: share * g, block_grads)
            grads = scaled if grads is None else jax.tree_util.tree_map(np.add, grads, scaled)
    norms = jax.tree_util.tree_map(
        lambda g: float(np.sqrt(np.sum(np.square(g, dtype=np.float64)))), grads
    )
    return loss, norms, np.concatenate(outputs)


def step_readings(loss: float, norms: Any, ref_loss: float, ref_norms: Any) -> Dict[str, Any]:
    """The sibling's readings, with ``leaf`` read otherwise for the
    attention's ``wq`` and ``wk``. On a machine's smooth rows the keys of
    a window are nearly alike, so the softmax is nearly flat and what
    reaches ``wq`` and ``wk`` is 1e-5 (sliding layers) to 2e-3 (layer 0)
    of the whole gradient's norm; what one bfloat16 pass of the backward
    products adds there is 2e-4 to 8e-4 of the whole (layer 0, nine
    windows of one sound build on the v5e), independent of the gradient,
    so the squares add. As a fraction of the leaf's own norm that read
    1.6e-3 to 1.6e-1 on ONE sound build, by whether the window put layer
    0's ``wk`` above the sibling's thousandth of the whole or below, and
    3.1e-2 to 3.2e-1 on the bfloat16 control: no limit lies between
    (PERF.md, section 6, PR 33's third round). So a ``wq`` or ``wk`` is read
    as ``|program ** 2 - reference ** 2| / (2 (reference ** 2 + floor **
    2))``, ``floor`` :data:`QK_FLOOR` of the whole: well above the floor
    that is the norm's relative error as for every other leaf, below it
    what the leaf adds to the whole's square, so that rounding reads
    3.4e-3 at most wherever it falls and a layer 0 ``wk`` three times
    what it should be reads 4e-2. Every other leaf is read as the
    sibling reads it, under the limit it had."""
    readings = _shared.step_readings(loss, norms, ref_loss, ref_norms)
    whole = readings["reference"]["grad_norm"]
    worst = (0.0, "")
    for (path, value), ref in zip(
        jax.tree_util.tree_flatten_with_path(norms)[0], jax.tree_util.tree_leaves(ref_norms)
    ):
        name = jax.tree_util.keystr(path)
        if name.endswith(QK_LEAVES):
            off = abs(value * value - ref * ref) / (2.0 * (ref * ref + (QK_FLOOR * whole) ** 2))
        elif ref > 1e-3 * whole:  # the sibling's "a leaf that carries gradient"
            off = abs(value - ref) / ref
        else:
            continue
        worst = max(worst, (off, name))
    readings["leaf"], readings["worst_leaf"] = worst
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
