"""
Plain float32 reference of the Keye-VL-2.0 sensor backbone
(Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type: KeyeVL2``: the
``qwen3_moe`` layer with DeepSeek-Sparse-Attention's indexer, equations
as DeepSeek-V3.2-Exp publishes them): the forward pass, the loss ``mse +
L_I`` and its gradients, in straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. The selection is a
brute-force top-k of each query's causal index scores; the experts are
a loop with a mask over the share held. No kernel, no sort of pairs, no
grouped product, no threshold, no block of queries skipped. Nothing here
comes from ``gordo_tpu``: the artifact's weights and the spec's sizes
are read off the estimator as plain arrays and numbers; the helpers that
have nothing of a model in them (norm, rotary, the windows, the
readings) are the sibling reference's, loaded by path.

One departure from "plain" that is no departure from the mathematics:
an 8,192-row window's ``[32, T, T]`` scores are 8.6 GB a layer, so the
attention runs :data:`QUERY_ROWS` queries at a time against every key
of the window (one loop of one body: sixteen bodies, each of another
length of keys, took the compiler three minutes), and each such piece,
and each layer around
them, is under ``jax.checkpoint``: the same numbers, and what the
gradient of one window keeps is a layer's inputs, not the 25 GB of
sixteen experts' products over 8,192 tokens in four layers. The step
and the forward are compiled (``jax.jit``: the same numbers) and run on
the device the process holds: op by op a window's step took the chip 17
minutes, and on the host's CPU it met the machine's 40 GiB beside what
the build's own compilations leave there.

What ``harness/correct.py`` holds a build to with it: the artifact's
prediction against :func:`forward` of the artifact's own weights, and
one training step at those weights (:func:`loss_band`, the hook the
harness has; the sibling's docstring says why a step and why through
that hook): outputs, loss and every leaf's gradient norm, the indexer's
leaves among them, of :data:`STEP_WINDOWS` whole window against
:func:`loss_and_grads` at "highest" on the device the process holds,
under :data:`STEP_LIMITS`.

Loaded by the child that is about to build the configuration
(``procs/build_worker.py``) in a checkout whose program has no
``kind: keye_vl2`` (every commit before PR 31), this module ends that
child at once with exit code 5, as the sibling does for a checkout
without a backbone, and for the reason written there.
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
            return "def keye_vl2(" in f.read()
    except OSError:
        return False


if _MAIN == "build_worker.py" and not _program_has_the_kind():
    print(
        "chipbench: this checkout's program has no kind keye_vl2 "
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
rms_norm, rotary, model_input = _shared.rms_norm, _shared.rotary, _shared.model_input
HIGHEST = _shared.HIGHEST

#: what of the spec the forward needs, read by name
SIZES = (
    "layer_ops", "layer_ffns", "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_experts", "experts_held", "expert_offset", "num_experts_per_tok",
    "rope_theta", "norm_eps", "lookback_window", "index_n_heads", "index_head_dim", "index_topk",
)

#: queries a piece of the attention (module docstring)
QUERY_ROWS = 512

#: the step check: one whole window of the machine's history (8,192
#: tokens at the published lookback; the selection acts only past row
#: 2,048, so less than a window would not see it).
STEP_WINDOWS = 1
#: limits of the step check, each between two readings on the v5e
#: (PERF.md, section 6, has every one): the largest of five sound builds,
#: a seed each, and the one build with ``compute_dtype: bfloat16``, the
#: nearest precision below, which the cell has to read as not correct.
#: ``output``: the window's outputs, max |program - reference| over
#: max(1, max |reference|): sound 1.7e-5 to 4.3e-5 (the outputs of a
#: build are some 20 wide, so a float32 product's one bfloat16 pass shows
#: a tenth of what it shows in ``lfm2_moe_build``), bfloat16 3.2e-3.
#: ``leaf``: the worst gradient norm, of the reference's, among the
#: leaves the forecast's gradient reaches (a router's or an attention
#: matrix's each time): sound 1.4e-3 to 4.0e-3, bfloat16 1.6e-1.
#: ``indexer_leaf_median``: the median of the indexers' twenty leaves:
#: sound 4.2e-4 to 1.0e-3, bfloat16 4.1e-3. The WORST indexer leaf is
#: read and printed and holds nothing: it swings from 1.6e-3 to 1.1e-1
#: over sound builds and reads 3.0e-2 in bfloat16, inside that range. An
#: indexer's gradient is ``r - p`` itself, ``p`` the attention's
#: probabilities, whose logits (up to 11 wide) take one bfloat16 pass of
#: the matrix unit in float32 and bfloat16 builds alike (XLA's default
#: precision for a float32 product on a TPU), where the forecast's
#: leaves see those probabilities only through sums over keys: with the
#: logits' operands rounded to bfloat16 on the CPU an indexer leaf moves
#: from 1e-7 to 5e-4 at toy widths (logits up to 3), as the others do,
#: and one leaf of twenty, each a sum of near-cancelling terms, reads
#: tens of times its fellows.
STEP_LIMITS = {"output": 1.5e-4, "leaf": 2e-2, "indexer_leaf_median": 3e-3}

_LAST: Dict[str, Any] = {}


def layers_of(estimator: Any) -> Dict[str, Any]:
    """The artifact's own weights as float32 arrays, with the sizes of
    its spec: ``{"weights": <the parameter tree>, "sizes": {...}}``."""
    spec = estimator.spec_
    weights = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float32), estimator.params_)
    layers = {"weights": weights, "sizes": {key: getattr(spec, key) for key in SIZES}}
    _LAST.update(estimator=estimator, layers=layers)
    return layers


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    variance = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(variance + eps) * gain + bias


def heads_of(u, w, sizes):
    """``u [B, T, H]`` -> ``q [B, T, heads, d]``, ``k``, ``v [B, T,
    heads, d]`` (each key/value head repeated for the query heads it
    serves), q and k normed a head and rotated."""
    batch, length, _ = u.shape
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    q = (u @ w["wq"]).reshape(batch, length, heads, -1)
    k = (u @ w["wk"]).reshape(batch, length, kv_heads, -1)
    v = (u @ w["wv"]).reshape(batch, length, kv_heads, -1)
    # assumed: the qwen3_moe family's per-head RMSNorm of q and k
    q = rotary(rms_norm(q, w["q_norm"], sizes["norm_eps"]), sizes["rope_theta"])
    k = rotary(rms_norm(k, w["k_norm"], sizes["norm_eps"]), sizes["rope_theta"])
    return q, jnp.repeat(k, heads // kv_heads, axis=2), jnp.repeat(v, heads // kv_heads, axis=2)


def indexer_of(u, w, sizes):
    """The indexer's ``(qI [B, T, heads, d], kI [B, T, d], w [B, T,
    heads])`` of the detached input ``u [B, T, H]``, the two scales
    ``16^-1/2`` and ``64^-1/2`` in ``w``."""
    batch, length, _ = u.shape
    heads, width = sizes["index_n_heads"], sizes["index_head_dim"]
    x = jax.lax.stop_gradient(u)
    qi = rotary((x @ w["wq"]).reshape(batch, length, heads, width), sizes["rope_theta"])
    # assumed (DeepSeek-V3.2-Exp): LayerNorm on the indexer's one key
    # head, rotary over all of its 64 dimensions at the main theta; its
    # Hadamard rotation and FP8 cast are left out
    ki = layer_norm(x @ w["wk"], w["k_norm"]["gain"], w["k_norm"]["bias"], sizes["norm_eps"])
    ki = rotary(ki[:, :, None, :], sizes["rope_theta"])[:, :, 0, :]
    return qi, ki, (x @ w["w"]) * heads**-0.5 * width**-0.5


def index_scores(qi, ki, head_weights):
    """``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``: ``[B, Q, S]``."""
    dots = jnp.einsum("bqjd,bsd->bqjs", qi, ki)
    return jnp.einsum("bqj,bqjs->bqs", head_weights, jax.nn.relu(dots))


def selected(index, first_row, top_k: int):
    """``index [B, Q, S]``, the scores of queries ``first_row ..`` against
    keys ``0 .. S - 1`` -> ``[B, Q, S]`` bool: ``S_t``, the ``min(t + 1,
    top_k)`` causal keys of largest score, by a top-k of each row."""
    batch, queries, keys = index.shape
    causal = jnp.arange(keys)[None, :] <= (first_row + jnp.arange(queries))[:, None]
    if keys <= top_k:
        return jnp.broadcast_to(causal, index.shape)
    _, chosen = jax.lax.top_k(jnp.where(causal, index, -jnp.inf), top_k)
    rows = (jnp.arange(batch)[:, None, None], jnp.arange(queries)[None, :, None])
    picked = jnp.zeros(index.shape, bool).at[(*rows, chosen)].set(True)
    return picked & causal  # a query with fewer causal keys than top_k keeps those


def attend(q, k, v, qi, ki, head_weights, first_row, top_k: int):
    """Queries ``q [B, Q, heads, d]`` from row ``first_row`` against
    every key ``k``, ``v [B, T, heads, d]`` under the indexer's
    selection (rows from ``T`` on are padding: they count nowhere):
    ``(out [B, Q, heads, d], sum over the queries of KL(p_t || r_t)
    [B], pairs kept [B])``."""
    index = index_scores(qi, ki, head_weights)
    keep = selected(jax.lax.stop_gradient(index), first_row, top_k)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    weights = jax.nn.softmax(jnp.where(keep[:, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    # the indexer's objective (DeepSeek-V3.2's sparse stage): its softmax
    # over S_t against the head-mean of the attention, which is detached
    p = jax.lax.stop_gradient(jnp.mean(weights, axis=1))
    log_r = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
    counted = keep & ((first_row + jnp.arange(q.shape[1])) < k.shape[1])[None, :, None]
    seen = counted & (p > 0)
    terms = jnp.where(seen, p * (jnp.log(jnp.where(seen, p, 1.0)) - jnp.where(seen, log_r, 0.0)), 0.0)
    return out, jnp.sum(terms, axis=(1, 2)), jnp.sum(counted, axis=(1, 2))


def sparse_attention(u, w, wi, sizes, query_rows: int = QUERY_ROWS):
    """``u [B, T, H]`` -> ``(output [B, T, H], mean over t of KL [B],
    pairs kept [B])``."""
    batch, length, _ = u.shape
    q, k, v = heads_of(u, w, sizes)
    qi, ki, head_weights = indexer_of(u, wi, sizes)
    # ``query_rows`` queries at a time against every key of the window,
    # the keys after a query masked, each piece under ``jax.checkpoint``:
    # one loop of one body, which the compiler sees once
    query_rows = min(query_rows, length)
    pieces = -(-length // query_rows)

    def in_pieces(a):  # [B, T, ...] -> [pieces, B, query_rows, ...], zeros after row T
        a = jnp.pad(a, ((0, 0), (0, pieces * query_rows - length)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((batch, pieces, query_rows) + a.shape[2:]), 1, 0)

    piece = jax.checkpoint(
        lambda one: attend(one[0], k, v, one[1], ki, one[2], one[3], sizes["index_topk"])
    )
    out, kl, kept = jax.lax.map(
        piece, (in_pieces(q), in_pieces(qi), in_pieces(head_weights), jnp.arange(pieces) * query_rows)
    )
    out = jnp.moveaxis(out, 0, 1).reshape(batch, pieces * query_rows, -1)[:, :length] @ w["wo"]
    return out, jnp.sum(kl, axis=0) / length, jnp.sum(kept, axis=0)


def moe_ffn(u, w, sizes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The share of the routed expert layer that the experts held give,
    and the tokens routed to each of the published experts."""
    experts, top_k = sizes["num_experts"], sizes["num_experts_per_tok"]
    # assumed: the qwen3_moe family's router: a softmax over all logits,
    # the k largest, renormalised (norm_topk_prob), no bias, no scaling
    probabilities = jax.nn.softmax(u @ w["router"], axis=-1)
    picked, chosen = jax.lax.top_k(probabilities, top_k)
    picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(chosen, experts, dtype=u.dtype)  # [B, T, k, E]
    gate = jnp.einsum("btk,btke->bte", picked, one_hot)
    counts = jnp.sum(one_hot, axis=(0, 1, 2)).astype(jnp.int32)
    # departure from the published model: only the experts held here add
    # to the result; what the absent experts would add lies on other chips
    first = sizes["expert_offset"]
    held_gates = jnp.moveaxis(gate[..., first : first + sizes["experts_held"]], -1, 0)

    def add_expert(out, expert):  # one expert after another: one loop of one body
        w1, w3, w2, expert_gate = expert
        hidden = jax.nn.silu(u @ w1) * (u @ w3)
        return out + expert_gate[..., None] * (hidden @ w2), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (w["w1"], w["w3"], w["w2"], held_gates))
    return out, counts


def block(h, w, sizes):
    """One layer: sparse attention, then the routed experts; returns
    ``(h, the layer's mean-over-t KL [B], pairs kept [B], router counts)``."""
    out, kl, kept = sparse_attention(
        rms_norm(h, w["operator_norm"], sizes["norm_eps"]), w["attn"], w["indexer"], sizes
    )
    h = h + out
    out, counts = moe_ffn(rms_norm(h, w["ffn_norm"], sizes["norm_eps"]), w["moe"], sizes)
    return h + out, kl, kept, counts


def _forward(weights, sizes, windows):
    # departure from the published model: a linear projection of sensor
    # rows stands where the token embedding stood (vocab_size replaced),
    # and no vision tower: no image enters
    h = windows @ weights["embed"]["W"] + weights["embed"]["b"]
    # the layers are alike: one after another, each under
    # ``jax.checkpoint``, as one loop of one body over their stacked weights
    names = [f"layer_{i}" for i in range(len(sizes["layer_ops"]))]
    stacked = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *(weights[n] for n in names))

    def layer(h, w):
        h, layer_kl, layer_kept, routed = jax.checkpoint(lambda h, w: block(h, w, sizes))(h, w)
        return h, (layer_kl, layer_kept, routed)

    h, (kl, kept, counts) = jax.lax.scan(layer, h, stacked)
    kl = jnp.sum(kl, axis=0)
    # departure: the final norm and a linear head to the tags, read at
    # the window's last position, stand where the LM head stood
    last = rms_norm(h[:, -1], weights["head"]["norm"], sizes["norm_eps"])
    out = last @ weights["head"]["W"] + weights["head"]["b"]
    return out, {"kl": kl, "kept": kept, "routed": counts}


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
    """Of one batch: ``routed [layers, experts]`` (tokens to each
    published expert), ``kept [layers]`` (query-key pairs selected) and
    ``kl [layers' sum, a window]``."""
    with jax.default_matmul_precision(HIGHEST):
        _, found = _forward(layers["weights"], layers["sizes"], jnp.asarray(windows, jnp.float32))
    return {
        "routed": np.asarray(found["routed"]),
        "kept": np.asarray(found["kept"]).sum(axis=1),
        "kl": np.asarray(found["kl"]),
    }


def loss_and_grads(layers: Dict[str, Any], windows, targets, weights=None):
    """``mse + L_I`` of a batch and its gradient with respect to every
    weight: the weighted mean squared error, and the mean over the
    windows that count (weight above 0) of the layers' sum of the mean
    over t of KL(p_t || r_t)."""
    return _loss_grads_outputs(layers, windows, targets, weights)[:2]


def _loss_grads_outputs(layers: Dict[str, Any], windows, targets, weights=None):
    windows = jnp.asarray(windows, jnp.float32)
    targets = jnp.asarray(targets, jnp.float32)
    w = jnp.ones(len(windows), jnp.float32) if weights is None else jnp.asarray(weights, jnp.float32)
    counted = (w > 0).astype(jnp.float32)

    def loss_of(tree):
        out, found = _forward(tree, layers["sizes"], windows)
        per_sample = jnp.mean((out - targets) ** 2, axis=-1)
        objective = jnp.sum(found["kl"] * counted) / jnp.maximum(jnp.sum(counted), 1.0)
        return jnp.sum(per_sample * w) / jnp.sum(w) + objective, out

    with jax.default_matmul_precision(HIGHEST):
        (loss, out), grads = jax.jit(jax.value_and_grad(loss_of, has_aux=True))(
            jax.tree_util.tree_map(jnp.asarray, layers["weights"])
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
        if share == 1.0:  # one block: the gradient as it is, no second copy of 1.55 GB
            grads = block_grads
        else:
            scaled = jax.tree_util.tree_map(lambda g: share * g, block_grads)
            grads = scaled if grads is None else jax.tree_util.tree_map(np.add, grads, scaled)
    norms = jax.tree_util.tree_map(
        lambda g: float(np.sqrt(np.sum(np.square(g, dtype=np.float64)))), grads
    )
    return loss, norms, np.concatenate(outputs)


def step_readings(loss: float, norms: Any, ref_loss: float, ref_norms: Any) -> Dict[str, Any]:
    """The sibling's readings (the program's loss and gradient norms of a
    batch beside the reference's, as fractions of the reference's), the
    indexers' leaves read apart: ``leaf`` is the worst of the leaves
    that the forecast's gradient reaches, ``indexer_leaf`` the worst of
    the indexers', every one of them (their gradient is the objective's
    alone and small beside the forecast's, so the sibling's rule, a
    thousandth of the whole norm carries gradient, would pass them by),
    ``indexer_leaf_median`` their median, which :data:`STEP_LIMITS`
    holds (the worst is one noisy leaf of twenty: see there)."""
    got = jax.tree_util.tree_flatten_with_path(norms)[0]
    want = jax.tree_util.tree_leaves(ref_norms)
    named = [(jax.tree_util.keystr(path), value, ref) for (path, value), ref in zip(got, want)]
    forecast = [entry for entry in named if "indexer" not in entry[0]]
    readings = _shared.step_readings(
        loss, {name: value for name, value, _ in forecast},
        ref_loss, {name: ref for name, _, ref in forecast},
    )
    # the whole norm, the indexers' leaves in it
    total = math.sqrt(sum(value * value for _, value, _ in named))
    ref_total = math.sqrt(sum(ref * ref for _, _, ref in named))
    readings["grad_norm"] = abs(total - ref_total) / ref_total
    readings["program"]["grad_norm"], readings["reference"]["grad_norm"] = total, ref_total
    apart = sorted(
        (abs(value - ref) / ref, name) for name, value, ref in named if "indexer" in name and ref > 0
    )
    readings.update(
        indexer_leaf=apart[-1][0] if apart else 0.0,
        worst_indexer_leaf=apart[-1][1] if apart else "",
        indexer_leaf_median=apart[len(apart) // 2][0] if apart else 0.0,
    )
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
    # the reference's side on the device the process holds, at "highest"
    # (on the chip a float32 product is then six passes of the matrix
    # unit) and compiled as one program: the host cannot hold it beside
    # what the build's compilations left there (40 GiB met twice: my
    # runs, PR 31), and op by op on the chip it took 17 minutes
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
