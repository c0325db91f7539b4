"""
Plain float32 reference of the SmallThinker-21BA3B-Instruct sensor
backbone (PowerInfer/SmallThinker-21BA3B-Instruct: attention without
positions and window-4,096 attention with rotary mixed one to three, 28
query heads over 4 key/value heads, a router that reads the layer's
input before its attention, 64 experts of 768 gated by ``relu``, 6 a
token): the forward pass, the loss and its gradients, in
straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. Every mask is built from
positions (``s <= t``, and ``t - s < sliding_window`` where
``sliding_window_layout`` says 1) over every key of the window: no tile
is skipped, no band is cut out, no running softmax, no grouped product,
no sort. ``q`` and ``k`` are rotated where ``rope_layout`` says 1 and
are the bare projections where it says 0. The router is one plain
product of the layer's input ``x``, before ``input_layernorm``; the six
largest logits are found by counting, for each expert, the experts that
beat it; a softmax over those six weighs them. The experts are a loop
with a mask over the share held. Nothing here comes from ``gordo_tpu``:
the artifact's weights and the spec's sizes are read off the estimator
as plain arrays and numbers; the helpers that have nothing of this
model in them (norm, plain rotary, the windows, a piece of masked
attention, the readings) are the sibling references', loaded by path.

What ``configs/smallthinker-21b-a3b-50tag-lb8192.json`` lists under
``assumed`` is marked "assumed" where it enters here.

Two departures from "plain" that are none from the mathematics, as the
sibling ``laguna_banded_backbone`` has them: the attention runs
:data:`QUERY_ROWS` queries at a time against every key of the window
(an 8,192-row window's ``[28, T, T]`` scores are 7.5 GB a layer), each
such piece and each layer under ``jax.checkpoint``; and consecutive
layers of one kind (the cut's three sliding layers) are one loop of one
body over their weights laid side by side (:func:`like_layers`), for
the machine's compile cache of 192 MiB. The jitted functions take their
batch as arguments: a run's data are its seed's, and a program that
held them would be another to compile in every run.

What ``harness/correct.py`` holds a build to with it: the artifact's
prediction against :func:`forward` of the artifact's own weights, and
one training step at those weights (:func:`loss_band`, the hook the
harness has; ``lfm2_moe_backbone``'s docstring says why a step and why
through that hook): outputs, loss and every leaf's gradient norm of
:data:`STEP_WINDOWS` whole window against :func:`loss_and_grads` at
"highest" on the device the process holds, under :data:`STEP_LIMITS`.

Loaded by the child that is about to build the configuration
(``procs/build_worker.py``) in a checkout whose program has no
``kind: smallthinker`` (every commit before PR 39), this module ends
that child at once with exit code 5, as the siblings do, and for the
reason written in ``lfm2_moe_backbone``.
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
            return "def smallthinker(" in f.read()
    except OSError:
        return False


if _MAIN == "build_worker.py" and not _program_has_the_kind():
    print(
        "chipbench: this checkout's program has no kind smallthinker "
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
rms_norm, rotary = _shared.rms_norm, _shared.rotary
model_input, HIGHEST = _shared.model_input, _shared.HIGHEST
#: a piece of queries against every key under a mask from positions
_banded = _sibling("laguna_banded_backbone")
attend = _banded.attend
#: the leaves :func:`step_readings` reads over the sibling's floor (a
#: hundredth of the whole gradient's norm): ``wq`` and ``wk`` as there,
#: and a router
FLOORED_LEAVES = _banded.QK_LEAVES + ("['router']",)

#: what of the spec the forward needs, read by name
SIZES = (
    "layer_ops", "num_key_value_heads", "head_dim", "num_experts", "experts_held",
    "expert_offset", "num_experts_per_tok", "norm_eps", "lookback_window", "sliding_window",
    "rope_parameters",
)

#: queries a piece of the attention (module docstring)
QUERY_ROWS = 512

#: the step check: one whole window of the machine's history (8,192
#: tokens at the published lookback: a sliding layer's edge, 4,096 rows
#: back, acts only in a window longer than that)
STEP_WINDOWS = 1
#: limits of the step check, each between two readings on the v5e
#: (PERF.md, section 6, PR 39, has every one): the largest of 30 sound
#: builds of 29 seeds (ten of the first round, twenty of the review
#: round, the last thirteen of them on the committed program), and the
#: smallest of four builds with ``compute_dtype: bfloat16``, the nearest
#: precision below, which the cell has to read as not correct (all four
#: do, by ``output``, ``loss`` and ``grad_norm`` at 6 to 13 times the
#: limit; by ``leaf`` the two whose worst leaf is no router).
#: ``output``: the window's outputs, max |program - reference| over
#: max(1, max |reference|): sound 1.8e-5 to 5.8e-5, bfloat16 4.0e-3 to
#: 5.0e-3.
#: ``leaf``: the worst gradient norm, of the reference's, as
#: :func:`step_readings` reads it (``wq``, ``wk`` and a router over a
#: floor of a hundredth of the whole): sound 2.9e-4 to 3.9e-3 (the
#: first round's builds whose worst leaf was a router, read then by its
#: own norm: under 7.0e-3), bfloat16 2.2e-2 (layer 0's ``wv``) and
#: 2.4e-2 (layer 3's ``wv``); the other two controls' worst was a router
#: by its own norm. The two lie 5.5 times apart: the limit stands
#: nearer the control, because a fresh seed reads higher and the other
#: three limits refuse the control by themselves.
#: ``loss``: |program - reference| of the reference's: sound 3.2e-7 to
#: 1.4e-5, bfloat16 3.9e-3 to 8.7e-3.
#: ``grad_norm``: the whole gradient's norm, likewise: sound 3.8e-9 to
#: 8.0e-6, bfloat16 6.3e-4 to 1.8e-3.
STEP_LIMITS = {"output": 3e-4, "leaf": 1.8e-2, "loss": 3e-4, "grad_norm": 1e-4}

_LAST: Dict[str, Any] = {}


def layers_of(estimator: Any) -> Dict[str, Any]:
    """The artifact's own weights as float32 arrays, with the sizes of
    its spec: ``{"weights": <the parameter tree>, "sizes": {...}}``. The
    two layouts are the published config's, a flag a layer: 1 where the
    layer is limited by distance, 1 where it rotates."""
    spec = estimator.spec_
    weights = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float32), estimator.params_)
    sizes = {key: getattr(spec, key) for key in SIZES}
    ropes = {op: dict(pairs) for op, pairs in sizes.pop("rope_parameters")}
    ops = sizes.pop("layer_ops")
    sizes["sliding_window_layout"] = [int(op == "sliding_attention") for op in ops]
    sizes["rope_layout"] = [int(ropes[op]["rope_type"] != "none") for op in ops]
    sizes["rope_theta"] = float(ropes["sliding_attention"]["rope_theta"])
    layers = {"weights": weights, "sizes": sizes}
    _LAST.update(estimator=estimator, layers=layers)
    return layers


def attention(u, w, rotated: int, limited: int, sizes, query_rows: int = QUERY_ROWS):
    """``u [B, T, H]`` -> ``(output [B, T, H], pairs attended [B])``."""
    batch, length, _ = u.shape
    kv_heads, width = sizes["num_key_value_heads"], sizes["head_dim"]
    # assumed: no RMSNorm of q and k, no bias (the config names none)
    q = (u @ w["wq"]).reshape(batch, length, -1, width)
    k = (u @ w["wk"]).reshape(batch, length, kv_heads, width)
    v = (u @ w["wv"]).reshape(batch, length, kv_heads, width)
    if rotated:  # assumed: half-rotation over all of a head, positions 0..T-1
        q, k = rotary(q, sizes["rope_theta"]), rotary(k, sizes["rope_theta"])
    # rope_layout 0: no position encoding at all (NoPE): q and k as projected
    heads = q.shape[2]
    k, v = jnp.repeat(k, heads // kv_heads, axis=2), jnp.repeat(v, heads // kv_heads, axis=2)
    # assumed: a limited layer's query sees itself and the window - 1 rows before it
    window = sizes["sliding_window"] if limited else length
    query_rows = min(query_rows, length)
    pieces = -(-length // query_rows)
    padded = jnp.pad(q, ((0, 0), (0, pieces * query_rows - length), (0, 0), (0, 0)))
    in_pieces = jnp.moveaxis(padded.reshape(batch, pieces, query_rows, heads, width), 1, 0)
    piece = jax.checkpoint(lambda one: attend(one[0], k, v, one[1], window))
    out, pairs = jax.lax.map(piece, (in_pieces, jnp.arange(pieces) * query_rows))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, pieces * query_rows, heads, width)[:, :length]
    return out.reshape(batch, length, -1) @ w["wo"], jnp.sum(pairs, axis=0)


def router_gates(x, w, sizes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``x [B, T, H]``, the layer's input -> ``(gates, chosen) [B, T,
    experts]``: each chosen expert's weight, 0 for the others, and which
    are chosen."""
    logits = x @ w["router"]
    # brute force: an expert is chosen if fewer than k experts beat it (a
    # larger logit, or an equal one of a lower index)
    index = jnp.arange(logits.shape[-1])
    beats = (logits[..., None, :] > logits[..., :, None]) | (
        (logits[..., None, :] == logits[..., :, None]) & (index[None, :] < index[:, None])
    )
    chosen = jnp.sum(beats, axis=-1) < sizes["num_experts_per_tok"]
    # assumed (moe_primary_router_apply_softmax, norm_topk_prob): a
    # softmax over the chosen logits alone
    top = jnp.max(logits, axis=-1, keepdims=True)
    weights = jnp.where(chosen, jnp.exp(logits - top), 0.0)
    return weights / jnp.sum(weights, axis=-1, keepdims=True), chosen


def moe_ffn(n, gates, chosen, w, sizes):
    """The share of the routed experts' sum over ``n [B, T, H]`` that
    the experts held give, ``gates`` and ``chosen`` from
    :func:`router_gates`; the
    tokens routed to each published expert; and of the (token, expert)
    pairs of the experts held, the gate units above zero and all of
    them."""
    counts = jnp.sum(chosen, axis=(0, 1)).astype(jnp.int32)
    # departure from the published model: only the experts held here add
    # to the result; what the absent experts would add lies on other chips
    first = sizes["expert_offset"]
    held = slice(first, first + sizes["experts_held"])
    held_gates = jnp.moveaxis(gates[..., held], -1, 0)
    held_chosen = jnp.moveaxis(chosen[..., held], -1, 0)

    def add_expert(carry, expert):  # one expert after another: one loop of one body
        out, active = carry
        w1, w3, w2, expert_gate, routed_here = expert
        pre = n @ w1
        # assumed (sparse ReGLU): relu(gate) * up, then down
        hidden = jax.nn.relu(pre) * (n @ w3)
        active = active + jnp.sum((pre > 0) & routed_here[..., None])
        return (out + expert_gate[..., None] * (hidden @ w2), active), None

    (routed, active), _ = jax.lax.scan(
        add_expert, (jnp.zeros_like(n), jnp.zeros((), jnp.int32)), (w["w1"], w["w3"], w["w2"], held_gates, held_chosen),
    )
    units = jnp.sum(counts[held]) * w["w1"].shape[-1]
    return routed, counts, (active, units)


def block(x, w, rotated: int, limited: int, sizes):
    """One layer; returns ``(out, pairs attended [B], router counts,
    (gate units above zero, gate units))``."""
    # assumed: the router reads the layer's input x, before input_layernorm
    gates, chosen = router_gates(x, w["moe"], sizes)
    out, pairs = attention(rms_norm(x, w["operator_norm"], sizes["norm_eps"]), w["attn"], rotated, limited, sizes)
    h = x + out
    out, counts, units = moe_ffn(rms_norm(h, w["ffn_norm"], sizes["norm_eps"]), gates, chosen, w["moe"], sizes)
    return h + out, pairs, counts, units


def like_layers(sizes) -> List[Tuple[int, int, List[str]]]:
    """The layers in order as runs of like ones: ``(rotated, limited,
    names)`` of consecutive layers with the same two flags (the cut:
    layer 0; layers 1-3)."""
    runs: List[Tuple[int, int, List[str]]] = []
    for i, kind in enumerate(zip(sizes["rope_layout"], sizes["sliding_window_layout"])):
        if runs and runs[-1][:2] == kind:
            runs[-1][2].append(f"layer_{i}")
        else:
            runs.append((*kind, [f"layer_{i}"]))
    return runs


def _forward(weights, sizes, windows):
    # departure from the published model: a linear projection of sensor
    # rows stands where the token embedding stood (vocab_size replaced)
    h = windows @ weights["embed"]["W"] + weights["embed"]["b"]
    found: Dict[str, list] = {"attended": [], "routed": [], "gate_active": [], "gate_total": []}
    for rotated, limited, names in like_layers(sizes):
        # each layer under ``jax.checkpoint``; a run of like layers as
        # one loop of one body over their weights laid side by side
        layer = jax.checkpoint(lambda h, w, _r=rotated, _l=limited: block(h, w, _r, _l, sizes))

        def one_more(h, w, _layer=layer):
            h, pairs, counts, (active, units) = _layer(h, w)
            return h, (pairs, counts, active, units)

        side_by_side = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *[weights[name] for name in names]
        )
        h, rows = jax.lax.scan(one_more, h, side_by_side)
        for key, row in zip(("attended", "routed", "gate_active", "gate_total"), rows):
            found[key].extend(row)
    # departure: the final norm and a linear head to the tags, read at
    # the window's last position, stand where the LM head stood
    last = rms_norm(h[:, -1], weights["head"]["norm"], sizes["norm_eps"])
    out = last @ weights["head"]["W"] + weights["head"]["b"]
    return out, {key: jnp.stack(rows) for key, rows in found.items()}


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
    """Of one batch, a row a layer: ``routed [layers, experts]`` (tokens
    to each published expert), ``attended`` (query-key pairs inside the
    mask), ``gate_active`` and ``gate_total`` (of the pairs of the
    experts held, the gate units above zero and all of them)."""
    with jax.default_matmul_precision(HIGHEST):
        _, found = _forward(layers["weights"], layers["sizes"], jnp.asarray(windows, jnp.float32))
    found = {key: np.asarray(value) for key, value in found.items()}
    return dict(found, attended=found["attended"].sum(axis=1))


def loss_and_grads(layers: Dict[str, Any], windows, targets, weights=None):
    """The weighted mean squared error of a batch and its gradient with
    respect to every weight."""
    return _loss_grads_outputs(layers, windows, targets, weights)[:2]


def _loss_grads_outputs(layers: Dict[str, Any], windows, targets, weights=None):
    windows = jnp.asarray(windows, jnp.float32)
    targets = jnp.asarray(targets, jnp.float32)
    w = jnp.ones(len(windows), jnp.float32) if weights is None else jnp.asarray(weights, jnp.float32)

    # the batch is an argument and no constant of the program (module docstring)
    def loss_of(tree, windows, targets, w):
        out, _ = _forward(tree, layers["sizes"], windows)
        # assumed: no load-balancing term (the config names none)
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
        if share == 1.0:  # one block: the gradient as it is, no second copy of 1.85 GB
            grads = block_grads
        else:
            scaled = jax.tree_util.tree_map(lambda g: share * g, block_grads)
            grads = scaled if grads is None else jax.tree_util.tree_map(np.add, grads, scaled)
    norms = jax.tree_util.tree_map(
        lambda g: float(np.sqrt(np.sum(np.square(g, dtype=np.float64)))), grads
    )
    return loss, norms, np.concatenate(outputs)


def step_readings(loss: float, norms: Any, ref_loss: float, ref_norms: Any) -> Dict[str, Any]:
    """The sibling ``laguna_banded_backbone``'s readings, with a
    router's leaf read as it reads ``wq`` and ``wk``: ``|program ** 2 -
    reference ** 2| / (2 (reference ** 2 + floor ** 2))``, ``floor`` a
    hundredth of the whole gradient's norm. A router's gradient is no
    smooth function of its input: the program's layer input differs from
    the reference's by the rounding of the products before it (5e-4 of a
    logit's scale at layer 0, whose input is the 50-tag projection at
    the program's default precision), a token whose sixth and seventh
    logits lie nearer than that goes to another expert on one side, and
    every such token moves the router's gradient by a whole token's
    share. On one sound build of the v5e (seed 2147393505: 12 of a
    window's 8,192 tokens, sixth and seventh logits 1e-4 to 2e-3 apart
    where a logit's spread is 2.0; rematerialised or not, to the last
    digit) layer 0's router read 2.5e-2 of its own norm, where in
    seventeen other sound builds no leaf read over 7e-3 and the bfloat16
    control reads 2.4e-2 and up: no limit lies between (PERF.md, section
    6, PR 39's review round). The leaf carries 2e-3 of the whole
    gradient; read over the floor it says what it adds to the whole's
    square (that build: 9e-4), and a router three times what it should
    be still reads 0.14. Every
    other leaf is read as the sibling reads it. ``router_leaf``, the
    worst router's error as a fraction of its own norm, is printed
    beside the readings and held to nothing."""
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
