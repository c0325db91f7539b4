"""
Plain float32 reference of the Phi-4-mini-flash-reasoning sensor backbone
(microsoft/Phi-4-mini-flash-reasoning, ``model_type: phi4flash``, the
SambaY decoder-hybrid-decoder of arXiv:2507.06607: selective state-space
layers, differential attention in a window of 512 rows and in full, and
a cross-decoder whose gated memory units read one earlier layer's scan
output and whose attention reads one earlier layer's keys and values;
no position encoding at all, no routed layer): the forward pass, the
loss and its gradients, in straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, the equations as ISSUE 45
writes them out. Every layer is

    u   = LayerNorm(x; g_1, b_1)                  eps 1e-5, a gain and a bias
    h   = x + Mixer(u)
    out = h + W_2( silu(n W_1) * (n W_3) ),  n = LayerNorm(h; g_2, b_2)

and the mixers

    mamba:      [xs | z] = u W_in
                c_t   = silu( sum_k w_conv[:, k] * xs_{t-3+k} + b_conv )   four shifted sums
                [r_t | B_t | C_t] = c_t W_x
                dt_t  = softplus( r_t W_dt + b_dt )
                A     = -exp(A_log)
                s_t   = exp(dt_t (x) A) * s_{t-1} + (dt_t * c_t) (x) B_t   s_{-1} = 0
                y_t   = s_t C_t + D * c_t
                Mixer = ( y * silu(z) ) W_out;  hands on M = y
    gmu:        Mixer = ( M * silu(u W_g) ) W_o
    attention:  q = u W_q + b_q;  k = u W_k + b_k;  v = u W_v + b_v
                q1_j = q[2j], q2_j = q[2j+1];  k1_p = k[2p], k2_p = k[2p+1];  V_p = [v[2p] | v[2p+1]]
                A1_j = softmax(q1_j k1_p^T / 8 + mask) V_p,  A2_j likewise,  p = j // 2
                lam  = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_0,  lam_0 = 0.8 - 0.6 exp(-0.3 l)
                O_j  = RMSNorm(A1_j - lam A2_j; g_sub) * (1 - lam_0)
                Mixer = [O_0 | .. ] W_o + b_o;  a full layer hands on its k and v
    cross_attention:  q alone; k, v the full layer's; the rest as above

The scan is one plain ``lax.scan`` over the window's rows with the state
as its carry (no chunks, no derivative rule of its own: ``jax.grad`` goes
through it), the convolution four shifted sums, the two maps two
softmaxes over a mask built from positions (``s <= t and t - s <
window``) against every key of the window: no tile is skipped, no
running softmax, no stacked heads. ``M``, ``k`` and ``v`` are passed by
the index of the layer that makes them. Nothing here comes from
``gordo_tpu``: the artifact's weights and the spec's sizes are read off
the estimator as plain arrays and numbers; the helpers that have nothing
of this model in them (the gated feed-forward, the windows, a piece of
masked attention, the readings) are the sibling references', loaded by
path.

What ``configs/phi4-mini-flash-50tag-lb8192.json`` lists under
``assumed`` is marked "assumed" where it enters here.

Three departures from "plain" that are none from the mathematics. The
attention runs :data:`QUERY_ROWS` queries at a time against every key of
the window (an 8,192-row window's ``[20, T, T]`` scores of one map are
5.4 GB a layer), each such piece and each layer under
``jax.checkpoint``. The scan's state is carried with its channels last,
``[16, 5120]``: the same numbers in the order that fills a TPU
register's lanes (a ``[5120, 16]`` carry saved for 8,192 rows is padded
to eight times its 2.7 GB); and its step is under ``jax.checkpoint``, so
that the backward keeps the carry of each row and computes the row's
``exp`` again, not both. The jitted functions take their batch as
arguments: a run's data are its seed's, and a program that held them
would be another to compile in every run.

What ``harness/correct.py`` holds a build to with it: the artifact's
prediction against :func:`forward` of the artifact's own weights, and
one training step at those weights (:func:`loss_band`, the hook the
harness has; ``lfm2_moe_backbone``'s docstring says why a step and why
through that hook): outputs, loss and every leaf's gradient norm of
:data:`STEP_WINDOWS` whole window, the program's own training loss with
its products at "highest" against :func:`loss_and_grads` at "highest",
both on the device the process holds, under :data:`STEP_LIMITS`.

Loaded by the child that is about to build the configuration
(``procs/build_worker.py``) in a checkout whose program has no
``kind: phi4flash`` (every commit before PR 45), this module ends that
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
            return "def phi4flash(" in f.read()
    except OSError:
        return False


if _MAIN == "build_worker.py" and not _program_has_the_kind():
    print(
        "chipbench: this checkout's program has no kind phi4flash "
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
dense_ffn, rms_norm = _shared.dense_ffn, _shared.rms_norm
model_input, HIGHEST = _shared.model_input, _shared.HIGHEST
#: a piece of queries against every key under a mask from positions (its
#: scores scaled by the width of ``q``, its values as wide as ``v``), and
#: the floor a small leaf's gradient is read over
_banded = _sibling("laguna_banded_backbone")
attend, FLOOR = _banded.attend, _banded.QK_FLOOR
#: the leaves :func:`step_readings` reads over that floor (a hundredth
#: of the whole gradient's norm): what reaches a softmax that smooth rows
#: leave nearly flat (``wq``, ``wk`` and their biases), the four vectors
#: of the pair's weight, and the scan's own small leaves
FLOORED_LEAVES = (
    "['wq']", "['wk']", "['bq']", "['bk']", "['lambda_q1']", "['lambda_k1']", "['lambda_q2']",
    "['lambda_k2']", "['A_log']", "['D']", "['dt_bias']", "['conv_bias']",
)

#: what of the spec the forward needs, read by name
SIZES = (
    "layer_ops", "num_attention_heads", "num_key_value_heads", "head_dim", "sliding_window",
    "ssm_inner", "ssm_state", "ssm_dt_rank", "norm_eps", "lookback_window",
)

#: queries a piece of the attention (module docstring)
QUERY_ROWS = 512

#: the step check: one whole window of the machine's history (8,192
#: tokens at the published lookback: the scan integrates over all of
#: them, and the far keys of the full and the cross layer act only in a
#: window longer than a tile)
STEP_WINDOWS = 1
#: limits of the step check, each between two readings on the v5e
#: (PERF.md, section 6, PR 45, has every one with its seed). BOTH sides
#: of the step check compute their products at "highest"
#: (:func:`loss_band`): at the default precision a float32 product on
#: the TPU is one bfloat16 pass, the same rounded weights meet every one
#: of a window's 8,192 rows, the scan integrates what that moves, and
#: how far program and reference then lie apart says where the build
#: left the weights and nothing of the program. Read so, ``grad_norm``
#: spread from 7.6e-7 to 8.1e-4 over 21 sound builds of 18 seeds, and
#: at seed 260743010 (layer 0's ``mamba`` carries ``out_proj`` 38%,
#: ``x_proj`` 19%, ``in_proj`` 17% of the whole gradient, each 0.1%
#: high) the sound build's 8.1e-4 stood ABOVE the bfloat16 build's
#: 7.7e-4 of the same seed: no limit lies between those. With the
#: program's products at "highest" too, ten sound builds of ten seeds
#: read (260743010, the one whose ``grad_norm`` read 8.1e-4 above,
#: twice, the second time through ``run.py`` from the committed files,
#: equal to the digit; 97531, 1357924680, 805306457, 1999999999, 31337,
#: 777000111, 1234567891, 2024100301, 600000007) and the two controls,
#: both of seed 260743010, which the cell has to read as not correct:
#: ``compute_dtype: bfloat16`` (every product but the scan's) and,
#: because the scan is float32 by rule, the scan alone in bfloat16
#: (state, arithmetic and the two products that set its step and its
#: matrices). Every limit refuses both controls.
#: ``output``: the window's outputs, max |program - reference| over
#: max(1, max |reference|): sound 9.0e-8 to 2.3e-7; the scan alone in
#: bfloat16 8.5e-5, bfloat16 2.6e-3.
#: ``loss``: |program - reference| of the reference's: sound 0 to
#: 2.9e-7; the scan alone 1.5e-5, bfloat16 4.8e-4.
#: ``grad_norm``, the whole gradient's norm, of the reference's: sound
#: 1.6e-8 to 8.9e-7 (eight of the ten under 2.6e-7); the scan alone
#: 7.5e-5, bfloat16 7.7e-4.
#: ``leaf``: the worst gradient norm, of the reference's, as
#: :func:`step_readings` reads it (most often a leaf of layer 0's
#: ``mamba``: ``x_proj``, ``in_proj``, ``conv_kernel``): sound 2.2e-7 to
#: 9.8e-5 (six of the ten under 1e-5); the scan alone 7.2e-3, bfloat16
#: 8.3e-3. Each limit stands near the geometric mean of the largest
#: sound reading and the smaller control's, ``leaf`` nearer the
#: controls because its sound readings have the longer tail: 15 times
#: of room below it, 4.8 above.
STEP_LIMITS = {"output": 5e-6, "leaf": 1.5e-3, "loss": 2e-6, "grad_norm": 8e-6}

_LAST: Dict[str, Any] = {}


def layers_of(estimator: Any) -> Dict[str, Any]:
    """The artifact's own weights as float32 arrays, with the sizes of
    its spec: ``{"weights": <the parameter tree>, "sizes": {...}}``."""
    spec = estimator.spec_
    weights = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float32), estimator.params_)
    layers = {"weights": weights, "sizes": {key: getattr(spec, key) for key in SIZES}}
    _LAST.update(estimator=estimator, layers=layers)
    return layers


def layer_norm(x, w, eps):
    """assumed: ``nn.LayerNorm``, a gain and a bias (not an RMS norm)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    variance = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(variance + eps) * w["gain"] + w["bias"]


def sources_of(layer_ops) -> Dict[int, int]:
    """For each ``gmu`` the last ``mamba`` layer before it, for each
    ``cross_attention`` the last ``full_attention`` before it."""
    found, last = {}, {}
    for i, op in enumerate(layer_ops):
        if op == "gmu":
            found[i] = last["mamba"]
        if op == "cross_attention":
            found[i] = last["full_attention"]
        last[op] = i
    return found


def mamba(u, w, sizes):
    """``u [B, T, H]`` -> ``(output [B, T, H], y [B, T, inner])``."""
    d, n, rank = sizes["ssm_inner"], sizes["ssm_state"], sizes["ssm_dt_rank"]
    length = u.shape[1]
    stream = u @ w["in_proj"]  # assumed: no bias
    xs, z = stream[..., :d], stream[..., d:]
    # four shifted sums; assumed: zeros before the window (no state carried in)
    taps = w["conv_kernel"].shape[1]
    conv = w["conv_bias"]
    for k in range(taps):
        late = taps - 1 - k
        conv = conv + w["conv_kernel"][:, k] * jnp.pad(xs, ((0, 0), (late, 0), (0, 0)))[:, :length]
    c = jax.nn.silu(conv)
    row = c @ w["x_proj"]
    dt = jax.nn.softplus(row[..., :rank] @ w["dt_proj"] + w["dt_bias"])
    b_rows, c_rows = row[..., rank : rank + n], row[..., rank + n :]
    rates = -jnp.exp(w["A_log"]).T  # [state, inner]: the channels last (module docstring)

    def step(state, one):
        c_t, dt_t, b_t, out_t = one
        state = jnp.exp(dt_t[None, :] * rates) * state + (dt_t * c_t)[None, :] * b_t[:, None]
        return state, jnp.sum(state * out_t[:, None], axis=0)

    def window(c_w, dt_w, b_w, out_w):  # assumed: the state before a window is zero
        return jax.lax.scan(jax.checkpoint(step), jnp.zeros_like(rates), (c_w, dt_w, b_w, out_w))[1]

    y = jax.vmap(window)(c, dt, b_rows, c_rows) + w["D"] * c
    return (y * jax.nn.silu(z)) @ w["out_proj"], y  # assumed: M is y, before the gate, with the D term


def gated_memory(u, w, memory):
    return (memory * jax.nn.silu(u @ w["in_proj"])) @ w["out_proj"]


def in_pieces(q, k, v, window: int, query_rows: int):
    """``q [B, T, heads, d]`` against every key ``k``, ``v [B, T, heads,
    .]`` under the mask of ``window``, :data:`QUERY_ROWS` queries at a
    time: ``(out [B, T, heads, dv], pairs inside the mask [B])``."""
    batch, length, heads, width = q.shape
    query_rows = min(query_rows, length)
    pieces = -(-length // query_rows)
    padded = jnp.pad(q, ((0, 0), (0, pieces * query_rows - length), (0, 0), (0, 0)))
    parts = jnp.moveaxis(padded.reshape(batch, pieces, query_rows, heads, width), 1, 0)
    piece = jax.checkpoint(lambda one: attend(one[0], k, v, one[1], window))
    out, pairs = jax.lax.map(piece, (parts, jnp.arange(pieces) * query_rows))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, pieces * query_rows, heads, -1)[:, :length]
    return out, jnp.sum(pairs, axis=0)


def differential_attention(u, w, op: str, index: int, sizes, kv=None, query_rows: int = QUERY_ROWS):
    """``u [B, T, H]`` -> ``(output [B, T, H], pairs inside the mask
    [B], (k, v))``; ``kv``: the full layer's, for a cross layer."""
    batch, length, _ = u.shape
    heads, kv_heads, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    # assumed: a bias on the attention's projections and none elsewhere; no rotary, no q/k norm
    q = (u @ w["wq"] + w["bq"]).reshape(batch, length, heads, dh)
    if kv is None:
        k = (u @ w["wk"] + w["bk"]).reshape(batch, length, kv_heads, dh)
        v = (u @ w["wv"] + w["bv"]).reshape(batch, length, kv_heads, dh)
    else:  # a cross layer has no key and no value of its own
        k, v = kv
    # assumed: neighbouring heads pair up
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
    k1, k2 = k[:, :, 0::2], k[:, :, 1::2]
    value = jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1)
    # differential head j reads pair j // group: the pair's keys and value, once a head
    group = (heads // 2) // (kv_heads // 2)
    k1, k2, value = (jnp.repeat(t, group, axis=2) for t in (k1, k2, value))
    # assumed: a sliding query sees itself and the sliding_window - 1 rows before it
    window = sizes["sliding_window"] if op == "sliding_attention" else length
    first, pairs = in_pieces(q1, k1, value, window, query_rows)
    second, _ = in_pieces(q2, k2, value, window, query_rows)
    # assumed: lam_0 from the layer's index among the layers held
    start = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + start
    # assumed: the sub-norm's eps is the layers'
    out = rms_norm(first - lam * second, w["sub_norm"], sizes["norm_eps"]) * (1.0 - start)
    return out.reshape(batch, length, -1) @ w["wo"] + w["bo"], pairs, (k, v)


def block(h, w, op: str, index: int, sizes, read=None):
    """One layer; returns ``(h, what it makes, pairs attended [B] or None)``."""
    u = layer_norm(h, w["operator_norm"], sizes["norm_eps"])
    made = pairs = None
    if op == "mamba":
        out, made = mamba(u, w["mamba"], sizes)
    elif op == "gmu":
        out = gated_memory(u, w["gmu"], read)
    else:
        out, pairs, made = differential_attention(u, w["attn"], op, index, sizes, read)
    h = h + out
    return h + dense_ffn(layer_norm(h, w["ffn_norm"], sizes["norm_eps"]), w["ffn"]), made, pairs


def _forward(weights, sizes, windows):
    # departure from the published model: a linear projection of sensor
    # rows stands where the token embedding stood (vocab_size replaced)
    h = windows @ weights["embed"]["W"] + weights["embed"]["b"]
    sources = sources_of(sizes["layer_ops"])
    made: Dict[int, Any] = {}
    attended, scanned = [], []
    for i, op in enumerate(sizes["layer_ops"]):
        layer = jax.checkpoint(lambda h, w, read, _op=op, _i=i: block(h, w, _op, _i, sizes, read))
        h, made[i], pairs = layer(h, weights[f"layer_{i}"], made.get(sources.get(i)))
        if pairs is not None:
            attended.append(pairs)
        if op == "mamba":
            scanned.append(jnp.full((windows.shape[0],), windows.shape[1]))
    # departure: the final norm (assumed: a LayerNorm) and a linear head
    # to the tags, read at the window's last position, stand where the LM head stood
    last = layer_norm(h[:, -1], weights["head"]["norm"], sizes["norm_eps"])
    out = last @ weights["head"]["W"] + weights["head"]["b"]
    return out, {"attended": jnp.stack(attended), "scanned": jnp.stack(scanned)}


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
    """Of one batch: ``attended [attention layers]`` (query-key pairs
    inside the mask, once a layer: both maps read one mask) and
    ``scanned [mamba layers]`` (rows a scan stepped over)."""
    with jax.default_matmul_precision(HIGHEST):
        _, found = _forward(layers["weights"], layers["sizes"], jnp.asarray(windows, jnp.float32))
    return {
        "attended": np.asarray(found["attended"]).sum(axis=1),
        "scanned": np.asarray(found["scanned"]).sum(axis=1),
    }


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
        if share == 1.0:  # one block: the gradient as it is, no second copy of 2.53 GB
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
    ``kanana_latent_backbone`` read theirs and for the reasons written
    there: ``|program ** 2 - reference ** 2| / (2 (reference ** 2 +
    floor ** 2))``, ``floor`` a hundredth of the whole gradient's norm.
    Well above the floor that is the norm's relative error, as for every
    other leaf; below it what the leaf adds to the whole's square.
    ``small_leaves`` and ``worst_leaves``, the worst plain relative
    errors among the floored leaves and among the others with each
    leaf's share of the whole gradient, are printed beside the readings
    and held to nothing; so is ``heavy_leaves``, the leaves that make
    most of ``grad_norm``: ``(program ** 2 - reference ** 2) / (2 whole
    ** 2)`` with its sign (over all leaves they sum to the whole norm's
    relative error, to first order), the leaf and its share."""
    readings = _shared.step_readings(loss, norms, ref_loss, ref_norms)
    whole = readings["reference"]["grad_norm"]
    worst, small, plain, heavy = (0.0, ""), [], [], []
    for (path, value), ref in zip(
        jax.tree_util.tree_flatten_with_path(norms)[0], jax.tree_util.tree_leaves(ref_norms)
    ):
        name = jax.tree_util.keystr(path)
        heavy.append(((value * value - ref * ref) / (2.0 * whole * whole), name, ref / whole))
        if name.endswith(FLOORED_LEAVES):
            off = abs(value * value - ref * ref) / (2.0 * (ref * ref + (FLOOR * whole) ** 2))
            if ref > 0.0:
                small.append((abs(value - ref) / ref, name, ref / whole))
        elif ref > 1e-3 * whole:  # the siblings' "a leaf that carries gradient"
            off = abs(value - ref) / ref
            plain.append((off, name, ref / whole))
        else:
            continue
        worst = max(worst, (off, name))
    readings["leaf"], readings["worst_leaf"] = worst
    readings["small_leaves"] = sorted(small, reverse=True)[:4]
    readings["worst_leaves"] = sorted(plain, reverse=True)[:4]
    readings["heavy_leaves"] = sorted(heavy, key=lambda leaf: -abs(leaf[0]))[:6]
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
    # the program's step and forward with their products at "highest", as
    # the reference's are (STEP_LIMITS says why): the build ran them at the
    # default precision, and the harness's forward check holds that one
    with jax.default_matmul_precision(HIGHEST):
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
            "products_at": HIGHEST, "reference_on": str(jax.devices()[0]),
        }),
        flush=True,
    )
    return (math.nan, math.nan) if over else (0.0, sys.float_info.max)
