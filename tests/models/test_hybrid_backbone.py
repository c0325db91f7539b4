"""``kind: phi4flash`` at toy widths on the CPU against its plain reference
(loaded by path: it imports nothing of the program's layer code): a
selective scan in chunks against the plain ``lax.scan``, differential
heads through the tile loops and through the attention in one piece,
gated memory units and attention that read an earlier layer's tensors
through the layer loop's checkpoints; and the program, the weights and
the span attributes of the kinds that were here before it, which this
kind's arrival must not move (``lfm2_moe`` and ``keye_vl2`` are pinned in
``test_banded_backbone.py``, ``laguna`` in ``test_prerouted_backbone.py``,
``smallthinker`` in ``test_latent_backbone.py``; ``kanana`` here)."""

import hashlib
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import JaxBackboneForecast, backbone
from gordo_tpu.models.factories import phi4flash
from gordo_tpu.models.factories.backbone import PHI_4_MINI_FLASH_CONFIG, phi4flash_layer_types
from gordo_tpu.models.spec import BackboneSpec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
TOLERANCE = 1e-4  # of scale: both sides compute in float32 on the CPU
CUT = ("mamba", "sliding_attention", "mamba", "full_attention", "gmu", "cross_attention")
#: a window of 24 rows in tiles of 4 and chunks of 4 (every attention in
#: the tile loops, six chunks of scan), or under the tile and the chunk
#: as they ship (one chunk; the full and the cross layer hold every
#: score at once); a sliding query sees 6 rows
T, TILE, CHUNK, WINDOW = 24, 4, 4, 6
SHIPPED = (backbone.ATTENTION_TILE, backbone.SCAN_CHUNK)
#: 8 query heads of 4 over 4 key/value heads: 4 differential heads over
#: 2 pairs, so two heads read one pair, as 20 read 10
HEADS, KV_HEADS, HIDDEN, DH = 8, 4, 32, 4
INNER, STATE, RANK = 2 * HIDDEN, 16, 2


@pytest.fixture(params=[(TILE, CHUNK), SHIPPED], ids=["tiles_and_chunks_of_four", "one_piece"])
def blocks(request, monkeypatch):
    """The tile and the chunk are the program's constants, not options of a spec."""
    monkeypatch.setattr(backbone, "ATTENTION_TILE", request.param[0])
    monkeypatch.setattr(backbone, "SCAN_CHUNK", request.param[1])
    return request.param


@pytest.fixture
def of_four(monkeypatch):
    monkeypatch.setattr(backbone, "ATTENTION_TILE", TILE)
    monkeypatch.setattr(backbone, "SCAN_CHUNK", CHUNK)
    return monkeypatch


def load_by_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reference(name):
    return load_by_path(f"reference_{name}", os.path.join(CHIP, "reference", f"{name}.py"))


def sibling_tests(name):
    """A sibling test file, for its toy (``tests/models`` is no package)."""
    return load_by_path(f"sibling_{name}", os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py"))


@pytest.fixture(scope="module")
def reference():
    return load_reference("phi4flash_hybrid_backbone")


def toy(**overrides) -> BackboneSpec:
    """The cut's six layers at hidden 32."""
    sizes = dict(
        lookback_window=T, num_hidden_layers=6, layer_types=CUT, hidden_size=HIDDEN,
        num_attention_heads=HEADS, num_key_value_heads=KV_HEADS, intermediate_size=48, sliding_window=WINDOW,
    )
    sizes.update(overrides)
    return phi4flash(5, **sizes)


class Artifact:
    def __init__(self, spec, params):
        self.spec_, self.params_ = spec, params


def unseated(params, seed=1):
    """``params`` with every vector moved off its seeded 0 or 1: a bias
    that is zero, or a gain that is one, hides a bias left out."""
    rng = np.random.RandomState(seed)
    leaves, tree = jax.tree_util.tree_flatten(params)
    moved = [
        leaf + 0.05 * rng.standard_normal(leaf.shape).astype(np.float32) if leaf.ndim == 1 else leaf
        for leaf in leaves
    ]
    return jax.tree_util.tree_unflatten(tree, moved)


@pytest.fixture(scope="module")
def seeded(reference):
    spec = toy()
    params = unseated(backbone.init_backbone(jax.random.PRNGKey(7), spec))
    layers = reference.layers_of(Artifact(spec, params))
    rng = np.random.RandomState(3)
    x = rng.uniform(0, 1, (4, T, 5)).astype(np.float32)
    y = rng.uniform(0, 1, (4, 5)).astype(np.float32)
    return spec, params, layers, x, y


def close(got, want, what="", tolerance=TOLERANCE):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    scale = max(1.0, float(np.max(np.abs(want)))) if want.size else 1.0
    assert float(np.max(np.abs(got - want))) <= tolerance * scale, what


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(CHIP, "reference", "phi4flash_hybrid_backbone.py")) as f:
        source = f.read()
    code = source.split('"""', 2)[2]
    assert "import gordo_tpu" not in code and "from gordo_tpu" not in code
    assert "lax.scan(" in code and "custom_vjp" not in code


# ---------------------------------------------------------------------------
# the factory and the spec


def test_the_factorys_defaults_are_the_catalogs_row():
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
    assert row["config"] == PHI_4_MINI_FLASH_CONFIG
    spec = phi4flash(50)
    assert (spec.hidden_size, spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim) == (2560, 40, 20, 64)
    assert (spec.intermediate_size, spec.sliding_window, spec.norm_eps, spec.lookback_window) == (10240, 512, 1e-5, 8192)
    assert (spec.ssm_inner, spec.ssm_state, spec.ssm_conv, spec.ssm_dt_rank) == (5120, 16, 4, 160)
    assert spec.norm == "layer" and spec.attention_bias and spec.differential and not spec.qk_norm
    assert all(spec.rope_of(op)["rope_type"] == "none" for op in ("sliding_attention", "full_attention", "cross_attention"))
    assert set(spec.layer_ffns) == {"dense"} and spec.windowed and not spec.member_axis
    from gordo_tpu.planner.packing import trains_alone

    assert trains_alone(spec) and spec.forward_aux_fn() is backbone.forward_backbone_aux


def test_the_published_pattern_is_the_models_rule_and_a_cut_names_its_layers():
    published = phi4flash(50).layer_ops
    assert published == phi4flash_layer_types(32) and len(published) == 32
    # the self-decoder: eight periods; the memory; the one full layer; the cross-decoder: seven periods
    assert published[:16] == ("mamba", "sliding_attention") * 8
    assert published[16:18] == ("mamba", "full_attention") and published[18:] == ("gmu", "cross_attention") * 7
    assert phi4flash(50).layer_sources == (None,) * 18 + (16, 17) * 7
    # the rule gives every kind first at eight layers, and nothing at six
    assert phi4flash_layer_types(8) == ("mamba", "sliding_attention") * 2 + ("mamba", "full_attention", "gmu", "cross_attention")
    with pytest.raises(ValueError, match="divisible by 4"):
        phi4flash(50, num_hidden_layers=6)
    cut = phi4flash(50, num_hidden_layers=6, layer_types=CUT)
    assert cut.layer_ops == CUT and cut.layer_sources == (None, None, None, None, 2, 3)
    with pytest.raises(ValueError, match="a layer type for every layer held"):
        phi4flash(50, num_hidden_layers=6, layer_types=CUT[:4])


def test_what_the_kind_refuses_it_refuses_in_words():
    with pytest.raises(ValueError, match="layer 0 is a gmu and no mamba layer comes before it"):
        toy(num_hidden_layers=2, layer_types=("gmu", "mamba"))
    with pytest.raises(ValueError, match="layer 1 is a cross_attention and no full_attention layer comes before it"):
        toy(num_hidden_layers=2, layer_types=("sliding_attention", "cross_attention"))
    for key, value in (("mb_per_layer", 3), ("mlp_bias", True), ("lm_head_bias", True), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=f"phi4flash runs {key}="):
            toy(**{key: value})
    toy(mb_per_layer=2, mlp_bias=False, hidden_act="silu")  # as published: taken
    with pytest.raises(ValueError, match="unknown layer kinds"):
        toy(num_hidden_layers=1, layer_types=("attention",))
    with pytest.raises(ValueError, match="even number of query and of key/value heads"):
        toy(num_attention_heads=3, num_key_value_heads=3, hidden_size=36)


def test_param_count_is_the_count_of_the_leaves():
    for spec in (toy(), phi4flash(50, num_hidden_layers=6, layer_types=CUT), phi4flash(50)):
        shapes = jax.eval_shape(lambda key, s=spec: backbone.init_backbone(key, s), jax.random.PRNGKey(0))
        assert backbone.trained_param_count(shapes) == spec.param_count()
        assert spec.flops_per_sample() > 2.0 * spec.param_count() * spec.lookback_window * 0.9
    cut, published = phi4flash(50, num_hidden_layers=6, layer_types=CUT), phi4flash(50)
    # a layer of each kind, as ISSUE 45 reckons them
    kinds = {op: cut.layer_param_count(op, "dense") for op in CUT}
    assert kinds == {
        "mamba": 119_895_040, "sliding_attention": 98_322_304, "full_attention": 98_322_304,
        "gmu": 104_867_840, "cross_attention": 91_766_144,
    }
    ends = (50 * 2560 + 2560) + (2 * 2560 + 2560 * 50 + 50)
    assert cut.param_count() == 633_068_672 + ends == 633_332_402
    # the vocabulary put back by arithmetic: the model's published 3.8 B
    assert published.param_count() - ends + 2 * 2560 + 200_064 * 2560 == 3_852_562_944
    shapes = jax.eval_shape(lambda key: backbone.init_backbone(key, cut), jax.random.PRNGKey(0))
    mixer = shapes["layer_0"]["mamba"]
    assert mixer["in_proj"].shape == (2560, 10240) and mixer["x_proj"].shape == (5120, 192)
    assert mixer["dt_proj"].shape == (160, 5120) and mixer["A_log"].shape == (5120, 16)
    assert mixer["conv_kernel"].shape == (5120, 4) and mixer["out_proj"].shape == (5120, 2560)
    assert set(shapes["layer_5"]["attn"]) == {
        "wq", "bq", "wo", "bo", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "sub_norm",
    }
    assert shapes["layer_3"]["attn"]["wk"].shape == (2560, 1280) and shapes["layer_3"]["attn"]["sub_norm"].shape == (128,)
    assert shapes["layer_4"]["gmu"]["in_proj"].shape == (2560, 5120)
    assert set(shapes["layer_1"]["operator_norm"]) == set(shapes["head"]["norm"]) == {"gain", "bias"}


def test_the_seeded_scan_is_the_familys():
    w = backbone.init_backbone(jax.random.PRNGKey(7), toy())["layer_0"]["mamba"]
    assert np.allclose(w["A_log"], np.log(np.arange(1, 17))[None, :].repeat(INNER, 0)) and np.all(w["D"] == 1)
    steps = np.asarray(jax.nn.softplus(w["dt_bias"]))
    assert steps.min() >= 1e-3 * 0.999 and steps.max() <= 1e-1 * 1.001 and np.all(w["conv_bias"] == 0)
    attn = backbone.init_backbone(jax.random.PRNGKey(7), toy())["layer_1"]["attn"]
    assert 0.02 < float(jnp.std(attn["lambda_q1"])) < 0.3 and np.all(attn["bq"] == 0)


# ---------------------------------------------------------------------------
# the program against the reference


def test_the_forward_is_the_references(blocks, seeded, reference):
    spec, params, layers, x, _ = seeded
    out, penalty, aux = backbone.forward_backbone_aux(spec, params, x)
    close(out, reference.forward(layers, x), "forward")
    assert float(penalty) == 0.0
    found = reference.counters(layers, x)
    banded = WINDOW * (WINDOW + 1) / 2 + (T - WINDOW) * WINDOW
    causal = T * (T + 1) / 2
    assert found["attended"].tolist() == [4 * banded, 4 * causal, 4 * causal]
    # to the digit: the reference counts its masks and its rows, the program says its arithmetic
    assert aux["scan_steps"].tolist() == found["scanned"].tolist() == [4.0 * T] * 2
    if blocks == (TILE, CHUNK):  # all three attentions in the tile loops
        assert aux["pairs_attended"].tolist() == found["attended"].tolist()
        # six blocks: a window of 6 rows reaches two tiles back, 1 + 2 + 4 x 3; a causal layer 1 + .. + 6
        assert aux["pairs_multiplied"].tolist() == [4.0 * 15 * 16, 4.0 * 21 * 16, 4.0 * 21 * 16]
    else:  # the sliding layer alone runs in tiles: one tile of 24 rows
        assert aux["pairs_attended"].tolist() == [4 * banded] and aux["pairs_multiplied"].tolist() == [4.0 * T * T]
    # no layer routes: nothing of a router's among the counters
    assert not {"router_tokens", "pairs_here", "pairs_total"} & set(aux)


def test_a_window_of_padding_adds_nothing_to_the_counters(of_four, seeded, reference):
    spec, params, layers, x, _ = seeded
    aux = backbone.forward_backbone_aux(spec, params, x, active=jnp.asarray([True, False, True, False]))[2]
    found = reference.counters(layers, x[:2])
    assert aux["pairs_attended"].tolist() == found["attended"].tolist()
    assert aux["scan_steps"].tolist() == found["scanned"].tolist() == [2.0 * T] * 2
    assert aux["pairs_multiplied"].tolist() == [2.0 * 15 * 16, 2.0 * 21 * 16, 2.0 * 21 * 16]


def test_every_leafs_gradient_is_the_references(blocks, seeded, reference):
    spec, params, layers, x, y = seeded
    want_loss, want = reference.loss_and_grads(layers, x, y)

    def loss_of(tree):
        out, penalty, _ = backbone.forward_backbone_aux(spec, tree, x)
        return jnp.mean(jnp.mean((out - y) ** 2, axis=-1)) + penalty

    loss, got = jax.jit(jax.value_and_grad(loss_of))(params)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    leaves = jax.tree_util.tree_flatten_with_path(got)[0]
    # the two ends; six layers of two norms and a feed-forward; two scans, a gate, two attentions, a cross layer
    assert len(leaves) == (2 + 4) + 6 * (4 + 3) + 2 * 9 + 2 + 2 * 13 + 9
    for (path, leaf), ref in zip(leaves, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        close(leaf, ref, name)
        # every leaf learns but a key's bias: it moves every score of a query alike, and a softmax does not see it
        assert (float(np.max(np.abs(ref))) > 1e-6) != name.endswith("['bk']"), name


@pytest.mark.parametrize("active", [None, [True, False, True, True]])
def test_remat_on_and_off_give_the_same_outputs_and_gradients(blocks, seeded, active):
    spec, params, _, x, y = seeded
    weights = jnp.ones(4) if active is None else jnp.asarray(active, jnp.float32)
    active = None if active is None else jnp.asarray(active)

    def loss_of(tree, remat):
        out, _, aux = backbone.forward_backbone_aux(spec, tree, x, remat=remat, active=active)
        return jnp.sum(jnp.mean((out - y) ** 2, axis=-1) * weights) / jnp.sum(weights), (out, aux)

    step = jax.jit(jax.value_and_grad(loss_of, has_aux=True), static_argnums=1)
    (plain_loss, (plain_out, plain_aux)), plain = step(params, False)
    (remat_loss, (remat_out, remat_aux)), remat = step(params, True)
    # the same numbers up to rounding: the compiler fuses a block that is computed twice another way
    close(plain_out, remat_out, "outputs", 1e-6)
    assert float(plain_loss) == pytest.approx(float(remat_loss), rel=1e-6)
    for name in plain_aux:
        assert np.array_equal(plain_aux[name], remat_aux[name]), name
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(plain)[0], jax.tree_util.tree_leaves(remat)):
        close(a, b, jax.tree_util.keystr(path), 1e-5)


# ---------------------------------------------------------------------------
# the scan


def scan_inputs(length, seed=5, d=6, n=3, batch=2):
    rng = np.random.RandomState(seed)
    x = rng.standard_normal((batch, length, d)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (batch, length, d)).astype(np.float32)
    a = -rng.uniform(0.5, 2.0, (d, n)).astype(np.float32)
    b, c = (rng.standard_normal((batch, length, n)).astype(np.float32) for _ in range(2))
    skip = rng.standard_normal((d,)).astype(np.float32)
    return tuple(jnp.asarray(t) for t in (x, dt, a, b, c, skip))


def plain_scan(x, dt, a, b, c, skip):
    """The recurrence as the equations write it: a ``[d, N]`` state, a row at a time."""

    def window(x_w, dt_w, b_w, c_w):
        def step(state, row):
            x_t, dt_t, b_t, c_t = row
            state = jnp.exp(dt_t[:, None] * a) * state + (dt_t * x_t)[:, None] * b_t[None, :]
            return state, state @ c_t + skip * x_t

        return jax.lax.scan(step, jnp.zeros(a.shape), (x_w, dt_w, b_w, c_w))[1]

    return jax.vmap(window)(x, dt, b, c)


@pytest.mark.parametrize("length, chunk", [(12, 1), (12, 4), (12, 12), (12, 256), (11, 4), (13, 5)])
def test_the_chunked_scan_is_the_plain_one(monkeypatch, length, chunk):
    """Values and every gradient, for chunks of one row, of four, of the
    whole window and longer than it, and for windows whose length is no
    multiple of the chunk."""
    monkeypatch.setattr(backbone, "SCAN_CHUNK", chunk)
    inputs = scan_inputs(length)
    close(backbone.selective_scan(*inputs), plain_scan(*inputs), "values", 1e-5)
    weights = jnp.asarray(np.random.RandomState(9).standard_normal((2, length, 6)), jnp.float32)
    grads = lambda scan: jax.jit(jax.grad(lambda *t: jnp.sum(scan(*t) * weights), argnums=range(6)))(*inputs)  # noqa: E731
    for name, got, want in zip(("x", "dt", "a", "b", "c", "skip"), grads(backbone.selective_scan), grads(plain_scan)):
        close(got, want, name, 1e-5)


def test_the_scan_is_float32_whatever_the_layer_computes_in(of_four):
    spec = toy(compute_dtype="bfloat16")
    w = backbone.init_backbone(jax.random.PRNGKey(7), spec)["layer_0"]["mamba"]
    u = jnp.asarray(np.random.RandomState(2).standard_normal((1, T, HIDDEN)), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda u: backbone.mamba(spec, w, u)[0])(u))
    scan = text[text.index("custom_vjp_call"):]
    assert "f32[16,64]" in scan and "bf16[16,64]" not in scan
    assert backbone.SCAN_DTYPE == jnp.float32


def test_the_state_before_a_window_is_zero_and_its_first_rows_see_zeros(of_four):
    spec = toy()
    w = unseated(backbone.init_backbone(jax.random.PRNGKey(7), spec)["layer_0"]["mamba"])
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.standard_normal((1, T, INNER)), jnp.float32)
    kernel = w["conv_kernel"]
    conv = backbone.causal_conv(x, kernel)
    # a window's first three rows see zeros where the rows before it would be
    close(conv[0, 0], kernel[:, 3] * x[0, 0])
    close(conv[0, 1], kernel[:, 2] * x[0, 0] + kernel[:, 3] * x[0, 1])
    close(conv[0, 2], kernel[:, 1] * x[0, 0] + kernel[:, 2] * x[0, 1] + kernel[:, 3] * x[0, 2])
    close(conv[0, 5], sum(kernel[:, k] * x[0, 2 + k] for k in range(4)))
    # the first row's output is its own input through a state that was zero
    u = jnp.asarray(rng.standard_normal((1, T, HIDDEN)), jnp.float32)
    _, y, steps = backbone.mamba(spec, w, u)
    c0 = jax.nn.silu(kernel[:, 3] * (u[0, 0] @ w["in_proj"])[:INNER] + w["conv_bias"])
    row = c0 @ w["x_proj"]
    dt0 = jax.nn.softplus(row[:RANK] @ w["dt_proj"] + w["dt_bias"])
    first = dt0 * c0 * jnp.dot(row[RANK : RANK + STATE], row[RANK + STATE :]) + w["D"] * c0
    close(y[0, 0], first, "the first row", 1e-5)
    assert float(steps) == T
    # and no row sees a later one
    moved = backbone.mamba(spec, w, u.at[0, 10].add(1.0))[1]
    assert np.array_equal(moved[0, :10], y[0, :10]) and not np.allclose(moved[0, 10:], y[0, 10:])


def test_the_memory_is_the_scan_output_before_the_gate_and_with_the_skip(of_four):
    spec = toy()
    w = unseated(backbone.init_backbone(jax.random.PRNGKey(7), spec)["layer_2"]["mamba"])
    u = jnp.asarray(np.random.RandomState(6).standard_normal((2, T, HIDDEN)), jnp.float32)
    out, memory, _ = backbone.mamba(spec, w, u)
    # the gate's half of W_in moves the layer's output and leaves what a gmu reads as it was
    gate_moved = dict(w, in_proj=w["in_proj"].at[:, INNER:].add(0.1))
    out_moved, memory_moved, _ = backbone.mamba(spec, gate_moved, u)
    assert np.array_equal(memory_moved, memory) and not np.allclose(out_moved, out)
    # D enters it: y = s C + D c
    with_skip = backbone.mamba(spec, dict(w, D=w["D"] + 1.0), u)[1]
    stream = (u @ w["in_proj"])[..., :INNER]
    conv = jax.nn.silu(backbone.causal_conv(stream, w["conv_kernel"]) + w["conv_bias"])
    close(with_skip - memory, conv, "the skip term", 1e-5)
    # and the whole model hands it on: the gate's half of the memory layer's W_in moves layer 2, not the gmu's input
    params = unseated(backbone.init_backbone(jax.random.PRNGKey(7), spec))
    seen = {}
    real = backbone.gated_memory

    def spy(w, u, memory):
        seen["memory"] = memory
        return real(w, u, memory)

    x = jnp.asarray(np.random.RandomState(3).uniform(0, 1, (2, T, 5)), jnp.float32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backbone, "gated_memory", spy)
        backbone.forward_backbone_aux(spec, params, x, remat=False)
        before = seen["memory"]
        layer = dict(params["layer_2"], mamba=dict(params["layer_2"]["mamba"]))
        layer["mamba"]["in_proj"] = layer["mamba"]["in_proj"].at[:, INNER:].add(0.1)
        backbone.forward_backbone_aux(spec, dict(params, layer_2=layer), x, remat=False)
    assert before.shape == (2, T, INNER) and np.array_equal(seen["memory"], before)


# ---------------------------------------------------------------------------
# the differential heads


def attention_layer(index=3, seed=7):
    spec = toy()
    w = unseated(backbone.init_backbone(jax.random.PRNGKey(seed), spec)[f"layer_{index}"]["attn"])
    # heads of 4 x 8 = hidden: W_o as the identity shows the heads themselves
    w = dict(w, wo=jnp.eye(HIDDEN), bo=jnp.zeros((HIDDEN,)))
    u = jnp.asarray(np.random.RandomState(8).standard_normal((2, T, HIDDEN)), jnp.float32)
    return spec, w, u


def test_the_pair_reads_two_keys_and_one_value_twice_as_wide(blocks):
    spec, w, u = attention_layer()
    out, band, (k, v) = backbone.differential_attention(spec, "full_attention", 3, w, u)
    assert k.shape == v.shape == (2, T, KV_HEADS, DH) and (band is None) == (blocks == SHIPPED)
    heads = np.asarray(out).reshape(2, T, HEADS // 2, 2 * DH)  # O_j, j = 0..3, each [first 4 | last 4]
    # the value of key head 2p + 1 is the last half of pair p's: moving it
    # moves the heads that read pair p (j // 2 == p) and no other ...
    for p in range(KV_HEADS // 2):
        moved_w = dict(w, bv=w["bv"].at[(2 * p + 1) * DH : (2 * p + 2) * DH].add(1.0))
        moved = np.asarray(backbone.differential_attention(spec, "full_attention", 3, moved_w, u)[0])
        moved = moved.reshape(2, T, HEADS // 2, 2 * DH)
        for j in range(HEADS // 2):
            if j // 2 != p:
                assert np.array_equal(moved[:, :, j], heads[:, :, j]), (p, j)
                continue
            # ... and in them the last half alone: the first half keeps its
            # direction (the norm over the difference rescales all of a head alike)
            ratio = moved[:, :, j, :DH] / heads[:, :, j, :DH]
            assert np.allclose(ratio, ratio[..., :1], rtol=1e-3), (p, j)
            last = moved[:, :, j, DH:] / heads[:, :, j, DH:]
            assert not np.allclose(last, last[..., :1], rtol=1e-3), (p, j)


def test_the_pairs_weight_is_the_formula_and_the_output_is_scaled_by_what_is_left(of_four):
    spec, w, u = attention_layer()
    for index in (0, 3, 5):
        start = 0.8 - 0.6 * math.exp(-0.3 * index)
        want = (
            math.exp(float(np.dot(w["lambda_q1"], w["lambda_k1"])))
            - math.exp(float(np.dot(w["lambda_q2"], w["lambda_k2"]))) + start
        )
        lam, lam_0 = backbone.differential_weight(w, index)
        assert float(lam) == pytest.approx(want, rel=1e-6) and lam_0 == pytest.approx(start)
    # two maps made alike (the query and key heads of a pair equal): A1 = A2,
    # the norm forgets 1 - lam, and what is left of a layer's index is 1 - lam_0
    twin = dict(w)
    for name, heads in (("q", HEADS), ("k", KV_HEADS)):
        matrix = np.array(w["w" + name]).reshape(HIDDEN, heads // 2, 2, DH)
        matrix[:, :, 1] = matrix[:, :, 0]
        bias = np.array(w["b" + name]).reshape(heads // 2, 2, DH)
        bias[:, 1] = bias[:, 0]
        twin["w" + name], twin["b" + name] = jnp.asarray(matrix.reshape(HIDDEN, -1)), jnp.asarray(bias.reshape(-1))
    twin["sub_norm"] = jnp.ones((2 * DH,))
    at = lambda index: np.asarray(backbone.differential_attention(spec, "full_attention", index, twin, u)[0])  # noqa: E731
    left = lambda index: 1.0 - (0.8 - 0.6 * math.exp(-0.3 * index))  # noqa: E731
    assert float(backbone.differential_weight(twin, 5)[0]) < 1.0
    close(at(5) / left(5), at(1) / left(1), "the scale", 5e-3)  # but for the norm's eps under a small difference
    heads = at(1).reshape(2, T, HEADS // 2, 2 * DH) / left(1)
    close(np.sqrt(np.mean(heads**2, axis=-1)), np.ones((2, T, HEADS // 2)), "an RMSNorm over the difference", 5e-3)


def test_a_sliding_query_sees_exactly_its_window(of_four):
    spec, w, u = attention_layer(index=1)
    out = np.asarray(backbone.differential_attention(spec, "sliding_attention", 1, w, u)[0])
    for row in (0, 7, 15):
        # the key and the value of one row, moved (a cross layer's hook: the q stays)
        _, _, (k, v) = backbone.differential_attention(spec, "sliding_attention", 1, w, u)
        kv = (k.at[:, row].add(1.0), v.at[:, row].add(1.0))
        moved = np.asarray(backbone.differential_attention(spec, "sliding_attention", 1, w, u, kv=kv)[0])
        changed = {t for t in range(T) if not np.array_equal(moved[:, t], out[:, t])}
        assert changed == set(range(row, min(row + WINDOW, T))), row


def test_layer_norm_has_a_bias(of_four):
    spec = toy()
    x = jnp.asarray(np.random.RandomState(1).standard_normal((3, HIDDEN)), jnp.float32)
    w = {"gain": jnp.full((HIDDEN,), 2.0), "bias": jnp.full((HIDDEN,), 0.5)}
    normed = np.asarray(backbone.block_norm(spec, x, w))
    plain = (np.asarray(x) - np.asarray(x).mean(-1, keepdims=True)) / np.sqrt(np.asarray(x).var(-1, keepdims=True) + 1e-5)
    close(normed, 2.0 * plain + 0.5)
    assert abs(float(normed.mean()) - 0.5) < 1e-5  # a mean taken out, a bias put in: no RMS norm
    # and the other kinds' norm is the RMS norm it was, under a gain alone
    assert np.array_equal(
        backbone.block_norm(kanana_toy(), x, jnp.ones((HIDDEN,))), backbone.rms_norm(x, jnp.ones((HIDDEN,)), 1e-6)
    )


# ---------------------------------------------------------------------------
# the layers that read an earlier layer's tensors


def test_a_cross_layer_has_no_key_and_no_value_of_its_own(of_four, seeded):
    spec, params, _, x, y = seeded
    assert not {"wk", "wv", "bk", "bv"} & set(params["layer_5"]["attn"])
    _, w, u = attention_layer()
    made = backbone.differential_attention(spec, "full_attention", 3, w, u)[2]
    cross = {k: v for k, v in w.items() if k[1:] not in ("k", "v")}
    out, band, handed = backbone.differential_attention(spec, "cross_attention", 5, cross, u, kv=made)
    assert handed[0] is made[0] and handed[1] is made[1] and band is not None
    # the same q against the same keys and values: the full layer's own heads, but for the layer's index
    own = backbone.differential_attention(spec, "full_attention", 5, w, u)[0]
    close(out, own, "a cross layer over the layer's own keys", 1e-6)


def test_the_full_layers_key_gradient_is_the_sum_over_its_readers(of_four, seeded):
    spec, params, _, x, y = seeded

    def loss_of(tree, read=lambda t: t):
        with pytest.MonkeyPatch.context() as patch:
            real = backbone.differential_attention

            def reading(spec, op, index, w, u, active=None, kv=None):
                return real(spec, op, index, w, u, active, None if kv is None else read(kv))

            patch.setattr(backbone, "differential_attention", reading)
            out = backbone.forward_backbone_aux(spec, tree, x)[0]
        return jnp.mean(jnp.mean((out - y) ** 2, axis=-1))

    whole = jax.grad(loss_of)(params)["layer_3"]["attn"]
    # the cross layer's reading cut off: what the full layer's own attention gives its keys and values
    own = jax.grad(lambda tree: loss_of(tree, jax.lax.stop_gradient))(params)["layer_3"]["attn"]
    # and the reading alone: the keys and values as a constant of the full layer, a variable of the cross layer
    shapes = (2 * (x.shape[0], T, KV_HEADS, DH),)

    def through_the_reader(moved):
        return loss_of(params, lambda kv: (kv[0] + moved[0], kv[1] + moved[1]))

    zeros = (jnp.zeros(shapes[0][:4]), jnp.zeros(shapes[0][:4]))
    d_k, d_v = jax.grad(through_the_reader)(zeros)
    normed = backbone.block_norm(spec, _layer_input(spec, params, x, 3), params["layer_3"]["operator_norm"])
    read_wk = jnp.einsum("bth,btd->hd", normed, d_k.reshape(x.shape[0], T, -1))
    read_wv = jnp.einsum("bth,btd->hd", normed, d_v.reshape(x.shape[0], T, -1))
    assert float(jnp.max(jnp.abs(read_wv))) > 1e-6 and float(jnp.max(jnp.abs(own["wv"]))) > 1e-6
    close(whole["wk"], own["wk"] + read_wk, "wk", 1e-5)
    close(whole["wv"], own["wv"] + read_wv, "wv", 1e-5)
    close(whole["bv"], own["bv"] + d_v.reshape(-1, KV_HEADS * DH).sum(0), "bv", 1e-5)


def _layer_input(spec, params, x, index):
    """The residual as layer ``index`` takes it in."""
    seen = {}
    real = backbone.block

    def spy(spec, op, ffn, w, h, active=None, index=0, read=None):
        seen[index] = h
        return real(spec, op, ffn, w, h, active, index, read)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backbone, "block", spy)
        backbone.forward_backbone_aux(spec, params, x, remat=False)
    return seen[index]


# ---------------------------------------------------------------------------
# the normal path


def test_the_fit_counts_rows_and_pairs_and_skips_padding(of_four, seeded):
    from gordo_tpu.models.training import FitConfig, build_raw_windowed_fit_fn

    spec, params, _, _, _ = seeded
    fit = build_raw_windowed_fit_fn(spec, FitConfig(epochs=2, batch_size=2, shuffle=False))
    rng = np.random.RandomState(4)
    series = rng.uniform(0, 1, (T + 4, 5)).astype(np.float32)
    ytgt = rng.uniform(0, 1, (4, 5)).astype(np.float32)
    opt_state = spec.optimizer.to_optax().init(params)
    order = jnp.asarray([0, 1, 2, 0, 0, 0], jnp.int32)
    wtr = jnp.asarray([1, 1, 1, 0, 0, 0], jnp.float32)  # three windows, a slot of padding, a step of padding alone
    outs = jax.jit(fit)(params, opt_state, series, ytgt, order, wtr, jnp.zeros((0,), jnp.float32), jax.random.PRNGKey(0))
    counters = jax.tree_util.tree_map(lambda a: np.asarray(a).sum(axis=0), outs[5])
    assert counters["steps_run"] == 4  # of six: a member without a member axis skips a step of padding alone
    assert counters["scan_steps"].tolist() == [6 * T] * 2
    banded = WINDOW * (WINDOW + 1) / 2 + (T - WINDOW) * WINDOW
    assert counters["pairs_attended"].tolist() == [6 * banded, 6 * T * (T + 1) / 2, 6 * T * (T + 1) / 2]
    assert counters["pairs_multiplied"].tolist() == [6 * 15 * 16, 6 * 21 * 16, 6 * 21 * 16]
    attrs = spec.fit_counter_attrs(counters)
    assert (attrs["ssm_inner"], attrs["ssm_state"], attrs["scan_chunk"], attrs["memory_width"]) == (INNER, STATE, CHUNK, INNER)
    assert attrs["memory_reads"] == [2] and attrs["kv_reads"] == [3]
    # the three that say which experts are held, only where there are experts
    assert not {"num_experts", "experts_held", "expert_offset", "index_topk", "kv_lora_rank"} & set(attrs)
    assert not {"pairs_here", "pairs_total", "router_tokens"} & set(attrs)
    from gordo_tpu.telemetry.progress import scan_text

    assert scan_text(attrs) == f"scan {INNER} x {STATE} in chunks of {CHUNK}; 1 layer reads layer 2's output, 1 reads layer 3's keys"
    published = phi4flash(50).fit_counter_attrs({})
    assert scan_text(published) == f"scan 5,120 x 16 in chunks of {CHUNK}; 7 layers read layer 16's output, 7 read layer 17's keys"


def test_the_estimator_builds_the_kind_by_name(of_four):
    estimator = JaxBackboneForecast(
        kind="phi4flash", lookback_window=T, num_hidden_layers=6, layer_types=list(CUT), hidden_size=HIDDEN,
        num_attention_heads=HEADS, num_key_value_heads=KV_HEADS, intermediate_size=48, sliding_window=WINDOW,
        epochs=1, batch_size=2,
    )
    rng = np.random.RandomState(11)
    X = rng.uniform(0, 1, (T + 6, 5)).astype(np.float32)
    estimator.fit(X, X)
    assert estimator.spec_ == toy() and estimator.predict(X).shape == (6, 5)
    loss, norms = estimator.training_loss_and_grad_norms(X, X)
    assert np.isfinite(loss) and all(np.isfinite(v) for v in jax.tree_util.tree_leaves(norms))
    with pytest.raises(ValueError, match="phi4flash runs mb_per_layer=2 only"):
        JaxBackboneForecast(kind="phi4flash", lookback_window=T, mb_per_layer=3).fit(X, X)


def test_a_short_window_holds_every_score_at_once_with_the_pair_as_well(seeded, reference):
    """Under the tile as it ships a full and a cross layer of 24 rows go
    ``gqa_attention``'s way (:func:`backbone._attend_in_one_piece`), a
    map a call as in the tiles."""
    spec, params, layers, x, _ = seeded
    calls = []
    real = backbone._attend_in_one_piece

    def spy(q, k, v):
        calls.append((q.shape, k.shape, v.shape))
        return real(q, k, v)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backbone, "_attend_in_one_piece", spy)
        out = backbone.forward_backbone_aux(spec, params, x, remat=False)[0]
    # a map a call, two layers: 2 pairs of keys, each serving its 2 queries, over a value twice as wide
    assert calls == [((4, T, KV_HEADS // 2, 2, DH), (4, T, KV_HEADS // 2, DH), (4, T, KV_HEADS // 2, 2 * DH))] * 4
    close(out, reference.forward(layers, x), "forward")


# ---------------------------------------------------------------------------
# the kinds that were here keep their programs, their weights and their spans


def kanana_toy():
    return sibling_tests("test_latent_backbone").toy()


@pytest.mark.parametrize("tile, want", [(SHIPPED[0], "shipped"), (TILE, "tiles_of_four")])
def test_a_kanana_members_fit_program_is_the_text_the_parent_lowers(monkeypatch, tile, want):
    """``_heads`` takes a bias and an earlier layer's keys, the gated
    convolution's sum and ``gqa_attention``'s scores are functions of
    their own, the norms go through ``block_norm``, the layer loop hands
    tensors on: a ``kanana`` member's lowered fit program is, to the
    character, the text the parent lowers, with the tile as it ships and
    in tiles of 4; hashes taken at commit ``333ead6`` before any edit
    (the four kinds before it: module docstring). Another text would be
    another compilation, and on the chip another routing lottery (PR 28). The
    hash of the text in tiles of 4 (the one that runs tile loops) was taken again at commit ``9fae991`` with PR 47's
    change applied: the tile loops' results pass through
    ``checkpoint_name`` (``backbone.SAVED_TILES``), an identity that
    lowers to no operation, but the counter behind the numbers at the
    end of private functions' names (``@closed_call_317``) runs further,
    so those numbers move and nothing else does
    (``test_tiles_kept.py`` holds the parent's text against the new one
    with the numbers stripped; a toy rematerialises nothing)."""
    monkeypatch.setattr(backbone, "ATTENTION_TILE", tile)
    lowered_fit_text = sibling_tests("test_latent_backbone").lowered_fit_text
    assert hashlib.sha256(lowered_fit_text(kanana_toy()).encode()).hexdigest() == KANANA_TOY_FIT_TEXT[want]


def test_a_kanana_members_seeded_weights_are_what_they_were():
    params = backbone.init_backbone(jax.random.PRNGKey(7), kanana_toy())
    digest = hashlib.sha256(
        b"".join(np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(params))
    ).hexdigest()
    assert digest == KANANA_TOY_DIGEST


KANANA_TOY_DIGEST = "ec454cf7b1371ce3f62db8da853c61856f942c768cba7621d361d8dcfe30cab3"
KANANA_TOY_FIT_TEXT = {
    "shipped": "e4b5e8ba4f9b3dab1f4b3e3e6c84c2f3458d2d6a5eb957b6ce57db7f34d1cf57",
    "tiles_of_four": "4d03793714c5f6adfbd99d973c86a6fa794dce1f29b82794b03deaaf90112a81",
}

#: what each kind's fit span carries at commit ``333ead6`` (the toys of
#: the five test files, a batch of one window and one of padding), under
#: the tile as it ships and in tiles of 4: the names ``fit_counter_attrs``
#: gives, which ``fleet._fit_counter_attrs`` lists in ``fit_counters``;
#: and, since PR 47, how many layers' tile outputs the backward pass is
#: handed by name (every backbone's span says: 0 for these toys, which
#: rematerialise nothing) and, since PR 48, a ``sparse_attention`` kind's
#: ``selection_blocks_searched``
_ROUTED = [
    "expert_offset", "experts_held", "num_experts", "pairs_here", "pairs_total", "router_tokens", "steps_run",
    "tile_outputs_kept",
]
_BAND = ["pairs_attended", "pairs_multiplied"]
_LATENT = ["kv_expanded_dim", "kv_lora_rank", "qk_rope_head_dim", "v_head_dim"]
SPAN_ATTRIBUTES_AT_THE_PARENT = {
    "lfm2_moe": {"shipped": _ROUTED, "tiles_of_four": _ROUTED + _BAND},
    "keye_vl2": {
        tile: _ROUTED + ["index_topk", "indexer_kl", "keys_causal", "keys_selected", "selection_blocks_searched"]
        for tile in ("shipped", "tiles_of_four")
    },
    "laguna": {tile: _ROUTED + _BAND for tile in ("shipped", "tiles_of_four")},
    "smallthinker": {tile: _ROUTED + _BAND + ["gate_active", "gate_total"] for tile in ("shipped", "tiles_of_four")},
    "kanana": {"shipped": _ROUTED + _LATENT, "tiles_of_four": _ROUTED + _LATENT + _BAND},
}


def _toys():
    test_backbone, test_prerouted_backbone = sibling_tests("test_backbone"), sibling_tests("test_prerouted_backbone")
    return {
        "lfm2_moe": test_backbone.toy, "keye_vl2": test_backbone.sparse_toy,
        "laguna": test_prerouted_backbone.laguna_toy, "smallthinker": test_prerouted_backbone.toy,
        "kanana": kanana_toy,
    }


@pytest.mark.parametrize("tile, want", [(SHIPPED[0], "shipped"), (TILE, "tiles_of_four")])
@pytest.mark.parametrize("kind", sorted(SPAN_ATTRIBUTES_AT_THE_PARENT))
def test_each_kinds_fit_span_carries_what_it_carried_at_the_parent(monkeypatch, kind, tile, want):
    monkeypatch.setattr(backbone, "ATTENTION_TILE", tile)
    spec = _toys()[kind]()
    assert not spec.member_axis and spec.forward_aux_fn() is backbone.forward_backbone_aux
    params = backbone.init_backbone(jax.random.PRNGKey(7), spec)
    x = jnp.ones((2, spec.lookback_window, 5))
    aux = spec.forward_aux_fn()(spec, params, x, active=jnp.asarray([True, False]))[2]
    counters = {name: np.asarray(value) for name, value in aux.items()}
    counters["steps_run"] = np.asarray(1)
    attrs = spec.fit_counter_attrs(counters)
    assert sorted(attrs) == sorted(SPAN_ATTRIBUTES_AT_THE_PARENT[kind][want])
    assert (attrs["num_experts"], attrs["experts_held"], attrs["expert_offset"]) == (
        spec.num_experts, spec.experts_held, spec.expert_offset
    )
