"""``kind: kanana`` at toy widths on the CPU against its plain reference
(loaded by path: it imports nothing of the program's layer code): latent
attention whose heads come from a normed latent and one rotary key that
every head shares, scores and values of unlike widths through the tile
loops and through the attention in one piece, a leading dense layer
before the routed ones, eight shares that add up to the uncut layer; and
the program and the weights of the kind that arrived last before it,
which this kind's arrival must not move (the three before that are
pinned in ``test_banded_backbone.py`` and ``test_prerouted_backbone.py``)."""

import dataclasses
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import JaxBackboneForecast, backbone
from gordo_tpu.models.factories import kanana, smallthinker
from gordo_tpu.models.factories.backbone import KANANA_2_30B_A3B_CONFIG
from gordo_tpu.models.spec import BackboneSpec
from gordo_tpu.models.training import FitConfig, build_raw_windowed_fit_fn

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOLERANCE = 1e-4  # of scale: both sides compute in float32 on the CPU
#: a window of 24 rows in tiles of 4 (every layer in the tile loops), or
#: in one piece under the tile as it ships (``gqa_attention``'s path)
T, TILE = 24, 4
PUBLISHED_TILE = backbone.ATTENTION_TILE
#: 4 heads whose scores are 6 wide (4 + 2 rotary) and whose values are 4
#: wide, off a latent of 8: the two widths differ, as 192 and 128 do
HEADS, RANK, NOPE, ROPE, VALUE = 4, 8, 4, 2, 4


@pytest.fixture(params=[TILE, PUBLISHED_TILE], ids=["tiles_of_four", "one_piece"])
def tile(request, monkeypatch):
    """The tile is the program's constant, not an option of a spec."""
    monkeypatch.setattr(backbone, "ATTENTION_TILE", request.param)
    return request.param


@pytest.fixture
def tiles_of_four(monkeypatch):
    monkeypatch.setattr(backbone, "ATTENTION_TILE", TILE)
    return monkeypatch


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(CHIP, "reference", "kanana_latent_backbone.py")
    spec = importlib.util.spec_from_file_location("reference_kanana_latent_backbone", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def toy(**overrides) -> BackboneSpec:
    """A leading dense layer and two routed ones at hidden 32: experts
    13-14 of 16 held (two that the seeded routers send tokens to), 3 a
    token, two shared experts of 12 as one of 24."""
    sizes = dict(
        lookback_window=T, num_hidden_layers=3, hidden_size=32, num_attention_heads=HEADS,
        kv_lora_rank=RANK, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VALUE,
        intermediate_size=48, moe_intermediate_size=12, n_routed_experts=16, experts_held=2,
        expert_offset=13, num_experts_per_tok=3, rope_theta=100.0,
    )
    sizes.update(overrides)
    return kanana(5, **sizes)


class Artifact:
    def __init__(self, spec, params):
        self.spec_, self.params_ = spec, params


@pytest.fixture(scope="module")
def seeded(reference):
    spec = toy()
    params = backbone.init_backbone(jax.random.PRNGKey(7), spec)
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
    source = open(os.path.join(CHIP, "reference", "kanana_latent_backbone.py")).read()
    assert "import gordo_tpu" not in source and "from gordo_tpu" not in source


def test_the_factory_defaults_are_the_catalog_row_key_by_key():
    published = KANANA_2_30B_A3B_CONFIG
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert row["config"] == published
    stated = json.load(open(os.path.join(CHIP, "configs", "kanana-2-30b-a3b-50tag-lb8192.json")))
    differ = {key for key, value in published.items() if stated[key] != value}
    assert differ == set(stated["reduced"]) == {"num_hidden_layers", "n_routed_experts"}
    assert stated["published"] == {key: published[key] for key in stated["reduced"]}
    spec = kanana(50)
    assert len(spec.layer_ops) == 48 and set(spec.layer_ops) == {"full_attention"}
    assert spec.layer_ffns == ("dense",) + ("moe",) * 47  # first_k_dense_replace 1, moe_layer_freq 1
    assert (spec.hidden_size, spec.num_attention_heads, spec.num_key_value_heads) == (2048, 32, 32)
    assert (spec.kv_lora_rank, spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim) == (512, 128, 64, 128)
    assert (spec.head_dim, spec.kv_expanded_dim, spec.rope_interleave) == (192, 8192, True)
    assert (spec.intermediate_size, spec.moe_intermediate_size, spec.shared_expert_intermediate_size) == (6144, 768, 1536)
    assert (spec.num_experts, spec.experts_held, spec.num_experts_per_tok) == (128, 128, 6)
    assert (spec.router, spec.router_input, spec.expert_activation) == ("sigmoid_bias", "ffn_input", "silu")
    assert (spec.routed_scaling_factor, spec.rope_theta, spec.norm_eps) == (2.448, 1e6, 1e-6)
    assert not spec.qk_norm and not spec.attention_gate and spec.lookback_window == 8192


def test_the_cut_holds_the_weights_the_issue_counted():
    spec = kanana(50, num_hidden_layers=5, experts_held=16)
    attention = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
    assert attention == 26_345_984 == spec.latent_param_count
    dense = attention + 4096 + 3 * 2048 * 6144
    routed = attention + 4096 + 2048 * 128 + 3 * 2048 * 1536 + 16 * 3 * 2048 * 768
    assert (dense, routed) == (64_098_816, 111_546_880)
    assert spec.layer_param_count("full_attention", "dense") == dense
    assert spec.layer_param_count("full_attention", "moe") == routed
    assert spec.param_count() == dense + 4 * routed + (50 * 2048 + 2048) + (2048 + 2048 * 50 + 50) == 510_495_282
    shapes = jax.eval_shape(lambda key: backbone.init_backbone(key, spec), jax.random.PRNGKey(0))
    assert backbone.trained_param_count(shapes) == 510_495_282
    attn = shapes["layer_1"]["attn"]
    assert set(attn) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert attn["wq"].shape == (2048, 6144) and attn["wkv_a"].shape == (2048, 576)
    assert attn["kv_norm"].shape == (512,) and attn["wkv_b"].shape == (512, 8192) and attn["wo"].shape == (4096, 2048)
    assert shapes["layer_0"]["ffn"]["w1"].shape == (2048, 6144) and "moe" not in shapes["layer_0"]
    assert shapes["layer_1"]["moe"]["w1"].shape == (16, 2048, 768)
    assert shapes["layer_1"]["moe"]["shared"]["w1"].shape == (2048, 1536)
    assert shapes["layer_1"]["moe"]["router"].shape == (2048, 128) and shapes["layer_1"]["moe"]["expert_bias"].shape == (128,)
    # a window of 8,192 rows: a step's forward FLOPs as ISSUE 41 counted them (14.16 T a step of two)
    pairs = 8192 * 8193 // 2
    assert pairs == 33_558_528 and 2 * 32 * (192 + 128) == 20_480
    by_hand = 8192 * (
        2 * 50 * 2048 + 5 * 2 * (attention - 512) + 6 * 2048 * 6144
        + 4 * (2 * 2048 * 128 + 6 * 2048 * 1536 + 6 * 2048 * 768 * 6 * 16 / 128)
    ) + 5 * 20_480 * (8192 * 8192 / 2.0) + 2 * 2048 * 50
    assert spec.flops_per_sample() == pytest.approx(by_hand)
    assert 14.1e12 < 2 * spec.flops_per_sample() < 14.2e12


def test_what_the_factory_and_the_spec_refuse():
    for key, value in (
        ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
        ("topk_method", "greedy"), ("norm_topk_prob", False), ("rope_scaling", {"type": "yarn"}),
        ("attention_bias", True), ("moe_layer_freq", 2), ("hidden_act", "gelu"),
    ):
        with pytest.raises(ValueError, match=f"kanana runs {key}="):
            toy(**{key: value})
    assert toy(q_lora_rank=None, n_group=1, topk_group=1) == toy()  # the published values are taken
    with pytest.raises(ValueError, match="even width of the shared rotary key"):
        toy(qk_rope_head_dim=3)
    with pytest.raises(ValueError, match="a key and a value width"):
        toy(v_head_dim=0)
    for field, value in (("qk_norm", True), ("attention_gate", True), ("num_key_value_heads", 2),
                         ("layer_ops", ("full_attention", "sliding_attention", "full_attention"))):
        with pytest.raises(ValueError, match="latent attention"):
            dataclasses.replace(toy(), sliding_window=8, **{field: value})


def test_the_dense_layer_and_the_routed_layers_are_where_the_config_puts_them(seeded):
    spec, params, _, x, _ = seeded
    assert spec.layer_ffns == ("dense", "moe", "moe")
    assert "ffn" in params["layer_0"] and "moe" not in params["layer_0"]
    for name in ("layer_1", "layer_2"):
        assert "moe" in params[name] and "ffn" not in params[name]
        assert params[name]["moe"]["shared"]["w1"].shape == (32, 24)  # two shared experts of 12 as one
    two = toy(first_k_dense_replace=2)
    assert two.layer_ffns == ("dense", "dense", "moe")
    assert backbone.forward_backbone_aux(spec, params, x)[2]["router_tokens"].shape == (2, 16)
    leaves = jax.tree_util.tree_leaves(params)
    assert backbone.trained_param_count(params) == spec.param_count() == sum(l.size for l in leaves) - 2 * 16


def test_the_forward_is_the_references(tile, seeded, reference):
    spec, params, layers, x, _ = seeded
    out, penalty, aux = backbone.forward_backbone_aux(spec, params, x)
    close(out, reference.forward(layers, x), "forward")
    assert float(penalty) == 0.0
    found = reference.counters(layers, x)
    # to the digit: the reference counts its masks and its choices, the program says its arithmetic
    assert np.array_equal(aux["router_tokens"], found["routed"]) and found["routed"].shape == (2, 16)
    assert aux["pairs_here"].tolist() == found["routed"][:, 13:15].sum(axis=1).tolist()
    assert all(pairs > 0 for pairs in aux["pairs_here"])
    assert aux["pairs_total"].tolist() == [4 * T * 3] * 2
    if tile == TILE:  # all three layers in the tile loops: a latent layer is an attention in tiles
        assert aux["pairs_attended"].tolist() == found["attended"].tolist() == [4 * T * (T + 1) / 2] * 3
        assert aux["pairs_multiplied"].tolist() == [4.0 * 21 * TILE * TILE] * 3  # six blocks: 1 + 2 + .. + 6 tiles
    else:  # one piece holds every score: no tile, no such row
        assert "pairs_attended" not in aux and found["attended"].tolist() == [4 * T * (T + 1) / 2] * 3


def test_every_leafs_gradient_is_the_references(tile, seeded, reference):
    spec, params, layers, x, y = seeded
    want_loss, want = reference.loss_and_grads(layers, x, y)

    def loss_of(tree):
        out, penalty, _ = backbone.forward_backbone_aux(spec, tree, x)
        return jnp.mean(jnp.mean((out - y) ** 2, axis=-1)) + penalty

    loss, got = jax.jit(jax.value_and_grad(loss_of))(params)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    leaves = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(leaves) == 5 + (2 + 5 + 3) + 2 * (2 + 5 + 5 + 3)
    for (path, leaf), ref in zip(leaves, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        close(leaf, ref, name)
        # every leaf learns, the latent's norm and the routers too; the bias is a buffer
        assert np.any(np.asarray(ref)) != name.endswith("['expert_bias']"), name


@pytest.mark.parametrize("active", [None, [True, False, True, True]])
def test_remat_on_and_off_give_the_same_outputs_and_gradients(tile, seeded, active):
    spec, params, _, x, y = seeded
    weights = jnp.ones(4) if active is None else jnp.asarray(active, jnp.float32)
    active = None if active is None else jnp.asarray(active)

    def loss_of(tree, remat):
        out, _, aux = backbone.forward_backbone_aux(spec, tree, x, remat=remat, active=active)
        return jnp.sum(jnp.mean((out - y) ** 2, axis=-1) * weights) / jnp.sum(weights), (out, aux)

    step = jax.jit(jax.value_and_grad(loss_of, has_aux=True), static_argnums=1)
    (plain_loss, (plain_out, plain_aux)), plain = step(params, False)
    (remat_loss, (remat_out, remat_aux)), remat = step(params, True)
    assert np.array_equal(plain_out, remat_out) and float(plain_loss) == float(remat_loss)
    for name in plain_aux:
        assert np.array_equal(plain_aux[name], remat_aux[name]), name
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(plain)[0], jax.tree_util.tree_leaves(remat)):
        close(a, b, jax.tree_util.keystr(path), 1e-6)


def test_the_rotary_key_is_one_head_and_the_latents_norm_leaves_it_alone(seeded):
    spec, params, _, _, _ = seeded
    u = jnp.asarray(np.random.RandomState(6).normal(0, 1, (2, T, 32)).astype(np.float32))
    w = params["layer_1"]["attn"]
    q, k, v = backbone._latent_heads(spec, w, u)
    assert q.shape == k.shape == (2, T, HEADS, NOPE + ROPE) and v.shape == (2, T, HEADS, VALUE)
    for head in range(1, HEADS):  # one key part for all four heads
        assert np.array_equal(k[:, :, head, NOPE:], k[:, :, 0, NOPE:])
        assert not np.array_equal(k[:, :, head, :NOPE], k[:, :, 0, :NOPE])
    # W_kva's last columns are the shared key's: moving them moves every head's scores alike ...
    moved = dict(w, wkv_a=w["wkv_a"].at[:, RANK:].add(0.3))
    q2, k2, v2 = backbone._latent_heads(spec, moved, u)
    assert np.array_equal(q2, q) and np.array_equal(v2, v) and np.array_equal(k2[..., :NOPE], k[..., :NOPE])
    change = k2[..., NOPE:] - k[..., NOPE:]
    assert np.max(np.abs(change)) > 0.1 and all(np.array_equal(change[:, :, h], change[:, :, 0]) for h in range(HEADS))
    # ... and the latent's norm does not touch it: a gain moves k_nope and v, not the shared key
    gained = dict(w, kv_norm=w["kv_norm"] * 3.0)
    _, k3, v3 = backbone._latent_heads(spec, gained, u)
    assert np.array_equal(k3[..., NOPE:], k[..., NOPE:])
    close(k3[..., :NOPE], 3.0 * k[..., :NOPE], "k_nope", 1e-5)
    close(v3, 3.0 * v, "v", 1e-5)
    # the shared key is the projection, turned, whatever its size (no norm): twice the columns, twice the key
    doubled = dict(w, wkv_a=w["wkv_a"].at[:, RANK:].multiply(2.0))
    close(backbone._latent_heads(spec, doubled, u)[1][..., NOPE:], 2.0 * k[..., NOPE:], "r", 1e-5)
    # the latent is normed: twice its columns change nothing (but for eps)
    scaled = dict(w, wkv_a=w["wkv_a"].at[:, :RANK].multiply(2.0))
    close(backbone._latent_heads(spec, scaled, u)[2], v, "v of a scaled latent", 1e-4)


def test_rotary_turns_pairs_together_and_only_the_trailing_part(seeded, reference):
    spec, params, _, _, _ = seeded
    u = jnp.asarray(np.random.RandomState(8).normal(0, 1, (1, T, 32)).astype(np.float32))
    w = params["layer_1"]["attn"]
    q, k, _ = backbone._latent_heads(spec, w, u)
    plain = (u @ w["wq"]).reshape(1, T, HEADS, NOPE + ROPE)
    assert np.array_equal(q[..., :NOPE], plain[..., :NOPE])  # no position in the leading part
    assert np.array_equal(q[:, 0], plain[:, 0])  # position 0 turns nothing
    assert not np.array_equal(q[:, 1:, :, NOPE:], plain[:, 1:, :, NOPE:])
    # dims (2i, 2i + 1) of the projection turn together by t * theta ** (-2i / d): by hand at d = 8
    x = jnp.asarray(np.random.RandomState(9).normal(0, 1, (1, 5, 2, 8)).astype(np.float32))
    got, ref = np.asarray(backbone.interleaved_rotary(x, 100.0)), np.asarray(reference.interleaved_rotary(x, 100.0))
    for t in range(5):
        for i in range(4):
            angle = t * 100.0 ** (-2 * i / 8)
            first, second = x[0, t, :, 2 * i], x[0, t, :, 2 * i + 1]
            turned = (first * np.cos(angle) - second * np.sin(angle), second * np.cos(angle) + first * np.sin(angle))
            # the reference keeps a pair where it was; the program parts the pairs (first members, then second)
            np.testing.assert_allclose(ref[0, t, :, 2 * i], turned[0], atol=1e-5)
            np.testing.assert_allclose(ref[0, t, :, 2 * i + 1], turned[1], atol=1e-5)
            np.testing.assert_allclose(got[0, t, :, i], turned[0], atol=1e-5)
            np.testing.assert_allclose(got[0, t, :, 4 + i], turned[1], atol=1e-5)
    # a q and a k permuted alike give the scores they gave
    scores = lambda f: np.einsum("bqhd,bkhd->bhqk", *(2 * [np.asarray(f(x, 100.0))]))  # noqa: E731
    np.testing.assert_allclose(scores(backbone.interleaved_rotary), scores(reference.interleaved_rotary), atol=1e-4)
    # rope_interleave false: the half-rotation layout on the same trailing part
    halves = dataclasses.replace(spec, rope_interleave=False)
    q_half = backbone._latent_heads(halves, w, u)[0]
    close(q_half[..., NOPE:], backbone.rotary(plain[..., NOPE:], 100.0), "half-rotation")
    assert np.array_equal(q_half[..., :NOPE], plain[..., :NOPE])


def test_the_latent_attention_is_the_references(tile, seeded, reference):
    spec, params, layers, _, _ = seeded
    u = jnp.asarray(np.random.RandomState(10).normal(0, 1, (2, T, 32)).astype(np.float32))
    w = params["layer_2"]["attn"]
    want, pairs = reference.attention(u, layers["weights"]["layer_2"]["attn"], layers["sizes"])
    if tile == TILE:
        got, (attended, multiplied) = backbone.banded_attention(spec, "full_attention", w, u)
        assert float(attended) == float(np.sum(pairs)) == 2 * T * (T + 1) / 2
        assert float(multiplied) == 2 * 21 * TILE * TILE
    else:
        got = backbone.gqa_attention(spec, w, u)
    close(got, want, "latent attention")
    # a later row moves no earlier output
    later = u.at[:, T - 1].add(5.0)
    moved = backbone.gqa_attention(spec, w, later) if tile != TILE else backbone.banded_attention(spec, "full_attention", w, later)[0]
    assert np.array_equal(np.asarray(moved)[:, : T - 1], np.asarray(got)[:, : T - 1])


def test_the_eight_shares_add_up_to_the_uncut_layer(seeded, reference):
    """Offsets 0, 2, .. 14 of 16 toy experts, two held each: what the
    eight holders of a layer give, with the shared expert that every
    holder computes whole counted once, adds up to the reference's layer
    with every expert held; nothing stands in for the others in any
    share."""
    spec, params, layers, _, _ = seeded
    rng = np.random.RandomState(9)
    n = jnp.asarray(rng.normal(0, 1, (2, T, 32)).astype(np.float32))
    w = dict(params["layer_1"]["moe"])
    for name, shape in (("w1", (16, 32, 12)), ("w3", (16, 32, 12)), ("w2", (16, 12, 32))):
        w[name] = jnp.asarray(rng.normal(0, 0.2, shape).astype(np.float32))
    whole = dict(layers["sizes"], experts_held=16, expert_offset=0)
    want, counts = reference.moe_ffn(n, w, whole)
    gates, chosen = reference.router_gates(n, w, whole)
    assert int(np.sum(counts)) == 2 * T * 3 and np.all(np.sum(chosen, axis=-1) == 3)
    np.testing.assert_allclose(np.asarray(gates).sum(axis=-1), 2.448, rtol=1e-5)  # normalised, then scaled
    shared = reference.dense_ffn(n, w["shared"])
    total, pairs = 0.0, 0
    for share in range(8):
        held = toy(expert_offset=2 * share)
        mine = dict(w, **{name: w[name][2 * share : 2 * share + 2] for name in ("w1", "w3", "w2")})
        out, routed, pairs_here, gate = backbone.moe_ffn(held, mine, n)
        assert np.array_equal(routed, counts) and int(pairs_here) == int(counts[2 * share : 2 * share + 2].sum())
        assert gate is None  # a silu gate counts no units
        total, pairs = total + out, pairs + int(pairs_here)
        # the block adds the shared expert to every share: whole, each time
        whole_block = backbone.block(held, "full_attention", "moe", dict(params["layer_1"], moe=mine), n)[0]
        assert whole_block.shape == n.shape
    close(total + shared, want, "eight shares and the shared expert once")
    assert pairs == 2 * T * 3


@pytest.mark.parametrize("padding", [[True, True, False, False], [False, True, True, True]])
def test_a_window_of_padding_adds_nothing_to_the_counters(tiles_of_four, seeded, padding):
    spec, params, _, x, _ = seeded
    _, _, aux = backbone.forward_backbone_aux(spec, params, x, active=jnp.asarray(padding))
    alone = backbone.forward_backbone_aux(spec, params, x[np.asarray(padding)])[2]
    assert set(aux) == set(alone) == {"router_tokens", "pairs_here", "pairs_total", "pairs_attended", "pairs_multiplied"}
    for name in aux:
        assert np.array_equal(aux[name], alone[name]), name


def test_the_fit_step_sums_its_counters_and_says_the_latents_sizes(tiles_of_four, seeded):
    """One member, two epochs of two steps of 2 windows, one slot of
    padding: the counters are those of the windows trained, and the span
    says what a row keeps of itself."""
    spec, params, _, _, _ = seeded
    fit = build_raw_windowed_fit_fn(spec, FitConfig(epochs=2, batch_size=2, shuffle=False))
    rng = np.random.RandomState(4)
    series = rng.uniform(0, 1, (T + 4, 5)).astype(np.float32)
    ytgt = rng.uniform(0, 1, (4, 5)).astype(np.float32)
    opt_state = spec.optimizer.to_optax().init(params)
    order = jnp.asarray([0, 1, 2, 0], jnp.int32)
    wtr = jnp.asarray([1, 1, 1, 0], jnp.float32)
    outs = jax.jit(fit)(params, opt_state, series, ytgt, order, wtr, jnp.zeros((0,), jnp.float32), jax.random.PRNGKey(0))
    counters = jax.tree_util.tree_map(lambda a: np.asarray(a).sum(axis=0), outs[5])
    assert counters["steps_run"] == 4
    assert counters["pairs_attended"].tolist() == [6 * T * (T + 1) / 2] * 3
    assert counters["pairs_multiplied"].tolist() == [6 * 21 * 16] * 3
    assert counters["pairs_total"].tolist() == [6 * T * 3] * 2
    assert "gate_active" not in counters and "keys_selected" not in counters
    attrs = spec.fit_counter_attrs(counters)
    assert (attrs["kv_lora_rank"], attrs["qk_rope_head_dim"], attrs["v_head_dim"]) == (RANK, ROPE, VALUE)
    assert attrs["kv_expanded_dim"] == HEADS * (NOPE + VALUE) and attrs["num_experts"] == 16
    assert "index_topk" not in attrs
    other = smallthinker_toy().fit_counter_attrs({})
    assert "kv_lora_rank" not in other and "kv_expanded_dim" not in other


def test_the_estimator_builds_the_kind_by_name(tiles_of_four):
    estimator = JaxBackboneForecast(
        kind="kanana", lookback_window=T, num_hidden_layers=3, hidden_size=32, num_attention_heads=HEADS,
        kv_lora_rank=RANK, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VALUE, intermediate_size=48,
        moe_intermediate_size=12, n_routed_experts=16, experts_held=2, expert_offset=13, num_experts_per_tok=3,
        rope_theta=100.0, epochs=1, batch_size=2,
    )
    rng = np.random.RandomState(11)
    X = rng.uniform(0, 1, (T + 6, 5)).astype(np.float32)
    estimator.fit(X, X)
    assert estimator.spec_ == toy() and estimator.predict(X).shape == (6, 5)
    loss, norms = estimator.training_loss_and_grad_norms(X, X)
    assert np.isfinite(loss) and all(np.isfinite(v) for v in jax.tree_util.tree_leaves(norms))
    with pytest.raises(ValueError, match="kanana runs q_lora_rank=None only"):
        JaxBackboneForecast(kind="kanana", lookback_window=T, q_lora_rank=1536).fit(X, X)


# ---------------------------------------------------------------------------
# the kind that arrived last before this one keeps its program and its weights


def smallthinker_toy(**overrides):
    """``tests/models/test_prerouted_backbone.py:toy``, to the letter."""
    sizes = dict(
        lookback_window=24, num_hidden_layers=4, hidden_size=32, head_dim=16, num_attention_heads=14,
        num_key_value_heads=2, moe_ffn_hidden_size=24, moe_num_primary_experts=8, experts_held=2,
        expert_offset=2, moe_num_active_primary_experts=3, sliding_window_size=10,
    )
    sizes.update(overrides)
    return smallthinker(5, **sizes)


def lowered_fit_text(spec) -> str:
    """``test_prerouted_backbone.py``'s lowering of a toy member's fit,
    traced anew: the fit of a spec is built once a process, and the
    tile is no part of a spec."""
    from gordo_tpu.models import training

    for cache in (training.build_raw_windowed_fit_fn, training.windowed_batch_loss_fn):
        cache.cache_clear()
    fit = build_raw_windowed_fit_fn(spec, FitConfig(epochs=2, batch_size=4))
    shapes = jax.eval_shape(lambda key: backbone.init_backbone(key, spec), jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: spec.optimizer.to_optax().init(p), shapes)
    S = jax.ShapeDtypeStruct
    return jax.jit(fit).lower(
        shapes, opt, S((spec.lookback_window + 8, 5), jnp.float32), S((8, 5), jnp.float32),
        S((8,), jnp.int32), S((8,), jnp.float32), S((0,), jnp.float32), S((2,), jnp.uint32),
    ).as_text()


@pytest.mark.parametrize("tile, want", [(PUBLISHED_TILE, "shipped"), (TILE, "tiles_of_four")])
def test_a_smallthinker_members_fit_program_is_the_text_the_parent_lowers(monkeypatch, tile, want):
    """The tile loops take a value width of their own and the heads come
    one of two ways: a ``smallthinker`` member's lowered fit program is,
    to the character, the text the parent lowers, with the tile as it
    ships (its full layer holds every score at once at 24 rows) and in
    tiles of 4; hashes taken at commit ``aa6d7f6`` before any edit
    (``lfm2_moe``'s and ``keye_vl2``'s: ``test_banded_backbone.py``;
    ``laguna``'s: ``test_prerouted_backbone.py``). Another text would be
    another compilation, and on the chip another routing lottery (PR 28). The
    hashes of both texts (a sliding layer attends in tiles under either tile) was taken again at commit ``9fae991`` with PR 47's
    change applied: the tile loops' results pass through
    ``checkpoint_name`` (``backbone.SAVED_TILES``), an identity that
    lowers to no operation, but the counter behind the numbers at the
    end of private functions' names (``@closed_call_317``) runs further,
    so those numbers move and nothing else does
    (``test_tiles_kept.py`` holds the parent's text against the new one
    with the numbers stripped; a toy rematerialises nothing)."""
    monkeypatch.setattr(backbone, "ATTENTION_TILE", tile)
    assert hashlib.sha256(lowered_fit_text(smallthinker_toy()).encode()).hexdigest() == SMALLTHINKER_TOY_FIT_TEXT[want]


def test_a_smallthinker_members_seeded_weights_are_what_they_were():
    params = backbone.init_backbone(jax.random.PRNGKey(7), smallthinker_toy())
    digest = hashlib.sha256(
        b"".join(np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(params))
    ).hexdigest()
    assert digest == SMALLTHINKER_TOY_DIGEST


SMALLTHINKER_TOY_DIGEST = "d042b74a7f6f784f9d67b0c30c512374115576d761de292a58f9917b06da0b5c"
SMALLTHINKER_TOY_FIT_TEXT = {
    "shipped": "9c3cf7dc561fc3167695e81281b07232febd8a1f7e973abac6912775422ba1cc",
    "tiles_of_four": "ccbd8e6467087988892bbf8a3540ecef0b94ccdd04a250f3057dbbbd93163cdf",
}
