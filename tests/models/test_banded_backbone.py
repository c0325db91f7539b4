"""``kind: laguna`` at toy widths on the CPU against its plain reference
(loaded by path: it imports nothing of the program's layer code):
attention in tiles under a mask by position (full and sliding), layers
of unlike head counts, YaRN's rotary over half a head, the gate on the
heads, the shared expert beside the scaled router's share layer, and
what the fit step counts."""

import hashlib
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import JaxBackboneForecast, backbone, register_model_builder
from gordo_tpu.models.factories import keye_vl2, laguna, lfm2_moe
from gordo_tpu.models.factories.backbone import LAGUNA_XS2_CONFIG
from gordo_tpu.models.training import FitConfig, build_raw_windowed_fit_fn
from gordo_tpu.ops.losses import resolve_loss, weighted_mean_loss
from gordo_tpu.planner.costmodel import spec_flops_per_sample, spec_param_count
from gordo_tpu.planner.packing import trains_alone, windowed_scoring_batch

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOLERANCE = 1e-4  # of scale: both sides compute in float32 on the CPU
T, WINDOW, TILE = 24, 6, 4
PUBLISHED_TILE = backbone.ATTENTION_TILE


@pytest.fixture(autouse=True)
def tiles_of_four(monkeypatch):
    """The toys' windows of 24 rows in tiles of 4: the tile is the
    program's constant, not an option of a spec."""
    monkeypatch.setattr(backbone, "ATTENTION_TILE", TILE)
    return monkeypatch

#: the published rotary parameters at a toy's scale: the ramp lies inside
#: the 4 frequencies of the rotated half of a 16-wide head
TOY_ROPES = {"full_attention": {"rope_theta": 100.0, "factor": 8, "original_max_position_embeddings": 16,
                                "beta_fast": 2, "beta_slow": 0.25}}


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(CHIP, "reference", "laguna_banded_backbone.py")
    spec = importlib.util.spec_from_file_location("reference_laguna_banded_backbone", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def toy(**overrides):
    """The five layers of the cut (full, three sliding, full; the first
    dense) at hidden 32: 6 heads of 16 in a full layer and 8 in a
    sliding one over 2 key/value heads, a window of 6 of 24 rows in
    tiles of 4, experts 2-3 of 8 held beside a shared expert."""
    sizes = dict(
        lookback_window=T, num_hidden_layers=5, hidden_size=32, head_dim=16,
        num_attention_heads_per_layer=[6, 8, 8, 8, 6], num_key_value_heads=2,
        intermediate_size=48, moe_intermediate_size=24, shared_expert_intermediate_size=20,
        num_experts=8, experts_held=2, expert_offset=2, num_experts_per_tok=2,
        sliding_window=WINDOW, rope_parameters=TOY_ROPES,
    )
    sizes.update(overrides)
    return laguna(5, **sizes)


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


def attended_by_arithmetic(length=T, window=WINDOW):
    return sum(min(t + 1, window) for t in range(length))


def test_the_reference_imports_nothing_from_the_program():
    source = open(os.path.join(CHIP, "reference", "laguna_banded_backbone.py")).read()
    assert "import gordo_tpu" not in source and "from gordo_tpu" not in source


def test_the_factory_defaults_are_the_catalog_row_key_by_key():
    """Every key of the published config is the factory's default or a
    fact it states; the benchmark's file repeats them but for the cut."""
    published = LAGUNA_XS2_CONFIG
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
        assert row["config"] == published
    with open(os.path.join(CHIP, "configs", "laguna-xs2-50tag-lb8192.json")) as f:
        stated = json.load(f)
    assert stated["reduced"] == ["num_hidden_layers", "num_experts"]
    for key, value in published.items():
        if key in stated["reduced"]:
            assert stated["published"][key] == value and stated[key] < value
        else:
            assert stated[key] == value, key
    spec = laguna(50)
    assert spec.layer_ops == ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention") * 10
    assert spec.layer_ffns == ("dense",) + ("moe",) * 39
    assert spec.layer_heads == (48, 64, 64, 64) * 10 and spec.heads_by_layer == spec.layer_heads
    assert (spec.hidden_size, spec.head_dim, spec.num_key_value_heads) == (2048, 128, 8)
    assert (spec.intermediate_size, spec.moe_intermediate_size, spec.shared_expert_intermediate_size) == (8192, 512, 512)
    assert (spec.num_experts, spec.experts_held, spec.num_experts_per_tok) == (256, 256, 8)
    assert (spec.router, spec.routed_scaling_factor, spec.norm_eps) == ("sigmoid_bias", 2.5, 1e-6)
    assert (spec.sliding_window, PUBLISHED_TILE) == (512, 512) and not hasattr(spec, "attention_tile")
    assert spec.attention_gate and not spec.qk_norm
    full, sliding = spec.rope_of("full_attention"), spec.rope_of("sliding_attention")
    assert full == published["rope_parameters"]["full_attention"]
    assert sliding == published["rope_parameters"]["sliding_attention"]
    assert spec.lookback_window == 8192 and spec.windowed and not spec.member_axis and trains_alone(spec)
    assert "laguna" in register_model_builder.factories["JaxBackboneForecast"]
    assert JaxBackboneForecast("laguna").lookahead == 1
    # what the layers cannot be told otherwise is refused, not ignored
    for key, other in [("attention_bias", True), ("gating", False), ("moe_apply_router_weight_on_input", True)]:
        with pytest.raises(ValueError, match=key):
            laguna(50, **{key: other})
    assert laguna(50, **{k: published[k] for k in ("attention_bias", "gating", "vocab_size")}) == spec


def test_the_cut_counts_the_files_weights_per_member():
    with open(os.path.join(CHIP, "configs", "laguna-xs2-50tag-lb8192.json")) as f:
        stated = json.load(f)
    spec = laguna(50, num_hidden_layers=5, experts_held=16)
    assert spec.layer_ops == ("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
    full = 2 * 2048 * 48 * 128 + 2 * 2048 * 1024 + 2048 * 48
    sliding = 2 * 2048 * 64 * 128 + 2 * 2048 * 1024 + 2048 * 64
    routed = 2048 * 256 + 16 * 3 * 2048 * 512 + 3 * 2048 * 512
    assert spec.layer_param_count("full_attention", "dense", 48) == 2 * 2048 + full + 3 * 2048 * 8192
    assert spec.layer_param_count("sliding_attention", "moe", 64) == 2 * 2048 + sliding + routed
    assert spec.layer_param_count("full_attention", "moe", 48) == 2 * 2048 + full + routed
    assert spec.param_count() == spec_param_count(spec) == stated["weights_per_member"] == 439_124_018
    shapes = jax.eval_shape(lambda key: backbone.init_backbone(key, spec), jax.random.PRNGKey(0))
    assert backbone.trained_param_count(shapes) == 439_124_018
    assert shapes["layer_1"]["attn"]["wq"].shape == (2048, 8192) and "q_norm" not in shapes["layer_1"]["attn"]
    assert shapes["layer_4"]["attn"]["gate"].shape == (2048, 48)
    assert shapes["layer_4"]["moe"]["shared"]["w2"].shape == (512, 2048)
    # a window: a sliding layer attends to an eighth of what a full one does
    full_pairs, full_tiles = backbone.band_pairs(8192, 8192, 512)
    band_pairs, band_tiles = backbone.band_pairs(8192, 512, 512)
    assert (full_pairs, band_pairs) == (8192 * 8193 / 2, attended_by_arithmetic(8192, 512)) == (33_558_528, 4_063_488)
    assert (full_tiles, band_tiles) == (136 * 512 * 512, 31 * 512 * 512)
    assert round(100 * (1 - full_pairs / full_tiles), 1) == 5.9 and round(100 * (1 - band_pairs / band_tiles), 1) == 50.0
    per_token = spec_flops_per_sample(spec) / 8192
    projections = lambda heads: 2 * 2048 * (2 * heads * 128 + 2 * 1024 + heads)  # noqa: E731
    by_hand = 2 * 50 * 2048 + (
        2 * projections(48) + 3 * projections(64)
        + 4 * 48 * 128 * 2 * 8192 / 2 + 4 * 64 * 128 * 3 * band_pairs / 8192  # a full layer's useful half
        + 6 * 2048 * 8192 + 4 * (2 * 2048 * 256 + 8 * 16 / 256 * 6 * 2048 * 512 + 6 * 2048 * 512)
    )
    assert per_token == pytest.approx(by_hand, rel=1e-6)
    assert windowed_scoring_batch(spec) == 2


@pytest.mark.parametrize("bad", [
    dict(sliding_window=0), dict(sliding_window=-3), dict(num_attention_heads_per_layer=[6, 8, 8, 7, 6]),
    dict(num_attention_heads_per_layer=[6, 8]), dict(mlp_layer_types=["dense", "routed", "sparse", "sparse", "sparse"]),
    dict(rope_parameters={"full_attention": {"partial_rotary_factor": 0.2}}),
    dict(rope_parameters={"sliding_attention": {"rope_type": "linear"}}), dict(experts_held=7),
])
def test_a_spec_that_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        toy(**bad)


def test_yarn_frequencies_against_a_hand_count(reference):
    """The published parameters: 32 frequencies over the first 64 of a
    head's 128 dimensions; the ramp runs from dimension 5 to 16."""
    rope = LAGUNA_XS2_CONFIG["rope_parameters"]["full_attention"]
    got = backbone.yarn_inverse_frequencies(rope, 64)
    low = 64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(500000))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(500000))
    assert (math.floor(low), math.ceil(high)) == (5, 16)
    plain = [500000 ** (-2 * i / 64) for i in range(32)]
    by_hand = [
        f if i <= 5 else f / 64 if i >= 16 else f / 64 * (i - 5) / 11 + f * (1 - (i - 5) / 11)
        for i, f in enumerate(plain)
    ]
    np.testing.assert_allclose(got, by_hand, rtol=1e-6)
    assert got.dtype == np.float32 and got[0] == 1.0 and got[31] == pytest.approx(plain[31] / 64)
    assert np.array_equal(got, reference.yarn_frequencies(rope, 64))
    assert rope["attention_factor"] == pytest.approx(0.1 * math.log(64) + 1)
    # half a head is rotated and scaled, the other half is as it was
    x = jnp.asarray(np.random.RandomState(0).normal(size=(1, 9, 2, 128)).astype(np.float32))
    turned = np.asarray(backbone.scaled_rotary(x, rope))
    assert np.array_equal(turned[..., 64:], np.asarray(x)[..., 64:])
    np.testing.assert_allclose(turned[0, 0, :, :64], 1.4158883083359672 * np.asarray(x)[0, 0, :, :64], rtol=1e-6)
    angle = 8 * got[3]
    want = 1.4158883083359672 * (np.asarray(x)[0, 8, 1, 3] * math.cos(angle) - np.asarray(x)[0, 8, 1, 35] * math.sin(angle))
    assert turned[0, 8, 1, 3] == pytest.approx(want, rel=1e-5)
    close(turned, reference.placed(x, rope), "placed", 1e-6)


@pytest.mark.parametrize("layer,op", [(0, "full_attention"), (2, "sliding_attention")])
def test_the_operator_against_the_reference(seeded, reference, layer, op):
    spec, params, layers, _, _ = seeded
    sizes, weights = layers["sizes"], layers["weights"][f"layer_{layer}"]
    u = jnp.asarray(np.random.RandomState(5).normal(size=(3, T, 32)).astype(np.float32))
    got, (attended, multiplied) = backbone.banded_attention(spec, op, params[f"layer_{layer}"]["attn"], u)
    want, pairs = reference.attention(u, weights["attn"], op, sizes, query_rows=7)
    close(got, want, "output")
    window = WINDOW if op == "sliding_attention" else T
    assert float(attended) == float(np.sum(pairs)) == 3 * attended_by_arithmetic(T, window)
    tiles = sum(min(i, -(-(window - 1) // TILE)) + 1 for i in range(T // TILE))
    assert float(multiplied) == 3 * tiles * TILE * TILE
    # a sliding block of 4 queries at a window of 6 visits its own tile and two before it
    assert tiles == (21 if op == "full_attention" else 1 + 2 + 3 * 4)


def test_the_band_against_every_query_one_at_a_time(seeded, reference):
    """Each query's own keys gathered and attended to, one query at a
    time, against the program's tiles: heads of two counts."""
    spec, params, layers, _, _ = seeded
    u = jnp.asarray(np.random.RandomState(6).normal(size=(2, T, 32)).astype(np.float32))
    for layer, op, heads in ((1, "sliding_attention", 8), (4, "full_attention", 6)):
        w = layers["weights"][f"layer_{layer}"]["attn"]
        got, _ = backbone.banded_attention(spec, op, params[f"layer_{layer}"]["attn"], u)
        rope = layers["sizes"]["rope_parameters"][op]
        q = np.asarray(reference.placed((u @ w["wq"]).reshape(2, T, heads, 16), rope))
        k = np.repeat(np.asarray(reference.placed((u @ w["wk"]).reshape(2, T, 2, 16), rope)), heads // 2, axis=2)
        v = np.repeat(np.asarray(u @ w["wv"]).reshape(2, T, 2, 16), heads // 2, axis=2)
        gate = 1.0 / (1.0 + np.exp(-np.asarray(u @ w["gate"], np.float64)))
        out = np.zeros((2, T, heads, 16))
        for b in range(2):
            for t in range(T):
                first = max(0, t - WINDOW + 1) if op == "sliding_attention" else 0
                scores = np.einsum("hd,shd->hs", q[b, t], k[b, first : t + 1]) / 4.0
                weights = np.exp(scores - scores.max(axis=1, keepdims=True))
                weights /= weights.sum(axis=1, keepdims=True)
                out[b, t] = np.einsum("hs,shd->hd", weights, v[b, first : t + 1]) * gate[b, t][:, None]
        close(got, out.reshape(2, T, heads * 16) @ w["wo"], op)


@pytest.mark.parametrize("window", [T, T + 5, 4096])
def test_a_window_of_at_least_the_length_makes_sliding_attention_full_attention(seeded, window):
    """At the same heads and rotary: a sliding layer's weights under
    both operators, the full one given the sliding layer's rotary."""
    spec, params, _, _, _ = seeded
    ropes = {**TOY_ROPES, "full_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}}
    wide = toy(sliding_window=window, rope_parameters=ropes)
    u = jnp.asarray(np.random.RandomState(8).normal(size=(2, T, 32)).astype(np.float32))
    sliding, (attended, multiplied) = backbone.banded_attention(wide, "sliding_attention", params["layer_1"]["attn"], u)
    full, (attended_f, multiplied_f) = backbone.banded_attention(wide, "full_attention", params["layer_1"]["attn"], u)
    assert np.array_equal(np.asarray(sliding), np.asarray(full))
    assert (float(attended), float(multiplied)) == (float(attended_f), float(multiplied_f)) == (2 * T * (T + 1) / 2, 2 * 21 * 16)
    # and, every score held at once, the operator that was here
    close(full, backbone.gqa_attention(wide, params["layer_1"]["attn"], u), "whole", 1e-6)


@pytest.mark.parametrize("tile", [3, 5, 6, 8, 23])
def test_tile_size_does_not_change_the_result(seeded, tiles_of_four, tile):
    """Tiles that divide the window and that do not (rows of padding
    after it), smaller and larger than the sliding window."""
    spec, params, _, x, _ = seeded
    out, _, aux = backbone.forward_backbone_aux(spec, params, x)
    tiles_of_four.setattr(backbone, "ATTENTION_TILE", tile)
    out_o, _, aux_o = backbone.forward_backbone_aux(spec, params, x)
    close(out_o, out, "output", 1e-6)
    assert np.array_equal(aux_o["pairs_attended"], aux["pairs_attended"])
    assert np.array_equal(aux_o["router_tokens"], aux["router_tokens"])
    blocks, back = -(-T // tile), -(-(WINDOW - 1) // tile)
    assert aux_o["pairs_multiplied"][1] == 4 * tile * tile * sum(min(i, back) + 1 for i in range(blocks))
    assert aux_o["pairs_multiplied"][0] == 4 * tile * tile * blocks * (blocks + 1) / 2


@pytest.mark.parametrize("tile", [T, PUBLISHED_TILE])
def test_a_window_no_longer_than_a_tile_holds_every_score_of_a_full_layer_at_once(seeded, tiles_of_four, tile):
    """``full_attention`` takes the tiles only above a tile's rows: a
    full layer of a shorter window reports no tiles, a sliding one does."""
    spec, params, _, x, _ = seeded
    tiled = backbone.forward_backbone_aux(spec, params, x)[0]
    tiles_of_four.setattr(backbone, "ATTENTION_TILE", tile)
    out, _, aux = backbone.forward_backbone_aux(spec, params, x)
    close(out, tiled, "output", 1e-6)
    assert aux["pairs_attended"].tolist() == [4 * attended_by_arithmetic()] * 3
    assert aux["pairs_multiplied"].tolist() == [4 * T * T] * 3


def test_the_whole_forward_and_its_counters_against_the_reference(seeded, reference):
    spec, params, layers, x, _ = seeded
    out, penalty, aux = jax.jit(lambda p, x: backbone.forward_backbone_aux(spec, p, x))(params, x)
    close(out, reference.forward(layers, x, block_windows=2), "forward")
    found = reference.counters(layers, x)
    assert np.array_equal(aux["router_tokens"], found["routed"]) and found["routed"].shape == (4, 8)
    assert np.array_equal(aux["pairs_here"], found["routed"][:, 2:4].sum(axis=1))
    assert aux["pairs_total"].tolist() == [4 * T * 2] * 4
    assert np.array_equal(aux["pairs_attended"], found["attended"])
    sliding, full = 4 * attended_by_arithmetic(), 4 * T * (T + 1) / 2
    assert aux["pairs_attended"].tolist() == [full, sliding, sliding, sliding, full]
    assert aux["pairs_multiplied"].tolist() == [4 * 21 * 16, 4 * 15 * 16, 4 * 15 * 16, 4 * 15 * 16, 4 * 21 * 16]
    assert float(penalty) == 0.0
    plain, _ = backbone.forward_backbone(spec, params, x)
    close(plain, out, "without the counters")


def loss_of(spec, x, y, w, remat=False, active=None):
    def loss(p):
        out, penalty, _ = backbone.forward_backbone_aux(spec, p, x, remat=remat, active=active)
        return weighted_mean_loss(resolve_loss("mse")(out, y), w) + penalty
    return loss


def test_loss_and_every_gradient_leaf_against_the_reference(seeded, reference):
    spec, params, layers, x, y = seeded
    w = np.array([1, 1, 0.5, 1], np.float32)
    loss, grads = jax.jit(jax.value_and_grad(loss_of(spec, x, y, w)))(params)
    want_loss, want = reference.loss_and_grads(layers, x, y, w)
    assert abs(float(loss) - want_loss) <= TOLERANCE * max(1.0, abs(want_loss))
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(want)) == 5 + 5 * 7 + 3 + 4 * 8
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        close(got, ref, name)
        if "expert_bias" in name:
            assert not np.any(np.asarray(got)) and not np.any(np.asarray(ref))
        elif "moe" not in name or "shared" in name or "router" in name:
            assert np.any(np.asarray(ref)), name  # every gate and shared expert learns
    # rematerialised layers and plain agree
    loss_r, grads_r = jax.jit(jax.value_and_grad(loss_of(spec, x, y, w, True)))(params)
    assert float(loss_r) == pytest.approx(float(loss), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads_r), jax.tree_util.tree_leaves(grads)):
        close(a, b, "remat")
    norms = jax.tree_util.tree_map(lambda g: float(np.sqrt(np.sum(np.square(g)))), grads)
    want_norms = jax.tree_util.tree_map(lambda g: float(np.sqrt(np.sum(np.square(g)))), want)
    readings = reference.step_readings(float(loss), norms, want_loss, want_norms)
    assert max(readings["leaf"], readings["loss"], readings["grad_norm"]) < 1e-4
    # each reading of the step check has its limit (PERF.md 6: the chip's readings set them)
    assert set(reference.STEP_LIMITS) == {"output", "leaf", "loss", "grad_norm"}
    assert reference.STEP_LIMITS["output"] == 1.5e-4  # the sibling cell's, on the same traffic


@pytest.mark.parametrize(
    "leaf, share, factor, over",
    [
        ("wk", 1.07e-3, 1.0499, False),  # the chip's reading that showed it: rounding, 5% of a leaf at the threshold
        ("wk", 2.5e-4, 3.4, False),  # the same rounding on a window that leaves the leaf smaller than it
        ("wq", 1e-5, 3.0, False),  # a sliding layer's: nothing but rounding
        ("wk", 1e-3, 3.0, True),  # layer 0's three times what it should be
        ("wq", 0.1, 1.03, True),  # well above the floor: its norm's relative error, as every other leaf
        ("gate", 2e-3, 1.05, True),  # every other leaf that carries gradient: as the sibling reads it
        ("gate", 2e-3, 1.005, False),
        ("gate", 5e-4, 1.05, False),  # below a thousandth of the whole the sibling passes by
    ],
)
def test_the_step_check_reads_wq_and_wk_over_a_floor_and_every_other_leaf_as_it_was(
    reference, leaf, share, factor, over
):
    """PERF.md 6 (PR 33, third round): a ``wq`` or ``wk`` is read as what
    it adds to the whole's square once it is below a hundredth of the
    whole; the other leaves keep the sibling's reading and threshold."""
    want = {"head": {"W": 1.0}, "layer_0": {"attn": {"gate": 2e-3, "wk": 1e-3, "wq": 1e-3}}}
    want["layer_0"]["attn"][leaf] = share
    got = json.loads(json.dumps(want))
    got["layer_0"]["attn"][leaf] = share * factor
    readings = reference.step_readings(1.0, got, 1.0, want)
    assert (readings["leaf"] > reference.STEP_LIMITS["leaf"]) is over, readings
    if over:
        assert readings["worst_leaf"] == f"['layer_0']['attn']['{leaf}']"
    sibling = reference._shared.step_readings(1.0, got, 1.0, want)
    assert {k: v for k, v in readings.items() if "leaf" not in k} == {
        k: v for k, v in sibling.items() if "leaf" not in k
    }
    if leaf == "gate":
        assert readings["leaf"] == sibling["leaf"]


def test_the_gate_multiplies_each_heads_output(seeded):
    """With ``wo`` laying the first two heads' outputs side by side: a
    gate of zero weights halves every head, the layer's own gate weighs
    each head by its sigmoid, and one head's gate driven shut switches
    that head off and no other."""
    spec, params, _, _, _ = seeded
    u = jnp.asarray(np.random.RandomState(9).normal(size=(2, T, 32)).astype(np.float32)).at[:, :, 0].set(1.0)
    w = dict(params["layer_2"]["attn"], wo=jnp.eye(8 * 16, dtype=jnp.float32)[:, :32])
    attend = lambda weights: np.asarray(backbone.banded_attention(spec, "sliding_attention", weights, u)[0])  # noqa: E731
    zero = jnp.zeros_like(w["gate"])
    half = attend(dict(w, gate=zero))
    assert half.shape == (2, T, 32) and np.any(half[..., :16]) and np.any(half[..., 16:])
    gate = 1.0 / (1.0 + np.exp(-np.asarray(u @ w["gate"], np.float64)))[..., :2]
    close(attend(w), 2 * half * np.repeat(gate, 16, axis=-1), "gated", 1e-6)
    shut = attend(dict(w, gate=zero.at[0, 1].set(-1e9)))
    assert np.array_equal(shut[..., :16], half[..., :16]) and not np.any(shut[..., 16:])


def test_head_counts_6_and_8_in_one_spec(seeded):
    spec, params, _, x, _ = seeded
    assert spec.heads_by_layer == (6, 8, 8, 8, 6)
    widths = [params[f"layer_{i}"]["attn"]["wq"].shape[1] // 16 for i in range(5)]
    gates = [params[f"layer_{i}"]["attn"]["gate"].shape[1] for i in range(5)]
    assert widths == gates == [6, 8, 8, 8, 6]
    assert [params[f"layer_{i}"]["attn"]["wo"].shape for i in (0, 1)] == [(96, 32), (128, 32)]
    assert spec.param_count() == backbone.trained_param_count(params) == spec_param_count(spec)


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(reference):
    """Sixteen holders of 2 of 32 experts each: the routed parts they
    give, and the shared expert that each of them computes, counted
    once, are what the uncut reference gives for the whole layer."""
    whole = toy(num_experts=32, experts_held=32, expert_offset=0, num_experts_per_tok=4)
    params = backbone.init_backbone(jax.random.PRNGKey(11), whole)
    w = params["layer_3"]["moe"]
    layers = reference.layers_of(Artifact(whole, params))
    u = jnp.asarray(np.random.RandomState(12).normal(size=(2, T, 32)).astype(np.float32))
    want, counts = reference.moe_ffn(u, layers["weights"]["layer_3"]["moe"], layers["sizes"])
    total, pairs, routed_by_all = jnp.zeros_like(u), 0, None
    for share in range(16):
        spec = toy(num_experts=32, experts_held=2, expert_offset=2 * share, num_experts_per_tok=4)
        held = dict(w, **{name: w[name][2 * share : 2 * share + 2] for name in ("w1", "w3", "w2")})
        out, routed, pairs_here, _ = backbone.moe_ffn(spec, held, u)
        total, pairs = total + out, pairs + int(pairs_here)
        assert routed_by_all is None or np.array_equal(routed, routed_by_all)  # every holder routes alike
        routed_by_all = routed
    shared = backbone.dense_ffn(w["shared"], u)
    close(total + shared, want, "sixteen shares and the shared expert once")
    assert pairs == 2 * T * 4 and np.array_equal(routed_by_all, counts)
    # counted sixteen times it is another layer
    assert float(np.max(np.abs(np.asarray(total + 16 * shared) - np.asarray(want)))) > 0.1
    # the router's weights sum to 2.5 a token
    _, weights = backbone.route(whole, w, u.reshape(-1, 32))
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 2.5, rtol=1e-5)


@pytest.mark.parametrize("padding", [[True, True, False, False], [False, True, True, True]])
def test_a_window_of_padding_adds_nothing_to_the_counters(seeded, padding):
    spec, params, _, x, y = seeded
    active = jnp.asarray(padding)
    _, _, aux = backbone.forward_backbone_aux(spec, params, x, active=active)
    kept = int(np.sum(padding))
    alone = backbone.forward_backbone_aux(spec, params, x[np.asarray(padding)])[2]
    for name in ("pairs_attended", "pairs_multiplied", "pairs_here", "pairs_total", "router_tokens"):
        assert np.array_equal(aux[name], alone[name]), name
    assert aux["pairs_attended"][1] == kept * attended_by_arithmetic()
    assert aux["pairs_attended"].dtype == aux["pairs_multiplied"].dtype == jnp.float32


def test_the_fit_step_sums_its_counters(seeded):
    """One member, two epochs of two steps of 2 windows, one slot of
    padding: the counters are those of the windows trained."""
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
    assert counters["pairs_attended"].tolist() == [6 * T * (T + 1) / 2] + [6 * attended_by_arithmetic()] * 3 + [6 * T * (T + 1) / 2]
    assert counters["pairs_multiplied"].tolist() == [6 * 21 * 16] + [6 * 15 * 16] * 3 + [6 * 21 * 16]
    assert counters["pairs_total"].tolist() == [6 * T * 2] * 4
    attrs = spec.fit_counter_attrs(counters)
    assert attrs["pairs_attended"] == counters["pairs_attended"].tolist()
    assert attrs["num_experts"] == 8 and "index_topk" not in attrs


def test_the_kinds_that_were_here_keep_their_weights_and_lfm2s_fit_program_its_text(tiles_of_four):
    """The gate's and the shared expert's keys come from a split of
    another length, so only a spec that has one takes it: a ``keye_vl2``
    member's weights at a seed are what they were (``lfm2_moe``'s:
    ``test_sparse_backbone.py``), and the lowered fit program of an
    ``lfm2_moe`` member is, to the character, the text the parent
    lowers (hashes taken at the parent): another program would be
    another compilation, and on the chip another routing lottery.
    ``lfm2_moe``'s is still the parent's (no tile loop, no name). The
    hash of the ``keye_vl2`` member's text was taken again at commit ``9fae991`` with PR 47's
    change applied: the tile loops' results pass through
    ``checkpoint_name`` (``backbone.SAVED_TILES``), an identity that
    lowers to no operation, but the counter behind the numbers at the
    end of private functions' names (``@closed_call_317``) runs further,
    so those numbers move and nothing else does
    (``test_tiles_kept.py`` holds the parent's text against the new one
    with the numbers stripped; a toy rematerialises nothing). Taken once
    more at commit ``5ef8279`` with PR 48's change applied: the
    selection's ``top_k`` of a block's scores is a search of 32 compare-
    and-row-sum passes (``backbone.kth_largest``), which selects the
    same bits (``test_sparse_backbone.py`` holds it to ``top_k``) in
    another text; ``lfm2_moe``'s is as it was, and so are the four other
    kinds' in their files."""
    tiles_of_four.undo()  # the program as it ships
    assert backbone.ATTENTION_TILE == PUBLISHED_TILE
    sparse = keye_vl2(
        5, lookback_window=24, num_hidden_layers=2, hidden_size=32, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, moe_intermediate_size=24, num_experts=8, experts_held=2, expert_offset=2,
        num_experts_per_tok=2,
        sa_config=dict(indexer_head_dim=8, indexer_num_heads=8, topk=6, q_chunk_size=8, kv_chunk_size=8),
    )
    params = backbone.init_backbone(jax.random.PRNGKey(7), sparse)
    digest = hashlib.sha256(
        b"".join(np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(params))
    ).hexdigest()
    assert digest == KEYE_TOY_DIGEST
    dense = lfm2_moe(
        5, lookback_window=12, layer_types=("conv", "full_attention", "conv", "conv"),
        num_dense_layers=1, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=48, moe_intermediate_size=24, num_experts=8, experts_held=2,
        expert_offset=2, num_experts_per_tok=2,
    )
    for spec, want in ((dense, LFM2_TOY_FIT_TEXT), (sparse, KEYE_TOY_FIT_TEXT)):
        fit = build_raw_windowed_fit_fn(spec, FitConfig(epochs=2, batch_size=4))
        shapes = jax.eval_shape(lambda key: backbone.init_backbone(key, spec), jax.random.PRNGKey(0))
        opt = jax.eval_shape(lambda p: spec.optimizer.to_optax().init(p), shapes)
        S = jax.ShapeDtypeStruct
        text = jax.jit(fit).lower(
            shapes, opt, S((spec.lookback_window + 8, 5), jnp.float32), S((8, 5), jnp.float32),
            S((8,), jnp.int32), S((8,), jnp.float32), S((0,), jnp.float32), S((2,), jnp.uint32),
        ).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() == want


KEYE_TOY_DIGEST = "3e2ebade1865677a88f9ab10cb0612a11ba8a4991ac9c2aa6c4000cec3ee07fb"
LFM2_TOY_FIT_TEXT = "9fc2c9e9fe4e74c3a3334d5b0b16d9b7120e9629a5893aab0290f52a967d05df"
KEYE_TOY_FIT_TEXT = "a8528ef5fb8cfc3817ec5e70bcee1fd81f1b865d7317aa77775e1f2d55cbf811"
