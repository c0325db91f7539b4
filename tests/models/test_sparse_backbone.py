"""``kind: keye_vl2`` at toy widths on the CPU against its plain
reference (loaded by path: it imports nothing of the program's layer
code): the sparse-attention operator, the indexer's objective, the
softmax router's share layer, and what the fit step counts."""

import functools
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import JaxBackboneForecast, backbone, register_model_builder
from gordo_tpu.models.factories import keye_vl2
from gordo_tpu.models.factories.backbone import KEYE_VL2_30B_A3B_CONFIG
from gordo_tpu.ops.losses import resolve_loss, weighted_mean_loss
from gordo_tpu.planner.costmodel import spec_flops_per_sample, spec_param_count
from gordo_tpu.planner.packing import windowed_scoring_batch

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
TOLERANCE = 1e-4  # of scale: both sides compute in float32 on the CPU
T, TOPK = 24, 6


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(CHIP, "reference", "keye_sparse_backbone.py")
    spec = importlib.util.spec_from_file_location("reference_keye_sparse_backbone", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def toy(**overrides):
    """2 layers of 4 heads of 16 over 2 key/value heads at hidden 32 (so
    q and o are 32 x 64), an indexer of 8 heads of 8 that keeps 6 of 24
    rows, 2 of 8 experts held."""
    sparse = dict(indexer_head_dim=8, indexer_num_heads=8, topk=TOPK, q_chunk_size=8, kv_chunk_size=8)
    sparse.update(overrides.pop("sa_config", {}))
    sizes = dict(
        lookback_window=T, num_hidden_layers=2, hidden_size=32, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2, moe_intermediate_size=24, num_experts=8,
        experts_held=2, expert_offset=2, num_experts_per_tok=2, sa_config=sparse,
    )
    sizes.update(overrides)
    return keye_vl2(5, **sizes)


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


def kept_by_arithmetic(length=T, top_k=TOPK):
    return sum(min(t + 1, top_k) for t in range(length))


def test_the_reference_imports_nothing_from_the_program():
    source = open(os.path.join(CHIP, "reference", "keye_sparse_backbone.py")).read()
    assert "import gordo_tpu" not in source and "from gordo_tpu" not in source


def test_the_factory_defaults_are_the_catalog_row_key_by_key():
    """Every key of the published config is the factory's default or a
    fact it states; the benchmark's file repeats them but for the cut."""
    published = KEYE_VL2_30B_A3B_CONFIG
    with open(os.path.join(CHIP, "configs", "keye-vl2-30b-a3b-50tag-lb8192.json")) as f:
        stated = json.load(f)
    assert stated["reduced"] == ["num_hidden_layers", "num_experts"]
    for key, value in published.items():
        if key in stated["reduced"]:
            assert stated["published"][key] == value and stated[key] < value
        else:
            assert stated[key] == value, key
    spec = keye_vl2(50)
    assert spec.layer_ops == ("sparse_attention",) * 48 and spec.layer_ffns == ("moe",) * 48
    assert (spec.hidden_size, spec.head_dim, spec.moe_intermediate_size) == (2048, 128, 768)
    assert (spec.num_attention_heads, spec.num_key_value_heads) == (32, 4)
    assert (spec.num_experts, spec.experts_held, spec.num_experts_per_tok) == (128, 128, 8)
    assert spec.router == "softmax"
    assert (spec.rope_theta, spec.norm_eps) == (1e7, 1e-6)
    sparse = published["sa_config"]
    assert (spec.index_n_heads, spec.index_head_dim, spec.index_topk) == (
        sparse["indexer_num_heads"], sparse["indexer_head_dim"], sparse["topk"]) == (16, 64, 2048)
    assert spec.index_chunk == 512
    assert spec.lookback_window == 8192 and spec.windowed and not spec.member_axis
    assert "keye_vl2" in register_model_builder.factories["JaxBackboneForecast"]
    assert JaxBackboneForecast("keye_vl2").lookahead == 1
    # what the layers cannot be told otherwise is refused, not ignored
    for key, other in [("attention_bias", True), ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
                       ("hidden_act", "gelu"), ("use_sliding_window", True), ("norm_topk_prob", False)]:
        with pytest.raises(ValueError, match=key):
            keye_vl2(50, **{key: other})
    assert keye_vl2(50, **{k: published[k] for k in ("attention_bias", "mlp_only_layers", "vocab_size")}) == spec
    with pytest.raises(ValueError):
        keye_vl2(50, sa_config={"indexer_num_kv_heads": 2})


def test_the_cut_counts_387_806_770_weights():
    spec = keye_vl2(50, num_hidden_layers=4, experts_held=16)
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    indexer = 2048 * 1024 + 2048 * 64 + 128 + 2048 * 16
    assert (attention, indexer, spec.indexer_param_count) == (18_874_624, 2_261_120, 2_261_120)
    a_layer = attention + indexer + 2 * 2048 + 2048 * 128 + 16 * 3 * 2048 * 768
    assert spec.layer_param_count("sparse_attention", "moe") == a_layer == 96_899_456
    assert spec.param_count() == spec_param_count(spec) == 387_806_770
    shapes = jax.eval_shape(lambda key: backbone.init_backbone(key, spec), jax.random.PRNGKey(0))
    assert backbone.trained_param_count(shapes) == 387_806_770
    assert "expert_bias" not in shapes["layer_0"]["moe"]  # a softmax router has none
    # a window: 43.7% of the causal pairs survive, and the operations say so
    kept = kept_by_arithmetic(8192, 2048)
    assert kept / (8192 * 8193 / 2) == pytest.approx(0.4375, abs=2e-4)
    per_token = spec_flops_per_sample(spec) / 8192
    by_hand = 2 * 50 * 2048 + 4 * (
        2 * 2048 * (2 * 4096 + 2 * 512 + 1024 + 64 + 16 + 128)
        + 4 * 4096 * kept / 8192 + 2 * 1024 * 8193 / 2 + 6 * 2048 * 768
    )
    assert per_token == pytest.approx(by_hand, rel=1e-6)
    # the scoring programs take as many rows a step as a fit step holds
    assert windowed_scoring_batch(spec) == 2


@pytest.mark.parametrize("bad", [
    dict(head_dim=15), dict(sa_config={"topk": 0}), dict(sa_config={"indexer_head_dim": 7}),
    dict(sa_config={"q_chunk_size": 0, "kv_chunk_size": 0}), dict(sa_config={"q_chunk_size": 4}),
    dict(num_key_value_heads=3), dict(experts_held=9),
])
def test_a_spec_that_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        toy(**bad)


def test_the_operator_against_the_reference(seeded, reference):
    spec, params, layers, _, _ = seeded
    sizes, weights = layers["sizes"], layers["weights"]["layer_0"]
    u = jnp.asarray(np.random.RandomState(5).normal(size=(3, T, 32)).astype(np.float32))
    got, objective, (kept, causal) = backbone.sparse_attention(
        spec, params["layer_0"]["attn"], params["layer_0"]["indexer"], u
    )
    want, kl, want_kept = reference.sparse_attention(u, weights["attn"], weights["indexer"], sizes)
    close(got, want, "output")
    assert float(objective) == pytest.approx(float(np.mean(kl)), rel=1e-5)
    assert float(kept) == float(np.sum(want_kept)) == 3 * kept_by_arithmetic()
    assert float(causal) == 3 * T * (T + 1) / 2
    # the index scores, a block of them
    qi, ki, wi = backbone.indexer_inputs(spec, params["layer_0"]["indexer"], u)
    r_qi, r_ki, r_wi = reference.indexer_of(u, weights["indexer"], sizes)
    close(backbone.index_scores(qi[1, 8:16], ki[1, :16], wi[1, 8:16]),
          reference.index_scores(r_qi, r_ki, r_wi)[1, 8:16, :16], "index scores")


def test_masked_blockwise_equals_a_gather_of_the_selected_keys(seeded, reference):
    """Each query's top-k keys gathered and attended to, one query at a
    time, against the program's masked blocks."""
    spec, params, layers, _, _ = seeded
    sizes, w = layers["sizes"], layers["weights"]["layer_0"]
    u = jnp.asarray(np.random.RandomState(6).normal(size=(2, T, 32)).astype(np.float32))
    got, _, _ = backbone.sparse_attention(spec, params["layer_0"]["attn"], params["layer_0"]["indexer"], u)
    q, k, v = (np.asarray(a) for a in reference.heads_of(u, w["attn"], sizes))
    index = np.asarray(reference.index_scores(*reference.indexer_of(u, w["indexer"], sizes)))
    out = np.zeros((2, T, 4, 16))
    for b in range(2):
        for t in range(T):
            order = np.argsort(-index[b, t, : t + 1], kind="stable")[:TOPK]  # S_t
            scores = np.einsum("hd,shd->hs", q[b, t], k[b, order]) / 4.0
            weights = np.exp(scores - scores.max(axis=1, keepdims=True))
            weights /= weights.sum(axis=1, keepdims=True)
            out[b, t] = np.einsum("hs,shd->hd", weights, v[b, order])
    close(got, out.reshape(2, T, 64) @ w["attn"]["wo"], "gathered")


@pytest.mark.parametrize("chunk", [4, 24, 5, 3, 512])
def test_block_sizes_do_not_change_the_result(seeded, chunk):
    """Tiles that divide the window, that do not (rows of padding after
    it), one tile, and one far larger than the window."""
    spec, params, _, x, _ = seeded
    other = toy(sa_config={"q_chunk_size": chunk, "kv_chunk_size": chunk})
    out, penalty, aux = backbone.forward_backbone_aux(spec, params, x)
    out_o, penalty_o, aux_o = backbone.forward_backbone_aux(other, params, x)
    close(out_o, out, "output", 1e-6)
    assert float(penalty_o) == pytest.approx(float(penalty), rel=1e-5)
    assert np.array_equal(aux_o["keys_selected"], aux["keys_selected"])
    assert np.array_equal(aux_o["router_tokens"], aux["router_tokens"])


@pytest.mark.parametrize("top_k", [T, T + 5, 4096])
def test_with_every_key_kept_the_operator_is_grouped_query_attention(seeded, top_k):
    spec, params, _, _, _ = seeded
    every = toy(sa_config={"topk": top_k})
    u = jnp.asarray(np.random.RandomState(8).normal(size=(2, T, 32)).astype(np.float32))
    got, _, (kept, causal) = backbone.sparse_attention(
        every, params["layer_1"]["attn"], params["layer_1"]["indexer"], u
    )
    close(got, backbone.gqa_attention(every, params["layer_1"]["attn"], u), "dense", 1e-6)
    assert float(kept) == float(causal)


def test_the_whole_forward_and_its_counters_against_the_reference(seeded, reference):
    spec, params, layers, x, _ = seeded
    out, penalty, aux = jax.jit(lambda p, x: backbone.forward_backbone_aux(spec, p, x))(params, x)
    close(out, reference.forward(layers, x, block_windows=2), "forward")
    found = reference.counters(layers, x)
    assert np.array_equal(aux["router_tokens"], found["routed"]) and found["routed"].shape == (2, 8)
    assert np.array_equal(aux["pairs_here"], found["routed"][:, 2:4].sum(axis=1))
    assert np.array_equal(aux["keys_selected"], found["kept"])
    assert np.array_equal(aux["keys_selected"], [4 * kept_by_arithmetic()] * 2)
    assert np.array_equal(aux["keys_causal"], [4 * T * (T + 1) / 2] * 2)
    assert float(penalty) == pytest.approx(float(found["kl"].mean()), rel=1e-5) and float(penalty) > 0
    assert float(np.sum(aux["indexer_kl"])) == pytest.approx(float(penalty), rel=1e-6)
    plain, plain_penalty = backbone.forward_backbone(spec, params, x)
    close(plain, out, "without the counters")
    assert float(plain_penalty) == pytest.approx(float(penalty), rel=1e-5)


def loss_of(spec, x, y, w, remat=False, active=None):
    def loss(p):
        out, penalty, _ = backbone.forward_backbone_aux(spec, p, x, remat=remat, active=active)
        return weighted_mean_loss(resolve_loss("mse")(out, y), w) + penalty
    return loss


@functools.partial(jax.jit, static_argnames=("spec", "remat"))
def loss_and_grads(spec, params, x, y, w, remat=False, active=None):
    """One program a shape for the whole file, the fit's own way. Run
    op by op, every loop of the forward pass and of its transpose is
    compiled alone, each time: 22 s a call where this takes 5 once."""
    return jax.value_and_grad(loss_of(spec, x, y, w, remat, active))(params)


def test_loss_and_every_gradient_leaf_against_the_reference(seeded, reference):
    spec, params, layers, x, y = seeded
    w = np.array([1, 1, 0.5, 0], np.float32)
    loss, grads = loss_and_grads(spec, params, x, y, w, active=jnp.asarray(w > 0))
    want_loss, want = reference.loss_and_grads(layers, x, y, w)
    assert abs(float(loss) - want_loss) <= TOLERANCE * max(1.0, abs(want_loss))
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(want)) > 30
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    assert sum("indexer" in name for name in names) == 2 * 5  # wq, wk, the norm's two, w
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(want)):
        close(got, ref, jax.tree_util.keystr(path))
        if "indexer" in jax.tree_util.keystr(path):
            assert np.any(np.asarray(ref)), jax.tree_util.keystr(path)
    # rematerialised (layers, and the blocks inside them) and plain agree
    loss_r, grads_r = loss_and_grads(spec, params, x, y, w, True, jnp.asarray(w > 0))
    assert float(loss_r) == pytest.approx(float(loss), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads_r), jax.tree_util.tree_leaves(grads)):
        close(a, b, "remat")
    # the step check reads every leaf of an indexer, however small beside the rest
    norms = jax.tree_util.tree_map(lambda g: float(np.sqrt(np.sum(np.square(g)))), grads)
    want_norms = jax.tree_util.tree_map(lambda g: float(np.sqrt(np.sum(np.square(g)))), want)
    readings = reference.step_readings(float(loss), norms, want_loss, want_norms)
    assert max(readings["leaf"], readings["indexer_leaf"], readings["indexer_leaf_median"]) < 1e-4
    assert "indexer" in readings["worst_indexer_leaf"] and "indexer" not in str(readings["worst_leaf"])
    # ... apart from the leaves the forecast's gradient reaches: a limit for each
    norms["layer_1"]["indexer"]["w"] *= 1.5
    off = reference.step_readings(float(loss), norms, want_loss, want_norms)
    assert off["indexer_leaf"] == pytest.approx(0.5) and off["leaf"] == readings["leaf"]
    assert off["worst_indexer_leaf"] == "['layer_1']['indexer']['w']" and off["indexer_leaf_median"] < 1e-4
    assert set(reference.STEP_LIMITS) == {"output", "leaf", "indexer_leaf_median"}


def test_two_disjoint_gradients_in_one_step(seeded):
    """The forecast loss gives the indexer's leaves nothing (its input is
    detached and the choice is discrete); the indexer's objective gives
    every other leaf nothing."""
    spec, params, _, x, y = seeded

    def parts(p):
        out, penalty, _ = backbone.forward_backbone_aux(spec, p, x, remat=True)
        return jnp.mean(resolve_loss("mse")(out, y)), penalty

    forecast = jax.jit(jax.grad(lambda p: parts(p)[0]))(params)
    objective = jax.jit(jax.grad(lambda p: parts(p)[1]))(params)
    carried = 0
    for (path, f), o in zip(
        jax.tree_util.tree_flatten_with_path(forecast)[0], jax.tree_util.tree_leaves(objective)
    ):
        name = jax.tree_util.keystr(path)
        if "indexer" in name:
            assert not np.any(np.asarray(f)) and np.any(np.asarray(o)), name
        else:
            assert not np.any(np.asarray(o)), name
            carried += bool(np.any(np.asarray(f)))
    # (a toy's last token may route to no expert held here: then its
    # layer's expert leaves carry nothing either)
    assert carried >= 20 and np.any(np.asarray(forecast["layer_0"]["attn"]["wq"]))


def test_equal_scores_are_taken_in_order_of_position_as_the_reference_takes_them(seeded, reference):
    """A relu makes exact ties (here: an indexer whose every head weighs
    nothing, so every score is 0): the earlier key is kept, in program
    and reference alike, and exactly ``index_topk`` of them."""
    spec, params, layers, _, _ = seeded
    u = jnp.asarray(np.random.RandomState(12).normal(size=(2, T, 32)).astype(np.float32))
    indexer = {**params["layer_0"]["indexer"], "w": jnp.zeros_like(params["layer_0"]["indexer"]["w"])}
    blocked = [backbone._blocked(a[0], spec.index_chunk) for a in backbone.indexer_inputs(spec, indexer, u)]
    # [blocks, chunk, blocks, words] -> [T, T]: a row a query, a tile of keys after another
    selected = backbone._unpack_bits(backbone.select_keys(spec, *blocked), spec.index_chunk)
    selected = np.asarray(selected).reshape(T, T)
    for t in range(T):
        assert np.array_equal(np.flatnonzero(selected[t]), np.arange(min(t + 1, TOPK))), t
    got, _, (kept, _) = backbone.sparse_attention(spec, params["layer_0"]["attn"], indexer, u)
    weights = {**layers["weights"]["layer_0"]["indexer"], "w": np.zeros((32, 8), np.float32)}
    want, _, want_kept = reference.sparse_attention(
        u, layers["weights"]["layer_0"]["attn"], weights, layers["sizes"]
    )
    close(got, want, "ties")
    assert float(kept) == float(np.sum(want_kept)) == 2 * kept_by_arithmetic()
    # bits in, bits out
    mask = np.random.RandomState(1).uniform(size=(5, 3, 70)) < 0.5
    assert np.array_equal(backbone._unpack_bits(backbone._pack_bits(jnp.asarray(mask)), 70), mask)


def _rows_of(what, rng, rows, keys):
    """Crafted index scores ``[rows, keys]`` (before the causal mask)."""
    tiny, lowest = np.float32(1e-45), np.finfo(np.float32).min
    signs = np.where(rng.uniform(size=(rows, keys)) < 0.5, np.float32(0.0), np.float32(-0.0))
    if what == "distinct":
        return np.stack([rng.permutation(keys) for _ in range(rows)]).astype(np.float32) - keys / 3
    if what == "every score zero, of both signs":
        return signs
    if what == "duplicates that straddle the k-th place":
        few = rng.randint(-2, 3, size=(rows, keys)).astype(np.float32)
        return np.where(few == 0, signs, few)
    if what == "one value":
        return np.full((rows, keys), -1.5, np.float32)
    if what == "infinities, denormals and the largest negative float":
        special = np.array([np.inf, -np.inf, tiny, -tiny, lowest, -lowest, 0.0, -0.0, 1.0], np.float32)
        return rng.choice(special, size=(rows, keys))
    assert what == "not a number among them"
    a = rng.normal(size=(rows, keys)).astype(np.float32)
    at = rng.uniform(size=(rows, keys))
    return np.where(at < 0.15, np.float32(np.nan), np.where(at < 0.3, -np.float32(np.nan), a))


@pytest.mark.parametrize("top_k", [10, 8, 1, 31])
@pytest.mark.parametrize("what", [
    "distinct", "every score zero, of both signs", "duplicates that straddle the k-th place", "one value",
    "infinities, denormals and the largest negative float", "not a number among them",
])
def test_the_selection_is_what_the_kth_entry_of_a_top_k_decides(monkeypatch, what, top_k):
    """The search (``backbone.kth_largest``) against the sort it took
    the place of, on crafted rows: 32 keys in blocks of 8, so that with a
    top-k of 10 or 8 there are queries with fewer causal keys than that,
    one with exactly as many, and a block that searches nothing. The
    selection is, to the bit, what the formula of ``select_keys`` makes
    of the k-th entry of ``jax.lax.top_k``, value and position: a top-k
    lists ``-0.0`` after ``0.0`` whatever their positions and the
    formula's ``==`` does not tell them apart, so a row of zeros keeps
    fewer or more than ``top_k`` keys, as it did."""
    keys, chunk = 32, 8
    spec = toy(lookback_window=keys, sa_config={"topk": top_k})
    scores = _rows_of(what, np.random.RandomState(len(what) + top_k), keys, keys)
    # the crafted scores stand in for the indexer's: a query's row rides in qI, a key's position in kI
    monkeypatch.setattr(
        backbone, "index_scores",
        lambda qi, ki, wi: jnp.take_along_axis(qi[:, 0, :], ki[:, 0].astype(jnp.int32)[None, :], axis=1),
    )
    blocked = [
        backbone._blocked(jnp.asarray(a), chunk)
        for a in (scores[:, None, :], np.arange(keys, dtype=np.float32)[:, None], np.zeros((keys, 1), np.float32))
    ]
    selected = backbone._unpack_bits(backbone.select_keys(spec, *blocked), chunk)
    selected = np.asarray(selected).reshape(keys, keys)
    positions = np.arange(keys)
    causal = positions[None, :] <= positions[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    values, at = jax.lax.top_k(masked, top_k)
    kth, kth_at = values[:, -1:], at[:, -1:]
    want = np.asarray(((masked > kth) | ((masked == kth) & (positions[None, :] <= kth_at))) & causal)
    # a block that ends at or before row top-k keeps its causal keys unasked (where nothing is a NaN, the same)
    unasked = ((positions // chunk + 1) * chunk <= top_k)[:, None]
    assert what == "not a number among them" or np.array_equal(want[unasked[:, 0]], causal[unasked[:, 0]])
    assert np.array_equal(selected, np.where(unasked, causal, want))
    # and the two numbers themselves, where a block searches: to the bit, a NaN's too
    value, position = backbone.kth_largest(masked, top_k, chunk)
    assert np.array_equal(np.asarray(value).view(np.int32), np.asarray(kth).view(np.int32))
    assert np.array_equal(np.asarray(position), np.asarray(kth_at))
    if what == "distinct":
        assert np.array_equal(selected.sum(axis=1), np.minimum(positions + 1, top_k))
    assert backbone.selection_blocks_searched(spec) == sum(top_k < (i + 1) * chunk for i in range(keys // chunk))


def test_the_selection_is_made_once_a_layer_and_kept_for_the_backward_pass(seeded):
    """Rematerialised, a layer selects once: the selection is kept by
    name and the backward pass only masks."""
    spec, params, _, x, y = seeded
    w = np.ones(4, np.float32)

    def chosen(traced):
        """(the selection's searches, the ``top_k`` s) of a traced program,
        as its text shows them: a search takes its scores' bits and hands
        the k-th largest back as a float, two ``bitcast_convert_type`` that
        nothing else of the program has, and the only ``top_k`` left is
        the router's."""
        text = str(traced)
        return text.count("bitcast_convert_type") / 2, text.count("top_k")

    def in_the_gradient(remat):
        return chosen(jax.make_jaxpr(jax.grad(loss_of(spec, x, y, w, remat)))(params))

    # a layer's selection is one loop with one search in it, and the router's choice
    forward = chosen(jax.make_jaxpr(loss_of(spec, x, y, w))(params))
    assert forward == in_the_gradient(False) == (2, 2)
    # rematerialised, only the router's choice, which nothing keeps, is made again
    assert in_the_gradient(True) == (2, 2 + 2)
    # and no row is sorted for a selection: the lowered program's sorts are the router's
    lowered = jax.jit(jax.grad(loss_of(spec, x, y, w, True))).lower(params).as_text()
    router_only = jax.jit(jax.grad(loss_of(toy(sa_config={"topk": T}), x, y, w, True))).lower(params).as_text()
    for operation in ("top_k", "stablehlo.sort"):  # (the router sorts its pairs by expert)
        assert lowered.count(operation) == router_only.count(operation) > 0


@pytest.mark.parametrize("padding", [(1,), (0, 3), (1, 2, 3)])
def test_a_window_of_padding_adds_nothing_to_penalty_or_counters(seeded, padding):
    spec, params, _, x, y = seeded
    weights = np.ones(4, np.float32)
    weights[list(padding)] = 0.0
    kept = [i for i in range(4) if i not in padding]
    forward = jax.jit(lambda p, x, a: backbone.forward_backbone_aux(spec, p, x, active=a))
    _, penalty, aux = forward(params, x, jnp.asarray(weights > 0))
    _, penalty_kept, aux_kept = forward(params, x[np.array(kept)], None)
    assert float(penalty) == pytest.approx(float(penalty_kept), rel=1e-5)
    for key in ("keys_selected", "keys_causal", "router_tokens", "pairs_here", "pairs_total"):
        assert np.array_equal(aux[key], aux_kept[key]), key
    assert np.array_equal(aux["keys_selected"], [len(kept) * kept_by_arithmetic()] * 2)
    np.testing.assert_allclose(aux["indexer_kl"], aux_kept["indexer_kl"], rtol=1e-5)
    # the step's loss and gradients are those of the windows that count
    loss, grads = loss_and_grads(spec, params, x, y, weights, True, jnp.asarray(weights > 0))
    loss_k, grads_k = loss_and_grads(
        spec, params, x[np.array(kept)], y[np.array(kept)], weights[np.array(kept)], True
    )
    assert float(loss) == pytest.approx(float(loss_k), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads_k)):
        close(a, b, "gradient")


def test_the_fit_step_adds_the_objective_and_sums_its_counters(seeded):
    """``windowed_batch_loss_fn`` adds the penalty to the forecast loss
    and hands the weights on as ``active``; the fit's span carries the
    sums, floats as floats."""
    from gordo_tpu.models.training import windowed_batch_loss_fn
    from gordo_tpu.parallel.fleet import _fit_counter_attrs

    spec, params, _, _, _ = seeded
    series = jnp.asarray(np.random.RandomState(9).uniform(0, 1, (T + 8, 5)).astype(np.float32))
    ytgt = series[T:]
    starts = jnp.asarray([0, 1, 2, 0], jnp.int32)
    weights = jnp.asarray([1, 1, 1, 0], jnp.float32)
    loss, aux = windowed_batch_loss_fn(spec)(params, series, ytgt, starts, weights)
    assert np.array_equal(aux["keys_selected"], [3 * kept_by_arithmetic()] * 2)
    windows = series[np.arange(3)[:, None] + np.arange(T)[None, :]]
    out, penalty, _ = backbone.forward_backbone_aux(spec, params, windows)
    forecast = float(jnp.mean(resolve_loss("mse")(out, ytgt[:3])))
    assert float(penalty) > 0 and float(loss) == pytest.approx(forecast + float(penalty), rel=1e-5)
    # [members, epochs, ...] as the fit returns them
    counters = {k: np.stack([np.asarray(v)] * 2)[None] for k, v in aux.items()}
    attrs = _fit_counter_attrs(spec, counters, 1)
    assert attrs["keys_selected"] == [2.0 * 3 * kept_by_arithmetic()] * 2
    assert attrs["indexer_kl"] == pytest.approx((2 * np.asarray(aux["indexer_kl"], np.float64)).tolist())
    assert sum(attrs["indexer_kl"]) == pytest.approx(2 * float(penalty), rel=1e-6)
    assert isinstance(attrs["pairs_here"][0], int) and attrs["index_topk"] == TOPK
    assert attrs["num_experts"] == 8 and set(attrs["fit_counters"]) == set(attrs) - {"fit_counters"}


def test_the_kinds_that_were_here_keep_their_seeded_weights():
    """The indexer's keys come from a split of another length, so only a
    spec that has one takes it: an ``lfm2_moe`` member's weights at a
    seed are what they were (a hash of the toy's, taken at the parent)."""
    import hashlib

    from gordo_tpu.models.factories import lfm2_moe

    spec = lfm2_moe(
        5, lookback_window=12, layer_types=("conv", "full_attention", "conv", "conv"),
        num_dense_layers=1, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=48, moe_intermediate_size=24, num_experts=8, experts_held=2,
        expert_offset=2, num_experts_per_tok=2,
    )
    params = backbone.init_backbone(jax.random.PRNGKey(7), spec)
    digest = hashlib.sha256(
        b"".join(np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(params))
    ).hexdigest()
    assert digest == LFM2_TOY_DIGEST


LFM2_TOY_DIGEST = "4c7953747fb3e7cf91e074a93547a4c84652cb0f90f85122af93019919582fa7"
