"""The backbone's layers, forward, loss and gradients at toy widths on the
CPU against the plain reference (loaded by path, as tests/chipbench
does: it imports nothing from gordo_tpu), and what the share layer
promises: the shares add up, and no pair is dropped."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import BackboneSpec, JaxBackboneForecast, register_model_builder
from gordo_tpu.models import backbone
from gordo_tpu.models.factories import keye_vl2, lfm2_moe
from gordo_tpu.models.factories.backbone import LFM2_8B_A1B_LAYER_TYPES
from gordo_tpu.models.nn import forward_fn_for, init_fn_for
from gordo_tpu.models.spec import FeedForwardSpec, LSTMSpec, ModelSpec
from gordo_tpu.planner.costmodel import spec_flops_per_sample, spec_param_count

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOLERANCE = 1e-4  # of scale: both sides compute in float32 on the CPU


def load_reference(name):
    path = os.path.join(ROOT, "benchmarks", "chip", "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return load_reference("lfm2_moe_backbone")


@pytest.fixture(scope="module")
def references(reference):
    """The plain reference of each backbone kind."""
    return {"lfm2_moe": reference, "keye_vl2": load_reference("keye_sparse_backbone")}


def toy(**overrides) -> BackboneSpec:
    sizes = dict(
        lookback_window=12, layer_types=("conv", "full_attention", "conv", "conv"),
        num_dense_layers=1, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=48, moe_intermediate_size=24, num_experts=8, experts_held=2,
        expert_offset=2, num_experts_per_tok=2,
    )
    sizes.update(overrides)
    return lfm2_moe(5, **sizes)


def sparse_toy(**overrides) -> BackboneSpec:
    """``kind: keye_vl2`` at toy widths: heads of 16 at hidden 32, an
    indexer that keeps 6 of 12 rows, a softmax router
    (tests/models/test_sparse_backbone.py has the operator's own tests)."""
    sizes = dict(
        lookback_window=12, num_hidden_layers=2, hidden_size=32, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2, moe_intermediate_size=24, num_experts=8,
        experts_held=2, expert_offset=2, num_experts_per_tok=2,
        sa_config=dict(indexer_head_dim=8, indexer_num_heads=8, topk=6, q_chunk_size=8, kv_chunk_size=8),
    )
    sizes.update(overrides)
    return keye_vl2(5, **sizes)


TOYS = {"lfm2_moe": toy, "keye_vl2": sparse_toy}


class Artifact:
    def __init__(self, spec, params):
        self.spec_, self.params_ = spec, params


@pytest.fixture(scope="module")
def seeded(reference):
    spec = toy()
    params = backbone.init_backbone(jax.random.PRNGKey(7), spec)
    layers = reference.layers_of(Artifact(spec, params))
    rng = np.random.RandomState(3)
    x = rng.uniform(0, 1, (6, 12, 5)).astype(np.float32)
    y = rng.uniform(0, 1, (6, 5)).astype(np.float32)
    return spec, params, layers, x, y


def close(got, want, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    scale = max(1.0, float(np.max(np.abs(want)))) if want.size else 1.0
    assert float(np.max(np.abs(got - want))) <= TOLERANCE * scale, what


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmarks", "chip", "reference", "lfm2_moe_backbone.py")
    source = open(path).read()
    assert "import gordo_tpu" not in source and "from gordo_tpu" not in source


def test_the_factory_defaults_are_the_published_config():
    spec = lfm2_moe(50)
    assert spec.layer_ops == LFM2_8B_A1B_LAYER_TYPES and len(spec.layer_ops) == 24
    assert spec.layer_ops.count("full_attention") == 6
    assert spec.layer_ffns == ("dense", "dense") + ("moe",) * 22
    assert (spec.hidden_size, spec.intermediate_size, spec.moe_intermediate_size) == (2048, 7168, 1792)
    assert (spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim) == (32, 8, 64)
    assert (spec.num_experts, spec.experts_held, spec.num_experts_per_tok) == (32, 32, 4)
    assert (spec.conv_L_cache, spec.rope_theta, spec.norm_eps) == (3, 1e6, 1e-5)
    assert spec.lookback_window == 512 and spec.windowed and not spec.member_axis
    assert "lfm2_moe" in register_model_builder.factories["JaxBackboneForecast"]
    assert JaxBackboneForecast("lfm2_moe").lookahead == 1


def test_the_cut_counts_474_472_626_weights():
    spec = lfm2_moe(
        50, layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        num_dense_layers=1, experts_held=8,
    )
    assert spec.layer_param_count("conv", "dense") == 60_827_648
    assert spec.layer_param_count("full_attention", "moe") == 98_635_904
    assert spec.layer_param_count("conv", "moe") == 104_933_376
    assert spec.param_count() == spec_param_count(spec) == 474_472_626
    # a step of 32 windows, forward and backward: 16.4 TFLOP
    assert 16.0e12 < 3 * 32 * spec_flops_per_sample(spec) < 17.0e12
    assert hash(spec) == hash(lfm2_moe(
        50, layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        num_dense_layers=1, experts_held=8,
    ))


@pytest.mark.parametrize("bad", [
    dict(layer_types=("conv", "window")), dict(experts_held=0),
    dict(experts_held=8, expert_offset=1), dict(num_attention_heads=5),
    dict(num_key_value_heads=3), dict(layer_types=()),
])
def test_a_spec_that_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        toy(**bad)


@pytest.mark.parametrize("spec", [
    FeedForwardSpec(4, 4, (3,), ("tanh",)),
    LSTMSpec(4, 4, 5, (3,), ("tanh",)),
    toy(),
    sparse_toy(),
], ids=["FeedForwardSpec", "LSTMSpec", "BackboneSpec", "BackboneSpec-keye_vl2"])
def test_every_spec_answers_for_itself(spec):
    params = init_fn_for(spec)(jax.random.PRNGKey(0), spec)
    leaves = sum(
        leaf.size for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
        if "expert_bias" not in jax.tree_util.keystr(path)
    )
    assert leaves == spec.param_count() == spec_param_count(spec) > 0
    assert spec_flops_per_sample(spec) == spec.flops_per_sample() > 0
    x = jnp.ones((2, spec.lookback_window, 4) if spec.windowed else (2, 4))
    if isinstance(spec, BackboneSpec):
        x = jnp.ones((2, spec.lookback_window, 5))
    out, penalty = forward_fn_for(spec)(spec, params, x)
    assert out.shape == (2, spec.n_features_out) and penalty.shape == ()
    assert (spec.forward_aux_fn() is not None) == isinstance(spec, BackboneSpec)


def test_an_unknown_spec_has_no_functions_and_no_count():
    class Other(ModelSpec):
        pass

    with pytest.raises(TypeError, match="No init function"):
        init_fn_for(Other())
    with pytest.raises(TypeError, match="No forward function"):
        forward_fn_for(object())
    assert spec_param_count(Other()) == 0 and spec_flops_per_sample(Other()) == 0.0


@pytest.mark.parametrize("kind", ["conv", "attention", "dense_ffn", "moe_ffn", "rms_norm"])
def test_each_layer_kind_against_the_reference(seeded, reference, kind):
    spec, params, layers, x, _ = seeded
    sizes, weights = layers["sizes"], layers["weights"]
    u = np.random.RandomState(5).normal(size=(3, 12, 32)).astype(np.float32)
    if kind == "conv":
        got = backbone.short_conv(spec, params["layer_0"]["conv"], jnp.asarray(u))
        want = reference.short_conv(jnp.asarray(u), weights["layer_0"]["conv"], sizes)
    elif kind == "attention":
        got = backbone.gqa_attention(spec, params["layer_1"]["attn"], jnp.asarray(u))
        want = reference.attention(jnp.asarray(u), weights["layer_1"]["attn"], sizes)
    elif kind == "dense_ffn":
        got = backbone.dense_ffn(params["layer_0"]["ffn"], jnp.asarray(u))
        want = reference.dense_ffn(jnp.asarray(u), weights["layer_0"]["ffn"])
    elif kind == "moe_ffn":
        got, routed, pairs, _ = backbone.moe_ffn(spec, params["layer_2"]["moe"], jnp.asarray(u))
        want, counts = reference.moe_ffn(jnp.asarray(u), weights["layer_2"]["moe"], sizes)
        assert np.array_equal(routed, counts) and int(routed.sum()) == 3 * 12 * 2
        assert int(pairs) == int(counts[2:4].sum())
    else:
        gain = np.random.RandomState(6).normal(size=32).astype(np.float32)
        got = backbone.rms_norm(jnp.asarray(u), jnp.asarray(gain), 1e-5)
        want = reference.rms_norm(jnp.asarray(u), gain, 1e-5)
    close(got, want, kind)


def test_the_whole_forward_and_its_counters_against_the_reference(seeded, reference):
    spec, params, layers, x, _ = seeded
    out, penalty, aux = jax.jit(lambda p, x: backbone.forward_backbone_aux(spec, p, x))(params, x)
    close(out, reference.forward(layers, x, block_windows=4), "forward")
    assert float(penalty) == 0.0 and out.dtype == jnp.float32
    counts = reference.router_counts(layers, x)
    assert np.array_equal(aux["router_tokens"], counts) and counts.shape == (3, 8)
    assert np.array_equal(aux["pairs_here"], counts[:, 2:4].sum(axis=1))
    assert np.array_equal(aux["pairs_total"], [6 * 12 * 2] * 3)
    plain, _ = backbone.forward_backbone(spec, params, x)
    close(plain, out, "without the counters")  # eager against jitted: rounding only
    # lookahead 1: window j covers rows j..j+11 and predicts row j+12
    series = np.arange(20 * 5, dtype=np.float32).reshape(20, 5)
    windows = reference.model_input(Artifact(spec, params), series)
    assert windows.shape == (8, 12, 5) and np.array_equal(windows[3], series[3:15])


def test_loss_and_every_gradient_leaf_against_the_reference(seeded, reference):
    spec, params, layers, x, y = seeded
    w = np.array([1, 1, 1, 1, 0.5, 0], np.float32)
    from gordo_tpu.ops.losses import resolve_loss, weighted_mean_loss

    def loss_of(p, remat):
        out, penalty, _ = backbone.forward_backbone_aux(spec, p, x, remat=remat)
        return weighted_mean_loss(resolve_loss("mse")(out, y), w) + penalty

    loss, grads = jax.jit(jax.value_and_grad(lambda p: loss_of(p, False)))(params)
    want_loss, want = reference.loss_and_grads(layers, x, y, w)
    assert abs(float(loss) - want_loss) <= TOLERANCE * max(1.0, abs(want_loss))
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(want)) > 30
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(want)):
        close(got, ref, jax.tree_util.keystr(path))
    # the expert bias is a buffer: it chooses and takes no gradient
    for name, layer in grads.items():
        if "moe" in layer:
            assert not np.any(np.asarray(layer["moe"]["expert_bias"]))
    assert np.any(np.asarray(grads["layer_1"]["moe"]["router"]))
    # rematerialised and plain gradients agree
    loss_r, grads_r = jax.jit(jax.value_and_grad(lambda p: loss_of(p, True)))(params)
    assert float(loss_r) == float(loss)
    for a, b in zip(jax.tree_util.tree_leaves(grads_r), jax.tree_util.tree_leaves(grads)):
        close(a, b, "remat")


@pytest.mark.parametrize("kind", ["lfm2_moe", "keye_vl2"])
def test_layers_are_rematerialised_from_the_bytes_of_the_state(monkeypatch, seeded, kind):
    x = seeded[3]
    spec = TOYS[kind]()
    params = backbone.init_backbone(jax.random.PRNGKey(7), spec)

    def checkpoints(p):
        jaxpr = jax.make_jaxpr(
            jax.grad(lambda q: backbone.forward_backbone_aux(spec, q, x)[0].sum())
        )(p)
        text = str(jaxpr)
        return text.count("checkpoint") + text.count("remat")

    # a few kilobytes of state: no layer is rematerialised (sparse
    # attention has its own derivative rule, which keeps no score)
    small = checkpoints(params)
    assert small == 0
    monkeypatch.setattr(backbone, "REMAT_MIN_PARAM_BYTES", 1024)
    assert checkpoints(params) >= small + len(spec.layer_ops)


@pytest.mark.parametrize("kind, held", [("lfm2_moe", 2), ("keye_vl2", 1)])
def test_the_shares_add_up_to_the_uncut_layer(references, kind, held):
    """8 experts of which ``held`` a share (four shares under the sigmoid
    router with its bias, eight under the softmax router): the shares,
    each with its own slice of the uncut layer's expert weights, add up
    to what the uncut layer gives (program and reference alike)."""
    reference, make = references[kind], TOYS[kind]
    whole = make(experts_held=8, expert_offset=0)
    layer = len(whole.layer_ops) - 1
    params = backbone.init_backbone(jax.random.PRNGKey(11), whole)[f"layer_{layer}"]["moe"]
    assert ("expert_bias" in params) == (kind == "lfm2_moe")
    u = jnp.asarray(np.random.RandomState(2).normal(size=(4, 12, 32)).astype(np.float32))
    uncut, routed, pairs, _ = backbone.moe_ffn(whole, params, u)
    assert int(pairs) == int(routed.sum()) == 4 * 12 * 2
    _, weights = backbone.route(whole, params, u.reshape(-1, 32))
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 1.0, rtol=1e-5)  # normalised
    total = np.zeros_like(np.asarray(uncut))
    for offset in range(0, 8, held):
        share = make(experts_held=held, expert_offset=offset)
        slices = {k: params[k][offset : offset + held] for k in ("w1", "w3", "w2")}
        out, routed_s, pairs_s, _ = backbone.moe_ffn(share, {**params, **slices}, u)
        assert np.array_equal(routed_s, routed)  # the router is the whole model's
        assert int(pairs_s) == int(routed[offset : offset + held].sum())
        sizes = {key: getattr(share, key) for key in reference.SIZES}
        want, _ = reference.moe_ffn(u, jax.tree_util.tree_map(np.asarray, {**params, **slices}), sizes)
        close(out, want, f"share at {offset}")
        total += np.asarray(out)
    close(total, uncut, "sum of the shares")
    sizes = {key: getattr(whole, key) for key in reference.SIZES}
    close(uncut, reference.moe_ffn(u, jax.tree_util.tree_map(np.asarray, params), sizes)[0], "uncut")


@pytest.mark.parametrize("favoured", [2, 3, 5])
def test_no_pair_is_dropped_when_one_expert_takes_every_token(reference, favoured):
    """A bias that sends every token to one expert: held, it computes
    every token (the pair buffer holds the worst case); absent, this
    share computes what is left and stays finite."""
    spec = toy()
    params = backbone.init_backbone(jax.random.PRNGKey(13), spec)["layer_2"]["moe"]
    params = {**params, "expert_bias": jnp.zeros(8).at[favoured].set(100.0)}
    u = jnp.asarray(np.random.RandomState(4).normal(size=(5, 12, 32)).astype(np.float32))
    out, routed, pairs, _ = jax.jit(lambda p, u: backbone.moe_ffn(spec, p, u))(params, u)
    assert int(routed[favoured]) == 5 * 12  # every token
    assert int(pairs) == int(routed[2:4].sum())
    sizes = {key: getattr(spec, key) for key in reference.SIZES}
    want, counts = reference.moe_ffn(u, jax.tree_util.tree_map(np.asarray, params), sizes)
    assert np.array_equal(routed, counts)
    close(out, want, "uneven routing")
    assert np.isfinite(np.asarray(out)).all()
    if favoured in (2, 3):
        assert np.all(np.any(np.asarray(out) != 0, axis=-1))  # no token left out


@pytest.mark.parametrize("kind", ["lfm2_moe", "keye_vl2"])
def test_bfloat16_compute_keeps_float32_parameters_and_output(seeded, kind):
    x = seeded[3]
    spec, half = TOYS[kind](), TOYS[kind](compute_dtype="bfloat16")
    params = backbone.init_backbone(jax.random.PRNGKey(7), spec)
    out, penalty = backbone.forward_backbone(half, params, x)
    full, _ = backbone.forward_backbone(spec, params, x)
    assert out.dtype == jnp.float32 and penalty.dtype == jnp.float32
    assert float(np.max(np.abs(np.asarray(out) - np.asarray(full)))) < 0.2


@pytest.mark.parametrize("padding", [(1,), (0, 3), (1, 2, 3)])
def test_a_window_of_padding_routes_nothing_and_changes_no_gradient(seeded, padding):
    """A fit step's padding slots (weight 0) are no tokens of the step:
    the expert layers route and count the other windows alone, their
    outputs are what they were, and the step's loss and gradients are
    the same with the padding routed or not."""
    spec, params, _, x, y = seeded
    x, y = x[:4], y[:4]
    weights = np.ones(4, np.float32)
    weights[list(padding)] = 0.0
    active = jnp.asarray(weights > 0)
    forward = jax.jit(lambda p, x, a: backbone.forward_backbone_aux(spec, p, x, active=a))
    out_all, _, aux_all = forward(params, x, None)
    out, _, aux = forward(params, x, active)
    kept = [i for i in range(4) if i not in padding]
    if kept:
        close(out[np.array(kept)], out_all[np.array(kept)], "the windows that count")
    a_window = 12 * 2
    assert np.array_equal(aux["pairs_total"], [len(kept) * a_window] * 3)
    assert np.array_equal(aux["router_tokens"].sum(axis=1), aux["pairs_total"])
    assert np.array_equal(aux["pairs_here"], aux["router_tokens"][:, 2:4].sum(axis=1))
    assert np.all(np.asarray(aux["router_tokens"]) <= np.asarray(aux_all["router_tokens"]))
    # one layer alone: the padding's rows get no expert's output
    u = jnp.asarray(np.random.RandomState(8).normal(size=(4, 12, 32)).astype(np.float32))
    layer_out, routed, pairs, _ = backbone.moe_ffn(spec, params["layer_2"]["moe"], u, active)
    whole, routed_all, _, _ = backbone.moe_ffn(spec, params["layer_2"]["moe"], u)
    assert not np.any(np.asarray(layer_out)[list(padding)])
    assert int(routed.sum()) == len(kept) * a_window and int(pairs) == int(routed[2:4].sum())
    if kept:
        close(layer_out[np.array(kept)], whole[np.array(kept)], "the layer's other windows")

    from gordo_tpu.ops.losses import resolve_loss, weighted_mean_loss

    def loss_of(p, mask):
        got, penalty, _ = backbone.forward_backbone_aux(spec, p, x, remat=True, active=mask)
        return weighted_mean_loss(resolve_loss("mse")(got, y), jnp.asarray(weights)) + penalty

    loss, grads = jax.jit(jax.value_and_grad(lambda p: loss_of(p, active)))(params)
    loss_all, grads_all = jax.jit(jax.value_and_grad(lambda p: loss_of(p, None)))(params)
    assert abs(float(loss) - float(loss_all)) <= TOLERANCE * max(1.0, abs(float(loss_all)))
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads_all)):
        close(a, b, "gradient")


def test_the_fit_step_tells_the_forward_which_slots_are_padding(seeded):
    """``windowed_batch_loss_fn`` hands the step's weights on as
    ``active``: the counters of a batch with two slots of padding are
    those of the four windows that count."""
    from gordo_tpu.models.training import windowed_batch_loss_fn

    spec, params, _, _, _ = seeded
    series = jnp.asarray(np.random.RandomState(9).uniform(0, 1, (20, 5)).astype(np.float32))
    ytgt = series[12:]
    starts = jnp.asarray([0, 1, 2, 3, 0, 0], jnp.int32)
    weights = jnp.asarray([1, 1, 1, 1, 0, 0], jnp.float32)
    loss, aux = windowed_batch_loss_fn(spec)(params, series, ytgt, starts, weights)
    assert np.array_equal(aux["pairs_total"], [4 * 12 * 2] * 3)
    loss_4, aux_4 = windowed_batch_loss_fn(spec)(params, series, ytgt, starts[:4], weights[:4])
    assert float(loss) == pytest.approx(float(loss_4), rel=1e-6)
    assert np.array_equal(aux["router_tokens"], aux_4["router_tokens"])
    assert np.array_equal(aux["pairs_here"], aux_4["pairs_here"])


@pytest.mark.parametrize("backend, compute, operand", [
    ("cpu", "float32", "float32"), ("tpu", "float32", "bfloat16"),
    ("gpu", "float32", "float32"), ("tpu", "bfloat16", "bfloat16"),
])
def test_grouped_operands_are_rounded_first_only_where_the_matrix_unit_rounds_them(
    monkeypatch, seeded, backend, compute, operand
):
    spec, params, _, _, _ = seeded
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert backbone._mxu_operand_dtype(jnp.dtype(compute)) == jnp.dtype(operand)
    u = jnp.asarray(np.random.RandomState(10).normal(size=(3, 12, 32)), jnp.dtype(compute))
    w = params["layer_2"]["moe"]
    out, vjp = jax.vjp(lambda w, u: backbone.moe_ffn(spec, w, u)[0], w, u)
    assert out.dtype == jnp.dtype(compute)  # the accumulator and the output keep the compute dtype
    dw, du = vjp(jnp.ones_like(out))
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree_util.tree_leaves(dw))
    assert du.dtype == jnp.dtype(compute) and np.isfinite(np.asarray(du, np.float32)).all()
    if compute == "float32":
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        exact = backbone.moe_ffn(spec, w, u)[0]
        # rounded operands, float32 accumulation: bfloat16's 2^-9 a factor
        assert float(np.max(np.abs(np.asarray(out) - np.asarray(exact)))) <= 2e-2 * max(
            1.0, float(np.max(np.abs(np.asarray(exact))))
        )
        if operand == "float32":
            assert np.array_equal(np.asarray(out), np.asarray(exact))


def test_the_expert_products_are_saved_and_not_computed_twice(monkeypatch, seeded):
    """Rematerialised layers keep the two grouped products that feed the
    gate (``moe_h1``, ``moe_h3``): the backward pass runs neither a
    second time (the third, left in the rematerialised forward, feeds
    nothing there and the compiler drops it), and its gradients are the
    plain ones (the remat test above)."""
    spec, params, _, x, _ = seeded

    def grouped_products(remat):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q: backbone.forward_backbone_aux(spec, q, x, remat=remat)[0].sum()
        ))(params)
        return str(jaxpr).count("ragged_dot")

    plain = grouped_products(False)  # forward 3 and backward 6 a routed layer
    one = plain // (3 * 9)  # times the name shows in an equation's text
    assert plain == 3 * 9 * one
    assert grouped_products(True) == plain + 3 * 1 * one
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names",
        lambda *names: jax.checkpoint_policies.nothing_saveable,
    )
    assert grouped_products(True) == plain + 3 * 3 * one  # what it was without the names
