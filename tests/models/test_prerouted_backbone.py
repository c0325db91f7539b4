"""``kind: smallthinker`` at toy widths on the CPU against its plain
reference (loaded by path: it imports nothing of the program's layer
code): a router that reads the layer's input before the attention, so
that the routing plan depends on nothing the operator computes; an
operator without positions beside one that rotates; a band several
tiles wide under groups of seven query heads; experts gated by ``relu``
and what the fit step counts of them; and the programs of the kinds that
were here, which this kind's arrival must not move."""

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
from gordo_tpu.models.factories import laguna, smallthinker
from gordo_tpu.models.factories.backbone import SMALLTHINKER_21B_A3B_CONFIG
from gordo_tpu.models.spec import BackboneSpec
from gordo_tpu.models.training import FitConfig, build_raw_windowed_fit_fn

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOLERANCE = 1e-4  # of scale: both sides compute in float32 on the CPU
#: a window of 24 rows in tiles of 4 under a band of 10: a block of
#: queries visits its diagonal tile, two whole tiles and an edge tile
T, WINDOW, TILE = 24, 10, 4
PUBLISHED_TILE = backbone.ATTENTION_TILE


@pytest.fixture(autouse=True)
def tiles_of_four(monkeypatch):
    """The toys' windows of 24 rows in tiles of 4: the tile is the
    program's constant, not an option of a spec."""
    monkeypatch.setattr(backbone, "ATTENTION_TILE", TILE)
    return monkeypatch


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(CHIP, "reference", "smallthinker_prerouted_backbone.py")
    spec = importlib.util.spec_from_file_location("reference_smallthinker_prerouted_backbone", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def toy(**overrides) -> BackboneSpec:
    """The four layers of the cut (full without positions, three sliding
    with rotary) at hidden 32: 14 heads of 16 over 2 key/value heads (a
    group is 7), a band of 10 of 24 rows, experts 2-3 of 8 held, 3 a
    token."""
    sizes = dict(
        lookback_window=T, num_hidden_layers=4, hidden_size=32, head_dim=16, num_attention_heads=14,
        num_key_value_heads=2, moe_ffn_hidden_size=24, moe_num_primary_experts=8, experts_held=2,
        expert_offset=2, moe_num_active_primary_experts=3, sliding_window_size=WINDOW,
    )
    sizes.update(overrides)
    return smallthinker(5, **sizes)


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


def tiles_by_arithmetic(length=T, window=WINDOW, tile=TILE):
    back = -(-(window - 1) // tile)
    return sum(min(i, back) + 1 for i in range(-(-length // tile)))


def test_the_reference_imports_nothing_from_the_program():
    source = open(os.path.join(CHIP, "reference", "smallthinker_prerouted_backbone.py")).read()
    assert "import gordo_tpu" not in source and "from gordo_tpu" not in source


def test_the_factory_defaults_are_the_catalog_row_key_by_key():
    """Every key of the published config is the factory's default or a
    fact it states; the benchmark's file repeats them but for the cut."""
    published = SMALLTHINKER_21B_A3B_CONFIG
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert row["config"] == published
    stated = json.load(open(os.path.join(CHIP, "configs", "smallthinker-21b-a3b-50tag-lb8192.json")))
    differ = {key for key, value in published.items() if stated[key] != value}
    assert differ == set(stated["reduced"]) == {"num_hidden_layers", "moe_num_primary_experts"}
    assert stated["published"] == {key: published[key] for key in stated["reduced"]}
    spec = smallthinker(50)
    assert len(spec.layer_ops) == 52 and set(spec.layer_ffns) == {"moe"}
    assert spec.layer_ops[:5] == ("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
    assert (spec.hidden_size, spec.head_dim, spec.num_attention_heads, spec.num_key_value_heads) == (2560, 128, 28, 4)
    assert (spec.moe_intermediate_size, spec.num_experts, spec.experts_held, spec.num_experts_per_tok) == (768, 64, 64, 6)
    assert (spec.sliding_window, spec.norm_eps, spec.lookback_window) == (4096, 1e-6, 8192)
    assert (spec.router, spec.router_input, spec.expert_activation) == ("softmax_of_chosen", "layer_input", "relu")
    assert not spec.qk_norm and not spec.attention_gate and not spec.shared_expert_intermediate_size
    assert spec.rope_of("full_attention")["rope_type"] == "none"
    assert spec.rope_of("sliding_attention") == {
        "rope_type": "default", "rope_theta": 1.5e6, "partial_rotary_factor": 1,
    }


def test_the_cut_holds_the_weights_the_issue_counted():
    spec = smallthinker(50, num_hidden_layers=4, experts_held=16)
    layer = 2 * 2560 * 3584 + 2 * 2560 * 512 + 2 * 2560 + 2560 * 64 + 16 * 3 * 2560 * 768
    assert layer == 115_512_320 and spec.layer_param_count("sliding_attention", "moe") == layer
    assert spec.param_count() == 4 * layer + (50 * 2560 + 2560) + (2560 + 2560 * 50 + 50) == 462_310_450
    shapes = jax.eval_shape(lambda key: backbone.init_backbone(key, spec), jax.random.PRNGKey(0))
    assert backbone.trained_param_count(shapes) == 462_310_450
    assert shapes["layer_1"]["attn"]["wq"].shape == (2560, 3584) and shapes["layer_1"]["attn"]["wo"].shape == (3584, 2560)
    assert shapes["layer_0"]["moe"]["w1"].shape == (16, 2560, 768) and "expert_bias" not in shapes["layer_0"]["moe"]
    assert set(shapes["layer_0"]["attn"]) == {"wq", "wk", "wv", "wo"}


def test_what_the_factory_and_the_spec_refuse():
    with pytest.raises(ValueError, match="rope_layout has to equal"):
        toy(rope_layout=[1, 1, 1, 1])
    with pytest.raises(ValueError, match="every layer held"):
        toy(num_hidden_layers=5, rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1])
    for key, value in (("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False), ("rope_scaling", {})):
        with pytest.raises(ValueError, match=key):
            toy(**{key: value})
    for field, value in (("router_input", "operator_output"), ("expert_activation", "gelu")):
        with pytest.raises(ValueError, match=field):
            dataclasses.replace(toy(), **{field: value})
    with pytest.raises(ValueError, match="rope_type"):
        dataclasses.replace(toy(), rope_parameters=(("full_attention", (("rope_type", "linear"),)),))


def test_logits_far_apart_choose_what_the_reference_chooses(seeded, reference):
    """Routers 300 times their seeded size, as a few steps of Adam on a
    residual stream that grows leave them on the chip: a softmax over all
    eight logits rounds all but the largest to zero there, and the three
    largest of that are the largest and the two lowest numbers. The
    choice is of the logits, so the counts stay the reference's, layer
    by layer, and so does the output."""
    spec, params, layers, x, _ = seeded
    far = {
        name: dict(w, moe=dict(w["moe"], router=300.0 * w["moe"]["router"])) if "moe" in w else w
        for name, w in params.items()
    }
    logits = np.asarray(x @ far["embed"]["W"] + far["embed"]["b"]).reshape(-1, 32) @ np.asarray(far["layer_0"]["moe"]["router"])
    ordered = -np.sort(-logits, axis=-1)
    assert np.median(ordered[:, 0] - ordered[:, 1]) > 104  # exp(-104) is no float32
    far_layers = dict(layers, weights=jax.tree_util.tree_map(np.asarray, far))
    out, _, aux = backbone.forward_backbone_aux(spec, far, x)
    found = reference.counters(far_layers, x)
    assert np.array_equal(aux["router_tokens"], found["routed"])
    assert aux["gate_total"].tolist() == found["gate_total"].tolist()
    close(out, reference.forward(far_layers, x), "forward")
    # the softmax over all, its largest renormalised: other counts, the same output
    other = dataclasses.replace(spec, router="softmax")
    out_other, _, aux_other = backbone.forward_backbone_aux(other, far, x)
    assert not np.array_equal(aux_other["router_tokens"], found["routed"])
    close(out_other, out, "the two routers' outputs")


def test_the_forward_is_the_references(seeded, reference):
    spec, params, layers, x, _ = seeded
    out, penalty, aux = backbone.forward_backbone_aux(spec, params, x)
    close(out, reference.forward(layers, x), "forward")
    assert float(penalty) == 0.0
    found = reference.counters(layers, x)
    assert np.array_equal(aux["router_tokens"], found["routed"]) and found["routed"].shape == (4, 8)
    # to the digit: the reference counts its masks and its gates, the program says its arithmetic
    assert aux["pairs_attended"].tolist() == found["attended"].tolist()
    assert aux["pairs_attended"].tolist() == [4 * T * (T + 1) / 2] + [4 * attended_by_arithmetic()] * 3
    assert aux["gate_active"].tolist() == found["gate_active"].tolist()
    assert aux["gate_total"].tolist() == found["gate_total"].tolist() == (aux["pairs_here"] * 24).tolist()
    assert all(0 < active < total for active, total in zip(aux["gate_active"], aux["gate_total"]))
    assert aux["gate_active"].dtype == aux["gate_total"].dtype == jnp.float32
    # the tiles visited: the loops' own bounds, as ``band_pairs`` sums them
    full, band = tiles_by_arithmetic(window=T), tiles_by_arithmetic()
    assert (full, band) == (21, 18)  # six blocks: 1 + 2 + .. + 6, and 1 + 2 + 3 + 4 + 4 + 4
    assert aux["pairs_multiplied"].tolist() == [4.0 * full * TILE * TILE] + [4.0 * band * TILE * TILE] * 3
    for window, tiles in ((T, full), (WINDOW, band)):
        attended, multiplied = backbone.band_pairs(T, window, TILE)
        assert (attended, float(multiplied)) == (attended_by_arithmetic(window=window), tiles * TILE * TILE)


def test_every_leafs_gradient_is_the_references(seeded, reference):
    spec, params, layers, x, y = seeded
    want_loss, want = reference.loss_and_grads(layers, x, y)

    def loss_of(tree):
        out, penalty, _ = backbone.forward_backbone_aux(spec, tree, x)
        return jnp.mean(jnp.mean((out - y) ** 2, axis=-1)) + penalty

    loss, got = jax.jit(jax.value_and_grad(loss_of))(params)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    leaves = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(leaves) == 4 * 10 + 5
    for (path, leaf), ref in zip(leaves, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        close(leaf, ref, name)
        assert np.any(np.asarray(ref)), name  # every leaf learns, the routers too


@pytest.mark.parametrize("active", [None, [True, False, True, True]])
def test_remat_on_and_off_give_the_same_outputs_and_gradients(seeded, active):
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


def test_a_rematerialised_layer_keeps_no_plan(seeded, capsys):
    """A rematerialised pre-routed layer is handed nothing by name but
    the two gate products, as the kinds whose router reads the experts'
    tensor: it makes its plan again from its saved input, one product at
    full precision, and chooses what it chose
    (``test_remat_on_and_off_give_the_same_outputs_and_gradients``)."""
    from jax.ad_checkpoint import print_saved_residuals

    spec, params, _, x, _ = seeded

    def names_of(spec, params, x):
        print_saved_residuals(
            lambda tree: jnp.sum(backbone.forward_backbone_aux(spec, tree, x, remat=True)[0]), params
        )
        said = capsys.readouterr().out.splitlines()
        return {line.split("named '")[1].split("'")[0] for line in said if "named '" in line}

    # (the two products are floats, which the listing names by the
    # operation that carries their name and not by the name)
    assert names_of(spec, params, x) == set()
    other = laguna_toy()
    other_params = backbone.init_backbone(jax.random.PRNGKey(7), other)
    assert names_of(other, other_params, x) == set()


def test_the_choice_reads_the_layers_input_and_nothing_the_attention_computes(seeded):
    """Perturb a layer's ``wo``: its attention's output moves, its
    routing does not (the router read the layer's input before); perturb
    the layer's input: it does. A ``laguna`` layer, whose router reads
    the normed tensor after the attention, moves with ``wo``."""
    spec, params, _, x, _ = seeded
    h = jnp.asarray(np.random.RandomState(5).normal(0, 1, (4, T, 32)).astype(np.float32))

    def routed(spec, w, h, op="sliding_attention"):
        out, counts, *_ = backbone.block(spec, op, "moe", w, h)
        return np.asarray(out), np.asarray(counts[0])

    w = params["layer_1"]
    moved = dict(w, attn=dict(w["attn"], wo=w["attn"]["wo"] + 0.5))
    out, counts = routed(spec, w, h)
    out_moved, counts_moved = routed(spec, moved, h)
    assert np.max(np.abs(out - out_moved)) > 0.1 and np.array_equal(counts, counts_moved)
    plan = backbone.routing_plan(spec, w["moe"], h.reshape(-1, 32), T)
    plan_moved = backbone.routing_plan(spec, moved["moe"], h.reshape(-1, 32), T)
    for name in plan:
        assert np.array_equal(plan[name], plan_moved[name]), name
    assert np.array_equal(plan["routed"], counts) and int(plan["pairs_here"]) == int(counts[2:4].sum())
    _, counts_other = routed(spec, w, h + 0.3 * jnp.roll(h, 1, axis=-1))
    assert not np.array_equal(counts, counts_other)
    # a kind that routes after its attention
    other = laguna_toy()
    w = backbone.init_backbone(jax.random.PRNGKey(7), other)["layer_1"]
    moved = dict(w, attn=dict(w["attn"], wo=w["attn"]["wo"] + 0.5))
    assert not np.array_equal(routed(other, w, h)[1], routed(other, moved, h)[1])


def test_a_full_layers_q_and_k_are_unrotated_and_a_sliding_layers_rotate(seeded):
    spec, params, _, x, _ = seeded
    u = jnp.asarray(np.random.RandomState(6).normal(0, 1, (2, T, 32)).astype(np.float32))
    w = params["layer_0"]["attn"]
    q, k, v = backbone._heads(spec, w, u, "full_attention")
    assert np.array_equal(q, (u @ w["wq"]).reshape(2, T, 14, 16))
    assert np.array_equal(k, (u @ w["wk"]).reshape(2, T, 2, 16))
    q, k, v = backbone._heads(spec, w, u, "sliding_attention")
    close(q, backbone.rotary((u @ w["wq"]).reshape(2, T, 14, 16), 1.5e6), "q")
    close(k, backbone.rotary((u @ w["wk"]).reshape(2, T, 2, 16), 1.5e6), "k")
    assert np.array_equal(q[:, 0], (u @ w["wq"]).reshape(2, T, 14, 16)[:, 0])  # position 0 turns nothing
    assert not np.array_equal(q[:, 1:], (u @ w["wq"]).reshape(2, T, 14, 16)[:, 1:])
    assert np.array_equal(v, (u @ w["wv"]).reshape(2, T, 2, 16))
    # without positions a full layer's output does not know the order of the rows before a query
    last = lambda rows: np.asarray(backbone.banded_attention(spec, "full_attention", w, rows)[0])[:, -1]  # noqa: E731
    shuffled = jnp.concatenate([u[:, :-1][:, ::-1], u[:, -1:]], axis=1)
    close(last(u), last(shuffled), "NoPE", 1e-5)
    sliding = lambda rows: np.asarray(backbone.banded_attention(spec, "sliding_attention", w, rows)[0])[:, -1]  # noqa: E731
    assert np.max(np.abs(sliding(u) - sliding(shuffled))) > 1e-3


def test_the_band_is_the_references_under_groups_of_seven(seeded, reference):
    spec, params, layers, _, _ = seeded
    u = jnp.asarray(np.random.RandomState(8).normal(0, 1, (2, T, 32)).astype(np.float32))
    for layer, op, flags in ((0, "full_attention", (0, 0)), (2, "sliding_attention", (1, 1))):
        got, (attended, multiplied) = backbone.banded_attention(spec, op, params[f"layer_{layer}"]["attn"], u)
        want, pairs = reference.attention(u, layers["weights"][f"layer_{layer}"]["attn"], *flags, layers["sizes"])
        close(got, want, op)
        assert float(attended) == float(np.sum(pairs))
    # a query in a sliding layer does not see row t - window
    w = params["layer_2"]["attn"]
    far = u.at[:, T - 1 - WINDOW].add(5.0)
    near = u.at[:, T - WINDOW].add(5.0)
    last = lambda rows: np.asarray(backbone.banded_attention(spec, "sliding_attention", w, rows)[0])[:, -1]  # noqa: E731
    assert np.array_equal(last(u), last(far)) and not np.array_equal(last(u), last(near))


def test_the_four_shares_add_up_to_the_uncut_layer(seeded, reference):
    """Offsets 0, 2, 4 and 6 of 8 toy experts, two held each: what the
    four holders of a layer give adds up to the reference's layer with
    every expert held; nothing stands in for the others in any share."""
    spec, params, layers, _, _ = seeded
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.normal(0, 1, (2, T, 32)).astype(np.float32))
    w = dict(params["layer_1"]["moe"])
    for name, shape in (("w1", (8, 32, 24)), ("w3", (8, 32, 24)), ("w2", (8, 24, 32))):
        w[name] = jnp.asarray(rng.normal(0, 0.2, shape).astype(np.float32))
    whole = dict(layers["sizes"], experts_held=8, expert_offset=0)
    n = reference.rms_norm(x + 0.1, params["layer_1"]["ffn_norm"], 1e-6)  # the experts' rows are not the router's
    gates, chosen = reference.router_gates(x, w, whole)
    want, counts, (active, units) = reference.moe_ffn(n, gates, chosen, w, whole)
    np.testing.assert_allclose(np.asarray(gates).sum(axis=-1), 1.0, rtol=1e-6)
    assert int(np.sum(counts)) == 2 * T * 3 and int(units) == 2 * T * 3 * 24
    total, pairs, gate_active = 0.0, 0, 0.0
    for share in range(4):
        held = toy(expert_offset=2 * share)
        mine = dict(w, **{name: w[name][2 * share : 2 * share + 2] for name in ("w1", "w3", "w2")})
        plan = backbone.routing_plan(held, mine, x.reshape(-1, 32), T)
        out, routed, pairs_here, gate = backbone.moe_ffn(held, mine, n, plan=plan)
        assert np.array_equal(routed, counts) and int(pairs_here) == int(counts[2 * share : 2 * share + 2].sum())
        total, pairs, gate_active = total + out, pairs + int(pairs_here), gate_active + float(gate[0])
    close(total, want, "four shares")
    assert pairs == 2 * T * 3 and gate_active == float(active)


def test_a_pair_whose_gate_is_all_negative_adds_exactly_zero(seeded):
    spec, params, _, _, _ = seeded
    rng = np.random.RandomState(10)
    n = jnp.asarray(np.abs(rng.normal(0, 1, (2, T, 32))).astype(np.float32))  # positive rows ...
    w = dict(params["layer_1"]["moe"])
    w["w1"] = -jnp.abs(w["w1"])  # ... through negative gate matrices: every pre-activation below zero
    out, _, pairs_here, (gate_active, gate_total) = backbone.moe_ffn(spec, w, n)
    assert int(pairs_here) > 0 and float(gate_total) == int(pairs_here) * 24
    assert float(gate_active) == 0.0 and not np.any(np.asarray(out))
    grads = jax.grad(lambda tree: jnp.sum(backbone.moe_ffn(spec, tree, n)[0] ** 2))(w)
    assert not any(np.any(np.asarray(leaf)) for leaf in jax.tree_util.tree_leaves(grads))
    # the same rows under a silu gate add something: the zero is the relu's
    assert np.any(np.asarray(backbone.moe_ffn(dataclasses.replace(spec, expert_activation="silu"), w, n)[0]))


@pytest.mark.parametrize("padding", [[True, True, False, False], [False, True, True, True]])
def test_a_window_of_padding_adds_nothing_to_the_counters(seeded, padding):
    spec, params, _, x, _ = seeded
    _, _, aux = backbone.forward_backbone_aux(spec, params, x, active=jnp.asarray(padding))
    alone = backbone.forward_backbone_aux(spec, params, x[np.asarray(padding)])[2]
    assert set(aux) == set(alone) >= {"gate_active", "gate_total", "pairs_attended", "pairs_multiplied"}
    for name in aux:
        assert np.array_equal(aux[name], alone[name]), name
    assert aux["pairs_attended"][1] == int(np.sum(padding)) * attended_by_arithmetic()


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
    assert counters["pairs_attended"].tolist() == [6 * T * (T + 1) / 2] + [6 * attended_by_arithmetic()] * 3
    assert counters["pairs_multiplied"].tolist() == [6 * 21 * 16] + [6 * 18 * 16] * 3
    assert counters["pairs_total"].tolist() == [6 * T * 3] * 4
    assert counters["gate_total"].tolist() == (counters["pairs_here"] * 24).tolist()
    assert all(0 < a < b for a, b in zip(counters["gate_active"], counters["gate_total"]))
    attrs = spec.fit_counter_attrs(counters)
    assert attrs["gate_active"] == counters["gate_active"].tolist()
    assert attrs["num_experts"] == 8 and "index_topk" not in attrs


def test_the_estimator_builds_the_kind_by_name():
    estimator = JaxBackboneForecast(
        kind="smallthinker", lookback_window=T, num_hidden_layers=4, hidden_size=32, head_dim=16,
        num_attention_heads=14, num_key_value_heads=2, moe_ffn_hidden_size=24, moe_num_primary_experts=8,
        experts_held=2, expert_offset=2, moe_num_active_primary_experts=3, sliding_window_size=WINDOW,
        epochs=1, batch_size=2,
    )
    rng = np.random.RandomState(11)
    X = rng.uniform(0, 1, (T + 6, 5)).astype(np.float32)
    estimator.fit(X, X)
    assert estimator.spec_ == toy() and estimator.predict(X).shape == (6, 5)
    loss, norms = estimator.training_loss_and_grad_norms(X, X)
    assert np.isfinite(loss) and all(np.isfinite(v) for v in jax.tree_util.tree_leaves(norms))


def laguna_toy(**overrides):
    """``tests/models/test_banded_backbone.py:toy``, to the letter."""
    sizes = dict(
        lookback_window=24, num_hidden_layers=5, hidden_size=32, head_dim=16,
        num_attention_heads_per_layer=[6, 8, 8, 8, 6], num_key_value_heads=2,
        intermediate_size=48, moe_intermediate_size=24, shared_expert_intermediate_size=20,
        num_experts=8, experts_held=2, expert_offset=2, num_experts_per_tok=2, sliding_window=6,
        rope_parameters={"full_attention": {"rope_theta": 100.0, "factor": 8, "original_max_position_embeddings": 16,
                                            "beta_fast": 2, "beta_slow": 0.25}},
    )
    sizes.update(overrides)
    return laguna(5, **sizes)


def lowered_fit_text(spec) -> str:
    """``test_banded_backbone.py``'s lowering of a toy member's fit,
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
def test_a_laguna_members_fit_program_is_the_text_the_parent_lowers(tiles_of_four, tile, want):
    """``moe_ffn`` parted into the plan and the grouped products and
    ``block`` orders them: a ``laguna`` member's lowered fit program is,
    to the character, the text the parent lowers, with the tile as it
    ships (its full layers hold every score at once at 24 rows) and in
    tiles of 4 (every layer in the tile loops); hashes taken at commit
    ``11389e9`` before any edit (``lfm2_moe``'s and ``keye_vl2``'s:
    ``test_banded_backbone.py``). Another text would be another
    compilation, and on the chip another routing lottery (PR 28). The
    hashes of both texts (a sliding layer attends in tiles under either tile) was taken again at commit ``9fae991`` with PR 47's
    change applied: the tile loops' results pass through
    ``checkpoint_name`` (``backbone.SAVED_TILES``), an identity that
    lowers to no operation, but the counter behind the numbers at the
    end of private functions' names (``@closed_call_317``) runs further,
    so those numbers move and nothing else does
    (``test_tiles_kept.py`` holds the parent's text against the new one
    with the numbers stripped; a toy rematerialises nothing)."""
    tiles_of_four.setattr(backbone, "ATTENTION_TILE", tile)
    assert hashlib.sha256(lowered_fit_text(laguna_toy()).encode()).hexdigest() == LAGUNA_TOY_FIT_TEXT[want]


def test_a_laguna_members_seeded_weights_are_what_they_were():
    params = backbone.init_backbone(jax.random.PRNGKey(7), laguna_toy())
    digest = hashlib.sha256(
        b"".join(np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(params))
    ).hexdigest()
    assert digest == LAGUNA_TOY_DIGEST


LAGUNA_TOY_DIGEST = "461061af7ad50f97561344acf5b539776bd050f3ba3fdf53410013df3aa8dc16"
LAGUNA_TOY_FIT_TEXT = {
    "shipped": "bd3f7fb9a60906a0f1d67225a8f03ee949ba810ab95f4e50d6e53f5c5e1b6d97",
    "tiles_of_four": "a5154b77f97e31786783f4a8b0f128b52bf3c382810e822519488f74f4ba6e0b",
}
