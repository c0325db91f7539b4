"""What a rematerialised layer keeps of an attention in tiles
(``backbone.SAVED_TILES``, PR 47): one toy of each kind whose attention
runs tile loops, on the CPU in tiles of 4. The backward pass of a layer
that keeps is handed its tile loops' output and normalisers by name and
runs no tile loop's forward a second time; the values kept are the ones
the second forward computed, so the gradients are the same to the bit;
a program that rematerialises nothing (every toy's fit) changes by the
numbers at the end of private functions' names alone; and the fit span
says how many layers are handed theirs."""

import hashlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import backbone

TILE = 4
TOLERANCE = 1e-4  # of scale, as the remat tests of the kinds' own files
HERE = os.path.dirname(os.path.abspath(__file__))


def sibling_tests(name):
    """A sibling test file, for its toy (``tests/models`` is no package)."""
    spec = importlib.util.spec_from_file_location(f"sibling_{name}", os.path.join(HERE, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TOYS = {
    "lfm2_moe": ("test_backbone", "toy"), "keye_vl2": ("test_backbone", "sparse_toy"),
    "laguna": ("test_prerouted_backbone", "laguna_toy"), "smallthinker": ("test_prerouted_backbone", "toy"),
    "kanana": ("test_latent_backbone", "toy"), "phi4flash": ("test_hybrid_backbone", "toy"),
}


def toy_of(kind, **overrides):
    file, name = TOYS[kind]
    return getattr(sibling_tests(file), name)(**overrides)


#: kind, what its toy is told, and for each layer that attends in tiles
#: whether a rematerialised one keeps: windows of 24 rows in tiles of 4
#: (the sparse toy's 12 in chunks of 4); a sliding window of 6 or 10 rows
#: is a band wider than a tile, one of 4 is not
CASES = {
    "keye_vl2": (
        "keye_vl2",
        {"sa_config": dict(indexer_head_dim=8, indexer_num_heads=8, topk=6, q_chunk_size=4, kv_chunk_size=4)},
        [True, True],
    ),
    "laguna": ("laguna", {}, [True] * 5),
    "laguna_band_of_one_tile": ("laguna", {"sliding_window": 4}, [True, False, False, False, True]),
    "smallthinker": ("smallthinker", {}, [True] * 4),
    "kanana": ("kanana", {}, [True] * 3),
    "phi4flash": ("phi4flash", {}, [True] * 3),
    "phi4flash_band_of_one_tile": ("phi4flash", {"sliding_window": 4}, [False, True, True]),
}


@pytest.fixture(params=sorted(CASES))
def case(request, monkeypatch):
    monkeypatch.setattr(backbone, "ATTENTION_TILE", TILE)
    kind, overrides, keeps = CASES[request.param]
    spec = toy_of(kind, **overrides)
    params = backbone.init_backbone(jax.random.PRNGKey(7), spec)
    x = jnp.asarray(np.random.RandomState(3).uniform(0, 1, (2, spec.lookback_window, 5)), jnp.float32)
    return spec, params, x, keeps


def compiled_gradient(spec, params, x, remat):
    def loss(tree):
        out, penalty, _ = backbone.forward_backbone_aux(spec, tree, x, remat=remat)
        return jnp.mean(jnp.square(out)) + penalty

    return jax.jit(jax.grad(loss)).lower(params).compile()


def whiles(compiled) -> int:
    return len(re.findall(r" while\(", compiled.as_text()))


def traced_loops(spec, params, x) -> int:
    """The loops of the rematerialised gradient as traced, before any compiler."""
    text = str(jax.make_jaxpr(jax.grad(
        lambda tree: sum(backbone.forward_backbone_aux(spec, tree, x, remat=True)[:2]).sum()
    ))(params))
    return text.count("while[") + text.count("scan[")


def without_the_tiles(monkeypatch):
    """Rematerialised layers keep what they kept at the parent: every
    name but ``SAVED_TILES``."""
    only_these = jax.checkpoint_policies.save_only_these_names
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names",
        lambda *names: only_these(*(name for name in names if name != backbone.SAVED_TILES)),
    )


def test_the_backward_pass_runs_no_tile_loops_forward_a_second_time(monkeypatch, case):
    """The compiled gradient of a rematerialised toy, its ``while``
    operations counted, against the same with the name taken out of the
    policy (the parent's program): a call of ``_banded_attention`` that
    keeps is two loops fewer (the ``map`` over blocks of queries and the
    ``fori_loop`` over tiles of keys inside it; a differential layer is
    two calls), and a layer that does not keep none. A ``sparse_attention``
    layer loses both of its ``fori_loop`` s and both ``map`` s around them
    from the traced program; this backend's compiler had folded some
    away before, so its compiled count falls by two a layer and one more."""
    spec, params, x, keeps = case
    kept, traced = whiles(compiled_gradient(spec, params, x, remat=True)), traced_loops(spec, params, x)
    without_the_tiles(monkeypatch)
    before, traced_before = whiles(compiled_gradient(spec, params, x, remat=True)), traced_loops(spec, params, x)
    if "sparse_attention" in spec.layer_ops:
        assert traced_before - traced == 4 * sum(keeps)
        assert before - kept == 2 * sum(keeps) + 1
    else:
        calls = 2 if spec.differential else 1
        assert before - kept == 2 * calls * sum(keeps)
        # the fori_loop, the map over blocks and the map over windows around the call
        assert traced_before - traced == 3 * calls * sum(keeps)
    # and a program that rematerialises nothing never ran one twice
    assert whiles(compiled_gradient(spec, params, x, remat=False)) <= kept


def test_the_values_kept_are_the_ones_the_second_forward_computed(monkeypatch, case):
    """Gradients with the names are the gradients without them to the
    bit, leaf for leaf, and both are the plain ones (no layer
    rematerialised) to the tolerance of the kinds' own remat tests."""
    spec, params, x, _ = case
    named = compiled_gradient(spec, params, x, remat=True)(params)
    plain = compiled_gradient(spec, params, x, remat=False)(params)
    without_the_tiles(monkeypatch)
    unnamed = compiled_gradient(spec, params, x, remat=True)(params)
    leaves = jax.tree_util.tree_leaves
    for (path, a), b, c in zip(jax.tree_util.tree_flatten_with_path(named)[0], leaves(unnamed), leaves(plain)):
        where = jax.tree_util.keystr(path)
        assert np.array_equal(np.asarray(a), np.asarray(b)), where
        scale = max(1.0, float(np.max(np.abs(c))))
        assert float(np.max(np.abs(np.asarray(a) - np.asarray(c)))) <= TOLERANCE * scale, where
    assert any(float(np.max(np.abs(leaf))) > 0 for leaf in leaves(named))


def test_a_layer_that_holds_every_score_at_once_is_untouched(monkeypatch):
    """An ``lfm2_moe`` toy under the tile as it ships (its
    ``full_attention`` fits one tile: no loop, no name): the compiled
    gradient of the rematerialised toy is the text it is without the
    name, and so is its count."""
    spec = toy_of("lfm2_moe")
    assert spec.lookback_window <= backbone.ATTENTION_TILE
    assert not any(backbone.keeps_tile_outputs(spec, op, spec.lookback_window) for op in spec.layer_ops)
    params = backbone.init_backbone(jax.random.PRNGKey(7), spec)
    x = jnp.asarray(np.random.RandomState(3).uniform(0, 1, (2, spec.lookback_window, 5)), jnp.float32)
    texts = []
    for patched in (False, True):  # one call site: the text names the lines it was traced from
        if patched:
            without_the_tiles(monkeypatch)
        texts.append(compiled_gradient(spec, params, x, remat=True).as_text())
    assert texts[0] == texts[1]  # and so its count of loops: none, at 12 rows
    assert " while(" not in texts[0]


def test_the_span_says_how_many_layers_are_handed_their_tiles(monkeypatch, case):
    """``tile_outputs_kept`` of the fit's ``device_program`` span
    (``BackboneSpec.fit_counter_attrs``, by the function the layer
    loop's policy uses): the layers that keep for a member whose
    parameters reach ``REMAT_MIN_PARAM_BYTES``, 0 for one under it (a
    toy's few kilobytes: nothing is rematerialised, nothing computed
    twice); and the forward decides the same from the same bytes."""
    spec, params, x, keeps = case
    assert spec.fit_counter_attrs({})["tile_outputs_kept"] == 0
    assert backbone.tile_outputs_kept(spec, remat=True) == sum(keeps)
    tiled = [op for op in spec.layer_ops if backbone.attends_in_tiles(op, spec.lookback_window)]
    assert [backbone.keeps_tile_outputs(spec, op, spec.lookback_window) for op in tiled] == keeps
    monkeypatch.setattr(backbone, "REMAT_MIN_PARAM_BYTES", 1024)
    assert backbone._spec_param_bytes(spec) == backbone._param_bytes(params) >= 1024
    attrs = spec.fit_counter_attrs({"steps_run": np.asarray(3)})
    assert attrs["tile_outputs_kept"] == sum(keeps) and attrs["steps_run"] == 3
    # the names the policy of the traced program lists, a layer
    listed = []
    only_these = jax.checkpoint_policies.save_only_these_names
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names",
        lambda *names: listed.append(backbone.SAVED_TILES in names) or only_these(*names),
    )
    jax.eval_shape(lambda tree: backbone.forward_backbone_aux(spec, tree, x), params)
    assert len(listed) == len(spec.layer_ops) and sum(listed) == sum(keeps)


def test_a_member_without_a_tile_loop_reads_zero(monkeypatch):
    spec = toy_of("lfm2_moe")
    monkeypatch.setattr(backbone, "REMAT_MIN_PARAM_BYTES", 1024)
    assert spec.fit_counter_attrs({})["tile_outputs_kept"] == 0 == backbone.tile_outputs_kept(spec, remat=True)


@pytest.mark.parametrize("rows, top_k, chunk, searched", [
    (8192, 2048, 512, 12),  # keye_dsa_build: the blocks that end after row 2,048, of 16
    (8192, 8192, 512, 0), (4096, 8192, 512, 0),  # a query may keep every key there is
    (8192, 2047, 512, 13), (8192, 1, 512, 16),
    (100, 24, 32, 4), (24, 6, 8, 3),  # the toys': a last block of padding searches as the rest
])
def test_the_span_says_how_many_blocks_search_their_selection(rows, top_k, chunk, searched):
    """``selection_blocks_searched`` of every ``device_program`` span of a
    spec with ``sparse_attention`` layers (``BackboneSpec.program_attrs``,
    which its ``fit_counter_attrs`` carries too): the blocks of queries a
    window a layer that search for a k-th largest score, by the function
    ``select_keys`` decides with; and a spec without such a layer says
    nothing."""
    from gordo_tpu.models.factories import keye_vl2

    spec = keye_vl2(
        5, lookback_window=rows, num_hidden_layers=2, experts_held=2,
        sa_config={"topk": top_k, "q_chunk_size": chunk, "kv_chunk_size": chunk},
    )
    assert backbone.selection_blocks_searched(spec) == searched
    assert spec.program_attrs() == {"selection_blocks_searched": searched}
    assert spec.fit_counter_attrs({})["selection_blocks_searched"] == searched
    blocks = -(-rows // chunk)
    assert searched == sum(bool(backbone.searches_selection(spec, (i + 1) * chunk)) for i in range(blocks))
    plain = toy_of("lfm2_moe")
    assert plain.program_attrs() == {} and "selection_blocks_searched" not in plain.fit_counter_attrs({})


#: operator, the spec's sliding window, the rows of a fit's window ->
#: attends in tiles, keeps; under the tile as it ships (512 rows)
RULE = [
    ("full_attention", 512, 8192, True, True),
    ("full_attention", 512, 513, True, True),
    ("full_attention", 512, 512, False, False),  # no longer than a tile: every score at once
    ("cross_attention", 512, 8192, True, True),
    ("cross_attention", 512, 512, False, False),
    ("sparse_attention", 512, 8192, True, True),
    ("sliding_attention", 4096, 8192, True, True),  # smallthinker's: nine tiles a block
    ("sliding_attention", 513, 8192, True, True),
    ("sliding_attention", 512, 8192, True, False),  # laguna's and phi4flash's: two tiles a block
    ("sliding_attention", 4096, 300, True, False),  # the window is the band, and one tile
    ("conv", 512, 8192, False, False),
    ("mamba", 512, 8192, False, False),
    ("gmu", 512, 8192, False, False),
]


@pytest.mark.parametrize("op, window, length, tiles, keeps", RULE)
def test_which_layers_keep_is_read_off_the_operator_and_the_shapes(op, window, length, tiles, keeps):
    """No option, no field of a spec, no kind: the operator, its mask's
    width and the window's rows against the tile."""
    assert backbone.ATTENTION_TILE == 512
    spec = toy_of("laguna", sliding_window=window)
    assert backbone.attends_in_tiles(op, length) is tiles
    assert backbone.keeps_tile_outputs(spec, op, length) is keeps


SMALLTHINKER_TOY_FIT_TEXT_AT_THE_PARENT = "e060e814c8275133780e19d22a29e85af103677f2df5b608d9804326b8715679"


def test_a_program_that_rematerialises_nothing_changes_by_the_numbers_in_private_names_alone(monkeypatch):
    """The ``smallthinker`` toy's lowered fit program (the tile as it
    ships; its sliding layers attend in tiles) with the names left out
    of the tile loops is the text pinned at the parent
    (``test_latent_backbone.py`` before PR 47), and the text with them
    is that text line for line once the numbers at the end of private
    functions' names are stripped: a name lowers to no operation."""
    latent = sibling_tests("test_latent_backbone")
    spec = latent.smallthinker_toy()
    new = latent.lowered_fit_text(spec)
    assert hashlib.sha256(new.encode()).hexdigest() == latent.SMALLTHINKER_TOY_FIT_TEXT["shipped"]
    name = backbone.checkpoint_name
    monkeypatch.setattr(
        backbone, "checkpoint_name", lambda value, tag: value if tag == backbone.SAVED_TILES else name(value, tag)
    )
    parent = latent.lowered_fit_text(spec)
    assert hashlib.sha256(parent.encode()).hexdigest() == SMALLTHINKER_TOY_FIT_TEXT_AT_THE_PARENT
    assert new != parent
    stripped = lambda text: re.sub(r"(@[A-Za-z_][A-Za-z_0-9]*?)_\d+\b", r"\1", text)  # noqa: E731
    assert stripped(new) == stripped(parent)
