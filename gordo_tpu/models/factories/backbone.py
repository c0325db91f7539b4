"""
Backbone factories: a language model's published layers as a windowed
sensor model (ROADMAP, Reach). Each returns a static
:class:`~gordo_tpu.models.spec.BackboneSpec`; its defaults are the
published ``config.json`` of the model it is named for, and what a
config cuts (depth, the experts this holder keeps) is an argument.
"""

from typing import Any, Dict, Optional, Sequence, Union

from ..register import register_model_builder
from ..spec import BackboneSpec, OptimizerSpec

#: LiquidAI/LFM2-8B-A1B config.json: 24 layers, attention at 2, 6, 10, 14, 18, 21
LFM2_8B_A1B_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24)
)


@register_model_builder(type="JaxBackboneForecast")
def lfm2_moe(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 512,
    layer_types: Sequence[str] = LFM2_8B_A1B_LAYER_TYPES,
    num_dense_layers: int = 2,
    hidden_size: int = 2048,
    num_attention_heads: int = 32,
    num_key_value_heads: int = 8,
    conv_L_cache: int = 3,
    intermediate_size: int = 7168,
    moe_intermediate_size: int = 1792,
    num_experts: int = 32,
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    num_experts_per_tok: int = 4,
    routed_scaling_factor: float = 1.0,
    rope_theta: float = 1000000.0,
    norm_eps: float = 1e-5,
    optimizer: Union[str, OptimizerSpec] = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    compile_kwargs: Optional[Dict[str, Any]] = None,
    compute_dtype: str = "float32",
    precision: str = "",
    **kwargs,
) -> BackboneSpec:
    """``model_type: lfm2_moe`` (defaults: LFM2-8B-A1B). ``layer_types``
    are the operators of the layers held, in order; the first
    ``num_dense_layers`` of them carry the dense feed-forward and the
    rest the routed experts, of which this holder keeps ``experts_held``
    (default: all) from ``expert_offset``."""
    compile_kwargs = compile_kwargs or {}
    layer_types = tuple(layer_types)
    return BackboneSpec(
        n_features=n_features,
        n_features_out=n_features_out or n_features,
        lookback_window=lookback_window,
        layer_ops=layer_types,
        layer_ffns=tuple(
            "dense" if i < num_dense_layers else "moe" for i in range(len(layer_types))
        ),
        hidden_size=hidden_size,
        num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads,
        conv_L_cache=conv_L_cache,
        intermediate_size=intermediate_size,
        moe_intermediate_size=moe_intermediate_size,
        num_experts=num_experts,
        experts_held=num_experts if experts_held is None else experts_held,
        expert_offset=expert_offset,
        num_experts_per_tok=num_experts_per_tok,
        routed_scaling_factor=float(routed_scaling_factor),
        rope_theta=float(rope_theta),
        norm_eps=float(norm_eps),
        optimizer=OptimizerSpec.from_config(optimizer, optimizer_kwargs),
        loss=compile_kwargs.get("loss", "mse"),
        compute_dtype=compute_dtype,
        precision=precision,
    )


#: Kwai-Keye/Keye-VL-2.0-30B-A3B config.json (``model_type: KeyeVL2``):
#: the language model's keys, every one. The factory's defaults are
#: these; the keys it does not take say nothing it can act on (the
#: vocabulary is replaced by the sensor projections, positions are a
#: window's, ``mrope_section`` gives each rotary frequency one of three
#: position ids that are equal where no image enters: one-dimensional
#: rotary, exactly) or name what it refuses to be told otherwise.
KEYE_VL2_30B_A3B_CONFIG: Dict[str, Any] = {
    "attention_bias": False,
    "decoder_sparse_step": 1,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 6144,
    "max_position_embeddings": 262144,
    "max_window_layers": 48,
    "mlp_only_layers": [],
    "model_type": "KeyeVL2",
    "moe_intermediate_size": 768,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_experts": 128,
    "num_experts_per_tok": 8,
    "num_hidden_layers": 48,
    "num_key_value_heads": 4,
    "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {
        "indexer_head_dim": 64,
        "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1,
        "kv_chunk_size": 512,
        "q_chunk_size": 512,
        "topk": 2048,
    },
    "sliding_window": None,
    "tie_word_embeddings": False,
    "use_sliding_window": False,
    "vocab_size": 151936,
}
_KEYE = KEYE_VL2_30B_A3B_CONFIG
#: what the layers here cannot be told otherwise: every layer is sparse
#: attention and routed experts, without bias, gated by silu, the chosen
#: experts' probabilities renormalised
_KEYE_FIXED = (
    "attention_bias", "decoder_sparse_step", "hidden_act", "mlp_only_layers", "norm_topk_prob",
    "use_sliding_window",
)


@register_model_builder(type="JaxBackboneForecast")
def keye_vl2(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 8192,
    num_hidden_layers: int = _KEYE["num_hidden_layers"],
    hidden_size: int = _KEYE["hidden_size"],
    head_dim: int = _KEYE["head_dim"],
    num_attention_heads: int = _KEYE["num_attention_heads"],
    num_key_value_heads: int = _KEYE["num_key_value_heads"],
    moe_intermediate_size: int = _KEYE["moe_intermediate_size"],
    num_experts: int = _KEYE["num_experts"],
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    num_experts_per_tok: int = _KEYE["num_experts_per_tok"],
    rope_theta: float = _KEYE["rope_theta"],
    rms_norm_eps: float = _KEYE["rms_norm_eps"],
    sa_config: Optional[Dict[str, int]] = None,
    optimizer: Union[str, OptimizerSpec] = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    compile_kwargs: Optional[Dict[str, Any]] = None,
    compute_dtype: str = "float32",
    precision: str = "",
    **kwargs,
) -> BackboneSpec:
    """``model_type: KeyeVL2`` (defaults: Keye-VL-2.0-30B-A3B's language
    model; the vision tower is left out: no image enters a sensor
    model). ``num_hidden_layers`` layers, all alike: sparse attention
    (grouped-query attention over the ``sa_config.topk`` keys a learned
    indexer selects for each query, computed in tiles of ``q_chunk_size``
    queries by ``kv_chunk_size`` keys, which have to be equal), then the
    routed experts under a softmax router, of which this holder keeps
    ``experts_held`` (default: all) from ``expert_offset``. Keys of
    ``sa_config`` that are left out keep their published values."""
    for key in _KEYE_FIXED:
        if key in kwargs and kwargs[key] != _KEYE[key]:
            raise ValueError(f"keye_vl2 runs {key}={_KEYE[key]!r} only; got {kwargs[key]!r}")
    sparse = {**_KEYE["sa_config"], **(sa_config or {})}
    if sparse["indexer_num_kv_heads"] != 1:
        raise ValueError("keye_vl2's indexer has one key head")
    if sparse["q_chunk_size"] != sparse["kv_chunk_size"]:
        raise ValueError("keye_vl2 computes square tiles: q_chunk_size has to equal kv_chunk_size")
    compile_kwargs = compile_kwargs or {}
    return BackboneSpec(
        n_features=n_features,
        n_features_out=n_features_out or n_features,
        lookback_window=lookback_window,
        layer_ops=("sparse_attention",) * num_hidden_layers,
        layer_ffns=("moe",) * num_hidden_layers,
        hidden_size=hidden_size,
        attention_head_dim=head_dim,
        num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads,
        moe_intermediate_size=moe_intermediate_size,
        num_experts=num_experts,
        experts_held=num_experts if experts_held is None else experts_held,
        expert_offset=expert_offset,
        num_experts_per_tok=num_experts_per_tok,
        router="softmax",
        rope_theta=float(rope_theta),
        norm_eps=float(rms_norm_eps),
        index_n_heads=sparse["indexer_num_heads"],
        index_head_dim=sparse["indexer_head_dim"],
        index_topk=sparse["topk"],
        index_chunk=sparse["q_chunk_size"],
        optimizer=OptimizerSpec.from_config(optimizer, optimizer_kwargs),
        loss=compile_kwargs.get("loss", "mse"),
        compute_dtype=compute_dtype,
        precision=precision,
    )


#: poolside/Laguna-XS.2 config.json (``model_type: laguna``), every key of
#: the catalog's row. The factory's defaults are these; the keys it does
#: not take say nothing it can act on (the vocabulary is replaced by the
#: sensor projections, positions are a window's) or name what it refuses
#: to be told otherwise.
LAGUNA_XS2_CONFIG: Dict[str, Any] = {
    "model_type": "laguna",
    "vocab_size": 100352,
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "num_hidden_layers": 40,
    "num_attention_heads": 48,
    "num_key_value_heads": 8,
    "head_dim": 128,
    "max_position_embeddings": 262144,
    "attention_bias": False,
    "rms_norm_eps": 1e-06,
    "num_experts": 256,
    "num_experts_per_tok": 8,
    "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False,
    "gating": True,
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000,
            "rope_type": "yarn",
            "factor": 64,
            "original_max_position_embeddings": 4096,
            "beta_slow": 1,
            "beta_fast": 64,
            "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096,
    },
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"] * 10,
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}
_LAGUNA = LAGUNA_XS2_CONFIG
#: what the layers here cannot be told otherwise: no bias, gated heads,
#: the router's weights on the experts' outputs
_LAGUNA_FIXED = ("attention_bias", "gating", "moe_apply_router_weight_on_input")


@register_model_builder(type="JaxBackboneForecast")
def laguna(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 8192,
    num_hidden_layers: int = _LAGUNA["num_hidden_layers"],
    layer_types: Sequence[str] = tuple(_LAGUNA["layer_types"]),
    mlp_layer_types: Sequence[str] = tuple(_LAGUNA["mlp_layer_types"]),
    num_attention_heads_per_layer: Sequence[int] = tuple(_LAGUNA["num_attention_heads_per_layer"]),
    hidden_size: int = _LAGUNA["hidden_size"],
    head_dim: int = _LAGUNA["head_dim"],
    num_key_value_heads: int = _LAGUNA["num_key_value_heads"],
    intermediate_size: int = _LAGUNA["intermediate_size"],
    moe_intermediate_size: int = _LAGUNA["moe_intermediate_size"],
    shared_expert_intermediate_size: int = _LAGUNA["shared_expert_intermediate_size"],
    num_experts: int = _LAGUNA["num_experts"],
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    num_experts_per_tok: int = _LAGUNA["num_experts_per_tok"],
    moe_routed_scaling_factor: float = _LAGUNA["moe_routed_scaling_factor"],
    sliding_window: int = _LAGUNA["sliding_window"],
    rope_parameters: Optional[Dict[str, Any]] = None,
    rms_norm_eps: float = _LAGUNA["rms_norm_eps"],
    optimizer: Union[str, OptimizerSpec] = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    compile_kwargs: Optional[Dict[str, Any]] = None,
    compute_dtype: str = "float32",
    precision: str = "",
    **kwargs,
) -> BackboneSpec:
    """``model_type: laguna`` (defaults: Laguna-XS.2). The first
    ``num_hidden_layers`` entries of ``layer_types`` (``full_attention``
    or ``sliding_attention``: a query sees the ``sliding_window`` rows up
    to itself), of ``mlp_layer_types`` (``dense`` or ``sparse``: the
    routed experts under a sigmoid router scaled by
    ``moe_routed_scaling_factor``, of which this holder keeps
    ``experts_held`` (default: all) from ``expert_offset``, beside a
    shared expert) and of ``num_attention_heads_per_layer`` are the
    layers held; every head is gated; ``rope_parameters`` gives each
    layer type its rotary embedding (keys left out keep their published
    values)."""
    for key in _LAGUNA_FIXED:
        if key in kwargs and kwargs[key] != _LAGUNA[key]:
            raise ValueError(f"laguna runs {key}={_LAGUNA[key]!r} only; got {kwargs[key]!r}")
    if min(len(layer_types), len(mlp_layer_types), len(num_attention_heads_per_layer)) < num_hidden_layers:
        raise ValueError("laguna needs a layer type, an mlp type and a head count for every layer held")
    unknown = set(mlp_layer_types) - {"dense", "sparse"}
    if unknown:
        raise ValueError(f"unknown mlp_layer_types {sorted(unknown)}")
    ropes = {
        op: {**_LAGUNA["rope_parameters"][op], **(rope_parameters or {}).get(op, {})}
        for op in ("full_attention", "sliding_attention")
    }
    compile_kwargs = compile_kwargs or {}
    return BackboneSpec(
        n_features=n_features,
        n_features_out=n_features_out or n_features,
        lookback_window=lookback_window,
        layer_ops=tuple(layer_types[:num_hidden_layers]),
        layer_ffns=tuple("dense" if kind == "dense" else "moe" for kind in mlp_layer_types[:num_hidden_layers]),
        layer_heads=tuple(int(heads) for heads in num_attention_heads_per_layer[:num_hidden_layers]),
        hidden_size=hidden_size,
        attention_head_dim=head_dim,
        num_attention_heads=int(num_attention_heads_per_layer[0]),
        num_key_value_heads=num_key_value_heads,
        intermediate_size=intermediate_size,
        moe_intermediate_size=moe_intermediate_size,
        shared_expert_intermediate_size=shared_expert_intermediate_size,
        num_experts=num_experts,
        experts_held=num_experts if experts_held is None else experts_held,
        expert_offset=expert_offset,
        num_experts_per_tok=num_experts_per_tok,
        routed_scaling_factor=float(moe_routed_scaling_factor),
        rope_theta=float(ropes["sliding_attention"]["rope_theta"]),
        rope_parameters=tuple((op, tuple(sorted(rope.items()))) for op, rope in sorted(ropes.items())),
        qk_norm=False,
        attention_gate=True,
        sliding_window=sliding_window,
        norm_eps=float(rms_norm_eps),
        optimizer=OptimizerSpec.from_config(optimizer, optimizer_kwargs),
        loss=compile_kwargs.get("loss", "mse"),
        compute_dtype=compute_dtype,
        precision=precision,
    )


#: PowerInfer/SmallThinker-21BA3B-Instruct config.json, every key of the
#: catalog's row. The factory's defaults are these; the keys it does not
#: take say nothing it can act on (the vocabulary is replaced by the
#: sensor projections, positions are a window's, the model's name) or
#: name what it refuses to be told otherwise.
SMALLTHINKER_21B_A3B_CONFIG: Dict[str, Any] = {
    "head_dim": 128,
    "hidden_size": 2560,
    "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct",
    "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6,
    "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True,
    "num_attention_heads": 28,
    "num_hidden_layers": 52,
    "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13,
    "rope_scaling": None,
    "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096,
    "tie_word_embeddings": False,
    "vocab_size": 151936,
}
_SMALLTHINKER = SMALLTHINKER_21B_A3B_CONFIG
#: what the layers here cannot be told otherwise: a softmax over the
#: chosen experts' logits, renormalised; plain rotary where there is any
_SMALLTHINKER_FIXED = ("moe_primary_router_apply_softmax", "norm_topk_prob", "rope_scaling")


@register_model_builder(type="JaxBackboneForecast")
def smallthinker(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 8192,
    num_hidden_layers: int = _SMALLTHINKER["num_hidden_layers"],
    rope_layout: Sequence[int] = tuple(_SMALLTHINKER["rope_layout"]),
    sliding_window_layout: Sequence[int] = tuple(_SMALLTHINKER["sliding_window_layout"]),
    hidden_size: int = _SMALLTHINKER["hidden_size"],
    head_dim: int = _SMALLTHINKER["head_dim"],
    num_attention_heads: int = _SMALLTHINKER["num_attention_heads"],
    num_key_value_heads: int = _SMALLTHINKER["num_key_value_heads"],
    moe_ffn_hidden_size: int = _SMALLTHINKER["moe_ffn_hidden_size"],
    moe_num_primary_experts: int = _SMALLTHINKER["moe_num_primary_experts"],
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    moe_num_active_primary_experts: int = _SMALLTHINKER["moe_num_active_primary_experts"],
    sliding_window_size: int = _SMALLTHINKER["sliding_window_size"],
    rope_theta: float = _SMALLTHINKER["rope_theta"],
    rms_norm_eps: float = _SMALLTHINKER["rms_norm_eps"],
    optimizer: Union[str, OptimizerSpec] = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    compile_kwargs: Optional[Dict[str, Any]] = None,
    compute_dtype: str = "float32",
    precision: str = "",
    **kwargs,
) -> BackboneSpec:
    """``model_name: smallthinker_*`` (defaults: SmallThinker-21BA3B-
    Instruct). The first ``num_hidden_layers`` entries of
    ``sliding_window_layout`` (1: a query sees the ``sliding_window_size``
    rows up to itself; 0: every causal row) and of ``rope_layout`` (1:
    plain rotary at ``rope_theta``; 0: no position encoding) are the
    layers held; the two have to agree a layer, as published (a layer
    limited by distance rotates, a full one has no positions). Every
    layer routes: the router reads the layer's input before its
    attention, the ``moe_num_active_primary_experts`` largest logits are
    chosen and a softmax over those weighs the experts, which are gated
    by ``relu`` and of which this holder keeps ``experts_held`` (default:
    all) from ``expert_offset``."""
    for key in _SMALLTHINKER_FIXED:
        if key in kwargs and kwargs[key] != _SMALLTHINKER[key]:
            raise ValueError(f"smallthinker runs {key}={_SMALLTHINKER[key]!r} only; got {kwargs[key]!r}")
    if min(len(rope_layout), len(sliding_window_layout)) < num_hidden_layers:
        raise ValueError("smallthinker needs a rope_layout and a sliding_window_layout entry for every layer held")
    rotated = tuple(bool(flag) for flag in rope_layout[:num_hidden_layers])
    limited = tuple(bool(flag) for flag in sliding_window_layout[:num_hidden_layers])
    if rotated != limited:
        raise ValueError(
            "smallthinker gives a rotary embedding by operator: rope_layout has to equal "
            "sliding_window_layout in every layer held"
        )
    ropes = {
        "full_attention": {"rope_type": "none"},
        "sliding_attention": {"rope_type": "default", "rope_theta": float(rope_theta), "partial_rotary_factor": 1},
    }
    compile_kwargs = compile_kwargs or {}
    return BackboneSpec(
        n_features=n_features,
        n_features_out=n_features_out or n_features,
        lookback_window=lookback_window,
        layer_ops=tuple("sliding_attention" if flag else "full_attention" for flag in limited),
        layer_ffns=("moe",) * num_hidden_layers,
        hidden_size=hidden_size,
        attention_head_dim=head_dim,
        num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads,
        moe_intermediate_size=moe_ffn_hidden_size,
        num_experts=moe_num_primary_experts,
        experts_held=moe_num_primary_experts if experts_held is None else experts_held,
        expert_offset=expert_offset,
        num_experts_per_tok=moe_num_active_primary_experts,
        router="softmax_of_chosen",
        router_input="layer_input",
        expert_activation="relu",
        rope_theta=float(rope_theta),
        rope_parameters=tuple((op, tuple(sorted(rope.items()))) for op, rope in sorted(ropes.items())),
        qk_norm=False,
        sliding_window=sliding_window_size,
        norm_eps=float(rms_norm_eps),
        optimizer=OptimizerSpec.from_config(optimizer, optimizer_kwargs),
        loss=compile_kwargs.get("loss", "mse"),
        compute_dtype=compute_dtype,
        precision=precision,
    )


#: kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json (``model_type:
#: deepseek_v3``), every key of the catalog's row. The factory's defaults
#: are these; the keys it does not take say nothing it can act on (the
#: vocabulary is replaced by the sensor projections, positions are a
#: window's, ``num_key_value_heads`` and the top-level ``head_dim`` repeat
#: what the latent's own keys say: every head has a key and a value, and
#: 64 is the rotary part) or name what it refuses to be told otherwise.
KANANA_2_30B_A3B_CONFIG: Dict[str, Any] = {
    "attention_bias": False,
    "first_k_dense_replace": 1,
    "head_dim": 64,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 6144,
    "kv_lora_rank": 512,
    "max_position_embeddings": 32768,
    "model_type": "deepseek_v3",
    "moe_intermediate_size": 768,
    "moe_layer_freq": 1,
    "n_group": 1,
    "n_routed_experts": 128,
    "n_shared_experts": 2,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_experts_per_tok": 6,
    "num_hidden_layers": 48,
    "num_key_value_heads": 32,
    "q_lora_rank": None,
    "qk_head_dim": 192,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_interleave": True,
    "rope_scaling": None,
    "rope_theta": 1000000,
    "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid",
    "tie_word_embeddings": False,
    "topk_group": 1,
    "topk_method": "noaux_tc",
    "v_head_dim": 128,
    "vocab_size": 128256,
}
_KANANA = KANANA_2_30B_A3B_CONFIG
#: what the layers here cannot be told otherwise: no bias, silu gates,
#: routed experts in every layer after the leading dense ones, a sigmoid
#: router whose bias buffer chooses (``noaux_tc``) among all experts as
#: one group, the chosen scores renormalised, plain rotary, one
#: projection to the query heads (the family's larger members project
#: through a normed latent of ``q_lora_rank``: not built, so refused)
_KANANA_FIXED = (
    "attention_bias", "hidden_act", "moe_layer_freq", "n_group", "norm_topk_prob", "q_lora_rank",
    "rope_scaling", "scoring_func", "topk_group", "topk_method",
)


@register_model_builder(type="JaxBackboneForecast")
def kanana(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 8192,
    num_hidden_layers: int = _KANANA["num_hidden_layers"],
    first_k_dense_replace: int = _KANANA["first_k_dense_replace"],
    hidden_size: int = _KANANA["hidden_size"],
    num_attention_heads: int = _KANANA["num_attention_heads"],
    kv_lora_rank: int = _KANANA["kv_lora_rank"],
    qk_nope_head_dim: int = _KANANA["qk_nope_head_dim"],
    qk_rope_head_dim: int = _KANANA["qk_rope_head_dim"],
    v_head_dim: int = _KANANA["v_head_dim"],
    rope_interleave: bool = _KANANA["rope_interleave"],
    intermediate_size: int = _KANANA["intermediate_size"],
    moe_intermediate_size: int = _KANANA["moe_intermediate_size"],
    n_routed_experts: int = _KANANA["n_routed_experts"],
    n_shared_experts: int = _KANANA["n_shared_experts"],
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    num_experts_per_tok: int = _KANANA["num_experts_per_tok"],
    routed_scaling_factor: float = _KANANA["routed_scaling_factor"],
    rope_theta: float = _KANANA["rope_theta"],
    rms_norm_eps: float = _KANANA["rms_norm_eps"],
    optimizer: Union[str, OptimizerSpec] = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    compile_kwargs: Optional[Dict[str, Any]] = None,
    compute_dtype: str = "float32",
    precision: str = "",
    **kwargs,
) -> BackboneSpec:
    """``model_type: deepseek_v3`` (defaults: kanana-2-30b-a3b-instruct-
    2601). ``num_hidden_layers`` layers of latent attention (keys and
    values from a normed latent of ``kv_lora_rank``, expanded to
    ``qk_nope_head_dim`` of a head's key and ``v_head_dim`` of its
    value; one rotary key of ``qk_rope_head_dim`` shared by every head;
    rotary on the trailing ``qk_rope_head_dim`` of ``q`` and on that key
    alone, in interleaved pairs under ``rope_interleave``); the first
    ``first_k_dense_replace`` of them carry the dense feed-forward, the
    rest the routed experts under a sigmoid router scaled by
    ``routed_scaling_factor``, of which this holder keeps
    ``experts_held`` (default: all) from ``expert_offset``, beside
    ``n_shared_experts`` shared experts, built as ``deepseek_v3``
    builds them: one feed-forward of ``n_shared_experts`` times the
    experts' width."""
    for key in _KANANA_FIXED:
        if key in kwargs and kwargs[key] != _KANANA[key]:
            raise ValueError(f"kanana runs {key}={_KANANA[key]!r} only; got {kwargs[key]!r}")
    compile_kwargs = compile_kwargs or {}
    return BackboneSpec(
        n_features=n_features,
        n_features_out=n_features_out or n_features,
        lookback_window=lookback_window,
        layer_ops=("full_attention",) * num_hidden_layers,
        layer_ffns=tuple("dense" if i < first_k_dense_replace else "moe" for i in range(num_hidden_layers)),
        hidden_size=hidden_size,
        num_attention_heads=num_attention_heads,
        num_key_value_heads=num_attention_heads,
        kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim,
        v_head_dim=v_head_dim,
        rope_interleave=bool(rope_interleave),
        intermediate_size=intermediate_size,
        moe_intermediate_size=moe_intermediate_size,
        shared_expert_intermediate_size=n_shared_experts * moe_intermediate_size,
        num_experts=n_routed_experts,
        experts_held=n_routed_experts if experts_held is None else experts_held,
        expert_offset=expert_offset,
        num_experts_per_tok=num_experts_per_tok,
        routed_scaling_factor=float(routed_scaling_factor),
        rope_theta=float(rope_theta),
        qk_norm=False,
        norm_eps=float(rms_norm_eps),
        optimizer=OptimizerSpec.from_config(optimizer, optimizer_kwargs),
        loss=compile_kwargs.get("loss", "mse"),
        compute_dtype=compute_dtype,
        precision=precision,
    )


#: microsoft/Phi-4-mini-flash-reasoning config.json (``model_type:
#: phi4flash``; the SambaY decoder-hybrid-decoder of arXiv:2507.06607),
#: every key of the catalog's row. The factory's defaults are these; the
#: keys it does not take say nothing it can act on (the vocabulary and
#: its tied head are replaced by the sensor projections, positions are a
#: window's and the model encodes none, the two dropouts are 0) or name
#: what it refuses to be told otherwise.
PHI_4_MINI_FLASH_CONFIG: Dict[str, Any] = {
    "embd_pdrop": 0,
    "hidden_act": "silu",
    "hidden_size": 2560,
    "intermediate_size": 10240,
    "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144,
    "mb_per_layer": 2,
    "model_type": "phi4flash",
    "num_attention_heads": 40,
    "num_hidden_layers": 32,
    "num_key_value_heads": 20,
    "resid_pdrop": 0,
    "sliding_window": 512,
    "tie_word_embeddings": True,
    "mlp_bias": False,
    "lm_head_bias": False,
    "vocab_size": 200064,
}
_PHI4FLASH = PHI_4_MINI_FLASH_CONFIG
#: what the layers here cannot be told otherwise: silu gates, no bias in
#: a feed-forward or at the head, a state-space layer every second layer
_PHI4FLASH_FIXED = ("hidden_act", "mlp_bias", "lm_head_bias", "mb_per_layer")
#: what the row does not spell of a state-space layer, as the published
#: model's ``Phi3Mamba`` takes Mamba-1's defaults: the inner stream is
#: ``expand`` times the hidden size, a channel's state ``d_state``
#: numbers, the convolution ``d_conv`` taps, the step's rank the hidden
#: size over ``dt_rank_divisor``, rounded up. With them 32 layers and the
#: vocabulary count the published 3,852,562,944 weights
MAMBA_1_DEFAULTS = {"expand": 2, "d_state": 16, "d_conv": 4, "dt_rank_divisor": 16}


def phi4flash_layer_types(num_hidden_layers: int, mb_per_layer: int = 2) -> tuple:
    """The published pattern, by the model's own rule: the first half
    (the self-decoder) alternates ``mamba`` and ``sliding_attention``;
    the layer at the half is a ``mamba`` (its scan output is the memory)
    and the one after it the one ``full_attention`` (its keys and values
    are kept); the rest (the cross-decoder) alternates ``gmu`` and
    ``cross_attention``, which compute no scan, no key and no value."""
    half = num_hidden_layers // 2
    if num_hidden_layers % (2 * mb_per_layer) or num_hidden_layers < 2 * mb_per_layer:
        raise ValueError(
            f"phi4flash's rule needs num_hidden_layers divisible by {2 * mb_per_layer} "
            f"(the layer at the half is a mamba): got {num_hidden_layers}; a cut names its layer_types"
        )

    def kind(i):
        scans = i % mb_per_layer == 0
        if i <= half + 1:
            return "mamba" if scans else ("sliding_attention" if i < half else "full_attention")
        return "gmu" if scans else "cross_attention"

    return tuple(kind(i) for i in range(num_hidden_layers))


@register_model_builder(type="JaxBackboneForecast")
def phi4flash(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 8192,
    num_hidden_layers: int = _PHI4FLASH["num_hidden_layers"],
    layer_types: Optional[Sequence[str]] = None,
    hidden_size: int = _PHI4FLASH["hidden_size"],
    num_attention_heads: int = _PHI4FLASH["num_attention_heads"],
    num_key_value_heads: int = _PHI4FLASH["num_key_value_heads"],
    intermediate_size: int = _PHI4FLASH["intermediate_size"],
    sliding_window: int = _PHI4FLASH["sliding_window"],
    layer_norm_eps: float = _PHI4FLASH["layer_norm_eps"],
    optimizer: Union[str, OptimizerSpec] = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    compile_kwargs: Optional[Dict[str, Any]] = None,
    compute_dtype: str = "float32",
    precision: str = "",
    **kwargs,
) -> BackboneSpec:
    """``model_type: phi4flash`` (defaults: Phi-4-mini-flash-reasoning).
    ``num_hidden_layers`` layers in the published pattern
    (:func:`phi4flash_layer_types`), or a cut's own ``layer_types`` (a
    ``gmu`` after a ``mamba``, a ``cross_attention`` after a
    ``full_attention``): selective state-space layers at Mamba-1's
    sizes (:data:`MAMBA_1_DEFAULTS`), differential attention with a bias
    on its projections and no position encoding at all (a window of
    ``sliding_window`` rows, or every causal row), gated memory units
    and attention that read one earlier layer's scan output and one
    earlier layer's keys and values; LayerNorms with a bias and a dense
    feed-forward of ``intermediate_size`` in every layer."""
    for key in _PHI4FLASH_FIXED:
        if key in kwargs and kwargs[key] != _PHI4FLASH[key]:
            raise ValueError(f"phi4flash runs {key}={_PHI4FLASH[key]!r} only; got {kwargs[key]!r}")
    if layer_types is None:
        layer_types = phi4flash_layer_types(num_hidden_layers, _PHI4FLASH["mb_per_layer"])
    if len(layer_types) < num_hidden_layers:
        raise ValueError("phi4flash needs a layer type for every layer held")
    layer_types = tuple(layer_types[:num_hidden_layers])
    compile_kwargs = compile_kwargs or {}
    no_position = (("rope_type", "none"),)
    return BackboneSpec(
        n_features=n_features,
        n_features_out=n_features_out or n_features,
        lookback_window=lookback_window,
        layer_ops=layer_types,
        layer_ffns=("dense",) * num_hidden_layers,
        hidden_size=hidden_size,
        num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads,
        intermediate_size=intermediate_size,
        sliding_window=sliding_window,
        rope_parameters=tuple(
            (op, no_position) for op in ("cross_attention", "full_attention", "sliding_attention")
        ),
        qk_norm=False,
        norm="layer",
        attention_bias=True,
        differential=True,
        ssm_inner=MAMBA_1_DEFAULTS["expand"] * hidden_size,
        ssm_state=MAMBA_1_DEFAULTS["d_state"],
        ssm_conv=MAMBA_1_DEFAULTS["d_conv"],
        ssm_dt_rank=-(-hidden_size // MAMBA_1_DEFAULTS["dt_rank_divisor"]),
        norm_eps=float(layer_norm_eps),
        optimizer=OptimizerSpec.from_config(optimizer, optimizer_kwargs),
        loss=compile_kwargs.get("loss", "mse"),
        compute_dtype=compute_dtype,
        precision=precision,
    )
