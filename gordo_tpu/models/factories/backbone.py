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
