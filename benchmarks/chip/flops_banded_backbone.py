"""
Operations of the banded-attention backbone configurations
(``model_type: laguna``) from the configuration file and the program's
own counters: the arithmetic behind ``banded_fit_mfu_pct``. Only what
the algorithm needs counts: the projections and the gate of every
trained token at each layer's own number of heads, attention over the
pairs inside the mask and no other (what a tile multiplies outside the
band or above the diagonal is no useful work), the router, the routed
experts' products of the pairs routed here, the shared expert and the
dense feed-forward of every trained token. A window of padding, an
all-padding step and what rematerialisation computes again count as
none. Shapes are read from the configuration file (published widths,
the layers and experts held), pairs from the counters on the fit
programs' ``device_program`` spans (``pairs_attended``, ``pairs_here``: a
list a layer, summed over the fit's steps, of the windows that trained).
"""

from typing import Any, Dict, List, Sequence

import flops_backbone
from flops_backbone import BACKWARD, head_flops_per_window, pair_flops, trained_windows


def held_layers(config: Dict[str, Any]) -> List[tuple]:
    """``(operator, feed-forward, query heads)`` of each layer held: the
    first ``num_hidden_layers`` of the published lists."""
    held = config["num_hidden_layers"]
    return list(zip(
        config["layer_types"][:held], config["mlp_layer_types"][:held],
        config["num_attention_heads_per_layer"][:held],
    ))


def projection_flops_per_token(config: Dict[str, Any], heads: int) -> float:
    """One token through the attention's matrices of a layer of
    ``heads`` query heads (forward): q and o at hidden x heads x
    head_dim, k and v at hidden x kv_heads x head_dim, the gate at
    hidden x heads."""
    h, head = config["hidden_size"], config["head_dim"]
    return 2.0 * h * (2 * heads * head + 2 * config["num_key_value_heads"] * head + heads)


def attention_flops_per_pair(config: Dict[str, Any], heads: int) -> float:
    """One (query, key) pair inside the mask (forward): its score and
    its share of the values, every query head of the layer."""
    return 4.0 * heads * config["head_dim"]


def feed_forward_flops_per_token(config: Dict[str, Any], ffn: str) -> float:
    """What every token takes of a layer's feed-forward (forward): the
    dense SwiGLU, or the router over every published expert and the
    shared expert (the routed experts are counted by pair)."""
    h = config["hidden_size"]
    if ffn == "dense":
        return 6.0 * h * config["intermediate_size"]
    return 2.0 * h * config["published"]["num_experts"] + 6.0 * h * config["shared_expert_intermediate_size"]


def fit_counters(programs: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The fit programs of a job that carry the band's counters beside
    the expert layer's; none for a program without."""
    return [
        p for p in flops_backbone.fit_counters(programs)
        if "pairs_attended" in p and "pairs_multiplied" in p
    ]


def job_useful_fit_flops(
    config: Dict[str, Any], history_rows: int, programs: Sequence[Dict[str, Any]]
) -> float:
    """Forward-and-backward FLOPs a job's training needs: tokens and
    windows from shapes (each fold's training windows and the final
    fit's, an epoch each), pairs from what the program counted of them
    (a row a layer: every layer held runs in tiles at this lookback)."""
    counted = fit_counters(programs)
    if not counted:
        raise KeyError("no fit program carries pairs_attended")
    layers = held_layers(config)
    if any(len(p["pairs_attended"]) != len(layers) for p in counted):
        raise ValueError("pairs_attended has not one row a layer held")
    windows = float(trained_windows(config, history_rows) * config["epochs"])
    tokens = windows * config["lookback_window"]
    per_token = 2.0 * config["tags"] * config["hidden_size"]
    attention = 0.0
    for i, (_, ffn, heads) in enumerate(layers):
        per_token += projection_flops_per_token(config, heads) + feed_forward_flops_per_token(config, ffn)
        attention += attention_flops_per_pair(config, heads) * sum(p["pairs_attended"][i] for p in counted)
    pairs_here = sum(sum(p["pairs_here"]) for p in counted)
    return BACKWARD * (
        per_token * tokens
        + attention
        + pair_flops(config) * pairs_here
        + head_flops_per_window(config) * windows
    )
