"""
Operations of the latent-attention backbone configurations
(``model_type: deepseek_v3``: latent attention in every layer, leading
dense layers, then routed experts beside shared ones) from the
configuration file and the program's own counters: the arithmetic behind
``latent_fit_mfu_pct``. Only what the algorithm needs counts: the
projections of every trained token (to the query heads, into the latent
and the shared rotary key, out of the latent to every head's key part
and value, the output), attention over the pairs inside the mask and no
other in the expanded form that training computes (a score 192 wide and
a value 128 wide a head: what a tile multiplies above the diagonal is no
useful work), the router over every published expert, the shared expert
and the dense feed-forward of every trained token, the routed experts'
products of the pairs routed here, the 50-tag ends. A window of padding,
an all-padding step and what rematerialisation computes again count as
none. Shapes are read from the configuration file (published widths, the
layers and experts held), pairs from the counters on the fit programs'
``device_program`` spans (``pairs_attended`` a list a layer,
``pairs_here`` a list a routed layer, summed over the fit's steps, of
the windows that trained).
"""

from typing import Any, Dict, Sequence

import flops_banded_backbone
from flops_backbone import BACKWARD, head_flops_per_window, pair_flops, trained_windows

#: the band's counters beside the expert layer's, as the banded
#: configurations' programs carry them: a latent layer is an attention in tiles
fit_counters = flops_banded_backbone.fit_counters


def projection_flops_per_token(config: Dict[str, Any]) -> float:
    """One token through a latent attention's matrices (forward):
    ``W_q`` at hidden x heads x (nope + rope), ``W_kva`` at hidden x
    (latent + rope), ``W_kvb`` at latent x heads x (nope + value),
    ``W_o`` at heads x value x hidden."""
    h, heads, rank = config["hidden_size"], config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope, value = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    return 2.0 * (
        h * heads * (nope + rope) + h * (rank + rope) + rank * heads * (nope + value) + heads * value * h
    )


def attention_flops_per_pair(config: Dict[str, Any]) -> float:
    """One (query, key) pair inside the mask (forward), every head: a
    score over nope + rope dimensions and its share of a value."""
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    return 2.0 * config["num_attention_heads"] * width


def feed_forward_flops_per_token(config: Dict[str, Any], dense: bool) -> float:
    """What every token takes of a layer's feed-forward (forward): the
    dense one, or the router over every published expert and the shared
    experts as one feed-forward (the routed experts are counted by
    pair)."""
    h = config["hidden_size"]
    if dense:
        return 6.0 * h * config["intermediate_size"]
    shared = config["n_shared_experts"] * config["moe_intermediate_size"]
    return 2.0 * h * config["published"]["n_routed_experts"] + 6.0 * h * shared


def job_useful_fit_flops(
    config: Dict[str, Any], history_rows: int, programs: Sequence[Dict[str, Any]]
) -> float:
    """Forward-and-backward FLOPs a job's training needs: tokens and
    windows from shapes (each fold's training windows and the final
    fit's, an epoch each), pairs from what the program counted of them
    (``pairs_attended`` a row a layer held: every layer runs in tiles at
    this lookback; ``pairs_here`` a row a routed layer)."""
    counted = fit_counters(programs)
    if not counted:
        raise KeyError("no fit program carries pairs_attended")
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    if any(len(p["pairs_attended"]) != layers or len(p["pairs_here"]) != layers - dense for p in counted):
        raise ValueError("pairs_attended has not a row a layer held, or pairs_here not a row a routed layer")
    windows = float(trained_windows(config, history_rows) * config["epochs"])
    tokens = windows * config["lookback_window"]
    per_token = (
        2.0 * config["tags"] * config["hidden_size"]
        + layers * projection_flops_per_token(config)
        + dense * feed_forward_flops_per_token(config, True)
        + (layers - dense) * feed_forward_flops_per_token(config, False)
    )
    attended = sum(sum(p["pairs_attended"]) for p in counted)
    pairs_here = sum(sum(p["pairs_here"]) for p in counted)
    return BACKWARD * (
        per_token * tokens
        + attention_flops_per_pair(config) * attended
        + pair_flops(config) * pairs_here
        + head_flops_per_window(config) * windows
    )
