"""
Operations of the pre-routed backbone configurations (``model_name:
smallthinker_*``: every layer attention and routed experts, the router
reading the layer's input) from the configuration file and the program's
own counters: the arithmetic behind ``prerouted_fit_mfu_pct``. Only what
the algorithm needs counts: the projections and the router of every
trained token, attention over the pairs inside the mask and no other
(what a tile multiplies outside the band or above the diagonal is no
useful work), the experts' products of the pairs routed here, the
50-tag ends. A window of padding, an all-padding step and what
rematerialisation computes again count as none. Shapes are read from the
configuration file (published widths, the layers and experts held),
pairs from the counters on the fit programs' ``device_program`` spans
(``pairs_attended``, ``pairs_here``: a list a layer, summed over the
fit's steps, of the windows that trained).
"""

from typing import Any, Dict, Sequence

import flops_banded_backbone
from flops_backbone import BACKWARD, head_flops_per_window, trained_windows

#: the band's counters beside the expert layer's, as the banded
#: configurations' programs carry them
fit_counters = flops_banded_backbone.fit_counters


def projection_flops_per_token(config: Dict[str, Any]) -> float:
    """One token through a layer's matrices outside its experts
    (forward): q and o at hidden x heads x head_dim, k and v at hidden x
    kv_heads x head_dim, the router over every published expert."""
    h, head = config["hidden_size"], config["head_dim"]
    attention = 2 * config["num_attention_heads"] * head + 2 * config["num_key_value_heads"] * head
    return 2.0 * h * (attention + config["published"]["moe_num_primary_experts"])


def attention_flops_per_pair(config: Dict[str, Any]) -> float:
    """One (query, key) pair inside the mask (forward): its score and
    its share of the values, every query head."""
    return 4.0 * config["num_attention_heads"] * config["head_dim"]


def pair_flops(config: Dict[str, Any]) -> float:
    """The three products of one (token, expert) pair (forward)."""
    return 6.0 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def job_useful_fit_flops(
    config: Dict[str, Any], history_rows: int, programs: Sequence[Dict[str, Any]]
) -> float:
    """Forward-and-backward FLOPs a job's training needs: tokens and
    windows from shapes (each fold's training windows and the final
    fit's, an epoch each), pairs from what the program counted of them
    (a row a layer: every layer held runs in tiles at this lookback and
    routes)."""
    counted = fit_counters(programs)
    if not counted:
        raise KeyError("no fit program carries pairs_attended")
    layers = config["num_hidden_layers"]
    if any(len(p["pairs_attended"]) != layers or len(p["pairs_here"]) != layers for p in counted):
        raise ValueError("pairs_attended and pairs_here have not one row a layer held")
    windows = float(trained_windows(config, history_rows) * config["epochs"])
    tokens = windows * config["lookback_window"]
    per_token = 2.0 * config["tags"] * config["hidden_size"] + layers * projection_flops_per_token(config)
    attended = sum(sum(p["pairs_attended"]) for p in counted)
    pairs_here = sum(sum(p["pairs_here"]) for p in counted)
    return BACKWARD * (
        per_token * tokens
        + attention_flops_per_pair(config) * attended
        + pair_flops(config) * pairs_here
        + head_flops_per_window(config) * windows
    )
