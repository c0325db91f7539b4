"""
Operations of the sparse-attention backbone configurations
(``model_type: KeyeVL2``) from the configuration file and the program's
own counters: the arithmetic behind ``sparse_fit_mfu_pct``. Only what
the algorithm needs counts: the projections of every trained token, the
indexer's scores over every causal pair (it has to see a key to leave it
out), attention over the pairs the selection kept and no other (a masked
formulation computes the rest too: that is no useful work), the router,
the experts' products of the pairs routed here. A window of padding, an
all-padding step and what rematerialisation computes again count as
none. Shapes are read from the configuration file (published widths,
the layers and experts held), pairs from the counters on the fit
programs' ``device_program`` spans (``keys_selected``, ``keys_causal``,
``pairs_here``: a list a layer, summed over the fit's steps, of the
windows that trained).
"""

from typing import Any, Dict, List, Sequence

import flops_backbone
from flops_backbone import BACKWARD, head_flops_per_window, pair_flops, trained_windows


def projection_flops_per_token(config: Dict[str, Any]) -> float:
    """One token through one layer's matrices (forward): q and o at
    hidden x heads x head_dim, k and v at hidden x kv_heads x head_dim,
    the indexer's three projections, the router over every published
    expert."""
    h, head = config["hidden_size"], config["head_dim"]
    qo, kv = config["num_attention_heads"] * head, config["num_key_value_heads"] * head
    sparse = config["sa_config"]
    index = sparse["indexer_num_heads"] * sparse["indexer_head_dim"]
    indexer = index + sparse["indexer_head_dim"] * sparse["indexer_num_kv_heads"] + sparse["indexer_num_heads"]
    return 2.0 * h * (2 * qo + 2 * kv + indexer + config["published"]["num_experts"])


def index_flops_per_pair(config: Dict[str, Any]) -> float:
    """One causal (query, key) pair through the indexer (forward): a dot
    product of 64 a head, and the head-weighted sum of their relus."""
    sparse = config["sa_config"]
    return 2.0 * sparse["indexer_num_heads"] * sparse["indexer_head_dim"] + 2.0 * sparse["indexer_num_heads"]


def attention_flops_per_pair(config: Dict[str, Any]) -> float:
    """One selected (query, key) pair through the attention (forward):
    its score and its share of the values, every query head."""
    return 4.0 * config["num_attention_heads"] * config["head_dim"]


def fit_counters(programs: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The fit programs of a job that carry the selection's counters
    beside the expert layer's; none for a program without."""
    return [
        p for p in flops_backbone.fit_counters(programs)
        if "keys_selected" in p and "keys_causal" in p
    ]


def job_useful_fit_flops(
    config: Dict[str, Any], history_rows: int, programs: Sequence[Dict[str, Any]]
) -> float:
    """Forward-and-backward FLOPs a job's training needs: tokens and
    windows from shapes (each fold's training windows and the final
    fit's, an epoch each), pairs from what the program counted of them."""
    counted = fit_counters(programs)
    if not counted:
        raise KeyError("no fit program carries keys_selected")
    layers = config["num_hidden_layers"]
    windows = float(trained_windows(config, history_rows) * config["epochs"])
    tokens = windows * config["lookback_window"]
    causal = sum(sum(p["keys_causal"]) for p in counted)
    selected = sum(sum(p["keys_selected"]) for p in counted)
    pairs_here = sum(sum(p["pairs_here"]) for p in counted)
    dense = (2.0 * config["tags"] * config["hidden_size"] + layers * projection_flops_per_token(config)) * tokens
    return BACKWARD * (
        dense
        + index_flops_per_pair(config) * causal
        + attention_flops_per_pair(config) * selected
        + pair_flops(config) * pairs_here
        + head_flops_per_window(config) * windows
    )
