"""
Operations of the backbone configurations (``model_type: lfm2_moe``)
from shapes and from the program's own pairs counter: the arithmetic
behind ``backbone_fit_mfu_pct``. Only what the algorithm needs counts:
a window of padding, an all-padding step, and what rematerialisation
computes a second time are no useful work. Everything is read from the
configuration file (published widths, the layers and experts held) and
from the counters on the fit programs' ``device_program`` spans.
"""

from typing import Any, Dict, List, Sequence

import flops

BACKWARD = 3.0  # forward and backward: the backward is twice the forward


def operator_flops_per_token(config: Dict[str, Any], op: str) -> float:
    """Products of one token through one operator (forward)."""
    h = config["hidden_size"]
    if op == "conv":
        # in_proj to 3h, the depthwise taps, out_proj
        return 2.0 * h * 3 * h + 2.0 * config["conv_L_cache"] * h + 2.0 * h * h
    head = h // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * head
    # q, o at h x h; k, v at h x kv; causal scores and values at their
    # useful half: a token attends to (T + 1) / 2 positions on average
    attended = (config["lookback_window"] + 1) / 2.0
    return 2.0 * h * (2 * h + 2 * kv) + 2.0 * 2 * attended * h


def dense_flops_per_token(config: Dict[str, Any]) -> float:
    """Everything but the routed experts' products: a token through the
    sensor projection, every operator, the dense feed-forwards and the
    routers (forward)."""
    h = config["hidden_size"]
    total = 2.0 * config["tags"] * h
    for op, ffn in zip(config["layer_types_held"], config["layer_ffns_held"]):
        total += operator_flops_per_token(config, op)
        if ffn == "dense":
            total += 6.0 * h * config["intermediate_size"]
        else:
            total += 2.0 * h * config["published"]["num_experts"]
    return total


def pair_flops(config: Dict[str, Any]) -> float:
    """The three products of one (token, expert) pair (forward)."""
    return 6.0 * config["hidden_size"] * config["moe_intermediate_size"]


def head_flops_per_window(config: Dict[str, Any]) -> float:
    return 2.0 * config["hidden_size"] * config["tags"]


def trained_windows(config: Dict[str, Any], history_rows: int) -> int:
    """Windows a job's training needs, a pass: each ``TimeSeriesSplit``
    fold's training windows and the whole history's for the final fit."""
    windows = history_rows - config["lookback_window"] - config.get("lookahead", 0) + 1
    return sum(flops.fold_train_rows(windows, config["cv_folds"])) + windows


def fit_counters(programs: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The fit programs of a job that carry the expert layer's counters
    (``pairs_here``, ``pairs_total``, ``router_tokens``: a list an expert
    layer, summed over the fit's steps); none for a program without."""
    return [
        p for p in programs
        if "fit" in p.get("program", "") and "pairs_here" in p and "pairs_total" in p
    ]


def job_useful_fit_flops(
    config: Dict[str, Any], history_rows: int, programs: Sequence[Dict[str, Any]]
) -> float:
    """Forward-and-backward FLOPs a job's training needs. The dense part
    is from shapes; the experts' part is the pairs the program counted
    here, scaled by the share of the tokens it counted that were no
    padding: 1 for a program that routes and counts the windows trained
    alone, less for one that also routes its slots of padding (they
    repeat a real window, so they route as the rest do)."""
    counted = fit_counters(programs)
    if not counted:
        raise KeyError("no fit program carries pairs_here")
    useful_windows = trained_windows(config, history_rows) * config["epochs"]
    useful_tokens = float(useful_windows) * config["lookback_window"]
    top_k = config["num_experts_per_tok"]
    # pairs_total is tokens x k a layer: the tokens the programs routed
    ran_tokens = sum(p["pairs_total"][0] for p in counted) / top_k
    pairs_here = sum(sum(p["pairs_here"]) for p in counted)
    experts = pair_flops(config) * pairs_here * (useful_tokens / ran_tokens)
    dense = dense_flops_per_token(config) * useful_tokens
    head = head_flops_per_window(config) * useful_windows
    return BACKWARD * (dense + experts + head)
