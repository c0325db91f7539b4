"""
Operations of the hybrid state-space and attention backbone
configurations (``model_type: phi4flash``: selective state-space layers,
differential attention in a window and in full, gated memory units and
attention that read an earlier layer's tensors; a dense feed-forward in
every layer, no routed one) from the configuration file and the
program's own counters: the arithmetic behind ``hybrid_fit_mfu_pct``.
Only what the algorithm needs counts: the products of every trained
token (the feed-forward's three; a state-space layer's ``W_in``,
``W_x``, ``W_dt``, ``W_out`` and its taps; a gated memory unit's ``W_g``
and ``W_o``; an attention's q, k, v and output projections, a cross
layer's q and output alone), attention over the pairs inside the mask
and no other at the two maps of a differential pair (2 x 20 heads x 2
maps x (a score 64 wide + a value 128 wide) = 15,360 a pair: what a tile
multiplies above the diagonal or outside the window is no useful work),
the scan at its elementwise count, the 50-tag ends. A window of padding,
an all-padding step and what rematerialisation or the scan's backward
computes again count as none. Shapes are read from the configuration
file (published widths, the layers held, the state-space sizes it lists
under ``assumed_sizes``), pairs and rows from the counters on the fit
programs' ``device_program`` spans (``pairs_attended`` a row an
attention layer computed in tiles, ``scan_steps`` a row a ``mamba``
layer, summed over the fit's steps, of the windows that trained).

The scan has no product in it. What is counted of one row of one layer,
forward, is its state update and its read-out as the equations write
them: a state entry (5,120 x 16 of them) takes ``dt * A``, ``exp``, the
decay times the state, the input times ``B``, their sum, the state
times ``C`` and its add into ``y``: 7 operations; a channel (5,120)
takes ``dt * c``, ``D * c`` and its add: 3. 588,800 a row a layer,
beside the 1.3 G of products a token takes through the same layer; a
share of the bf16 peak of products that they cannot reach says how
little of the step they are by count, not how fast they run.
"""

from typing import Any, Dict, List, Sequence

from flops_backbone import BACKWARD, head_flops_per_window, trained_windows

#: forward operations of the scan a state entry and a channel, a row
SCAN_OPS_PER_ENTRY = 7.0
SCAN_OPS_PER_CHANNEL = 3.0
ATTENTIONS = ("sliding_attention", "full_attention", "cross_attention")


def held_layers(config: Dict[str, Any]) -> List[str]:
    return list(config["layer_types"][: config["num_hidden_layers"]])


def mixer_flops_per_token(config: Dict[str, Any], op: str) -> float:
    """One token through one mixer's matrices (forward)."""
    h, sizes = config["hidden_size"], config["assumed_sizes"]
    d, n, rank = sizes["ssm_inner"], sizes["ssm_state"], sizes["ssm_dt_rank"]
    if op == "mamba":  # W_in to the stream and its gate, the taps, W_x, W_dt, W_out
        return 2.0 * (h * 2 * d + sizes["ssm_conv"] * d + d * (rank + 2 * n) + rank * d + d * h)
    if op == "gmu":  # W_g and W_o
        return 2.0 * (h * d + d * h)
    qo = config["num_attention_heads"] * sizes["head_dim"]
    kv = config["num_key_value_heads"] * sizes["head_dim"]
    if op == "cross_attention":  # no key and no value of its own
        return 2.0 * (h * qo + qo * h)
    return 2.0 * (h * qo + 2 * h * kv + qo * h)


def attention_flops_per_pair(config: Dict[str, Any]) -> float:
    """One (query, key) pair inside the mask (forward): two maps, each a
    score a head wide and its share of a value twice a head wide, every
    differential head (half the query heads)."""
    width = config["assumed_sizes"]["head_dim"]
    return 2.0 * (config["num_attention_heads"] // 2) * 2 * (width + 2 * width)


def scan_flops_per_row(config: Dict[str, Any]) -> float:
    """One row of one layer's scan (forward), elementwise (module docstring)."""
    sizes = config["assumed_sizes"]
    d, n = sizes["ssm_inner"], sizes["ssm_state"]
    return SCAN_OPS_PER_ENTRY * d * n + SCAN_OPS_PER_CHANNEL * d


def fit_counters(programs: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The fit programs of a job that carry the scan's counter beside
    the band's; none for a program without."""
    return [
        p for p in programs
        if "fit" in p.get("program", "") and "scan_steps" in p and "pairs_attended" in p
    ]


def job_useful_fit_flops(
    config: Dict[str, Any], history_rows: int, programs: Sequence[Dict[str, Any]]
) -> float:
    """Forward-and-backward FLOPs a job's training needs: tokens and
    windows from shapes (each fold's training windows and the final
    fit's, an epoch each), pairs and scanned rows from what the program
    counted of them."""
    counted = fit_counters(programs)
    if not counted:
        raise KeyError("no fit program carries scan_steps")
    layers = held_layers(config)
    attentions = sum(op in ATTENTIONS for op in layers)
    scans = layers.count("mamba")
    if any(len(p["pairs_attended"]) != attentions or len(p["scan_steps"]) != scans for p in counted):
        raise ValueError("pairs_attended has not a row an attention layer, or scan_steps not a row a mamba layer")
    windows = float(trained_windows(config, history_rows) * config["epochs"])
    tokens = windows * config["lookback_window"]
    h = config["hidden_size"]
    per_token = 2.0 * config["tags"] * h + sum(
        mixer_flops_per_token(config, op) + 6.0 * h * config["intermediate_size"] for op in layers
    )
    attended = sum(sum(p["pairs_attended"]) for p in counted)
    scanned = sum(sum(p["scan_steps"]) for p in counted)
    return BACKWARD * (
        per_token * tokens
        + attention_flops_per_pair(config) * attended
        + scan_flops_per_row(config) * scanned
        + head_flops_per_window(config) * windows
    )
