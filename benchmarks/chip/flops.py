"""
Operations, bytes and optimizer steps from shapes: the arithmetic behind
``fit_step_ms``, ``fit_mfu_pct`` and ``pallas_dense_roofline``. Only what
the algorithm needs counts: padding rows, dummy members and the zeros of
a packed layout are no useful work. Copied in idea from ``bench.py``'s
``_useful_flops`` (listed in PERF.md, open questions, for deletion).
"""

from typing import Any, Dict, List, Sequence


def dense_forward_flops(tags: int, dims: Sequence[int]) -> int:
    """Multiply-adds x 2 of one row through ``tags -> dims... -> tags``."""
    widths = [tags, *dims, tags]
    return 2 * sum(a * b for a, b in zip(widths, widths[1:]))


def lstm_forward_flops(tags: int, dims: Sequence[int], lookback: int) -> int:
    """One window through the stacked LSTM (four gates a layer, input and
    recurrent product at each of ``lookback`` timesteps) and the dense
    head on the last hidden state."""
    total, width = 0, tags
    for units in dims:
        total += lookback * 2 * 4 * units * (width + units)
        width = units
    return total + 2 * width * tags


def forward_flops(config: Dict[str, Any]) -> int:
    """Forward FLOPs of one sample (a row, or a window) of ``config``."""
    if "lookback_window" in config:
        return lstm_forward_flops(
            config["tags"], config["layer_dims"], config["lookback_window"]
        )
    return dense_forward_flops(config["tags"], config["layer_dims"])


def fit_steps(padded_samples: int, batch_size: int, epochs: int) -> int:
    """Optimizer steps one fit program runs: it scans every batch of the
    padded sample axis in every epoch (``models/training.py``: steps =
    n_total // batch_size; an all-padding batch is a no-op but a step)."""
    return epochs * (padded_samples // batch_size)


def fold_train_rows(samples: int, folds: int) -> List[int]:
    """Training rows of each ``TimeSeriesSplit(folds)`` fold of
    ``samples`` samples, as scikit-learn cuts them."""
    test = samples // (folds + 1)
    return [samples - test * (folds - i) for i in range(folds)]


def job_useful_fit_flops(
    config: Dict[str, Any], machines: int, history_rows: int
) -> float:
    """Forward-and-backward FLOPs a job's training needs: each machine
    fits ``cv_folds`` folds and the whole history, ``epochs`` passes
    each, backward counted as twice forward."""
    samples = history_rows - (config.get("lookback_window", 1) - 1)
    trained = sum(fold_train_rows(samples, config["cv_folds"])) + samples
    return 3.0 * forward_flops(config) * trained * config["epochs"] * machines


def kernel_least_seconds(
    config: Dict[str, Any], rows: int, peaks: Dict[str, float]
) -> Dict[str, Any]:
    """The least time one chip could take to score ``rows`` rows through
    one member's dense stack: the larger of FLOPs over the bf16 peak
    (every program on the chip multiplies bf16-rounded operands) and
    bytes over the HBM peak (rows in and out in float32, the member's
    weights once). Says which bounds."""
    flops = float(rows) * dense_forward_flops(config["tags"], config["layer_dims"])
    nbytes = 4.0 * (2 * rows * config["tags"] + config["weights_per_member"])
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(compute, memory),
        "bound": "compute" if compute >= memory else "memory",
        "flops": flops,
        "bytes": nbytes,
    }
