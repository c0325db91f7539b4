"""Tiny sizes of the cells, and a stand-in for the chip: the same code
the chip runs, as functions of sizes, on the CPU."""

import logging

import common
from harness import manifest

#: what ``common.start`` returns on a chip, for a process on the CPU
CPU_DEVICE = {
    "platform": "cpu",
    "kind": "cpu",
    "count": 1,
    "compile_cache": None,
    "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
}

TINY_LSTM = {
    "estimator": {
        "gordo_tpu.models.JaxLSTMAutoEncoder": {
            "kind": "lstm_symmetric", "lookback_window": 6, "dims": [8, 4],
            "funcs": ["tanh", "tanh"], "epochs": 2, "batch_size": 32,
        }
    },
    "lookback_window": 6,
    "layer_dims": [8, 4, 4, 8],
    "epochs": 2,
    "tags": 5,
}


def cell(name: str) -> manifest.Cell:
    """A cell of the manifest, or of the manifest grown by its pending
    entries (``benchmarks/chip/pending/<name>.json``)."""
    document = manifest.load_manifest()
    if name not in {w["name"] for w in document["workloads"]}:
        document = manifest.with_pending(document, name)
    return manifest.Cell(document, name)


def tiny_config(config: dict) -> dict:
    """The LSTM at toy widths (the CPU cannot train 1.1 M weights a
    member in a test); the hourglass as it is, it is tiny by nature."""
    return dict(config, **TINY_LSTM) if "lookback_window" in config else dict(config)


def build_spec(name: str, run_dir: str, trace: bool = False, **traffic) -> dict:
    c = cell(name)
    sizes = {"machines_per_job": 2, "history_days": 2, "verify_rows": 48,
             "verify_machines": 2, "trace_max_seconds": 20}
    return {
        "cell": name, "chips": 1, "config": tiny_config(c.config),
        "traffic": dict(c.traffic, **{**sizes, **traffic}),
        "seed": 3, "seconds": 1.5, "trace": trace, "run_dir": run_dir,
    }


def quiet_start():
    """Counter and error log as ``common.start`` installs them."""
    errors = common.ErrorLog()
    logging.getLogger().addHandler(errors)
    return common.CompileCounter().install(), errors
