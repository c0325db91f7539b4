"""Median over the window's answered requests of model_resolve +
data_decode + response_assemble + serialize, from each response's
``Server-Timing``."""

from harness.evidence import stage_p50


def read(evidence):
    return stage_p50(
        evidence, ("model_resolve", "data_decode", "response_assemble", "serialize")
    )
