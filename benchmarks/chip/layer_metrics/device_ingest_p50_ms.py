"""Median ``device_ingest`` stage of the window's answered requests,
from ``Server-Timing``."""

from harness.evidence import stage_p50


def read(evidence):
    return stage_p50(evidence, ("device_ingest",))
