"""Useful forward-and-backward FLOPs of the traced job's training (from
shapes, ``flops.job_useful_fit_flops``: padding rows, dummy members and
all-padding steps are no useful work) over what the chips could do at
the bf16 peak in the device time the fit modules took (the same time as
``fit_step_ms``)."""

import flops
from harness.data import history_rows
from harness.evidence import fit_seconds_and_steps


def read(evidence):
    found = fit_seconds_and_steps(evidence)
    if found is None:
        return None
    traffic = evidence["traffic"]
    useful = flops.job_useful_fit_flops(
        evidence["config"], traffic["machines_per_job"], history_rows(traffic["history_days"])
    )
    peak = evidence["device"]["peaks"]["bf16_flops_per_s"] * evidence["cell"]["chips"]
    return 100.0 * useful / (found[0] * peak)
