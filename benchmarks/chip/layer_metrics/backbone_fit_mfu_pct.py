"""Useful forward-and-backward FLOPs of the traced job's training of a
backbone (``flops_backbone.job_useful_fit_flops``: products from shapes,
the experts' from the program's pairs counter; padding, all-padding
steps and rematerialised work are no useful work) over what the chips
could do at the bf16 peak in the device time the fit modules took (the
same time as ``fit_step_ms``). None where the fit programs carry no
pairs counter (a program without the expert layer) or the traced slice
holds no whole fit module."""

import flops_backbone
from harness.data import history_rows
from harness.evidence import fit_seconds_and_steps


def read(evidence):
    job = next(
        (j for j in evidence.get("jobs", []) if j["index"] == evidence.get("traced_job")),
        None,
    )
    if job is None or not flops_backbone.fit_counters(job.get("programs", [])):
        return None
    found = fit_seconds_and_steps(evidence)
    if found is None:
        return None
    useful = flops_backbone.job_useful_fit_flops(
        evidence["config"], history_rows(evidence["traffic"]["history_days"]), job["programs"]
    )
    peak = evidence["device"]["peaks"]["bf16_flops_per_s"] * evidence["cell"]["chips"]
    return 100.0 * useful / (found[0] * peak)
