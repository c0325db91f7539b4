"""Useful forward-and-backward FLOPs of the traced job's training of a
hybrid state-space and attention backbone
(``flops_hybrid_backbone.job_useful_fit_flops``: every layer's products
from shapes, attention over the pairs inside the mask that the program
counted at 15,360 a pair of a differential head's two maps, the scans
at their elementwise count from the rows the program counted, the
50-tag ends; pairs a tile multiplies above the diagonal, padding,
skipped steps, rematerialised work and the states the scan's backward
computes again are no useful work) over what the chip could do at the
bf16 peak in the device time the fit modules took (the same time as
``backbone_fit_step_ms``): the share of the whole step's roofline. None
where the fit programs carry no ``scan_steps`` (a program without the
operator: every commit before PR 45), where the configuration is not of
this family, or where the traced slice holds no whole fit module."""

import flops_hybrid_backbone
from harness.data import history_rows
from harness.evidence import fit_seconds_and_steps


def read(evidence):
    job = next(
        (j for j in evidence.get("jobs", []) if j["index"] == evidence.get("traced_job")),
        None,
    )
    if job is None or not flops_hybrid_backbone.fit_counters(job.get("programs", [])):
        return None
    if "assumed_sizes" not in evidence["config"]:
        return None
    found = fit_seconds_and_steps(evidence)
    if found is None:
        return None
    useful = flops_hybrid_backbone.job_useful_fit_flops(
        evidence["config"], history_rows(evidence["traffic"]["history_days"]), job["programs"]
    )
    peak = evidence["device"]["peaks"]["bf16_flops_per_s"] * evidence["cell"]["chips"]
    return 100.0 * useful / (found[0] * peak)
