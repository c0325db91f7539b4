"""Useful forward-and-backward FLOPs of the traced job's training of a
latent-attention backbone (``flops_latent_backbone.job_useful_fit_flops``:
the latent attention's four projections, the router, the shared expert
and the dense layer from shapes, attention over the pairs inside the
mask that the program counted at 2 x heads x (192 + 128) a pair, the
experts held from its pairs counter, the 50-tag ends; pairs a tile
multiplies above the diagonal, padding, skipped steps and rematerialised
work are no useful work) over what the chip could do at the bf16 peak in
the device time the fit modules took (the same time as
``backbone_fit_step_ms``): the share of the whole step's roofline. None
where the fit programs carry no band counters (a program without the
operator), where the configuration is not of this family, or where the
traced slice holds no whole fit module."""

import flops_latent_backbone
from harness.data import history_rows
from harness.evidence import fit_seconds_and_steps


def read(evidence):
    job = next(
        (j for j in evidence.get("jobs", []) if j["index"] == evidence.get("traced_job")),
        None,
    )
    if job is None or not flops_latent_backbone.fit_counters(job.get("programs", [])):
        return None
    if "kv_lora_rank" not in evidence["config"]:
        return None
    found = fit_seconds_and_steps(evidence)
    if found is None:
        return None
    useful = flops_latent_backbone.job_useful_fit_flops(
        evidence["config"], history_rows(evidence["traffic"]["history_days"]), job["programs"]
    )
    peak = evidence["device"]["peaks"]["bf16_flops_per_s"] * evidence["cell"]["chips"]
    return 100.0 * useful / (found[0] * peak)
