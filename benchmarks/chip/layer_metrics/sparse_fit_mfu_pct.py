"""Useful forward-and-backward FLOPs of the traced job's training of a
sparse-attention backbone (``flops_sparse_backbone.job_useful_fit_flops``:
projections from shapes, the indexer over the causal pairs and the
attention over the selected pairs the program counted, the experts from
its pairs counter; masked-out pairs, padding, skipped steps and
rematerialised work are no useful work) over what the chip could do at
the bf16 peak in the device time the fit modules took (the same time as
``backbone_fit_step_ms``). None where the fit programs carry no
selection counters (a program without the operator) or the traced slice
holds no whole fit module."""

import flops_sparse_backbone
from harness.data import history_rows
from harness.evidence import fit_seconds_and_steps


def read(evidence):
    job = next(
        (j for j in evidence.get("jobs", []) if j["index"] == evidence.get("traced_job")),
        None,
    )
    if job is None or not flops_sparse_backbone.fit_counters(job.get("programs", [])):
        return None
    found = fit_seconds_and_steps(evidence)
    if found is None:
        return None
    useful = flops_sparse_backbone.job_useful_fit_flops(
        evidence["config"], history_rows(evidence["traffic"]["history_days"]), job["programs"]
    )
    peak = evidence["device"]["peaks"]["bf16_flops_per_s"] * evidence["cell"]["chips"]
    return 100.0 * useful / (found[0] * peak)
