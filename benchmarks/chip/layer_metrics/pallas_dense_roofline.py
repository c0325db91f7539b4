"""The least time the chip could take for the rows scored inside the
traced slice (``flops.kernel_least_seconds``: the larger of FLOPs over
the bf16 peak and bytes over the HBM peak, from the rows each answered
request asked for; padding rows are no useful work) over the device
time of the serving kernel's custom calls in the trace
(``harness.evidence.kernel_seconds``)."""

import flops
from harness.evidence import kernel_seconds


def read(evidence):
    seconds = kernel_seconds(evidence)
    trace = evidence.get("trace") or {}
    if seconds is None or trace.get("started_wall") is None:
        return None
    start, stop = trace["started_wall"], trace["stopped_wall"]
    rows = sum(
        r["rows"]
        for r in evidence["requests"]
        if r["status"] == 200 and start <= r["sent"] and r["sent"] + r["seconds"] <= stop
    )
    if not rows:
        return None
    least = flops.kernel_least_seconds(evidence["config"], rows, evidence["device"]["peaks"])
    return 100.0 * least["seconds"] / seconds
