"""How unevenly the router loads the experts held here: in each expert
layer, the busiest held expert's tokens over the mean of the held, less
one, in percent; the mean over the expert layers. From the
``router_tokens`` counter on the fit programs' ``device_program`` spans
(tokens routed to each published expert, summed over a fit's steps),
summed over the window's jobs. 0 is even; the grouped products' time
follows the sum of the pairs, their tiling the largest group. None where
no fit program carries the counter."""

import flops_backbone


def read(evidence):
    counted = [
        p for job in evidence.get("jobs", [])
        for p in flops_backbone.fit_counters(job.get("programs", []))
        if "router_tokens" in p
    ]
    if not counted:
        return None
    offset, held = counted[0]["expert_offset"], counted[0]["experts_held"]
    layers = len(counted[0]["router_tokens"])
    shares = []
    for layer in range(layers):
        tokens = [
            sum(p["router_tokens"][layer][expert] for p in counted)
            for expert in range(offset, offset + held)
        ]
        mean = sum(tokens) / float(held)
        if mean > 0:
            shares.append(100.0 * (max(tokens) / mean - 1.0))
    return sum(shares) / len(shares) if shares else None
