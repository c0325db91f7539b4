"""The share of the (token, expert) pairs that this chip computed: pairs
whose expert is held here over tokens x experts per token, in percent,
over every expert layer and fit program of the window's jobs
(``pairs_here`` / ``pairs_total`` on the ``device_program`` spans). The
deployment's share is experts held / experts published (25% at 8 of
32): above it this chip does more than its share of the layer, below it
less. None where no fit program carries the counters."""

import flops_backbone


def read(evidence):
    counted = [
        p for job in evidence.get("jobs", [])
        for p in flops_backbone.fit_counters(job.get("programs", []))
    ]
    total = sum(sum(p["pairs_total"]) for p in counted)
    if not total:
        return None
    return 100.0 * sum(sum(p["pairs_here"]) for p in counted) / total
