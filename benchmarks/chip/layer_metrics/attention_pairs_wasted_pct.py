"""The share of the (query, key) pairs the attention's tiles multiplied
that lie outside the mask: 1 - ``pairs_attended`` / ``pairs_multiplied``,
in percent, over every layer computed in tiles and every fit program of
the window's jobs (the counters on the ``device_program`` spans; they
count the windows trained). By arithmetic at tiles of 512 rows and a
window of 8,192: 50% in a layer limited to 512 rows (a block visits its
diagonal tile and the one before it, half of each inside the band),
5.9% in a full layer (the diagonal tiles' upper halves); it falls when
the tiles at the band's edges are trimmed. None where no fit program
carries the counters."""

import flops_banded_backbone


def read(evidence):
    counted = [
        p for job in evidence.get("jobs", [])
        for p in flops_banded_backbone.fit_counters(job.get("programs", []))
    ]
    multiplied = sum(sum(p["pairs_multiplied"]) for p in counted)
    if not multiplied:
        return None
    return 100.0 * (1.0 - sum(sum(p["pairs_attended"]) for p in counted) / multiplied)
