"""dump / job seconds, from ``build_status.json`` phases; median over
the window's jobs."""

from harness.evidence import phase_share_pct


def read(evidence):
    return phase_share_pct(evidence, ("dump",))
