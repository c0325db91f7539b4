"""Device time of the fit programs of the traced job over the optimizer
steps they ran (``harness.evidence.fit_seconds_and_steps``): what the
``jit_fit`` modules occupy on the device timeline, gaps inside a
launch-bound step included, over the steps their shapes say. None where
the traced slice holds no whole fit module."""

from harness.evidence import fit_seconds_and_steps


def read(evidence):
    found = fit_seconds_and_steps(evidence)
    return None if found is None else 1000.0 * found[0] / found[1]
