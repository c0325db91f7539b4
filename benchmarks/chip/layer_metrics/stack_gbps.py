"""The rate at which the host fills the fit programs' blocks: bytes of
the blocks ``stack`` filled (``X``, ``y`` where there is one, the two
weight planes) over its seconds, in the ``cv_train`` and ``final_fit``
phases' parts (``build_status.json``), GB/s; median over the window's
jobs. A plain copy on the same host is the limit beside it. None where
the program records no bytes."""

from harness.parts import rate_gbps

PHASES = ("cv_train", "final_fit")


def read(evidence):
    return rate_gbps(evidence, PHASES, "stack")
