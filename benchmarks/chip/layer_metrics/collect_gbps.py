"""What a job feels of bringing its fits' results back: bytes that
``fetch_to_host`` returned over the seconds of the whole ``collect``
part (the fetch and the per-member unstacking), in the ``cv_train`` and
``final_fit`` phases' parts (``build_status.json``), GB/s; median over
the window's jobs. (The entry's ``d2h_seconds``, the fetch alone, has
no reader here: PR 37 found it equal to the part's seconds to three
digits in every cell, the unstacking being views; ``build-status``
prints it.) None where the program records no bytes."""

from harness.parts import rate_gbps

PHASES = ("cv_train", "final_fit")


def read(evidence):
    return rate_gbps(evidence, PHASES, "collect")
