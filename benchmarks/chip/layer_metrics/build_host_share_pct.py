"""Share of a job in which no training or CV-prediction program was the
phase: 1 - (cv_train + cv_predict + final_fit) / job seconds, from
``build_status.json`` phases and the host clock around the command;
median over the window's jobs."""

from harness.evidence import phase_share_pct


def read(evidence):
    return 100.0 - phase_share_pct(evidence, ("cv_train", "cv_predict", "final_fit"))
