"""
Traffic of a build cell: ``build-fleet`` jobs back to back.

Parameters (the cell's traffic file): ``machines_per_job``,
``history_days`` (10-minute rows a machine), ``verify_machines`` and
``verify_rows`` (the artifacts compared with the reference after the
window), ``trace_max_seconds`` (the cap of the traced slice). Machine
names, and so the data, come from ``--seed`` and the job's index
(``harness/data.py``). A new job starts only if the time used plus the
last job's time fits into ``--seconds``; the first always runs; the
window ends at the last completion.
"""

from typing import Any, Dict

from harness.child import end_child, load_report, start_child, tail, wait_child

PROC = "build_worker"


def run(cell, seed: int, seconds: float, trace: bool, run_dir: str) -> Dict[str, Any]:
    """Run the cell once; returns the run's evidence (see README.md)."""
    spec = {
        "cell": cell.name,
        "chips": cell.chips,
        "config": cell.config,
        "traffic": cell.traffic,
        "seed": seed,
        "seconds": seconds,
        "trace": trace,
        "run_dir": run_dir,
    }
    child = start_child(cell.root, PROC, spec)
    try:
        code = wait_child(child)
    finally:
        end_child(child)
    if code != 0:
        raise RuntimeError(f"{PROC} exited {code}:\n{tail(run_dir)}")
    report = load_report(run_dir)
    window = report["window"]
    seconds_used = window["end"] - window["start"]
    report["end_to_end"] = {
        # verified artifacts of completed jobs, over the whole window
        "models_built_per_hour": report["verified"] * 3600.0 / seconds_used,
    }
    report["failed"] = report["attempted"] - report["verified"]
    report["notes"] = [
        f"jobs {len(report['jobs'])} of {cell.traffic['machines_per_job']} machines, "
        f"seconds each {[round(j['seconds'], 3) for j in report['jobs']]}, "
        f"warm-up job {report['warm_job']['seconds']:.3f}s, window {seconds_used:.3f}s",
    ]
    return report
