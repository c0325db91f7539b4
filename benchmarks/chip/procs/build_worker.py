"""
The child of a build cell: holds the chip, runs one warm-up job of the
cell's own shape (set-up), then ``build-fleet`` jobs back to back for
the window, then checks what they built. Writes ``report.json`` into the
run's directory; prints nothing the driver reads.
"""

import json
import os
import shutil
import sys
import time

from common import (
    NoChip, Trace, build_job, compiled_between, die_with_parent, memory, start, write_json,
)
from jobs import read_spans, read_status


def main(spec_path: str) -> int:
    die_with_parent()
    with open(spec_path) as f:
        spec = json.load(f)
    try:
        device, counter, errors = start(
            spec["chips"], os.path.join(spec["run_dir"], "child.log")
        )
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 3
    write_json(
        os.path.join(spec["run_dir"], "report.json"),
        run(spec, device, counter, errors),
    )
    return 0


def run(spec: dict, device: dict, counter, errors) -> dict:
    """The cell on the device this process holds; returns the report.
    A function of ``spec``, so the tests run it tiny on the CPU."""
    run_dir, config, traffic = spec["run_dir"], spec["config"], spec["traffic"]

    from harness import correct
    from harness.data import history_rows, machine_names, machines_document
    from harness.manifest import load_module, ROOT

    reference = load_module(ROOT, "reference", config["reference"])
    machines, days = traffic["machines_per_job"], traffic["history_days"]

    def job(index) -> dict:
        document = machines_document(config, spec["seed"], index, machines, days)
        job_dir = os.path.join(run_dir, "jobs", str(index))
        record = build_job(document, job_dir, os.path.join(job_dir, "build"))
        record["index"] = index
        return record

    # set-up: one whole job of the cell's own shape compiles (or loads
    # from the persistent cache) every program the window will run
    warm = job("warm")
    before = counter.snapshot()
    error_mark = len(errors.records)

    trace = Trace(os.path.join(run_dir, "trace")) if spec["trace"] else None
    jobs = []
    window_start = time.time()
    while True:
        if trace is not None and not jobs:
            # the traced slice: the window's first job from its start, a
            # whole job or the cell's cap, whichever ends first
            trace.start()
            timer = trace.stop_after(traffic["trace_max_seconds"])
        jobs.append(job(len(jobs)))
        if trace is not None and len(jobs) == 1:
            timer.cancel()
            trace.stop()
        used = time.time() - window_start
        if used + jobs[-1]["seconds"] > spec["seconds"]:
            break
    window_end = jobs[-1]["end"]
    after = counter.snapshot()

    # outside the window: what the jobs left, and whether it is right
    checks = correct.Checks()
    for line in errors.records[error_mark:]:
        checks.check(False, f"error logged in the window: {line}")
    samples = history_rows(days) - (config.get("lookback_window", 1) - 1)
    verified = 0
    for record in [warm] + jobs:
        record["status"] = read_status(record["output_dir"])
        record.update(read_spans(record["output_dir"]))
    for record in jobs:
        names = machine_names(spec["seed"], record["index"], machines)
        record["verified"] = correct.check_build_job(checks, record, names, config)
        correct.check_programs(checks, record, config, samples)
        verified += record["verified"]

    import numpy as np

    last = jobs[-1]
    rng = np.random.RandomState(spec["seed"])
    names = machine_names(spec["seed"], last["index"], machines)
    drawn = [names[i] for i in rng.choice(machines, traffic["verify_machines"], replace=False)]
    band = None
    if last["exit_code"] == 0:
        for name in drawn:
            correct.check_artifact_forward(
                checks, reference, last["output_dir"], name,
                traffic["verify_rows"], spec["seed"], device["platform"],
            )
        document = machines_document(config, spec["seed"], last["index"], machines, days)
        band = correct.check_loss_band(
            checks, reference, config, document, last["output_dir"], drawn[0]
        )

    report = {
        "device": {**device, **memory(spec["chips"])},
        "window": {"start": window_start, "end": window_end},
        "attempted": machines * len(jobs),
        "verified": verified,
        "correct": checks.ok,
        "failures": checks.failures,
        "worst_fraction_of_scale": checks.worst_fraction,
        "loss_band": band,
        "compiles": {"before_window": before, "after_window": after},
        "in_window": compiled_between(before, after, device.get("compile_cache")),
        "jobs": jobs,
        "warm_job": warm,
        "traced_job": 0 if trace is not None else None,
        "trace": trace.reduce(spec["chips"]) if trace is not None else None,
    }
    for record in [warm] + jobs:
        shutil.rmtree(record["output_dir"], ignore_errors=True)
    return report


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
