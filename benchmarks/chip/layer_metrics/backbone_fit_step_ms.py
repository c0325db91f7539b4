"""Device time of the traced job's fit programs over the optimizer
steps they ran: the same time as ``fit_step_ms``
(``harness.evidence.fit_seconds_and_steps``), over the ``steps_run``
counter on the fit programs' ``device_program`` spans. A one-member
program skips a step whose batch is padding alone, so the steps the
shapes say (``fit_step_ms``'s divisor) are more than the steps that cost
time, by a share that follows the history's length; the program counts
the ones that held data. None where no fit program carries the counter
or the traced slice holds no whole fit module."""

from harness.evidence import fit_seconds_and_steps


def read(evidence):
    job = next(
        (j for j in evidence.get("jobs", []) if j["index"] == evidence.get("traced_job")),
        None,
    )
    steps = sum(
        int(p["steps_run"])
        for p in (job or {}).get("programs", [])
        if "fit" in p.get("program", "") and "steps_run" in p
    )
    found = fit_seconds_and_steps(evidence) if steps else None
    return None if found is None else 1000.0 * found[0] / steps
