"""Share of a job that lies under no build phase: (job seconds - sum of
``build_status.json`` ``phases[*].seconds``) / job seconds, the job
timed on the host clock around the whole ``build-fleet`` command;
median over the window's jobs. What is left is the command's own
start and end (argument parsing, the last status write), since the
program records its config load and its reporters as phases
``config_load`` and ``report``. None where a job left no phases."""

from harness.stats import median


def read(evidence):
    shares = []
    for job in evidence["jobs"]:
        phases = (job.get("status") or {}).get("phases")
        if not phases:
            return None
        spanned = sum(phase["seconds"] for phase in phases.values())
        shares.append(100.0 * (job["seconds"] - spanned) / job["seconds"])
    return median(shares)
