"""``device_program`` spans a job in ``build_trace.jsonl`` (a count; it
repeats exactly); median over the window's jobs."""

from harness.stats import median


def read(evidence):
    return median([len(job["programs"]) for job in evidence["jobs"]])
