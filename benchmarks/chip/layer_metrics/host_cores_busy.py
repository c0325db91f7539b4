"""The host's cores a job kept busy: the process's CPU seconds
(``time.process_time()`` at each phase's two ends, every thread's work:
``phases[p]["process_cpu_seconds"]`` in ``build_status.json``) summed
over the phases, over the job's seconds (host clock around the
command); median over the window's jobs. 1.0 is one core all the time;
``resources.host_cpu_count`` is what the process may use. None where
the program records no process CPU."""

from harness.parts import phases_of
from harness.stats import median


def read(evidence):
    busy = []
    for job in evidence["jobs"]:
        cpu = [
            phase["process_cpu_seconds"]
            for phase in phases_of(job).values()
            if "process_cpu_seconds" in phase
        ]
        if not cpu or not job.get("seconds"):
            return None
        busy.append(sum(cpu) / job["seconds"])
    return median(busy) if busy else None
