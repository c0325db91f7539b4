"""The cores the fetch pool really kept busy: CPU seconds of the
``machine_fetch`` parts (``time.thread_time()`` on each pool thread,
summed over the pool) over the wall seconds of the ``data_fetch``
phase, both from ``build_status.json``; median over the window's jobs.
Beside ``fetch_parallelism`` (thread-seconds over the same wall: the
fetches in flight, waits for the GIL included), this is what they
computed: near 1.0 the sixteen threads ran one at a time, near the
host's cores the pool worked. None where the program records no CPU
seconds."""

from harness.parts import part_sums, phases_of
from harness.stats import median


def read(evidence):
    ratios = []
    for job in evidence["jobs"]:
        wall = (phases_of(job).get("data_fetch") or {}).get("seconds")
        fetched = part_sums(job, ("data_fetch",), "machine_fetch", ("cpu_seconds",))
        if fetched is None or not wall:
            return None
        ratios.append(fetched["cpu_seconds"] / wall)
    return median(ratios) if ratios else None
