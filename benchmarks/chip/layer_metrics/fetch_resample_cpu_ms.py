"""What every user's data source pays a machine: CPU seconds of the
``resample_join`` parts of the ``data_fetch`` phase over the machines
fetched (the part's ``count``), in milliseconds, from
``build_status.json``; median over the window's jobs. A thread's CPU
clock leaves out its waits (for the GIL, for a core) and the part
leaves out the synthetic provider, so this is the arithmetic
``TimeSeriesDataset`` does a machine. None where the program records
no CPU seconds."""

from harness.parts import part_sums
from harness.stats import median


def read(evidence):
    readings = []
    for job in evidence["jobs"]:
        joined = part_sums(job, ("data_fetch",), "resample_join", ("cpu_seconds", "count"))
        if joined is None or not joined["count"]:
            return None
        readings.append(1000.0 * joined["cpu_seconds"] / joined["count"])
    return median(readings) if readings else None
