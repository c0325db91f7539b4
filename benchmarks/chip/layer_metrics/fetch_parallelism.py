"""What the ``data_workers`` pool gives: thread-seconds of the
``machine_fetch`` parts (one a machine, summed over the pool's threads)
over the wall seconds of the ``data_fetch`` phase, both from
``build_status.json``; median over the window's jobs. 1.0 is one fetch
at a time; under the GIL a thread's seconds include its waiting for the
lock, so this is fetches in flight, not a speed-up. None where the
program records no parts."""

from harness.stats import median


def read(evidence):
    ratios = []
    for job in evidence["jobs"]:
        phase = ((job.get("status") or {}).get("phases") or {}).get("data_fetch") or {}
        fetched = (phase.get("parts") or {}).get("machine_fetch")
        if not fetched or not phase.get("seconds"):
            return None
        ratios.append(fetched["seconds"] / phase["seconds"])
    return median(ratios)
