"""The largest resident set the building process has had, in GB (1e9
bytes): ``build_status.json["resources"]["host_rss_peak_bytes"]``
(``getrusage``'s ``ru_maxrss`` where the builder samples the device's
memory) of the window's last job: a peak only grows, and the jobs of a
run share one process. None where the program writes no
``resources``."""


def read(evidence):
    jobs = evidence["jobs"]
    if not jobs:
        return None
    resources = (jobs[-1].get("status") or {}).get("resources") or {}
    peak = resources.get("host_rss_peak_bytes")
    return None if peak is None else peak / 1e9
