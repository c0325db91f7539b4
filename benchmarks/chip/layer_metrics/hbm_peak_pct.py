"""Peak bytes on the fullest chip over its ``bytes_limit``: the
runtime's peak of live buffers plus the peak it reserved for program
scratch (``procs/common.memory``), for the life of the child. In a serve
cell that includes the set-up build of the served collection, which is
where the peak comes from."""


def read(evidence):
    device = evidence["device"]
    if not device.get("bytes_limit"):
        return None
    return 100.0 * device["memory_peak_bytes"] / device["bytes_limit"]
