"""1 - union of device-operation intervals / traced window, from the
xplane reduction; mean over the chips the cell uses. In a build cell
whose slice is cut by ``trace_max_seconds`` this is the slice's idle
share, not the whole job's."""


def read(evidence):
    trace = evidence.get("trace") or {}
    if not trace.get("devices") or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
