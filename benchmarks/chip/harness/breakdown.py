"""
The traced run's ``breakdown``: the device operations that took most
time, and the longest idle gaps named by what the host was doing. It is
the only trace the writer of the next issue sees.
"""

from typing import Any, Dict, List, Tuple

Labelled = Tuple[float, float, str]  # wall-clock start, end, label


def host_intervals(evidence: Dict[str, Any]) -> Tuple[List[Labelled], str]:
    """What the host was doing, on the wall clock, and the label of the
    time in which it was doing none of it: the build phases of the
    traced job (``build_trace.jsonl``), or the requests in flight (the
    client's own timestamps)."""
    if "jobs" in evidence:
        traced = evidence.get("traced_job")
        intervals = [
            (phase["start"], phase["end"], f"build phase {phase.get('phase')}")
            for job in evidence["jobs"]
            if job.get("index") == traced
            for phase in job.get("phases", [])
        ]
        return intervals, "between build phases"
    intervals = [
        (r["sent"], r["sent"] + r["seconds"], "request in flight")
        for r in evidence.get("requests", [])
    ]
    return intervals, "no request in flight"


def name_gap(start: float, end: float, intervals: List[Labelled], otherwise: str) -> str:
    """The label that covers most of ``[start, end]``."""
    covered: Dict[str, float] = {}
    for a, b, label in intervals:
        overlap = min(b, end) - max(a, start)
        if overlap > 0:
            covered[label] = covered.get(label, 0.0) + overlap
    if not covered:
        return otherwise
    label, seconds = max(covered.items(), key=lambda kv: kv[1])
    return label if seconds >= 0.5 * (end - start) or label == "request in flight" else otherwise


def build(evidence: Dict[str, Any], keep: int = 10) -> Dict[str, Any]:
    trace = evidence.get("trace") or {}
    devices = trace.get("devices") or []
    if not devices:
        return {"device_ops": [], "idle_gaps": []}
    first = devices[0]
    ops = [[name, seconds] for name, seconds, _ in first["ops"][:keep]]
    zero = trace.get("profile_start_wall_ns")
    intervals, otherwise = host_intervals(evidence)
    gaps: Dict[str, float] = {}
    for start_ns, duration_ns in first["gaps"]:
        if zero is None:
            label = "unplaced (no clock in the trace)"
        else:
            start = (zero + start_ns) / 1e9
            label = name_gap(start, start + duration_ns / 1e9, intervals, otherwise)
        gaps[label] = gaps.get(label, 0.0) + duration_ns / 1e9
    return {
        "device_ops": ops,
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:keep],
    }
