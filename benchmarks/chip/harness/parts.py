"""What the readers of the host's accounting (PR 37) share: the CPU
seconds and bytes a ``build_part`` leaves beside its seconds in
``build_status.json["phases"][p]["parts"]``. A program that records
none of them (the parent's) gives every function here nothing to read:
None, never a raise."""

from typing import Any, Dict, Optional, Sequence

from .stats import median


def phases_of(job: Dict[str, Any]) -> Dict[str, Any]:
    return (job.get("status") or {}).get("phases") or {}


def part_sums(
    job: Dict[str, Any], phases: Sequence[str], part: str, keys: Sequence[str]
) -> Optional[Dict[str, float]]:
    """``keys`` of ``part``'s entries summed over those of ``phases``
    that hold the part; None where none holds it with every key."""
    found = [
        entry
        for entry in ((phases_of(job).get(p) or {}).get("parts", {}).get(part) for p in phases)
        if entry and all(key in entry for key in keys)
    ]
    if not found:
        return None
    return {key: sum(float(entry[key]) for entry in found) for key in keys}


def rate_gbps(
    evidence: Dict[str, Any], phases: Sequence[str], part: str
) -> Optional[float]:
    """Median over the window's jobs of ``part``'s bytes over its
    seconds in ``phases``, in GB/s (1e9 bytes a second)."""
    rates = []
    for job in evidence["jobs"]:
        sums = part_sums(job, phases, part, ("bytes", "seconds"))
        if sums is None or not sums["seconds"]:
            return None
        rates.append(sums["bytes"] / 1e9 / sums["seconds"])
    return median(rates) if rates else None
