"""What several per-layer readers take from a run's evidence (README.md):
each reader stays a file of its own and says what its metric is; the
arithmetic they share is here."""

from typing import Any, Dict, Optional, Sequence, Tuple

import flops

from .stats import median


def stage_p50(evidence: Dict[str, Any], stages: Sequence[str]) -> Optional[float]:
    """Median over the window's answered requests of the sum of
    ``stages`` of each response's ``Server-Timing`` (milliseconds); a
    request that names none of them is left out."""
    sums = [
        sum(r["stages"].get(stage, 0.0) for stage in stages)
        for r in evidence["requests"]
        if r["status"] == 200 and any(stage in r["stages"] for stage in stages)
    ]
    return median(sums) if sums else None


def phase_share_pct(evidence: Dict[str, Any], phases: Sequence[str]) -> float:
    """Median over the window's jobs of ``phases``' seconds
    (``build_status.json``) over the job's seconds (host clock around
    the command), in percent."""
    shares = []
    for job in evidence["jobs"]:
        timed = job["status"]["phases"]
        seconds = sum(timed[p]["seconds"] for p in phases if p in timed)
        shares.append(100.0 * seconds / job["seconds"])
    return median(shares)


def fit_seconds_and_steps(evidence: Dict[str, Any]) -> Optional[Tuple[float, int]]:
    """Device seconds of the traced job's fit programs (the XLA modules
    whose name holds ``fit``: module events of the trace, first
    operation to last, mean over chips) and the optimizer steps they ran
    (from the shapes of the job's ``fleet_*fit`` spans, by
    ``flops.fit_steps``). None where the traced slice does not hold
    exactly the job's fit programs (a slice cut by ``trace_max_seconds``)."""
    devices = (evidence.get("trace") or {}).get("devices") or []
    job = next(j for j in evidence["jobs"] if j["index"] == evidence["traced_job"])
    programs = [p for p in job["programs"] if "fit" in p["program"]]
    runs = seconds = 0.0
    for device in devices:
        for name, module in device["modules"].items():
            if "fit" in name:
                seconds += module["seconds"] / len(devices)
                runs += module["count"] / len(devices)
    if not devices or not programs or runs != len(programs):
        return None
    steps = sum(
        flops.fit_steps(p["stacked_samples"], evidence["config"]["batch_size"], p["epochs"])
        for p in programs
    )
    return seconds, steps


def kernel_seconds(evidence: Dict[str, Any]) -> Optional[float]:
    """Device time of the Mosaic custom calls (``xplane.category``:
    ``tpu_custom_call`` in the operation's HLO text) on the first chip,
    or None where the slice holds none."""
    devices = (evidence.get("trace") or {}).get("devices") or []
    return (devices[0].get("kernel_seconds") or None) if devices else None
