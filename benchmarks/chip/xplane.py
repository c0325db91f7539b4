"""
From a profiler trace to numbers: device busy and idle time, device time
by XLA module and by operation, the longest idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. A TPU shows
as one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds
one event per executed HLO operation and whose line ``XLA Modules`` one
event per executed program; times are nanoseconds from the start of the
profile. The plane ``Task Environment`` gives the profile's start and
stop on the host's wall clock, and the benchmark's own
``chipbench_clock`` annotation (``procs/common.Trace``) carries the wall
clock onto the trace's timeline, so host spans and device gaps can be
laid side by side.

This file is the yardstick for every device-time metric: per-layer
readers take their numbers from what :func:`reduce` returns and from
nowhere else.
"""

import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_ANNOTATION = "chipbench_clock"
#: how many operations and gaps a reduction keeps per device
KEEP_OPS = 40
KEEP_GAPS = 200

Interval = Tuple[float, float]


def union_seconds(intervals: List[Interval]) -> float:
    """Total length of the union of ``(start_ns, end_ns)`` intervals, in
    seconds: overlapping operations count once."""
    total, current_start, current_end = 0.0, None, None
    for start, end in sorted(intervals):
        if current_end is None or start > current_end:
            if current_end is not None:
                total += current_end - current_start
            current_start, current_end = start, end
        else:
            current_end = max(current_end, end)
    if current_end is not None:
        total += current_end - current_start
    return total / 1e9


def longest_gaps(
    intervals: List[Interval],
    keep: int = KEEP_GAPS,
    window: Optional[Interval] = None,
) -> List[Interval]:
    """The ``keep`` longest stretches in which nothing ran, as
    ``(start_ns, duration_ns)``: between the first and the last
    interval, and, where ``window`` (start_ns, end_ns of the trace) is
    given, before the first and after the last as well."""
    gaps: List[Interval] = []
    end_so_far = window[0] if window else None
    for start, end in sorted(intervals):
        if end_so_far is not None and start > end_so_far:
            gaps.append((end_so_far, start - end_so_far))
        end_so_far = end if end_so_far is None else max(end_so_far, end)
    if window and end_so_far is not None and window[1] > end_so_far:
        gaps.append((end_so_far, window[1] - end_so_far))
    return sorted(gaps, key=lambda g: -g[1])[:keep]


def module_name(event_name: str) -> str:
    """``jit_fit(123456789)`` -> ``jit_fit``: the program's name without
    the fingerprint the profiler appends."""
    return re.sub(r"\(\d+\)\s*$", "", event_name).strip()


#: marks of an operation's kind in its HLO text
KERNEL_MARK = "tpu_custom_call"  # a Mosaic (Pallas) kernel
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                    "collective-permute", "collective-broadcast")


def short_name(hlo_text: str) -> str:
    """The first 80 characters of an operation's name: on a TPU an
    operation's event is named by its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``), and the same ``%name``
    recurs in every module, so the start of its type is kept with it."""
    return hlo_text.strip()[:80]


def category(hlo_text: str) -> str:
    """``kernel`` for a Mosaic custom call, ``collective`` for an
    operation that moves data between chips, else ``""``."""
    if KERNEL_MARK in hlo_text:
        return "kernel"
    opcode = hlo_text.split(" = ", 1)[-1]
    if any(f" {mark}" in opcode or opcode.startswith(mark) for mark in COLLECTIVE_MARKS):
        return "collective"
    return ""


def _stats(event) -> Dict[str, Any]:
    try:
        return dict(event.stats)
    except Exception:  # noqa: BLE001 - a stat the binding cannot decode
        return {}


def _reduce_device(plane, window: Optional[Interval]) -> Dict[str, Any]:
    lines = {line.name: line for line in plane.lines}
    intervals: List[Interval] = []
    ops: Dict[str, List[Any]] = {}
    by_category: Dict[str, float] = {}
    if OPS_LINE in lines:
        for event in lines[OPS_LINE].events:
            start, duration = float(event.start_ns), float(event.duration_ns)
            intervals.append((start, start + duration))
            entry = ops.get(event.name)
            if entry is None:
                entry = ops[event.name] = [0.0, 0, category(event.name)]
            entry[0] += duration / 1e9
            entry[1] += 1
        for seconds, _, kind in ops.values():
            by_category[kind] = by_category.get(kind, 0.0) + seconds
    modules: Dict[str, Dict[str, float]] = {}
    if MODULES_LINE in lines:
        for event in lines[MODULES_LINE].events:
            entry = modules.setdefault(
                module_name(event.name), {"seconds": 0.0, "count": 0}
            )
            entry["seconds"] += float(event.duration_ns) / 1e9
            entry["count"] += 1
    top = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "name": plane.name,
        "events": len(intervals),
        "busy_s": union_seconds(intervals),
        "first_ns": min((s for s, _ in intervals), default=None),
        "last_ns": max((e for _, e in intervals), default=None),
        "modules": modules,
        "ops": [
            [short_name(name), seconds, count] for name, (seconds, count, _) in top[:KEEP_OPS]
        ],
        "distinct_ops": len(ops),
        "op_seconds": sum(by_category.values()),
        "kernel_seconds": by_category.get("kernel", 0.0),
        "collective_seconds": by_category.get("collective", 0.0),
        "gaps": [list(gap) for gap in longest_gaps(intervals, window=window)],
    }


def _clock_from_annotation(host_planes) -> Optional[float]:
    """Wall-clock nanoseconds of the trace's zero, from the benchmark's
    ``chipbench_clock`` annotation (its ``wall_ns`` stat minus its start)."""
    for plane in host_planes:
        for line in plane.lines:
            for event in line.events:
                if event.name == CLOCK_ANNOTATION:
                    wall = _stats(event).get("wall_ns")
                    if wall is not None:
                        return int(wall) - float(event.start_ns)
    return None


def reduce(path: str, chips: Optional[int] = None) -> Dict[str, Any]:
    """
    The trace at ``path`` as a plain dict:

    - ``window_s``: the profile's length on the host's clock;
    - ``profile_start_wall_ns``: where the trace's zero lies on the wall clock
      (None where neither the environment plane nor the annotation says);
    - ``devices``: one entry per chip (the first ``chips`` of them) with
      ``busy_s`` (union of the operation intervals), ``modules`` (device
      seconds and runs per XLA module), ``ops`` (the operations with most
      device time: name, seconds, count), ``op_seconds`` with its parts
      ``kernel_seconds`` (Mosaic custom calls) and ``collective_seconds``
      (``op_seconds`` counts a ``while`` and the operations of its body
      both; ``busy_s`` counts time once), and ``gaps`` (the longest idle
      stretches: start_ns, duration_ns);
    - ``busy_s``: mean of the devices' ``busy_s``.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    start_wall_ns = stop_wall_ns = None
    host_planes, device_planes = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
        elif plane.name == "Task Environment":
            try:
                stats = dict(plane.stats)
            except Exception:  # noqa: BLE001
                stats = {}
            start_wall_ns = stats.get("profile_start_time")
            stop_wall_ns = stats.get("profile_stop_time")
        elif plane.name.startswith("/host:"):
            host_planes.append(plane)
    # with the profile's length known, the idle before the first and
    # after the last operation counts among the gaps
    window = None
    if start_wall_ns is not None and stop_wall_ns is not None:
        window = (0.0, float(stop_wall_ns - start_wall_ns))
    devices = [_reduce_device(plane, window) for plane in device_planes]
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d["name"]).group(1)))
    if chips is not None:
        devices = devices[:chips]
    # the trace's zero on the wall clock: the profile's own start, or,
    # where the environment plane does not give it, the annotation
    zero_wall_ns: Optional[float] = start_wall_ns
    if zero_wall_ns is None:
        zero_wall_ns = _clock_from_annotation(host_planes)
    if start_wall_ns is not None and stop_wall_ns is not None:
        window_s = (stop_wall_ns - start_wall_ns) / 1e9
    else:
        firsts = [d["first_ns"] for d in devices if d["first_ns"] is not None]
        lasts = [d["last_ns"] for d in devices if d["last_ns"] is not None]
        window_s = (max(lasts) - min(firsts)) / 1e9 if firsts else 0.0
    busy = [d["busy_s"] for d in devices]
    return {
        "window_s": window_s,
        "profile_start_wall_ns": zero_wall_ns,
        "devices": devices,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
    }


def describe(path: str, events_per_line: int = 3) -> str:
    """Planes, lines and the first events of each, for a reader who
    meets a trace for the first time (``python xplane.py <file>``)."""
    from jax.profiler import ProfileData

    out: List[str] = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name}: {len(events)} events")
            for event in events[:events_per_line]:
                out.append(
                    f"    {event.name} start={event.start_ns} "
                    f"dur={event.duration_ns} stats={list(_stats(event).items())[:6]}"
                )
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys

    print(describe(sys.argv[1]))
    print(json.dumps(reduce(sys.argv[1]), indent=1)[:6000])
