"""
Device-utilization telemetry: measured HBM occupancy and compile-cache
hit accounting.

Everything the planner (PR 5) says about device memory is a *prediction*
from spec geometry, and everything the compile-cache work (PR 5) does is
invisible once it works — until now nothing measured either. This module
closes both gaps:

- :func:`memory_snapshot` reads ``Device.memory_stats()`` off every
  local device (``bytes_in_use`` / ``peak_bytes_in_use`` / the backend's
  limit) and aggregates them into one JSON-able dict. The fleet builder
  samples it at phase boundaries beside the host's own numbers
  (:func:`sample_resources`: the measured counterpart of the FleetPlan's
  predicted HBM, and ``build_status.json["resources"]``), and the
  Prometheus device collector reads it at scrape time. Backends without
  the stats (the CPU platform) answer ``{"available": False}`` —
  callers never branch on platform.
- :func:`note_program_execution` is the process-wide compile-vs-cache-hit
  counter pair, fed by the two places that know: the build side's
  :func:`~gordo_tpu.telemetry.recorder.program_span` (first call per
  signature = compile, later = hit — the jit cache's own semantics) and
  the serving engine's fused-program bookkeeping. The persistent
  compile-cache directory ``parallel/mesh.configure_compile_cache``
  settles on (``JAX_COMPILATION_CACHE_DIR``, else
  ``<checkout>/.jax_cache`` on an accelerator) is inventoried by
  :func:`persistent_cache_info` (entries + bytes on disk, plus this
  process's hits and misses against it).
- :func:`compile_path_counters` sums what JAX's own duration events
  say the process spent on the way to its executables (tracing,
  lowering, the backend's compile call, reading the persistent cache),
  on the same ``jax.monitoring`` registration as the hits and misses
  (:func:`watch_compile_path`). The fleet builder writes a build's
  share of them into ``build_status.json["compile"]``.
- :func:`device_identity` names where the process runs (``platform``,
  ``device_kind``, device count) — every snapshot carries it, so a run
  JAX quietly started on the CPU never reads like one on the chip.

The counters and snapshots here are stdlib data; only the memory probe
touches jax, lazily, so importing this module stays free on hosts
without an accelerator stack.
"""
# gt-lint: file-disable=jax-stdlib-only -- this module IS the telemetry
# package's Device.memory_stats() wrapper; the jax import stays lazy and
# failure-isolated so the package still imports (and the counters still
# work) on hosts without jax

import os
import resource
import threading
from typing import Any, Dict, Optional

#: master switch for the (slightly costly) device memory probe; the
#: counters are a few ns and stay on with telemetry itself
DEVICE_TELEMETRY_ENV = "GORDO_TPU_DEVICE_TELEMETRY"

#: memory_stats() keys aggregated across local devices (keys a backend
#: does not report simply contribute nothing)
_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def device_sampling_enabled() -> bool:
    """Memory sampling on? (telemetry master switch AND
    ``GORDO_TPU_DEVICE_TELEMETRY``, both default-on)."""
    from ..utils.env import env_bool
    from .recorder import enabled

    return enabled() and env_bool(DEVICE_TELEMETRY_ENV, True)


# -- compile-cache hit/miss counters -----------------------------------------

_counter_lock = threading.Lock()
#: kind -> {"compiles": n, "cache_hits": n}; ``build`` is fed by
#: program_span's first-call attribution, ``serve`` by the engine's
#: fused-program set
_program_counters: Dict[str, Dict[str, int]] = {}


def note_program_execution(
    compiled: bool, kind: str = "build", precision: Optional[str] = None
) -> None:
    """Count one jit-program execution: ``compiled=True`` for a
    cache-miss (trace+compile happened inside the call), False for a
    steady-state cache-hit run. ``precision`` (the serve engine's
    precision ladder: ``f32``/``bf16``/``int8``) additionally buckets
    the count per serving precision, so the compile-cache console can
    answer "did the bf16 ladder actually warm" per axis."""
    with _counter_lock:
        counters = _program_counters.get(kind)
        if counters is None:
            counters = _program_counters[kind] = {
                "compiles": 0,
                "cache_hits": 0,
            }
        counters["compiles" if compiled else "cache_hits"] += 1
        if precision:
            by_precision = counters.setdefault("by_precision", {})
            sub = by_precision.setdefault(
                precision, {"compiles": 0, "cache_hits": 0}
            )
            sub["compiles" if compiled else "cache_hits"] += 1


def program_cache_counters() -> Dict[str, Dict[str, Any]]:
    """Snapshot of the per-kind compile/cache-hit counters, each with a
    derived ``hit_rate`` (None until anything executed); the serve
    kind's per-precision sub-counters ride along under
    ``by_precision``."""
    with _counter_lock:
        snapshot = {}
        for kind, counters in _program_counters.items():
            copied = dict(counters)
            if "by_precision" in copied:
                copied["by_precision"] = {
                    prec: dict(sub)
                    for prec, sub in copied["by_precision"].items()
                }
            snapshot[kind] = copied
    for counters in snapshot.values():
        total = counters["compiles"] + counters["cache_hits"]
        counters["hit_rate"] = (
            round(counters["cache_hits"] / total, 4) if total else None
        )
    return snapshot


def reset_program_counters() -> None:
    """Zero the counters (tests only — production keeps them for the
    life of the process, like the jit caches they describe)."""
    with _counter_lock:
        _program_counters.clear()


# -- persistent compile cache -------------------------------------------------

_cache_dir_lock = threading.Lock()
_persistent_cache_dir: Optional[str] = None


def note_compile_cache_dir(path: Optional[str]) -> None:
    """Record the persistent compile-cache directory
    ``parallel/mesh.configure_compile_cache`` settled on."""
    global _persistent_cache_dir
    with _cache_dir_lock:
        _persistent_cache_dir = path


#: this process's lookups against the persistent cache, fed by JAX's own
#: monitoring events (a hit is a compile that was loaded from disk)
_PERSISTENT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_persistent_counts = {"hits": 0, "misses": 0}

#: the compile path's duration events of this JAX (0.9: all four exist,
#: ``jax/_src/dispatch.py`` and ``jax/_src/compiler.py``): tracing a
#: function to a jaxpr, lowering the jaxpr to MLIR, the backend's
#: compile call, and reading an executable from the persistent cache.
#: The backend call encloses the cache read, so ``backend_s`` below is
#: kept net of it and the four seconds add up without counting twice.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_DURATION_KEYS = {
    _TRACE_EVENT: "trace_s",
    _LOWER_EVENT: "lower_s",
    _BACKEND_EVENT: "backend_s",
    _CACHE_LOAD_EVENT: "cache_load_s",
}
#: the keys of :func:`compile_path_counters`, in the order they are shown
COMPILE_PATH_KEYS = (
    *_DURATION_KEYS.values(), "programs", "persistent_hits", "persistent_misses"
)
_compile_seconds = {key: 0.0 for key in _DURATION_KEYS.values()}
_compile_programs = 0
_compile_watch_installed = False


def _on_jax_event(event: str, **_kwargs: Any) -> None:
    key = _PERSISTENT_EVENTS.get(event)
    if key is not None:
        with _cache_dir_lock:
            _persistent_counts[key] += 1


def _on_jax_duration(event: str, seconds: float, **_kwargs: Any) -> None:
    global _compile_programs
    key = _DURATION_KEYS.get(event)
    if key is not None:
        with _cache_dir_lock:
            _compile_seconds[key] += seconds
            if event == _BACKEND_EVENT:
                _compile_programs += 1


def watch_compile_path() -> None:
    """Count persistent-cache hits and misses, and sum the compile
    path's seconds, from here on (idempotent; JAX offers no way to
    unregister a listener, so the pair is installed once per process)."""
    global _compile_watch_installed
    with _cache_dir_lock:
        if _compile_watch_installed:
            return
        _compile_watch_installed = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def compile_path_counters() -> Dict[str, Any]:
    """What this process has spent on the way to its executables since
    :func:`watch_compile_path`: ``trace_s`` (function to jaxpr),
    ``lower_s`` (jaxpr to MLIR), ``backend_s`` (the backend's compile
    call, net of cache reads), ``cache_load_s`` (executables read from
    the persistent cache), ``programs`` (executables built or loaded)
    and the persistent cache's ``persistent_hits`` / ``persistent_misses``.
    A ``jax.jit`` made anew traces, lowers and loads again though
    nothing compiles; ``FleetBuilder.build`` writes the difference over
    a build as ``build_status.json["compile"]``."""
    with _cache_dir_lock:
        seconds = dict(_compile_seconds)
        seconds["backend_s"] = max(
            0.0, seconds["backend_s"] - seconds["cache_load_s"]
        )
        return {
            **{key: round(value, 6) for key, value in seconds.items()},
            "programs": _compile_programs,
            "persistent_hits": _persistent_counts["hits"],
            "persistent_misses": _persistent_counts["misses"],
        }


def persistent_cache_info() -> Optional[Dict[str, Any]]:
    """Inventory of the persistent compile cache (entry count + bytes on
    disk, and this process's hits/misses), or None when no cache
    directory is configured. Best-effort: a vanished directory reports
    zero entries, never raises. A process that never configured one
    (``fleet-status`` over an artifact volume) inventories the directory
    ``JAX_COMPILATION_CACHE_DIR`` names."""
    with _cache_dir_lock:
        cache_dir = _persistent_cache_dir
        counts = dict(_persistent_counts)
    if cache_dir is None:
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    if not cache_dir:
        return None
    entries = 0
    total_bytes = 0
    try:
        with os.scandir(cache_dir) as it:
            for entry in it:
                try:
                    if entry.is_file():
                        entries += 1
                        total_bytes += entry.stat().st_size
                except OSError:
                    continue
    except OSError:
        pass
    return {
        "path": cache_dir,
        "entries": entries,
        "bytes": total_bytes,
        **counts,
    }


# -- device memory ------------------------------------------------------------


def _local_devices() -> Optional[list]:
    """The local devices, or None when JAX (or its backend) is not
    available — telemetry degrades, it never takes the caller down."""
    try:
        import jax

        return jax.local_devices()
    except Exception:  # noqa: BLE001 - no jax / broken backend
        return None


def device_identity() -> Optional[Dict[str, Any]]:
    """Where this process runs, as JAX reports it: ``platform``,
    ``device_kind`` and the local device count (None without a
    backend)."""
    devices = _local_devices()
    if not devices:
        return None
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_snapshot() -> Optional[Dict[str, Any]]:
    """
    Aggregate ``Device.memory_stats()`` over the local devices:
    ``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit`` summed
    across devices, plus the per-device maxima (the number an HBM-cap
    planner compares against) and how many devices actually reported.
    A key no device reports is absent from the document.

    Returns None when sampling is disabled or jax is unavailable;
    ``{"available": False, ...}`` when the backend has no stats (the
    distinction callers render differently: "off" vs "not measurable").
    """
    if not device_sampling_enabled():
        return None
    devices = _local_devices()
    if devices is None:
        return None
    doc: Dict[str, Any] = {
        "devices": len(devices),
        "measured_devices": 0,
        "available": False,
    }
    totals: Dict[str, int] = {}
    maxima: Dict[str, int] = {}
    for device in devices:
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 - per-device isolation
            stats = None
        if not stats:
            continue
        doc["measured_devices"] += 1
        for key in _MEMORY_KEYS:
            value = stats.get(key)
            if value is None:
                continue
            value = int(value)
            totals[key] = totals.get(key, 0) + value
            maxima[key] = max(maxima.get(key, 0), value)
    if doc["measured_devices"]:
        doc["available"] = True
        for key, total in totals.items():
            doc[key] = total
            doc[f"max_{key}"] = maxima[key]
        limit = totals.get("bytes_limit") or 0
        if limit and "bytes_in_use" in totals:
            doc["utilization"] = round(totals["bytes_in_use"] / limit, 4)
    return doc


def utilization_snapshot() -> Dict[str, Any]:
    """The full device-telemetry document: device identity + memory +
    compile-cache counters + persistent-cache inventory (each section
    None/absent when unavailable). This is what the fleet-status
    surface carries."""
    doc: Dict[str, Any] = {"compile_cache": program_cache_counters()}
    identity = device_identity()
    if identity is not None:
        doc["device"] = identity
    memory = memory_snapshot()
    if memory is not None:
        doc["memory"] = memory
    persistent = persistent_cache_info()
    if persistent is not None:
        doc["persistent_cache"] = persistent
    return doc


def sample_resources() -> Dict[str, Any]:
    """What the process holds, where a build samples it (the end of a
    device-heavy phase: a handful of samples a build, not one a
    program): ``memory``, the :func:`memory_snapshot` (None when sampling
    is off or unavailable); ``host_rss_peak_bytes``, the largest resident
    set the process has had so far (``getrusage``'s ``ru_maxrss``, which
    Linux counts in KiB); and ``host_cpu_count``, the cores it may run
    on, which is what its CPU seconds are to be held against."""
    return {
        "memory": memory_snapshot(),
        "host_rss_peak_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        * 1024,
        "host_cpu_count": len(os.sched_getaffinity(0)),
    }
