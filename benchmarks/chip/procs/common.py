"""
What every chip-holding child does: find the chip or refuse, keep the
compile cache by the repo's one rule, count compilations, read device
memory, run ``build-fleet`` in-process, and open and reduce a profiler
trace.
"""

import glob
import json
import logging
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, List, Optional

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))
for _path in (ROOT, CHIP_DIR):
    if _path not in sys.path:
        sys.path.insert(0, _path)

#: jax.monitoring events: one BACKEND_COMPILE per executable built or
#: loaded from the persistent cache; hits and misses of that cache
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def die_with_parent() -> None:
    """The first thing a child does: ask the kernel to kill this process
    when the process that started it dies (Linux ``PR_SET_PDEATHSIG``),
    because a parent that is killed outright cannot end its child
    (``harness/child.py``), and a child left behind holds the chip. A
    parent that died before the request was in place is found by its
    pid, which the parent left in the environment."""
    import ctypes

    pr_set_pdeathsig = 1
    ctypes.CDLL(None, use_errno=True).prctl(pr_set_pdeathsig, int(signal.SIGKILL), 0, 0, 0)
    parent = os.environ.get("CHIPBENCH_PARENT_PID")
    if parent and os.getppid() != int(parent):
        os._exit(3)


class NoChip(Exception):
    """JAX found no accelerator, too few chips, or a kind with no peaks."""


def require_chip(chips: int) -> Dict[str, Any]:
    """The device as JAX reports it, with its published peaks; raises
    :class:`NoChip` on the CPU platform, with fewer than ``chips``
    devices, or for a ``device_kind`` that ``peaks.json`` does not hold
    (a device that is not in the table is an error, not a default)."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] == "cpu":
        raise NoChip(f"JAX reports no accelerator: {device}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports {device}")
    with open(os.path.join(CHIP_DIR, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    if device["kind"] not in peaks:
        raise NoChip(
            f"no peaks for device_kind {device['kind']!r} in peaks.json "
            f"(known: {sorted(peaks)}); add them with their source"
        )
    device["peaks"] = peaks[device["kind"]]
    return device


def memory(chips: int) -> Dict[str, Any]:
    """Peak bytes on the fullest chip, as the runtime reports them: the
    peak of live buffers (``peak_bytes_in_use``) plus the peak it
    reserved for the scratch of loaded programs
    (``peak_bytes_reserved``). The first alone leaves out what a program
    holds while it runs: a program with 1.07 GB of scratch left
    ``peak_bytes_in_use`` where it was and moved ``bytes_reserved`` by
    1.07 GB (PERF.md, findings of PR 23). And that chip's limit."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()[:chips]]
    fullest = max(
        stats, key=lambda s: s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
    )
    live = int(fullest.get("peak_bytes_in_use", 0))
    reserved = int(fullest.get("peak_bytes_reserved", 0))
    return {
        "memory_peak_bytes": live + reserved,
        "live_peak_bytes": live,
        "reserved_peak_bytes": reserved,
        "bytes_limit": int(fullest.get("bytes_limit", 0)),
    }


class CompileCounter:
    """Counts executables built or loaded (and persistent-cache hits and
    misses) through ``jax.monitoring``, for the life of the process."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counts = {"programs": 0, "cache_hits": 0, "cache_misses": 0}
        self.compile_seconds = 0.0

    def install(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, seconds: float, **_: Any) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.counts["programs"] += 1
                self.compile_seconds += seconds

    def _on_event(self, event: str, **_: Any) -> None:
        key = {CACHE_HIT: "cache_hits", CACHE_MISS: "cache_misses"}.get(event)
        if key:
            with self._lock:
                self.counts[key] += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self.counts, compile_seconds=self.compile_seconds)


def compiled_between(before: Dict[str, float], after: Dict[str, float], cache: Any) -> Dict[str, int]:
    """What happened between two snapshots: ``compiles``, the programs
    the backend really compiled (misses of the persistent cache, which
    every run on a chip keeps), and ``loads``, the executables it built
    or fetched in all (a ``jax.jit`` made anew per call lowers again and
    fetches from the cache; that is steady work of the program, not a
    shape the warm-up missed). Without a persistent cache (the CPU
    platform of the tests) only ``loads`` can be counted."""
    return {
        "compiles": int(after["cache_misses"] - before["cache_misses"]) if cache else 0,
        "loads": int(after["programs"] - before["programs"]),
    }


class ErrorLog(logging.Handler):
    """Every ERROR-or-worse log record: the program contains faults and
    answers from slower paths, and says so only in its log."""

    def __init__(self) -> None:
        super().__init__(level=logging.ERROR)
        self.records: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(f"{record.name}: {record.getMessage()}"[:300])


def start(chips: int, log_path: Optional[str] = None):
    """Common start of a child: logging, the chip, the compile cache and
    the compile counter. Returns ``(device, counter, error_log)``."""
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s [%(name)s] %(message)s",
        filename=log_path,
    )
    errors = ErrorLog()
    logging.getLogger().addHandler(errors)
    device = require_chip(chips)
    from gordo_tpu.parallel.mesh import configure_compile_cache

    device["compile_cache"] = configure_compile_cache()
    return device, CompileCounter().install(), errors


def run_cli(args: List[str]) -> int:
    """The ``gordo-tpu`` click group in-process, as ``python -m
    gordo_tpu`` enters it; returns the exit code."""
    from gordo_tpu.cli import gordo_tpu_cli

    try:
        gordo_tpu_cli.main(args, standalone_mode=False)
    except SystemExit as exc:
        return int(exc.code or 0)
    return 0


def build_job(document: Dict[str, Any], job_dir: str, output_dir: str) -> Dict[str, Any]:
    """One ``build-fleet`` of ``document`` into ``output_dir``, timed on
    the host clock around the whole command. Returns the job's record:
    wall-clock start and end, seconds, exit code."""
    import yaml

    os.makedirs(job_dir, exist_ok=True)
    config_path = os.path.join(job_dir, "machines.yaml")
    with open(config_path, "w") as f:
        yaml.safe_dump(document, f)
    start_wall, started = time.time(), time.monotonic()
    code = run_cli(["build-fleet", config_path, output_dir])
    seconds = time.monotonic() - started
    return {
        "output_dir": output_dir,
        "machines": len(document["machines"]),
        "start": start_wall,
        "end": start_wall + seconds,
        "seconds": seconds,
        "exit_code": code,
    }


class Trace:
    """A ``jax.profiler`` trace of a slice of the window: device events
    and the benchmark's own annotations, host and Python tracers kept to
    the annotations."""

    def __init__(self, directory: str) -> None:
        self.directory = directory
        self.started_wall: Optional[float] = None
        self.stopped_wall: Optional[float] = None
        self._lock = threading.Lock()

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        with self._lock:
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self.started_wall = time.time()
            # one annotation carrying the host's wall clock: the
            # reduction puts host spans on the trace's clock with it
            with jax.profiler.TraceAnnotation(
                "chipbench_clock", wall_ns=time.time_ns()
            ):
                pass

    def stop(self) -> None:
        import jax

        with self._lock:
            if self.started_wall is None or self.stopped_wall is not None:
                return
            self.stopped_wall = time.time()
            jax.profiler.stop_trace()

    def stop_after(self, seconds: float) -> threading.Timer:
        timer = threading.Timer(seconds, self.stop)
        timer.daemon = True
        timer.start()
        return timer

    def reduce(self, chips: int) -> Optional[Dict[str, Any]]:
        """The trace as ``xplane.reduce`` sees it, or None where none
        was written. The profiler's files (hundreds of MB for a whole
        job) are removed once reduced."""
        import shutil

        import xplane

        files = sorted(
            glob.glob(os.path.join(self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        )
        if not files:
            return None
        started = time.monotonic()
        reduced = xplane.reduce(files[-1], chips=chips)
        reduced["reduce_seconds"] = time.monotonic() - started
        reduced["file_bytes"] = os.path.getsize(files[-1])
        reduced["started_wall"] = self.started_wall
        reduced["stopped_wall"] = self.stopped_wall
        shutil.rmtree(self.directory, ignore_errors=True)
        return reduced


def write_json(path: str, document: Any) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump(document, f, indent=1, default=str)
    os.replace(path + ".tmp", path)
