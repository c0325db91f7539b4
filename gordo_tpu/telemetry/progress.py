"""
Live fleet-build progress: the ``build_status.json`` heartbeat.

The reference operator watched a build with ``argo get`` — per-machine
phase, counts and durations straight from the pod DAG. The chip-fan-out
build's equivalent is this compact document, atomically rewritten beside
the build journal on every phase transition and machine completion, so
*any* moment of the build has a current, parseable status on disk:

- the ``gordo-tpu build-status <output-dir>`` CLI renders it (per-phase
  table, progress bar, ETA from the completed-machine rate),
- the model server serves it verbatim from
  ``/gordo/v0/<project>/build-status``,
- dashboards can poll the file over whatever volume carries the
  artifacts.

Writes are throttled by ``GORDO_TPU_TELEMETRY_HEARTBEAT`` (seconds
between machine-completion writes; default 0.5). The throttle is what
makes the surface free at any scale: an atomic replace costs ~1ms, so
per-completion writes would tax a toy build measurably while a real
heartbeat is at most ~2 writes/second no matter how many thousand
machines are landing. ``0`` opts into exact per-completion durability
(the fault-injection drills use it so the status is never behind the
journal). First entry of each phase and the final state always write.
"""

import contextlib
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional

from .recorder import PART_SUMS, PHASE_SUMS, _iso, add_sums

logger = logging.getLogger(__name__)

HEARTBEAT_ENV = "GORDO_TPU_TELEMETRY_HEARTBEAT"
DEFAULT_HEARTBEAT_SECONDS = 0.5

#: canonical names of the telemetry files written beside the artifacts.
#: They live HERE (not serializer.py) because this package must stay
#: stdlib-only importable from the training hot path; the serializer's
#: artifact-discovery predicates re-export them.
BUILD_STATUS_FILE = "build_status.json"
BUILD_TRACE_FILE = "build_trace.jsonl"

#: a part's sums that are counts (of pieces, bytes, machines), written
#: whole; the rest are seconds
_WHOLE_NUMBERS = (
    "count",
    "bytes",
    "in_process",
    "bytes_deferred",
    "bytes_fetched_beside_write",
)


class BuildProgress:
    """
    Counter/phase tracker that heartbeats ``<output_dir>/build_status.json``.

    Thread-safe: the dump pool reports completions concurrently. With
    ``output_dir=None`` the counters still track (and feed the Prometheus
    gauges via the builder) but nothing is written.
    """

    def __init__(
        self,
        output_dir: Optional[str],
        project: str = "",
        total: int = 0,
        phase_seconds: Optional[Dict[str, float]] = None,
        heartbeat_seconds: Optional[float] = None,
        robustness: Optional[Dict[str, int]] = None,
        device: Optional[Dict[str, Any]] = None,
    ):
        self.path = (
            os.path.join(output_dir, BUILD_STATUS_FILE)
            if output_dir is not None
            else None
        )
        if output_dir is not None:
            try:
                os.makedirs(output_dir, exist_ok=True)
            except OSError:
                self.path = None  # advisory: never fail the build
        self.project = project
        self.total = total
        self.completed = 0
        self.failed = 0
        self.resumed = 0
        self.cached = 0
        self.degraded = 0
        #: machines the fleet path could not plan, built one by one on
        #: the sequential ModelBuilder instead
        self.fallbacks = 0
        #: reference to the builder's live robustness counters
        #: (bucket_bisects, sequential_degraded, fleet_retries, ...) — a
        #: build that exits 0 after containing device faults says so here
        self.robustness = robustness if robustness is not None else {}
        #: where the build runs (``telemetry.device_identity``)
        self.device = device
        self.state = "running"
        self.started_at = time.time()
        #: reference to the builder's live phase_seconds dict — snapshot
        #: at every write so the doc carries the fine-grained breakdown
        self.phase_seconds = phase_seconds if phase_seconds is not None else {}
        if heartbeat_seconds is None:
            from ..utils.env import env_float

            heartbeat_seconds = env_float(HEARTBEAT_ENV, DEFAULT_HEARTBEAT_SECONDS)
        self.heartbeat_seconds = max(0.0, heartbeat_seconds)
        #: phase -> part -> {seconds, count, ...}: what ``build_part``
        #: spans measured inside a phase, summed over the threads that
        #: did it (so a pooled phase's parts can exceed its wall
        #: ``seconds``); beside the two, each of ``PART_SUMS`` a span gave
        self._parts: Dict[str, Dict[str, Dict[str, float]]] = {}
        #: phase -> {cpu_seconds, process_cpu_seconds}: the CPU seconds
        #: of the thread that ran the phase and of the whole process
        #: between the phase's two ends (``add_phase_cpu``)
        self._phase_cpu: Dict[str, Dict[str, float]] = {}
        #: the newest resource sample (``hbm_peak_bytes``,
        #: ``host_rss_peak_bytes``, ``host_cpu_count``), set by the
        #: builder where it samples the device
        self.resources: Optional[Dict[str, Any]] = None
        #: what the build spent tracing, lowering, compiling and loading
        #: programs (``telemetry.device.compile_path_counters`` deltas),
        #: set by the builder at build end
        self.compile: Optional[Dict[str, Any]] = None
        #: one entry a fit program that has counters of its own (the
        #: router counts of an expert layer): ``add_fit_counters``
        self._fit_counters: List[Dict[str, Any]] = []
        self._phase: Optional[str] = None
        self._phase_order: List[str] = []
        self._lock = threading.Lock()
        self._write_lock = threading.Lock()  # serializes write+rename
        self._last_write = 0.0

    # -- build lifecycle ----------------------------------------------------

    def phase(self, name: str) -> None:
        """Enter a build phase. Phases re-enter freely (the CV loop
        interleaves train/predict/score once per bucket chunk); only the
        FIRST entry of each phase forces a write — re-entries ride the
        heartbeat throttle, so a thousand-chunk CV costs one forced
        write, not a thousand ~ms atomic replaces."""
        with self._lock:
            changed = self._phase != name
            self._phase = name
            first_entry = name not in self._phase_order
            if first_entry:
                self._phase_order.append(name)
        if first_entry:
            self.write(force=True)
        elif changed:
            self.write(min_interval=self.PHASE_REENTRY_INTERVAL)

    def add_part(
        self, phase: str, part: str, seconds: float, count: int = 1, **sums: float
    ) -> None:
        """Fold one ``build_part`` span into ``phases[phase]["parts"]``:
        its seconds and ``count``, and of ``PART_SUMS`` (``cpu_seconds``,
        ``bytes``, ``bytes_reused``, ``d2h_seconds``, ``bytes_deferred``,
        ``bytes_fetched_beside_write``, ``fetch_wait_seconds``) what the span gave,
        so an entry has such a key only where a span had it. Nothing is
        written until the next heartbeat or phase entry."""
        with self._lock:
            entry = self._parts.setdefault(phase, {}).setdefault(
                part, {"seconds": 0.0, "count": 0}
            )
            entry["seconds"] += seconds
            entry["count"] += count
            add_sums(entry, PART_SUMS, sums)

    def add_phase_cpu(self, phase: str, **cpu: Optional[float]) -> None:
        """Fold one ``build_phase`` span's ``cpu_seconds`` (its own
        thread's) and ``process_cpu_seconds`` (every thread's) into the
        phase's entry, summed over its re-entries."""
        with self._lock:
            add_sums(self._phase_cpu.setdefault(phase, {}), PHASE_SUMS, cpu)

    def add_fit_counters(self, counters: Dict[str, Any]) -> None:
        """Keep one fit program's own counters (what its
        ``device_program`` span carries) for ``fit_counters``."""
        with self._lock:
            self._fit_counters.append(dict(counters))

    def machine_completed(self, name: str = "") -> None:
        with self._lock:
            self.completed += 1
        self.write()

    def machine_failed(self, name: str = "") -> None:
        with self._lock:
            self.failed += 1
        self.write()

    def finish(self, state: str = "complete") -> None:
        with self._lock:
            self.state = state
            self._phase = None
        self.write(force=True)

    # -- the document -------------------------------------------------------

    def document(self) -> Dict[str, Any]:
        with self._lock:
            now = time.time()
            phases = {
                name: {
                    "seconds": round(
                        float(self.phase_seconds.get(name, 0.0)), 6
                    ),
                    "status": "running" if name == self._phase else "done",
                }
                for name in self._phase_order
            }
            for name, cpu in self._phase_cpu.items():
                if name in phases:
                    phases[name].update(
                        {key: round(value, 6) for key, value in cpu.items()}
                    )
            for name, parts in self._parts.items():
                if name in phases:
                    phases[name]["parts"] = {
                        part: {
                            key: int(value)
                            if key in _WHOLE_NUMBERS
                            else round(value, 6)
                            for key, value in entry.items()
                        }
                        for part, entry in parts.items()
                    }
            resources = (
                {"resources": dict(self.resources)}
                if self.resources is not None
                else {}
            )
            compile_path = (
                {"compile": dict(self.compile)} if self.compile is not None else {}
            )
            fit_counters = (
                {"fit_counters": list(self._fit_counters)} if self._fit_counters else {}
            )
            return {
                "version": 1,
                "project": self.project,
                "state": self.state,
                "phase": self._phase,
                "started_at": _iso(self.started_at),
                "updated_at": _iso(now),
                "elapsed_sec": round(now - self.started_at, 3),
                "machines": {
                    "total": self.total,
                    "completed": self.completed,
                    "failed": self.failed,
                    "resumed": self.resumed,
                    "cached": self.cached,
                    "degraded": self.degraded,
                    "fallbacks": self.fallbacks,
                },
                "robustness": {k: int(v) for k, v in self.robustness.items()},
                "device": self.device,
                "phases": phases,
                **resources,
                **compile_path,
                **fit_counters,
            }

    #: floor on how often phase RE-entries rewrite the doc — the CV loop
    #: cycles train/predict/score once per bucket chunk, and each atomic
    #: replace costs ~1ms; machine completions are not floored (their
    #: durability mirrors the journal's per-machine event append)
    PHASE_REENTRY_INTERVAL = 0.2

    def write(
        self, force: bool = False, min_interval: Optional[float] = None
    ) -> None:
        """Atomically replace the status file (best-effort: the build
        must never fail because its progress doc could not land).
        ``min_interval`` raises the throttle floor for this call only."""
        if self.path is None:
            return
        interval = self.heartbeat_seconds
        if min_interval is not None:
            interval = max(interval, min_interval)
        now = time.time()
        with self._write_lock:
            with self._lock:
                if not force and now - self._last_write < interval:
                    return
                self._last_write = now
            doc = self.document()
            # Dotted staging-convention name, like the journal's flush:
            # an interrupted write leaves a file every discovery path
            # already classifies as a staging leftover. The write+rename
            # happens under _write_lock (a dedicated lock so document()
            # can take _lock): the dump pool reports completions from 8
            # threads sharing this one pid-named tmp path, and an
            # unlocked open(tmp, "w") would truncate a sibling's
            # in-flight write — renaming torn JSON into the status file.
            tmp = f"{os.path.join(os.path.dirname(self.path), '.' + BUILD_STATUS_FILE)}.tmp-{os.getpid()}"
            try:
                with open(tmp, "w") as f:
                    json.dump(doc, f, default=str)
                os.replace(tmp, self.path)
            except OSError as exc:
                logger.debug("build_status heartbeat not written: %r", exc)
                with contextlib.suppress(OSError):
                    os.remove(tmp)


def load_status(output_dir: str) -> Optional[Dict[str, Any]]:
    """The build-status document from ``output_dir``, or None when no
    build has written one (or it is unreadable)."""
    try:
        with open(os.path.join(output_dir, BUILD_STATUS_FILE)) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return doc if isinstance(doc, dict) else None


def eta_seconds(doc: Dict[str, Any]) -> Optional[float]:
    """ETA from the completed-machine rate, or None while no machine has
    completed (training phases finish machines in bulk at dump time, so
    the estimate firms up as artifacts start landing)."""
    machines = doc.get("machines") or {}
    completed = int(machines.get("completed") or 0)
    elapsed = float(doc.get("elapsed_sec") or 0.0)
    if doc.get("state") != "running" or completed <= 0 or elapsed <= 0:
        return None
    remaining = (
        int(machines.get("total") or 0)
        - completed
        - int(machines.get("resumed") or 0)
        - int(machines.get("failed") or 0)
    )
    if remaining <= 0:
        return 0.0
    return remaining * elapsed / completed


def _gigabytes(count: float) -> str:
    return f"{float(count) / 1e9:.2f} GB"


def part_rates_text(measured: Dict[str, Any]) -> str:
    """What a part's ``cpu_seconds``, ``bytes``, ``bytes_reused`` and
    ``d2h_seconds`` say beside its seconds (``build-status`` and
    ``gordo-tpu trace`` print it): the share of its seconds a CPU was
    computing for it (the rest it waited: for the GIL, for I/O, for the
    device, for a core), its GB/s where it moved bytes, of a ``stack``'s
    bytes the share filled into buffers the staging pool already held,
    a ``collect``'s fetch alone and what it started on its way and did
    not wait for (``bytes_deferred``), and of a ``write``'s bytes those
    its pickler took from such a transfer, with the seconds it waited
    for them (``bytes_fetched_beside_write``, ``fetch_wait_seconds``:
    near none where the md5 sets the pace, and the link's where the
    link does). Empty where the part carries none of them."""
    seconds = float(measured.get("seconds") or 0.0)
    shown = []
    if "cpu_seconds" in measured and seconds > 0:
        shown.append(f"cpu {100.0 * float(measured['cpu_seconds']) / seconds:.0f}%")
    if measured.get("bytes"):
        size = float(measured["bytes"])
        # a fetch's bytes are what crossed back after it was computed:
        # over its seconds they would read as a rate nothing ran at
        fetched = "in_process" in measured
        if seconds > 0 and not fetched:
            shown.append(f"{size / 1e9 / seconds:.2f} GB/s")
        if seconds <= 0 or fetched or "bytes_reused" in measured:
            shown.append(_gigabytes(size))
        if "bytes_reused" in measured:
            shown.append(f"{100.0 * float(measured['bytes_reused']) / size:.0f}% reused")
    if measured.get("d2h_seconds"):
        rate = (
            f" at {float(measured['bytes']) / 1e9 / measured['d2h_seconds']:.2f} GB/s"
            if measured.get("bytes")
            else ""
        )
        shown.append(f"d2h {float(measured['d2h_seconds']):.2f} s{rate}")
    if measured.get("bytes_deferred"):
        shown.append(f"{_gigabytes(measured['bytes_deferred'])} on their way")
    if measured.get("bytes_fetched_beside_write"):
        shown.append(
            f"{_gigabytes(measured['bytes_fetched_beside_write'])} fetched beside, "
            f"waited {float(measured.get('fetch_wait_seconds') or 0.0):.2f} s"
        )
    return f"  [{', '.join(shown)}]" if shown else ""


def cores_busy_text(entry: Dict[str, Any]) -> str:
    """What a phase's two CPU clocks say beside its seconds
    (``build-status`` and ``gordo-tpu trace`` print it): the cores it
    kept busy (the process's CPU seconds between its two ends over its
    wall seconds) and the share of its seconds its own thread computed.
    A phase whose own thread reads near 100% is bound by the builder's
    one thread; a low share beside many cores is a pool or the runtime
    at work; both low, the host waited (for the device, for a lock).
    The process's CPU seconds hold the fetch workers' (a phase's cores
    are the job's, whichever process computed), and beside them a
    ``data_fetch`` says how many of its machines were fetched in worker
    processes (``machine_fetch``'s ``in_process`` of its ``count``): all
    of them where the cores were the pool's, none in a job of one
    machine. Empty where the phase has none of these."""
    seconds = float(entry.get("seconds") or 0.0)
    if seconds <= 0:
        return ""
    shown = []
    if "process_cpu_seconds" in entry:
        shown.append(f"{float(entry['process_cpu_seconds']) / seconds:.2f} cores busy")
    if "cpu_seconds" in entry:
        shown.append(f"own thread cpu {100.0 * float(entry['cpu_seconds']) / seconds:.0f}%")
    fetched = (entry.get("parts") or {}).get("machine_fetch") or {}
    if "in_process" in fetched:
        shown.append(
            f"{int(fetched['in_process'])} of {int(fetched.get('count', 0))} "
            "machines fetched in processes"
        )
    return f"  [{', '.join(shown)}]" if shown else ""


def latent_text(attrs: Dict[str, Any]) -> str:
    """What a row keeps of itself in a latent attention, of what that
    expands to for the heads (a fit program's ``kv_lora_rank``,
    ``qk_rope_head_dim`` and ``kv_expanded_dim``: ``BackboneSpec.fit_counter_attrs``)."""
    return (
        f"latent {int(attrs['kv_lora_rank']):,} + {int(attrs['qk_rope_head_dim']):,} "
        f"of {int(attrs['kv_expanded_dim']):,} floats a row"
    )


def scan_text(attrs: Dict[str, Any]) -> str:
    """What a state-space backbone's scans hold and which layers read an
    earlier layer's tensors (a fit program's ``ssm_inner``, ``ssm_state``,
    ``scan_chunk``, ``memory_reads`` and ``kv_reads``:
    ``BackboneSpec.fit_counter_attrs``)."""
    text = (
        f"scan {int(attrs['ssm_inner']):,} x {int(attrs['ssm_state']):,} "
        f"in chunks of {int(attrs['scan_chunk']):,}"
    )
    reads = []
    for key, what in (("memory_reads", "output"), ("kv_reads", "keys")):
        sources = list(attrs.get(key) or ())
        for source in sorted(set(sources)):
            count = sources.count(source)
            who = f"{count}" if reads else f"{count} layer{'' if count == 1 else 's'}"
            reads.append(f"{who} read{'s' if count == 1 else ''} layer {source}'s {what}")
    return f"{text}; {', '.join(reads)}" if reads else text


def render_status(doc: Dict[str, Any]) -> str:
    """Human rendering of a build-status document (the ``build-status``
    CLI's output): header, progress bar + ETA, per-phase table."""
    machines = doc.get("machines") or {}
    total = int(machines.get("total") or 0)
    completed = int(machines.get("completed") or 0)
    resumed = int(machines.get("resumed") or 0)
    failed = int(machines.get("failed") or 0)
    done = completed + resumed
    state = doc.get("state", "unknown")
    phase = doc.get("phase")
    lines = [
        f"Project:  {doc.get('project') or '-'}",
        f"State:    {state}" + (f" (phase: {phase})" if phase else ""),
        f"Started:  {doc.get('started_at', '-')}  "
        f"(elapsed {doc.get('elapsed_sec', 0):.0f}s)",
        f"Machines: {done}/{total} done"
        + (f" ({resumed} resumed)" if resumed else "")
        + (f", {failed} failed" if failed else "")
        + (
            f", {machines.get('degraded')} degraded"
            if machines.get("degraded")
            else ""
        ),
    ]
    device = doc.get("device")
    if device:
        lines.insert(
            1,
            f"Device:   {device.get('platform')} — "
            f"{device.get('count')} x {device.get('device_kind')}",
        )
    contained = dict(
        doc.get("robustness") or {}, fallbacks=machines.get("fallbacks")
    )
    shown = [f"{k}={v}" for k, v in sorted(contained.items()) if v]
    if shown:
        lines.append("Contained: " + ", ".join(shown))
    if total:
        frac = min(1.0, (done + failed) / total)
        width = 30
        fill = int(round(frac * width))
        bar = "#" * fill + "." * (width - fill)
        eta = eta_seconds(doc)
        eta_text = f"   ETA ~{eta:.0f}s" if eta is not None else ""
        lines.append(f"Progress: [{bar}] {frac * 100:3.0f}%{eta_text}")
    phases = doc.get("phases") or {}
    if phases:
        lines.append("Phases:")
        name_width = max(len(name) for name in phases)
        lines.append(f"  {'phase'.ljust(name_width)}  {'seconds':>9}  status")
        for name, entry in phases.items():
            lines.append(
                f"  {name.ljust(name_width)}  "
                f"{float(entry.get('seconds', 0.0)):9.2f}  "
                f"{entry.get('status', '')}{cores_busy_text(entry)}"
            )
            for part, measured in (entry.get("parts") or {}).items():
                lines.append(
                    f"    {part.ljust(max(0, name_width - 2))}  "
                    f"{float(measured.get('seconds', 0.0)):9.2f}  "
                    f"x{measured.get('count', 0)} (thread-seconds)"
                    f"{part_rates_text(measured)}"
                )
    latent = next((c for c in doc.get("fit_counters") or () if c.get("kv_lora_rank")), None)
    if latent:
        lines.append(f"Attention: {latent_text(latent)}")
    scanned = next((c for c in doc.get("fit_counters") or () if c.get("ssm_inner")), None)
    if scanned:
        lines.append(f"State space: {scan_text(scanned)}")
    resources = doc.get("resources")
    if resources:
        lines.append(
            "Resources: "
            + ", ".join(
                f"{key}={_gigabytes(value)}" if key.endswith("_bytes") else f"{key}={value}"
                for key, value in resources.items()
                if value is not None
            )
        )
    compile_path = doc.get("compile")
    if compile_path:
        lines.append(
            "Compile path: "
            + ", ".join(f"{k}={v}" for k, v in compile_path.items())
        )
    return "\n".join(lines)
