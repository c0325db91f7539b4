"""
The per-member fleet health ledger and the joined fleet-status surface.

The paper's operating premise is thousands of models watching machines
for months — and after PRs 3/6/7 an operator can watch a *build* (the
``build_status.json`` heartbeat), a *request* (serve traces + RED
metrics) and a *lifecycle cycle* (state.json), but still cannot answer
the fleet question: *which of my machines are degraded, drifting or
quarantined right now, and is the device actually full?* This module is
that answer:

- :class:`FleetHealthLedger` — one rolling health record per machine:
  serving counts (requests/errors/rows + a running residual mean),
  the latest drift verdict (the PR 6 windows' feature-shift σ and
  residual ratio), build provenance (revision, final loss,
  degraded/bisected flags from ``BuildMetadata.robustness``), and
  quarantine state. Fed by the serve path (``app._finalize`` + the
  fleet route), the fleet builder's span listener, and the lifecycle
  supervisor; persisted as atomic, heartbeat-throttled
  ``fleet_health.json`` snapshots beside the artifacts.
- Per-machine detail lives HERE, never in Prometheus labels (the PR 8
  cardinality contract): the scrape side gets bounded aggregates only —
  machines-by-state counts and a health-score histogram
  (``server/prometheus/metrics.py`` reads :func:`ledger_summaries` at
  scrape time).
- :func:`fleet_status_document` — the one joined operator view:
  ``build_status.json`` + ``fleet_plan.json`` (with the measured
  padding/HBM actuals the builder records back into the ledger) +
  lifecycle ``state.json``/``quarantine.json`` + the health ledger +
  device utilization, rendered by ``gordo-tpu fleet-status`` and served
  at ``/gordo/v0/<project>/fleet-health``.

Stdlib-only, like the rest of the package: the device-memory section is
*injected* by callers (``telemetry/device.py`` owns the jax probe).
"""

import contextlib
import datetime
import heapq
import json
import logging
import os
import threading
import time
import zlib
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple, Union

from ..utils.postfork import register_postfork_reset
from .recorder import _iso, enabled, worker_sink_path

logger = logging.getLogger(__name__)

#: the ledger snapshot written beside the artifacts (a builder dropping,
#: like build_status.json — serializer.is_builder_dropping knows it)
FLEET_HEALTH_FILE = "fleet_health.json"

#: the sharded snapshot layout beside it: past the monolithic-comfort
#: threshold the ledger splits its persistence into bounded per-shard
#: files under ``fleet_health.d/`` (``fleet_health-<pid>.d/`` per worker
#: — same worker-variant grammar as the sinks), so one noisy machine's
#: flush rewrites ONE shard, not 10k records. ``summary.json`` inside
#: the dir is the bounded read path: folded fleet summary + top-K
#: offenders, rewritten on every flush.
FLEET_HEALTH_SHARD_DIR = "fleet_health.d"
FLEET_HEALTH_SUMMARY_FILE = "summary.json"

#: shard count override: 0 (default) sizes adaptively — one shard while
#: the fleet fits a monolithic snapshot, then the next power of two of
#: ``machines / _SHARD_TARGET_MACHINES`` — any positive value pins it
HEALTH_SHARDS_ENV = "GORDO_TPU_HEALTH_SHARDS"
#: adaptive target: shards sized so a dirty-shard flush rewrites about
#: this many records regardless of fleet size (10k members -> 32 shards)
_SHARD_TARGET_MACHINES = 512
_MAX_SHARDS = 64
#: cached per-shard summaries go stale as breaker age-out cutoffs pass;
#: refresh untouched shards after this many seconds
_SUMMARY_MAX_AGE_S = 60.0
#: offenders kept per shard summary (consumers slice their own top-K)
_OFFENDER_CAP = 32

#: fleet-status bounding: past this many machines the joined document
#: stops inlining per-machine records by default (summary + top-K
#: offenders instead) — also the hard cap on one ``?machines=`` page
FLEET_STATUS_MAX_MACHINES_ENV = "GORDO_TPU_FLEET_STATUS_MAX_MACHINES"
DEFAULT_FLEET_STATUS_MAX_MACHINES = 500
#: offender rows carried by the bounded fleet-status health section
FLEET_STATUS_TOP_K_ENV = "GORDO_TPU_FLEET_STATUS_TOP_K"
DEFAULT_FLEET_STATUS_TOP_K = 10

#: master switch for the ledger (rides the telemetry master switch too)
FLEET_HEALTH_ENV = "GORDO_TPU_FLEET_HEALTH"
#: seconds between serving-count snapshot writes (state transitions —
#: drift verdicts, quarantines, build records — always force a write)
HEALTH_HEARTBEAT_ENV = "GORDO_TPU_HEALTH_HEARTBEAT"
DEFAULT_HEALTH_HEARTBEAT = 2.0
#: rows after which the rolling serving window decays (halves), so a
#: months-lived server's residual mean tracks the present, not January
HEALTH_WINDOW_ENV = "GORDO_TPU_HEALTH_WINDOW"
DEFAULT_HEALTH_WINDOW = 100_000

#: upper edges of the bounded health-score histogram the Prometheus side
#: exports — fixed, so the scrape cardinality is a constant
SCORE_BUCKETS = (0.25, 0.5, 0.75, 0.9, 1.0)

#: lifecycle state-file names, mirrored from ``gordo_tpu.lifecycle.state``
#: (the layering contract forbids telemetry -> lifecycle imports; a test
#: asserts the two spellings stay equal)
_LIFECYCLE_DIR = ".lifecycle"
_LIFECYCLE_STATE_FILE = "state.json"
_LIFECYCLE_QUARANTINE_FILE = "quarantine.json"


def health_enabled() -> bool:
    """Ledger on? (telemetry master switch AND ``GORDO_TPU_FLEET_HEALTH``,
    both default-on)."""
    from ..utils.env import env_bool

    return enabled() and env_bool(FLEET_HEALTH_ENV, True)


# -- the health math ----------------------------------------------------------


def _new_machine() -> Dict[str, Any]:
    return {
        "serving": {
            "requests": 0,
            "errors": 0,
            "rows": 0,
            "residual_mean": None,
            "last_request_at": None,
        },
        "drift": {
            "drifted": False,
            "reasons": [],
            "feature_shift_max": None,
            "residual_ratio": None,
            "window_rows": 0,
            "evaluated_at": None,
        },
        "build": {
            "revision": None,
            "final_loss": None,
            "degraded": False,
            "failed": False,
            "error": None,
            "bisects": 0,
            "retries": 0,
            "built_at": None,
        },
        "quarantine": {
            "active": False,
            "revision": None,
            "reasons": [],
            "since": None,
        },
        # the SERVING circuit breaker (gordo_tpu.serve.breaker): device
        # programs for this member kept failing and the engine tripped
        # it into quarantine (503 + Retry-After) — distinct from the
        # lifecycle `quarantine` section (a rolled-back canary)
        "breaker": {
            "state": "closed",
            "trips": 0,
            "cooldown_s": None,
            "reason": None,
            "updated_at": None,
        },
    }


#: seconds after which a persisted breaker record stops influencing the
#: displayed machine health: the record is written by the SERVING
#: process on transitions only, so a dead server (or a revision swapped
#: out from under its ledger) can never retire its own "open" — without
#: an age cutoff a machine would display quarantined forever while
#: serving 200s. Live breakers re-stamp on every transition (an actual
#: quarantine refreshes itself through its half-open probes).
BREAKER_STATE_MAX_AGE_S = 3600.0


def _live_breaker_state(
    machine: Dict[str, Any], max_age_s: float = BREAKER_STATE_MAX_AGE_S
) -> Optional[str]:
    """The machine's breaker state IF it is tripped and fresh enough to
    trust, else None. Stamps are wall-clock ISO strings (they must
    compare across processes and restarts, where monotonic can't
    reach); ``.get`` everywhere so pre-breaker snapshots read closed."""
    breaker = machine.get("breaker") or {}
    state = breaker.get("state")
    if state not in ("open", "half_open"):
        return None
    stamp = breaker.get("updated_at")
    if max_age_s and stamp:
        try:
            age = (
                datetime.datetime.now(datetime.timezone.utc)
                - datetime.datetime.fromisoformat(str(stamp))
            ).total_seconds()
        except ValueError:
            return state  # unparseable stamp: trust the state
        if age > max_age_s:
            return None
    return state


def health_score(machine: Dict[str, Any]) -> float:
    """One machine's health in [0, 1]: 1.0 healthy, descending through
    drift (−0.2), a degraded/failed build (−0.3), serving errors (up to
    −0.3, proportional to the error rate) and quarantine (−0.5).
    Deterministic in the record — the score is derived state, never
    stored ground truth."""
    score = 1.0
    if machine["quarantine"]["active"]:
        score -= 0.5
    breaker_state = _live_breaker_state(machine)
    if breaker_state == "open":
        score -= 0.4
    elif breaker_state == "half_open":
        score -= 0.2
    if machine["build"]["degraded"] or machine["build"]["failed"]:
        score -= 0.3
    if machine["drift"]["drifted"]:
        score -= 0.2
    serving = machine["serving"]
    if serving["requests"]:
        score -= min(0.3, 3.0 * serving["errors"] / serving["requests"])
    return round(max(0.0, min(1.0, score)), 4)


def machine_state(machine: Dict[str, Any]) -> str:
    """The machine's headline state, by severity: ``quarantined`` >
    ``degraded`` (failed/degraded build) > ``drifting`` > ``healthy``.
    A member whose serving circuit breaker is open (or probing
    half-open) IS quarantined — the serving-plane twin of a rolled-back
    canary."""
    if machine["quarantine"]["active"]:
        return "quarantined"
    if _live_breaker_state(machine) is not None:
        return "quarantined"
    if machine["build"]["degraded"] or machine["build"]["failed"]:
        return "degraded"
    if machine["drift"]["drifted"]:
        return "drifting"
    return "healthy"


def summarize(machines: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """Bounded aggregates over the per-machine records: state counts,
    fleet-wide request/error totals, and the fixed-bucket health-score
    histogram (per-bin counts; the Prometheus collector cumulates)."""
    counts = {"healthy": 0, "degraded": 0, "drifting": 0, "quarantined": 0}
    requests = errors = breaker_tripped = 0
    score_sum = 0.0
    bins = [0] * len(SCORE_BUCKETS)
    for machine in machines.values():
        counts[machine_state(machine)] += 1
        requests += machine["serving"]["requests"]
        errors += machine["serving"]["errors"]
        if _live_breaker_state(machine) is not None:
            breaker_tripped += 1
        score = health_score(machine)
        score_sum += score
        for i, edge in enumerate(SCORE_BUCKETS):
            if score <= edge:
                bins[i] += 1
                break
    return {
        "machines": len(machines),
        **counts,
        "requests": requests,
        "errors": errors,
        "error_rate": round(errors / requests, 6) if requests else 0.0,
        # serving-breaker trips, counted here so bounded readers (the
        # lifecycle supervisor's rebuild feed) can skip the full
        # machine parse when nothing is tripped fleet-wide
        "breaker_tripped": breaker_tripped,
        "score_histogram": {
            "buckets": list(SCORE_BUCKETS),
            "counts": bins,
            # the histogram's sum: mean fleet health is one PromQL
            # division (sum / count), so it must be the sum of SCORES,
            # not the machine count
            "score_sum": round(score_sum, 4),
        },
    }


def _fold_summaries(summaries: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold per-shard :func:`summarize` outputs into one fleet summary.
    Exact, not approximate: every field is a sum (shards partition the
    machines), so the fold equals ``summarize`` over the union."""
    folded = summarize({})
    bins = folded["score_histogram"]["counts"]
    score_sum = 0.0
    for summary in summaries:
        if not isinstance(summary, dict):
            continue
        for key in (
            "machines",
            "healthy",
            "degraded",
            "drifting",
            "quarantined",
            "requests",
            "errors",
            "breaker_tripped",
        ):
            folded[key] += int(summary.get(key) or 0)
        histogram = summary.get("score_histogram") or {}
        score_sum += float(histogram.get("score_sum") or 0.0)
        for i, count in enumerate(histogram.get("counts") or ()):
            if i < len(bins):
                bins[i] += int(count)
    folded["error_rate"] = (
        round(folded["errors"] / folded["requests"], 6)
        if folded["requests"]
        else 0.0
    )
    folded["score_histogram"]["score_sum"] = round(score_sum, 4)
    return folded


def _offender_reason(machine: Dict[str, Any], state: str) -> Optional[str]:
    """The one-line why behind an unhealthy machine (what the renderer
    prints after the score)."""
    if state == "quarantined":
        reasons = machine.get("quarantine", {}).get("reasons") or []
        if reasons:
            return str(reasons[0])
        breaker = machine.get("breaker") or {}
        if breaker.get("reason"):
            return str(breaker["reason"])
        return None
    if state == "degraded":
        error = machine.get("build", {}).get("error")
        return str(error) if error else None
    reasons = machine.get("drift", {}).get("reasons") or []
    return str(reasons[0]) if reasons else None


def _offenders(
    machines: Dict[str, Dict[str, Any]], cap: int
) -> List[Dict[str, Any]]:
    """The ``cap`` unhealthiest machines as bounded rows (name, score,
    state, first reason) — what the fleet-status surfaces show instead
    of 10k inline records."""
    entries = []
    for name, machine in machines.items():
        state = machine_state(machine)
        if state == "healthy":
            continue
        entries.append(
            {
                "machine": name,
                "score": health_score(machine),
                "state": state,
                "reason": _offender_reason(machine, state),
            }
        )
    return heapq.nsmallest(
        cap, entries, key=lambda e: (e["score"], e["machine"])
    )


def _merge_offenders(
    pools: Iterable[List[Dict[str, Any]]], top_k: int
) -> List[Dict[str, Any]]:
    merged: List[Dict[str, Any]] = []
    for pool in pools:
        merged.extend(e for e in pool if isinstance(e, dict))
    return heapq.nsmallest(
        top_k, merged, key=lambda e: (e.get("score", 0.0), str(e.get("machine")))
    )


# -- the ledger ---------------------------------------------------------------


class NullLedger:
    """The do-nothing ledger (health telemetry off): every recording
    method is a no-op, so feed sites stay unconditional."""

    enabled = False
    path = None

    def record_request(self, *args, **kwargs):
        pass

    def record_scores(self, *args, **kwargs):
        pass

    def record_build(self, *args, **kwargs):
        pass

    def record_drift(self, *args, **kwargs):
        pass

    def record_quarantine(self, *args, **kwargs):
        pass

    def record_breaker(self, *args, **kwargs):
        pass

    def record_promotion(self, *args, **kwargs):
        pass

    def record_plan_accuracy(self, accuracy):
        pass

    def document(self):
        return None

    def bounded_document(self, top_k=10):
        return None

    def summary(self):
        return None

    def offenders(self, top_k=10):
        return []

    def machine_count(self):
        return 0

    def write(self, force=False):
        pass

    def flush(self):
        pass


NULL_LEDGER = NullLedger()


def _shard_dir_for(path: str) -> str:
    """``fleet_health.json`` -> ``fleet_health.d`` (pid suffix kept:
    ``fleet_health-123.json`` -> ``fleet_health-123.d``)."""
    stem, _ = os.path.splitext(path)
    return stem + ".d"


def _shard_file_name(shard: int, count: int) -> str:
    # the layout generation rides the name: a reshard (count change)
    # produces a disjoint file set, so stale-generation files are
    # recognizable and removable
    return f"shard-{shard:03d}of{count:03d}.json"


def _shard_files(shard_dir: str) -> List[str]:
    try:
        entries = sorted(os.listdir(shard_dir))
    except OSError:
        return []
    return [
        os.path.join(shard_dir, entry)
        for entry in entries
        if entry.startswith("shard-") and entry.endswith(".json")
    ]


class FleetHealthLedger:
    """The per-machine health ledger for one artifact directory.

    Thread-safe (request threads, dispatcher threads and the builder's
    dump pool all record concurrently); every snapshot write is an
    atomic dotted-tmp + ``os.replace``, throttled like the
    ``build_status.json`` heartbeat so serving traffic cannot turn the
    ledger into an IO load."""

    enabled = True

    def __init__(
        self,
        directory: Optional[str] = None,
        project: str = "",
        heartbeat_seconds: Optional[float] = None,
        window_rows: Optional[int] = None,
    ):
        self.directory = (
            os.path.normpath(directory) if directory is not None else None
        )
        # under a multi-worker server every process snapshots its OWN
        # `fleet_health-<pid>.json` — N workers atomically replacing one
        # shared path were silently overwriting each other's counts;
        # readers merge the variants (load_merged_health)
        self.path = (
            worker_sink_path(os.path.join(self.directory, FLEET_HEALTH_FILE))
            if self.directory is not None
            else None
        )
        # the sharded layout lives beside the monolithic spelling:
        # fleet_health.json -> fleet_health.d/ (worker variants keep
        # their pid suffix: fleet_health-<pid>.json -> fleet_health-<pid>.d/)
        self.shard_dir = (
            _shard_dir_for(self.path) if self.path is not None else None
        )
        self.project = project
        #: the process that built this ledger — ledger_for() compares it
        #: so a child forked AFTER construction (gunicorn --preload)
        #: rebuilds with its own pid-suffixed snapshot path instead of
        #: inheriting the parent's and clobbering it from N workers
        self._pid = os.getpid()
        from ..utils.env import env_float, env_int

        self.heartbeat_seconds = max(
            0.0,
            heartbeat_seconds
            if heartbeat_seconds is not None
            else (
                env_float(HEALTH_HEARTBEAT_ENV, DEFAULT_HEALTH_HEARTBEAT)
                or DEFAULT_HEALTH_HEARTBEAT
            ),
        )
        self.window_rows = max(
            1,
            window_rows
            if window_rows is not None
            else env_int(HEALTH_WINDOW_ENV, DEFAULT_HEALTH_WINDOW),
        )
        self._machines: Dict[str, Dict[str, Any]] = {}
        #: running (sum, rows) behind each machine's residual mean —
        #: kept out of the document (the document carries the mean)
        self._residuals: Dict[str, List[float]] = {}
        self._plan_accuracy: Optional[Dict[str, Any]] = None
        self._listeners: List[Callable[[dict], None]] = []
        self._lock = threading.Lock()
        self._write_lock = threading.Lock()
        self._last_write = 0.0
        # -- shard bookkeeping (all mutated under self._lock) --
        #: pinned shard count from the env (0 = adaptive)
        self._forced_shards = max(0, env_int(HEALTH_SHARDS_ENV, 0))
        self._shard_count = self._forced_shards or 1
        #: shard -> member names, maintained incrementally so flushing
        #: one dirty shard never walks the full fleet
        self._shard_members: Dict[int, set] = {}
        #: shards with unpersisted record changes
        self._dirty: set = set()
        #: shard layout changed (reshard): next flush rewrites the dir
        self._layout_changed = False
        #: per-shard cached {"summary", "offenders"} + refresh stamps —
        #: the scrape/fleet-status summary is a fold over these, O(S),
        #: recomputed only for shards touched since the last refresh
        self._summary_cache: Dict[int, Dict[str, Any]] = {}
        self._summary_stamp: Dict[int, float] = {}
        self._summary_dirty: set = set()

    # -- recording ----------------------------------------------------------

    def _shard_of(self, name: str) -> int:
        # crc32, NOT hash(): shard assignment must be stable across
        # processes and restarts (Python string hashing is randomized)
        return zlib.crc32(name.encode("utf-8")) % self._shard_count

    def _reshard_locked(self) -> None:
        """Grow the shard count to the adaptive target and rebuild the
        membership map (O(N), but only on power-of-two growth — the
        per-record path never walks the fleet)."""
        needed = (len(self._machines) + _SHARD_TARGET_MACHINES - 1) // (
            _SHARD_TARGET_MACHINES
        )
        count = 1 << max(0, needed - 1).bit_length()
        count = min(_MAX_SHARDS, max(1, count))
        if count <= self._shard_count:
            return
        self._shard_count = count
        self._shard_members = {}
        for name in self._machines:
            self._shard_members.setdefault(self._shard_of(name), set()).add(
                name
            )
        self._dirty.update(range(count))
        self._summary_cache.clear()
        self._summary_stamp.clear()
        self._summary_dirty.update(range(count))
        self._layout_changed = True

    def _machine(self, name: str) -> Dict[str, Any]:
        """The (create-once) record for ``name`` — called under
        ``self._lock`` by every mutator, so it is also where the
        machine's shard is marked dirty."""
        machine = self._machines.get(name)
        if machine is None:
            machine = self._machines[name] = _new_machine()
            if (
                not self._forced_shards
                and self._shard_count < _MAX_SHARDS
                and len(self._machines)
                > self._shard_count * _SHARD_TARGET_MACHINES
            ):
                self._reshard_locked()
            shard = self._shard_of(name)
            self._shard_members.setdefault(shard, set()).add(name)
        else:
            shard = self._shard_of(name)
        self._dirty.add(shard)
        self._summary_dirty.add(shard)
        return machine

    def machine_count(self) -> int:
        with self._lock:
            return len(self._machines)

    def record_request(
        self, machine: str, error: bool = False, count: int = 1
    ) -> None:
        """One served request (or ``count`` of them) for ``machine``;
        ``error`` marks server-side failures (5xx) — client errors are
        the client's problem, not the machine's health."""
        with self._lock:
            serving = self._machine(machine)["serving"]
            serving["requests"] += count
            if error:
                serving["errors"] += count
            serving["last_request_at"] = _iso(time.time())
        self.write()

    def record_scores(
        self,
        machine: str,
        rows: int,
        residual_mean: Optional[float] = None,
        write: bool = True,
    ) -> None:
        """Fold one scored window into the machine's rolling serving
        stats: ``rows`` scored, at mean reconstruction error
        ``residual_mean`` (raw-target-space mse, as ``fleet_scores``
        reports it). The window decays (halves) past ``window_rows`` so
        the mean tracks the present. ``write=False`` lets a caller
        batching many machines snapshot once at the end."""
        if rows <= 0:
            return
        with self._lock:
            serving = self._machine(machine)["serving"]
            serving["rows"] += int(rows)
            if residual_mean is not None and residual_mean == residual_mean:
                total, seen = self._residuals.get(machine, (0.0, 0))
                if seen >= self.window_rows:
                    # decay BEFORE folding the new batch, so recent
                    # windows outweigh history instead of averaging
                    # into it forever
                    total *= 0.5
                    seen = int(seen * 0.5)
                total += float(residual_mean) * rows
                seen += rows
                self._residuals[machine] = [total, seen]
                serving["residual_mean"] = round(total / seen, 8)
        if write:
            self.write()

    def record_build(self, machine: str, **fields: Any) -> None:
        """Build provenance for one machine: any of ``revision``,
        ``final_loss``, ``degraded``, ``failed``, ``error``, ``bisects``,
        ``retries``. A successful (re)build clears the failed/degraded
        flags unless the caller re-asserts them."""
        with self._lock:
            build = self._machine(machine)["build"]
            for key, value in fields.items():
                if key in build and value is not None:
                    build[key] = value
            if (
                not build["failed"]
                and not build["degraded"]
                and not fields.get("error")
            ):
                # a clean (re)build supersedes the previous failure's
                # evidence — a recovered machine must not read
                # 'degraded' in the console forever
                build["error"] = None
            build["built_at"] = _iso(time.time())
        # a thousand-machine fleet records a thousand of these — only
        # the state-changing ones (failures/degradations) force the
        # snapshot; healthy completions ride the heartbeat throttle
        self.write(
            force=bool(
                fields.get("failed")
                or fields.get("degraded")
                or fields.get("error")
            )
        )

    def record_drift(
        self,
        machine: str,
        drifted: bool,
        reasons: Any = (),
        stats: Optional[Dict[str, Any]] = None,
        write: bool = True,
    ) -> None:
        """The machine's latest drift verdict (the PR 6 windows).
        ``write=False`` lets the lifecycle loop record a whole fleet's
        verdicts under one forced snapshot (its own ``flush()``)."""
        stats = stats or {}
        with self._lock:
            drift = self._machine(machine)["drift"]
            drift["drifted"] = bool(drifted)
            drift["reasons"] = [str(r) for r in (reasons or [])]
            for key in ("feature_shift_max", "residual_ratio", "window_rows"):
                if key in stats:
                    drift[key] = stats[key]
            drift["evaluated_at"] = _iso(time.time())
        if write:
            self.write(force=True)

    def record_quarantine(
        self,
        machines: Any,
        revision: Optional[str] = None,
        reasons: Any = (),
    ) -> None:
        """Mark ``machines`` quarantined (their canary was rolled back)."""
        now = _iso(time.time())
        with self._lock:
            for name in machines:
                quarantine = self._machine(str(name))["quarantine"]
                quarantine["active"] = True
                quarantine["revision"] = revision
                quarantine["reasons"] = [str(r) for r in (reasons or [])][:5]
                quarantine["since"] = now
        self.write(force=True)

    def record_breaker(
        self,
        machine: str,
        state: str,
        trips: Optional[int] = None,
        cooldown_s: Optional[float] = None,
        reason: Optional[str] = None,
    ) -> None:
        """The member's serving circuit-breaker state (fed by the serve
        engine on every transition). An ``open`` record is what nominates
        the member to the lifecycle supervisor as a rebuild candidate
        (:func:`breaker_tripped_machines`); ``closed`` retires it."""
        now = _iso(time.time())
        with self._lock:
            record = self._machine(machine).setdefault(
                "breaker", _new_machine()["breaker"]
            )
            record["state"] = str(state)
            if trips is not None:
                record["trips"] = int(trips)
            record["cooldown_s"] = cooldown_s
            record["reason"] = str(reason)[:200] if reason else None
            record["updated_at"] = now
        # every breaker transition is a state change: force the snapshot
        self.write(force=True)

    def record_promotion(
        self, revision: Optional[str], machines: Any = ()
    ) -> None:
        """A promoted revision: the rebuilt ``machines`` leave
        quarantine, drift AND breaker state (their windows restart
        against the new artifacts), their build revision advances, and
        any degraded/failed flags clear — a rebuild that passed the
        gates and took traffic IS a successful build."""
        with self._lock:
            for name in machines:
                machine = self._machine(str(name))
                machine["quarantine"] = _new_machine()["quarantine"]
                machine["drift"] = _new_machine()["drift"]
                # a tripped serving breaker drove (or rode along with)
                # this rebuild: the fresh artifacts start closed — the
                # engine's in-process breaker reset the same way when
                # the hot-swap minted a new RevisionFleet
                machine["breaker"] = _new_machine()["breaker"]
                build = machine["build"]
                build["degraded"] = False
                build["failed"] = False
                build["error"] = None
                if revision is not None:
                    build["revision"] = revision
        self.write(force=True)

    def record_plan_accuracy(self, accuracy: Dict[str, Any]) -> None:
        """The build's predicted-vs-measured plan numbers (compiles,
        wall seconds, padding waste, HBM) — the ledger carries them so
        the joined fleet-status view can show plan accuracy without
        re-reading the whole span trace."""
        with self._lock:
            self._plan_accuracy = dict(accuracy)
        self.write(force=True)

    def add_listener(self, listener: Callable[[dict], None]) -> None:
        """Call ``listener(summary_dict)`` after every forced snapshot
        write (advisory, exceptions swallowed)."""
        with self._lock:
            self._listeners.append(listener)

    # -- the document -------------------------------------------------------

    def machine(self, name: str) -> Optional[Dict[str, Any]]:
        """One machine's record (deep-ish copy), with derived health."""
        with self._lock:
            machine = self._machines.get(name)
            if machine is None:
                return None
            machine = json.loads(json.dumps(machine))
        machine["health"] = {
            "score": health_score(machine),
            "state": machine_state(machine),
        }
        return machine

    def document(self) -> Dict[str, Any]:
        # one json.dumps pass under the lock (the cheapest consistent
        # snapshot of the nested records), the loads + summarize +
        # derived-health math OUTSIDE it — document() runs on whichever
        # request thread loses the heartbeat race, and holding the
        # shared lock through the full round-trip would stall every
        # concurrent record_* call behind one serialization
        with self._lock:
            payload = json.dumps(self._machines, default=str)
            plan_accuracy = (
                dict(self._plan_accuracy) if self._plan_accuracy else None
            )
        machines = json.loads(payload)
        for machine in machines.values():
            machine["health"] = {
                "score": health_score(machine),
                "state": machine_state(machine),
            }
        doc: Dict[str, Any] = {
            "version": 1,
            "project": self.project,
            "updated_at": _iso(time.time()),
            "machines": machines,
            "summary": summarize(machines),
        }
        if plan_accuracy is not None:
            doc["plan_accuracy"] = plan_accuracy
        return doc

    def _refresh_summaries_locked(self) -> None:
        """Recompute the per-shard summary cache for shards touched
        since the last refresh (or stale past the breaker age-out
        window). Caller holds ``self._lock``."""
        now = time.time()
        for shard in range(self._shard_count):
            if (
                shard not in self._summary_dirty
                and shard in self._summary_cache
                and now - self._summary_stamp.get(shard, 0.0)
                <= _SUMMARY_MAX_AGE_S
            ):
                continue
            names = self._shard_members.get(shard) or ()
            machines = {
                name: self._machines[name]
                for name in names
                if name in self._machines
            }
            self._summary_cache[shard] = {
                "summary": summarize(machines),
                "offenders": _offenders(machines, _OFFENDER_CAP),
            }
            self._summary_stamp[shard] = now
        self._summary_dirty.clear()

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            if self._shard_count > 1:
                # fold of per-shard cached summaries: O(shards + dirty)
                # — this is what keeps the Prometheus scrape flat as
                # the fleet grows
                self._refresh_summaries_locked()
                return _fold_summaries(
                    entry["summary"] for entry in self._summary_cache.values()
                )
            machines = dict(self._machines)
        return summarize(machines)

    def offenders(self, top_k: int = 10) -> List[Dict[str, Any]]:
        """The ``top_k`` unhealthiest machines as bounded rows."""
        with self._lock:
            if self._shard_count > 1:
                self._refresh_summaries_locked()
                pools = [
                    entry["offenders"]
                    for entry in self._summary_cache.values()
                ]
                return _merge_offenders(pools, top_k)
            machines = dict(self._machines)
        return _offenders(machines, top_k)

    def bounded_document(self, top_k: int = 10) -> Dict[str, Any]:
        """The summary-first view of this ledger: fleet summary, top-K
        offenders and the machine count — never the per-machine map.
        O(shards + dirty) however large the fleet is; what the bounded
        fleet-status path reads instead of :meth:`document`."""
        with self._lock:
            total = len(self._machines)
            plan_accuracy = (
                dict(self._plan_accuracy) if self._plan_accuracy else None
            )
            if self._shard_count > 1:
                self._refresh_summaries_locked()
                summary = _fold_summaries(
                    entry["summary"] for entry in self._summary_cache.values()
                )
                offenders = _merge_offenders(
                    [
                        entry["offenders"]
                        for entry in self._summary_cache.values()
                    ],
                    top_k,
                )
                machines = None
            else:
                machines = dict(self._machines)
        if machines is not None:
            summary = summarize(machines)
            offenders = _offenders(machines, top_k)
        doc: Dict[str, Any] = {
            "version": 1,
            "project": self.project,
            "updated_at": _iso(time.time()),
            "machines_total": total,
            "summary": summary,
            "offenders": offenders,
        }
        if plan_accuracy is not None:
            doc["plan_accuracy"] = plan_accuracy
        return doc

    # -- persistence --------------------------------------------------------

    def write(self, force: bool = False) -> None:
        """Atomically replace the snapshot (best-effort, throttled).
        Forced writes (state transitions) also notify listeners.

        Monolithic layout (one shard): the whole document replaces
        ``fleet_health.json`` exactly as it always has. Sharded layout:
        only the shards dirtied since the last flush are rewritten —
        one noisy machine costs one bounded shard file, not the fleet."""
        if self.path is None:
            return
        now = time.time()
        with self._write_lock:
            with self._lock:
                if not force and now - self._last_write < self.heartbeat_seconds:
                    return
                self._last_write = now
                listeners = list(self._listeners)
                sharded = self._shard_count > 1
            if sharded:
                summary = self._write_shards()
            else:
                doc = self.document()
                summary = doc["summary"]
                tmp = os.path.join(
                    os.path.dirname(self.path),
                    f".{FLEET_HEALTH_FILE}.tmp-{os.getpid()}",
                )
                try:
                    os.makedirs(os.path.dirname(self.path), exist_ok=True)
                    with open(tmp, "w") as f:
                        json.dump(doc, f, default=str)
                    os.replace(tmp, self.path)
                except OSError as exc:
                    logger.debug("fleet_health snapshot not written: %r", exc)
                    with contextlib.suppress(OSError):
                        os.remove(tmp)
                with self._lock:
                    self._dirty.clear()
                self._cleanup_shard_layout()
        if force and summary is not None:
            for listener in listeners:
                try:
                    listener(summary)
                except Exception:  # noqa: BLE001 - listeners are advisory
                    pass

    def _atomic_write(self, path: str, doc: Dict[str, Any]) -> None:
        tmp = os.path.join(
            os.path.dirname(path),
            f".{os.path.basename(path)}.tmp-{os.getpid()}",
        )
        try:
            with open(tmp, "w") as f:
                json.dump(doc, f, default=str)
            os.replace(tmp, path)
        except OSError:
            with contextlib.suppress(OSError):
                os.remove(tmp)
            raise

    def _write_shards(self) -> Optional[Dict[str, Any]]:
        """Flush the dirty shards (serialize under the lock, write
        outside it) plus the bounded ``summary.json``; returns the
        folded fleet summary. Caller holds ``self._write_lock``."""
        if self.shard_dir is None:
            return None
        with self._lock:
            count = self._shard_count
            dirty = sorted(self._dirty)
            self._dirty.clear()
            layout_changed = self._layout_changed
            self._layout_changed = False
            payloads = {}
            for shard in dirty:
                names = self._shard_members.get(shard) or ()
                payloads[shard] = json.dumps(
                    {
                        name: self._machines[name]
                        for name in sorted(names)
                        if name in self._machines
                    },
                    default=str,
                )
            plan_accuracy = (
                dict(self._plan_accuracy) if self._plan_accuracy else None
            )
            total = len(self._machines)
            self._refresh_summaries_locked()
            shard_summaries = {
                shard: entry["summary"]
                for shard, entry in self._summary_cache.items()
            }
            offender_pools = [
                entry["offenders"] for entry in self._summary_cache.values()
            ]
        summary = _fold_summaries(shard_summaries.values())
        offenders = _merge_offenders(offender_pools, _OFFENDER_CAP)
        stamp = _iso(time.time())
        current_names = {_shard_file_name(k, count) for k in range(count)}
        try:
            os.makedirs(self.shard_dir, exist_ok=True)
            if layout_changed:
                # a reshard re-homes every machine: drop files from the
                # previous layout so merge-on-read never sees a machine
                # in two generations of shards
                for entry in os.listdir(self.shard_dir):
                    if (
                        entry.startswith("shard-")
                        and entry.endswith(".json")
                        and entry not in current_names
                    ):
                        with contextlib.suppress(OSError):
                            os.remove(os.path.join(self.shard_dir, entry))
            for shard in dirty:
                machines = json.loads(payloads[shard])
                for machine in machines.values():
                    machine["health"] = {
                        "score": health_score(machine),
                        "state": machine_state(machine),
                    }
                shard_doc = {
                    "version": 1,
                    "kind": "fleet-health-shard",
                    "project": self.project,
                    "updated_at": stamp,
                    "shard": shard,
                    "shards": count,
                    "machines": machines,
                    "summary": shard_summaries.get(shard),
                }
                self._atomic_write(
                    os.path.join(
                        self.shard_dir, _shard_file_name(shard, count)
                    ),
                    shard_doc,
                )
            summary_doc: Dict[str, Any] = {
                "version": 1,
                "kind": "fleet-health-summary",
                "project": self.project,
                "updated_at": stamp,
                "shards": count,
                "machines_total": total,
                "summary": summary,
                "offenders": offenders,
            }
            if plan_accuracy is not None:
                summary_doc["plan_accuracy"] = plan_accuracy
            self._atomic_write(
                os.path.join(self.shard_dir, FLEET_HEALTH_SUMMARY_FILE),
                summary_doc,
            )
            # the shard layout is now authoritative: retire this
            # worker's monolithic spelling so merge-on-read can never
            # double-count the two layouts (the migration contract —
            # the legacy file is read once at restore, then gone)
            if self.path and os.path.exists(self.path):
                with contextlib.suppress(OSError):
                    os.remove(self.path)
        except OSError as exc:
            logger.debug("fleet_health shard flush failed: %r", exc)
        return summary

    def _cleanup_shard_layout(self) -> None:
        """Monolithic mode: remove a stale shard directory left by a
        previous (larger) incarnation, so readers never merge both."""
        if self.shard_dir is None or not os.path.isdir(self.shard_dir):
            return
        with contextlib.suppress(OSError):
            for entry in os.listdir(self.shard_dir):
                with contextlib.suppress(OSError):
                    os.remove(os.path.join(self.shard_dir, entry))
            os.rmdir(self.shard_dir)

    def flush(self) -> None:
        self.write(force=True)

    def restore(self, doc: Dict[str, Any]) -> None:
        """Adopt a previously persisted snapshot (a restarted server
        resumes its counts instead of starting the fleet 'healthy')."""
        if not isinstance(doc, dict) or not isinstance(
            doc.get("machines"), dict
        ):
            return
        template = _new_machine()
        with self._lock:
            for name, record in doc["machines"].items():
                machine = self._machine(str(name))
                for section in template:
                    incoming = record.get(section)
                    if isinstance(incoming, dict):
                        for key in template[section]:
                            if key in incoming:
                                machine[section][key] = incoming[key]
            if isinstance(doc.get("plan_accuracy"), dict):
                self._plan_accuracy = dict(doc["plan_accuracy"])

    def _load_own_snapshot(self) -> Optional[Dict[str, Any]]:
        """This worker's persisted state, whichever layout it left:
        the shard directory when it has files (newest flush wins per
        machine), else the legacy monolithic document — read ONCE here;
        the first sharded flush retires it."""
        if self.shard_dir:
            shard_docs = []
            for path in _shard_files(self.shard_dir):
                doc = _load_json(path)
                if isinstance(doc, dict) and isinstance(
                    doc.get("machines"), dict
                ):
                    shard_docs.append(doc)
            if shard_docs:
                shard_docs.sort(key=lambda d: str(d.get("updated_at") or ""))
                machines: Dict[str, Any] = {}
                for doc in shard_docs:
                    machines.update(doc["machines"])
                merged: Dict[str, Any] = {"machines": machines}
                summary_doc = _load_json(
                    os.path.join(self.shard_dir, FLEET_HEALTH_SUMMARY_FILE)
                )
                if isinstance(summary_doc, dict) and isinstance(
                    summary_doc.get("plan_accuracy"), dict
                ):
                    merged["plan_accuracy"] = summary_doc["plan_accuracy"]
                return merged
        return _load_json(self.path) if self.path else None


# -- the process-global registry ---------------------------------------------

_registry_lock = threading.Lock()
_ledgers: Dict[str, FleetHealthLedger] = {}


def _reset_after_fork() -> None:
    """Drop inherited ledgers in a freshly forked worker: each froze
    the PARENT's pid-suffixed snapshot path at construction, so N
    children writing through them would clobber one shared file — the
    gunicorn ``--preload`` collision the per-call ``_pid`` check in
    :func:`ledger_for` also guards (the reset makes the fresh start
    unconditional; the check stays as belt-and-braces). The child is
    single-threaded here and the inherited lock may be frozen
    mid-acquire, so rebind both without locking."""
    global _registry_lock, _ledgers
    _registry_lock = threading.Lock()
    # gt-lint: disable=lock-guard -- post-fork child is single-threaded;
    # taking the (possibly frozen-held) inherited lock could deadlock
    _ledgers = {}


register_postfork_reset(_reset_after_fork, name="telemetry.fleet_health.ledgers")


def ledger_for(directory: str, project: str = "") -> Any:
    """The (create-once) ledger for an artifact directory, or
    :data:`NULL_LEDGER` when health telemetry is off. One ledger per
    normalized path — the builder, the serve path and the lifecycle
    supervisor all feed the same record set for the same directory."""
    if not health_enabled():
        return NULL_LEDGER
    key = os.path.normpath(directory)
    ledger = _ledgers.get(key)
    if ledger is not None and ledger._pid == os.getpid():
        return ledger
    with _registry_lock:
        ledger = _ledgers.get(key)
        if ledger is not None and ledger._pid != os.getpid():
            # inherited across a fork: the snapshot path froze the
            # PARENT's pid, so every child writing through it would
            # clobber one shared file — exactly the collision the
            # worker-sink split exists to prevent. Rebuild per process.
            ledger = None
        if ledger is None:
            ledger = FleetHealthLedger(directory=key, project=project)
            # restore from the ledger's OWN snapshot (pid-suffixed
            # under worker sinks; shard dir when the last incarnation
            # was sharded): adopting another worker's snapshot would
            # double its counts once readers merge the variants
            persisted = ledger._load_own_snapshot()
            if isinstance(persisted, dict):
                ledger.restore(persisted)
            _ledgers[key] = ledger
    return ledger


def ledger_summaries() -> Dict[str, Dict[str, Any]]:
    """directory -> bounded summary for every live ledger (what the
    Prometheus fleet-health collector reads at scrape time)."""
    with _registry_lock:
        ledgers = dict(_ledgers)
    return {path: ledger.summary() for path, ledger in ledgers.items()}


def reset_ledgers() -> None:
    """Drop every live ledger (tests only)."""
    with _registry_lock:
        _ledgers.clear()


def _load_shard_unit(shard_dir: str) -> Optional[Dict[str, Any]]:
    """One worker's shard directory folded back into a single health
    document (machines union, newest flush wins; plan accuracy from
    ``summary.json``)."""
    shard_docs = []
    for path in _shard_files(shard_dir):
        doc = _load_json(path)
        if isinstance(doc, dict) and isinstance(doc.get("machines"), dict):
            shard_docs.append(doc)
    if not shard_docs:
        return None
    shard_docs.sort(key=lambda d: str(d.get("updated_at") or ""))
    machines: Dict[str, Any] = {}
    for doc in shard_docs:
        machines.update(doc["machines"])
    newest = shard_docs[-1]
    merged: Dict[str, Any] = {
        "version": 1,
        "project": newest.get("project", ""),
        "updated_at": newest.get("updated_at"),
        "machines": machines,
        "summary": summarize(machines),
    }
    summary_doc = _load_json(
        os.path.join(shard_dir, FLEET_HEALTH_SUMMARY_FILE)
    )
    if isinstance(summary_doc, dict) and isinstance(
        summary_doc.get("plan_accuracy"), dict
    ):
        merged["plan_accuracy"] = summary_doc["plan_accuracy"]
    return merged


def load_health(directory: str) -> Optional[Dict[str, Any]]:
    """The persisted shared-spelling health snapshot from ``directory``
    (the ``fleet_health.d/`` shard layout when present, else the
    monolithic ``fleet_health.json``), or None."""
    shard_dir = os.path.join(directory, FLEET_HEALTH_SHARD_DIR)
    if os.path.isdir(shard_dir):
        doc = _load_shard_unit(shard_dir)
        if doc is not None:
            return doc
    doc = _load_json(os.path.join(directory, FLEET_HEALTH_FILE))
    return doc if isinstance(doc, dict) else None


def health_snapshot_paths(directory: str) -> List[str]:
    """Every persisted monolithic health snapshot in ``directory``: the
    shared ``fleet_health.json`` plus per-worker
    ``fleet_health-<pid>.json`` variants (one grammar:
    ``aggregate.is_worker_variant``), sorted for determinism. Sharded
    workers don't appear here — see :func:`health_snapshot_units`."""
    from .aggregate import is_worker_variant

    try:
        entries = os.listdir(directory)
    except OSError:
        return []
    return [
        os.path.join(directory, entry)
        for entry in sorted(entries)
        if entry == FLEET_HEALTH_FILE
        or is_worker_variant(entry, FLEET_HEALTH_FILE)
    ]


def health_snapshot_units(directory: str) -> List[Dict[str, Any]]:
    """Every persisted health snapshot in ``directory``, one unit per
    WORKER: ``{"stem", "kind": "file"|"shards", "paths", "dir"}``. A
    worker that left both layouts (a crash between the shard flush and
    the legacy unlink) counts once — the shard directory wins, so the
    merge can never double its records."""
    from .aggregate import is_worker_variant

    try:
        entries = os.listdir(directory)
    except OSError:
        return []
    files: Dict[str, str] = {}
    shard_dirs: Dict[str, str] = {}
    for entry in sorted(entries):
        path = os.path.join(directory, entry)
        if entry == FLEET_HEALTH_FILE or is_worker_variant(
            entry, FLEET_HEALTH_FILE
        ):
            files[os.path.splitext(entry)[0]] = path
        elif (
            entry == FLEET_HEALTH_SHARD_DIR
            or is_worker_variant(entry, FLEET_HEALTH_SHARD_DIR)
        ) and os.path.isdir(path):
            shard_dirs[os.path.splitext(entry)[0]] = path
    units: List[Dict[str, Any]] = []
    for stem in sorted(set(files) | set(shard_dirs)):
        shard_dir = shard_dirs.get(stem)
        if shard_dir is not None:
            paths = _shard_files(shard_dir)
            if paths:
                units.append(
                    {
                        "stem": stem,
                        "kind": "shards",
                        "paths": paths,
                        "dir": shard_dir,
                    }
                )
                continue
        if stem in files:
            units.append(
                {
                    "stem": stem,
                    "kind": "file",
                    "paths": [files[stem]],
                    "dir": None,
                }
            )
    return units


def _load_unit_document(unit: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if unit["kind"] == "shards":
        return _load_shard_unit(unit["dir"])
    doc = _load_json(unit["paths"][0])
    return doc if isinstance(doc, dict) else None


def _unit_summary(unit: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """A worker unit's bounded summary WITHOUT parsing its machines:
    ``summary.json`` for sharded units (constant-size however large the
    worker's fleet), the persisted document's own summary for monolithic
    units (whose size is bounded by the monolithic threshold anyway).
    Returns ``{"summary", "offenders"?, "machines_total"?, ...}``."""
    if unit["kind"] == "shards":
        doc = _load_json(
            os.path.join(unit["dir"], FLEET_HEALTH_SUMMARY_FILE)
        )
        if isinstance(doc, dict) and isinstance(doc.get("summary"), dict):
            return doc
        return None
    doc = _load_json(unit["paths"][0])
    if isinstance(doc, dict) and isinstance(doc.get("summary"), dict):
        return {
            "summary": doc["summary"],
            "machines_total": len(doc.get("machines") or {}),
            "updated_at": doc.get("updated_at"),
            "plan_accuracy": doc.get("plan_accuracy"),
        }
    return None


def _newest(records: List[Dict[str, Any]], stamp_key: str) -> Dict[str, Any]:
    """The record with the greatest ISO timestamp at ``stamp_key``
    (records with no stamp lose to any stamped one; ties keep the
    later-listed, i.e. the live document's)."""
    best = records[0]
    best_stamp = str(best.get(stamp_key) or "")
    for record in records[1:]:
        stamp = str(record.get(stamp_key) or "")
        if stamp >= best_stamp:
            best, best_stamp = record, stamp
    return best


#: per-section timestamp used to pick the authoritative worker for the
#: non-additive machine sections (state, not counts)
_SECTION_STAMPS = {
    "drift": "evaluated_at",
    "build": "built_at",
    "quarantine": "since",
    "breaker": "updated_at",
}


def merge_health_documents(
    docs: List[Dict[str, Any]],
) -> Optional[Dict[str, Any]]:
    """
    One fleet-health document out of N per-worker snapshots:

    - **serving counts are summed** (requests/errors/rows — each worker
      saw a disjoint slice of the traffic, so the fleet totals are the
      sums; the RED regression test pins aggregated == Σ per-worker);
    - the residual mean is the row-weighted mean of the workers' means;
    - **state sections** (drift verdicts, build provenance, quarantine)
      are not additive — the record with the newest section timestamp
      wins (every worker that observed the transition wrote the same
      facts, the newest is simply the most current);
    - derived health (score/state) and the bounded summary are
      recomputed over the merged records.
    """
    docs = [
        doc
        for doc in docs
        if isinstance(doc, dict) and isinstance(doc.get("machines"), dict)
    ]
    if not docs:
        return None
    merged_machines: Dict[str, Dict[str, Any]] = {}
    by_machine: Dict[str, List[Dict[str, Any]]] = {}
    for doc in docs:
        for name, record in doc["machines"].items():
            if isinstance(record, dict):
                by_machine.setdefault(str(name), []).append(record)
    for name, records in by_machine.items():
        machine = _new_machine()
        serving = machine["serving"]
        weighted_residual = 0.0
        residual_rows = 0
        for record in records:
            incoming = record.get("serving") or {}
            serving["requests"] += int(incoming.get("requests") or 0)
            serving["errors"] += int(incoming.get("errors") or 0)
            rows = int(incoming.get("rows") or 0)
            serving["rows"] += rows
            residual = incoming.get("residual_mean")
            if residual is not None and rows > 0:
                weighted_residual += float(residual) * rows
                residual_rows += rows
            stamp = incoming.get("last_request_at")
            if stamp and str(stamp) > str(serving["last_request_at"] or ""):
                serving["last_request_at"] = stamp
        if residual_rows:
            serving["residual_mean"] = round(
                weighted_residual / residual_rows, 8
            )
        for section, stamp_key in _SECTION_STAMPS.items():
            candidates = [
                record[section]
                for record in records
                if isinstance(record.get(section), dict)
            ]
            if candidates:
                chosen = _newest(candidates, stamp_key)
                for key in machine[section]:
                    if key in chosen:
                        machine[section][key] = chosen[key]
        machine["health"] = {
            "score": health_score(machine),
            "state": machine_state(machine),
        }
        merged_machines[name] = machine
    newest_doc = _newest(docs, "updated_at")
    merged: Dict[str, Any] = {
        "version": 1,
        "project": newest_doc.get("project", ""),
        "updated_at": newest_doc.get("updated_at"),
        "workers_merged": len(docs),
        "machines": merged_machines,
        "summary": summarize(merged_machines),
    }
    accuracy = [
        doc["plan_accuracy"]
        for doc in docs
        if isinstance(doc.get("plan_accuracy"), dict)
    ]
    if accuracy:
        merged["plan_accuracy"] = accuracy[-1]
    return merged


def load_merged_health(
    directory: str,
    live_documents: Optional[List[Dict[str, Any]]] = None,
    exclude_paths: Optional[List[str]] = None,
) -> Optional[Dict[str, Any]]:
    """The merged health view over every snapshot in ``directory``,
    optionally folding in live in-process documents — whose own snapshot
    paths go in ``exclude_paths`` so a worker's counts never merge with
    its own persisted copy (see :func:`fleet_status_document`)."""
    docs = list(live_documents or [])
    # exclusion is per WORKER (stem), not per file: a live ledger must
    # skip its own persisted copy whichever layout it last wrote
    excluded = {
        os.path.splitext(os.path.basename(p))[0]
        for p in (exclude_paths or [])
    }
    for unit in health_snapshot_units(directory):
        if unit["stem"] in excluded:
            continue
        doc = _load_unit_document(unit)
        if isinstance(doc, dict):
            docs.append(doc)
    if len(docs) == 1:
        only = docs[0]
        if "machines" in only and "summary" in only:
            return only
    return merge_health_documents(docs)


def breaker_tripped_machines(
    directory: str, max_age_s: float = 3600.0
) -> Dict[str, Dict[str, Any]]:
    """
    Machines whose SERVING circuit breaker is currently tripped (open or
    probing half-open), from the merged health snapshots under
    ``directory`` — the feed the lifecycle supervisor reads to nominate
    tripped members as rebuild candidates (the serve layer never imports
    lifecycle; the ledger is the arrow between them).

    ``max_age_s`` ignores stale trip records (the shared
    :func:`_live_breaker_state` cutoff): a dead server (or a revision
    swapped out from under its ledger) can never resolve its own
    record, and a forgotten ``open`` stamp must not drive rebuild
    canaries forever (the same reasoning as the SLO engine's
    ``firing_alerts(max_age_s=...)``).

    Bounded fast path: every worker's persisted summary carries a
    ``breaker_tripped`` count (a trip forces a flush, so the counts are
    current); when they all read zero the full machine parse — O(N)
    per lifecycle cycle at 10k members — is skipped entirely.
    """
    # (only when the caller's cutoff is at most the summaries' own —
    # a laxer cutoff, including 0 = "no cutoff", could admit records
    # the summaries already aged out)
    units = (
        health_snapshot_units(directory)
        if 0 < max_age_s <= BREAKER_STATE_MAX_AGE_S
        else []
    )
    if units:
        tripped_hint = 0
        for unit in units:
            summary_doc = _unit_summary(unit)
            summary = (summary_doc or {}).get("summary")
            count = (summary or {}).get("breaker_tripped")
            if count is None:
                # pre-upgrade snapshot without the count: can't prove
                # anything cheaply, fall through to the full read
                tripped_hint = -1
                break
            tripped_hint += int(count)
        if tripped_hint == 0:
            return {}
    doc = load_merged_health(directory)
    if not isinstance(doc, dict):
        return {}
    tripped: Dict[str, Dict[str, Any]] = {}
    for name, record in (doc.get("machines") or {}).items():
        if _live_breaker_state(record or {}, max_age_s=max_age_s) is None:
            continue
        tripped[str(name)] = dict((record or {}).get("breaker") or {})
    return tripped


# -- the joined fleet-status surface -----------------------------------------


def _load_json(path: str) -> Optional[Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _machine_selection(
    machines: Union[None, str, Iterable[str]],
) -> Tuple[Optional[str], Optional[List[str]]]:
    """Normalize the ``machines=`` selector: ``(kind, names)`` where
    kind is None (adaptive default), ``"none"``, ``"all"``, a state
    filter (``healthy``/``degraded``/``drifting``/``quarantined``/
    ``unhealthy``) or ``"names"``."""
    if machines is None:
        return None, None
    if isinstance(machines, str):
        token = machines.strip()
        low = token.lower()
        if low in ("", "none", "summary"):
            return "none", None
        if low == "all":
            return "all", None
        if low in ("healthy", "degraded", "drifting", "quarantined", "unhealthy"):
            return low, None
        return "names", [t.strip() for t in token.split(",") if t.strip()]
    return "names", [str(name) for name in machines]


def _select_machines(
    machines: Dict[str, Dict[str, Any]],
    kind: Optional[str],
    names: Optional[List[str]],
    offset: int,
    limit: int,
) -> Tuple[Dict[str, Dict[str, Any]], bool]:
    """Apply a normalized selector + page window to the merged machine
    map; returns (selected, truncated)."""
    if kind == "names":
        wanted = [n for n in (names or []) if n in machines]
        page = wanted[offset : offset + limit]
        return (
            {name: machines[name] for name in page},
            len(wanted) > offset + len(page),
        )
    if kind == "unhealthy":
        pool = [
            name
            for name in sorted(machines)
            if (machines[name].get("health") or {}).get("state") != "healthy"
        ]
    elif kind in ("healthy", "degraded", "drifting", "quarantined"):
        pool = [
            name
            for name in sorted(machines)
            if (machines[name].get("health") or {}).get("state") == kind
        ]
    else:  # "all"
        pool = sorted(machines)
    page = pool[offset : offset + limit]
    return (
        {name: machines[name] for name in page},
        len(pool) > offset + len(page),
    )


def _doc_offenders(
    machines: Dict[str, Dict[str, Any]], top_k: int
) -> List[Dict[str, Any]]:
    """Top-K offender rows from a merged document's machine map (whose
    records already carry derived ``health``)."""
    entries = []
    for name, record in machines.items():
        health = record.get("health") or {}
        state = health.get("state")
        if state in (None, "healthy"):
            continue
        entries.append(
            {
                "machine": name,
                "score": health.get("score", 0.0),
                "state": state,
                "reason": _offender_reason(record, state),
            }
        )
    return heapq.nsmallest(
        top_k, entries, key=lambda e: (e["score"], e["machine"])
    )


def fleet_status_document(
    directory: str,
    device: Optional[Dict[str, Any]] = None,
    programs: Optional[Dict[str, Any]] = None,
    serving: Optional[Dict[str, Any]] = None,
    stream: Optional[Dict[str, Any]] = None,
    machines: Union[None, str, Iterable[str]] = None,
    limit: Optional[int] = None,
    offset: int = 0,
) -> Dict[str, Any]:
    """
    The one joined operator view over a build+serve directory:

    - ``build`` — the live ``build_status.json`` heartbeat (PR 3);
    - ``plan`` — ``fleet_plan.json`` strategy/totals plus the measured
      plan-accuracy actuals recorded into the health ledger;
    - ``lifecycle`` — the supervisor's ``state.json`` phase/identities,
      quarantine record count, and most recent history events;
    - ``health`` — the per-machine ledger (live when this process holds
      one, else the persisted snapshot) and its bounded summary;
    - ``device`` — injected device-utilization stats (memory +
      compile-cache counters; ``telemetry.device.utilization_snapshot``)
    - ``programs`` — injected serving program-cache stats.
    - ``serving`` — injected serve-engine stats (batch/shed counters and
      the precision ladder: per-precision coalesce counts, degrade
      counter, cached precision-parity gate reports).
    - ``stream`` — injected streaming-plane stats
      (``gordo_tpu.stream.plane.stream_plane_section``, like the other
      injected sections — telemetry never imports the plane):
      session/subscriber counts, the summed zero-gap row accounting,
      score-lag and watermark-delay freshness, flush/lag percentiles.

    Sections degrade to None independently: a build dir with no
    lifecycle state still joins, a serve dir with no plan still joins.

    The health section is BOUNDED at scale: per-machine records are
    inlined only while the fleet fits ``GORDO_TPU_FLEET_STATUS_MAX_MACHINES``
    (default 500); past that the section carries the summary, the
    machine count and the top-K offenders. ``machines=`` selects
    explicitly — ``"all"`` / a state name / ``"unhealthy"`` / a
    comma-separated name list / ``"none"`` — with ``limit``/``offset``
    paging (capped at the same knob).
    """
    from .progress import load_status

    directory = os.path.normpath(directory)
    root = os.path.dirname(directory)
    doc: Dict[str, Any] = {
        "version": 1,
        "directory": directory,
        "revision": os.path.basename(directory),
        "generated_at": _iso(time.time()),
    }
    doc["build"] = load_status(directory)

    plan = _load_json(os.path.join(directory, "fleet_plan.json"))

    from ..utils.env import env_int

    kind, names = _machine_selection(machines)
    max_inline = max(
        1,
        env_int(
            FLEET_STATUS_MAX_MACHINES_ENV, DEFAULT_FLEET_STATUS_MAX_MACHINES
        ),
    )
    top_k = max(
        1, env_int(FLEET_STATUS_TOP_K_ENV, DEFAULT_FLEET_STATUS_TOP_K)
    )
    page_limit = (
        max_inline if limit is None else max(0, min(int(limit), max_inline))
    )
    page_offset = max(0, int(offset or 0))

    # the health view is a MERGE: this process's live ledger (its own
    # snapshot excluded by worker stem — a worker must not double-count
    # with its persisted copy) plus every other worker's snapshots.
    # Bounded-first: when no per-machine records are wanted (or the
    # fleet outgrew the inline threshold) and a single source can
    # answer, the summary path never materializes the machine map —
    # O(shards), not O(fleet).
    ledger = _ledgers.get(directory)
    own_stems = set()
    if ledger is not None and ledger.path:
        own_stems.add(os.path.splitext(os.path.basename(ledger.path))[0])
    units = [
        unit
        for unit in health_snapshot_units(directory)
        if unit["stem"] not in own_stems
    ]
    single_live = ledger is not None and not units

    bounded_doc: Optional[Dict[str, Any]] = None
    health_doc: Optional[Dict[str, Any]] = None
    if single_live and (
        kind == "none"
        or (kind is None and ledger.machine_count() > max_inline)
    ):
        bounded_doc = ledger.bounded_document(top_k)
    elif (
        kind in (None, "none")
        and ledger is None
        and len(units) == 1
        and units[0]["kind"] == "shards"
    ):
        candidate = _unit_summary(units[0])
        if candidate is not None and (
            kind == "none"
            or int(candidate.get("machines_total") or 0) > max_inline
        ):
            bounded_doc = candidate
    if bounded_doc is None:
        live_docs = [ledger.document()] if ledger is not None else []
        own_paths = (
            [ledger.path] if ledger is not None and ledger.path else []
        )
        health_doc = load_merged_health(
            directory, live_documents=live_docs, exclude_paths=own_paths
        )

    accuracy_source = (
        bounded_doc if bounded_doc is not None else (health_doc or {})
    )
    if isinstance(plan, dict):
        doc["plan"] = {
            "strategy": plan.get("strategy"),
            "totals": plan.get("totals"),
            "accuracy": accuracy_source.get("plan_accuracy"),
        }
    else:
        doc["plan"] = None

    state = _load_json(
        os.path.join(root, _LIFECYCLE_DIR, _LIFECYCLE_STATE_FILE)
    )
    quarantine = _load_json(
        os.path.join(root, _LIFECYCLE_DIR, _LIFECYCLE_QUARANTINE_FILE)
    )
    if isinstance(state, dict):
        doc["lifecycle"] = {
            "phase": state.get("phase"),
            "serving_revision": state.get("serving_revision"),
            "canary_revision": state.get("canary_revision"),
            "stale": state.get("stale") or [],
            "quarantine_records": (
                len(quarantine) if isinstance(quarantine, list) else 0
            ),
            "history": (state.get("history") or [])[-5:],
        }
    else:
        doc["lifecycle"] = None

    if bounded_doc is not None:
        total = int(bounded_doc.get("machines_total") or 0)
        doc["health"] = {
            "summary": bounded_doc.get("summary"),
            "machines": None,
            "machines_total": total,
            "machines_truncated": total > 0,
            "top_offenders": (bounded_doc.get("offenders") or [])[:top_k],
            "updated_at": bounded_doc.get("updated_at"),
        }
    elif health_doc is not None:
        machines_all = health_doc.get("machines") or {}
        total = len(machines_all)
        section: Dict[str, Any] = {
            "summary": health_doc.get("summary"),
            "updated_at": health_doc.get("updated_at"),
            "machines_total": total,
            "top_offenders": _doc_offenders(machines_all, top_k),
        }
        if kind is None:
            # adaptive default: small fleets inline every record (the
            # document everyone always got); big ones get the bounded
            # summary + offenders and explicit selection on request
            if total <= max_inline:
                section["machines"] = machines_all
                section["machines_truncated"] = False
            else:
                section["machines"] = None
                section["machines_truncated"] = True
        elif kind == "none":
            section["machines"] = None
            section["machines_truncated"] = total > 0
        else:
            selected, truncated = _select_machines(
                machines_all, kind, names, page_offset, page_limit
            )
            section["machines"] = selected
            section["machines_offset"] = page_offset
            section["machines_truncated"] = truncated
        if health_doc.get("workers_merged"):
            section["workers_merged"] = health_doc["workers_merged"]
        doc["health"] = section
    else:
        doc["health"] = None
    # the SLO verdict joins the console: alert states from the engine's
    # persisted state machine (slo.py), summarized — budgets/burn rates
    # live in the full `gordo-tpu slo status` / /slo route document.
    # The state lives where the SINKS live (the configured telemetry
    # dir when set, else this directory) — resolved exactly as the /slo
    # route resolves it, so the two surfaces can never disagree
    from .slo import slo_directory, slo_section

    doc["slo"] = slo_section(slo_directory(directory) or directory)
    doc["device"] = device
    doc["programs"] = programs
    doc["serving"] = serving
    # the streaming plane joins the console like device/programs — an
    # injected live-process section, None wherever no plane is installed
    doc["stream"] = stream
    return doc


def render_fleet_status(doc: Dict[str, Any]) -> str:
    """Human rendering of the joined document (the ``fleet-status``
    CLI's table view)."""
    lines: List[str] = [
        f"Directory: {doc.get('directory', '-')}",
        f"Revision:  {doc.get('revision', '-')}",
    ]
    build = doc.get("build")
    if build:
        machines = build.get("machines") or {}
        lines.append(
            f"Build:     {build.get('state', '?')}"
            + (f" (phase: {build.get('phase')})" if build.get("phase") else "")
            + f" — {machines.get('completed', 0)}/{machines.get('total', 0)}"
            f" done, {machines.get('failed', 0)} failed"
        )
    else:
        lines.append("Build:     (no build_status.json)")
    plan = doc.get("plan")
    if plan and plan.get("totals"):
        totals = plan["totals"]
        accuracy = plan.get("accuracy") or {}
        lines.append(
            f"Plan:      {plan.get('strategy', '?')} — "
            f"{totals.get('buckets', 0)} bucket(s), "
            f"{totals.get('compiles', 0)} predicted compile(s), "
            f"waste {100.0 * float(totals.get('padding_waste') or 0.0):.1f}%"
        )
        if accuracy:
            measured = accuracy.get("measured_member_waste")
            hbm = accuracy.get("measured_hbm_peak_bytes")
            lines.append(
                "  actuals: "
                f"{accuracy.get('actual_compiles', '?')} compile(s), "
                f"fit {accuracy.get('actual_fit_s', '?')}s"
                + (
                    f", member waste {100.0 * float(measured):.1f}%"
                    if measured is not None
                    else ""
                )
                + (
                    f", HBM peak {int(hbm) / (1 << 20):.1f} MiB"
                    if hbm
                    else ""
                )
            )
    lifecycle = doc.get("lifecycle")
    if lifecycle:
        lines.append(
            f"Lifecycle: {lifecycle.get('phase', '?')} — "
            f"serving {lifecycle.get('serving_revision') or '-'}"
            + (
                f", canary {lifecycle['canary_revision']}"
                if lifecycle.get("canary_revision")
                else ""
            )
            + (
                f", {lifecycle.get('quarantine_records')} quarantine record(s)"
                if lifecycle.get("quarantine_records")
                else ""
            )
        )
    health = doc.get("health")
    if health and health.get("summary"):
        summary = health["summary"]
        lines.append(
            f"Health:    {summary.get('machines', 0)} machine(s) — "
            f"{summary.get('healthy', 0)} healthy, "
            f"{summary.get('drifting', 0)} drifting, "
            f"{summary.get('degraded', 0)} degraded, "
            f"{summary.get('quarantined', 0)} quarantined"
            f" (error rate {100.0 * float(summary.get('error_rate') or 0.0):.2f}%)"
        )
        total = health.get("machines_total")
        shown = health.get("machines")
        if health.get("machines_truncated") and total:
            lines.append(
                f"  (per-machine records elided at {total} members — "
                "select with --machines/?machines=)"
            )
        elif isinstance(shown, dict) and total and len(shown) < total:
            lines.append(
                f"  (showing {len(shown)} of {total} machine record(s))"
            )
        offenders = health.get("top_offenders")
        if offenders is None:
            # pre-upgrade documents: derive from the inline records
            machines = shown or {}
            offenders = [
                {
                    "machine": name,
                    "score": record["health"]["score"],
                    "state": record["health"]["state"],
                    "reason": _offender_reason(
                        record, record["health"]["state"]
                    ),
                }
                for name, record in machines.items()
                if record.get("health", {}).get("state") != "healthy"
            ]
            offenders = heapq.nsmallest(
                10, offenders, key=lambda e: (e["score"], e["machine"])
            )
        for entry in offenders:
            lines.append(
                f"  {entry.get('machine')}: {entry.get('state')} "
                f"(score {float(entry.get('score') or 0.0):.2f})"
                + (
                    f" — {entry['reason']}"
                    if entry.get("reason")
                    else ""
                )
            )
    else:
        lines.append("Health:    (no fleet_health.json)")
    slo = doc.get("slo")
    if slo:
        firing = slo.get("firing", 0)
        pending = slo.get("pending", 0)
        verdict = "inside SLO" if slo.get("ok", True) else "BURNING"
        lines.append(
            f"SLO:       {verdict} — {firing} firing, {pending} pending "
            f"alert(s)"
        )
        for name, remaining in sorted((slo.get("budgets") or {}).items()):
            lines.append(
                f"  {name}: {100.0 * float(remaining):.1f}% budget remaining"
            )
    device = doc.get("device")
    if device:
        identity = device.get("device")
        if identity:
            lines.append(
                f"Platform:  {identity.get('platform')} — "
                f"{identity.get('count')} x {identity.get('device_kind')}"
            )
        memory = device.get("memory")
        if memory and memory.get("available"):
            lines.append(
                f"Device:    {memory.get('measured_devices', 0)} device(s) — "
                f"{memory.get('bytes_in_use', 0) / (1 << 20):.1f} MiB in use"
                + (
                    f", peak {memory['peak_bytes_in_use'] / (1 << 20):.1f} MiB"
                    if "peak_bytes_in_use" in memory
                    else ""
                )
                + (
                    f" ({100.0 * memory['utilization']:.1f}% of limit)"
                    if memory.get("utilization") is not None
                    else ""
                )
            )
        else:
            lines.append("Device:    memory stats unavailable on this backend")
        for kind, counters in sorted(
            (device.get("compile_cache") or {}).items()
        ):
            rate = counters.get("hit_rate")
            lines.append(
                f"  {kind} programs: {counters.get('compiles', 0)} compile(s), "
                f"{counters.get('cache_hits', 0)} cache hit(s)"
                + (f" ({100.0 * rate:.1f}% hit rate)" if rate is not None else "")
            )
        persistent = device.get("persistent_cache")
        if persistent:
            lines.append(
                f"  persistent cache: {persistent.get('entries', 0)} entr"
                f"{'y' if persistent.get('entries', 0) == 1 else 'ies'}, "
                f"{persistent.get('bytes', 0) / (1 << 20):.1f} MiB, "
                f"{persistent.get('hits', 0)} hit(s) / "
                f"{persistent.get('misses', 0)} miss(es) in this process "
                f"({persistent.get('path')})"
            )
    programs = doc.get("programs")
    if programs:
        lines.append(
            f"Programs:  {programs.get('programs', 0)} cached jit entr"
            f"{'y' if programs.get('programs', 0) == 1 else 'ies'}, "
            f"{programs.get('signatures', 0)} compiled signature(s)"
        )
        by_precision = programs.get("by_precision")
        if by_precision:
            lines.append(
                "  by precision: "
                + ", ".join(
                    f"{prec}={count}"
                    for prec, count in sorted(by_precision.items())
                )
            )
    serving = doc.get("serving")
    if serving:
        precision = serving.get("precision") or {}
        coalesced = precision.get("coalesced") or {}
        gates = [
            g for g in serving.get("gates", []) if isinstance(g, dict)
        ]
        lines.append(
            f"Serving:   precision={precision.get('config', 'f32')}"
            + (
                " — coalesced "
                + ", ".join(
                    f"{p}={n}" for p, n in sorted(coalesced.items())
                )
                if coalesced
                else ""
            )
            + (
                f", {serving.get('precision_degraded', 0)} degraded req(s)"
                if serving.get("precision_degraded")
                else ""
            )
        )
        for gate in gates:
            lines.append(
                f"  gate {gate.get('precision')}: "
                f"{'PASS' if gate.get('passed') else 'FAIL — degraded to f32'}"
                + (
                    f" (agreement {gate.get('agreement_min'):.4f})"
                    if gate.get("agreement_min") is not None
                    else ""
                )
            )
        breaker = serving.get("breaker") or {}
        if breaker.get("open") or breaker.get("half_open") or breaker.get(
            "trips"
        ):
            lines.append(
                f"  breakers: {breaker.get('open', 0)} open, "
                f"{breaker.get('half_open', 0)} half-open "
                f"({breaker.get('trips', 0)} trip(s) total)"
            )
            for member in breaker.get("members", [])[:5]:
                lines.append(
                    f"    {member.get('member')}: {member.get('state')}"
                    + (
                        f", cooldown {member.get('cooldown_s')}s"
                        if member.get("cooldown_s")
                        else ""
                    )
                )
    stream = doc.get("stream")
    if stream:
        accounting = stream.get("accounting") or {}
        lag = stream.get("lag") or {}
        lag_p95 = lag.get("lag_p95_ms")
        lines.append(
            f"Stream:    {stream.get('sessions_active', 0)} active "
            f"session(s), {stream.get('subscribers', 0)} subscriber(s)"
            + (" — DRAINING" if stream.get("draining") else "")
        )
        lines.append(
            f"  rows: {accounting.get('rows_in', 0)} in, "
            f"{accounting.get('rows_scored', 0)} scored, "
            f"{accounting.get('rows_failed', 0)} failed, "
            f"{accounting.get('rows_pending', 0)} pending, "
            f"{accounting.get('rows_shed', 0)} shed "
            f"(gap {accounting.get('gap', 0)})"
        )
        lines.append(
            f"  freshness: lag p95 "
            + (f"{lag_p95:g}ms" if lag_p95 is not None else "-")
            + (
                f", watermark delay {lag['watermark_delay_max_ms']:g}ms"
                if lag.get("watermark_delay_max_ms") is not None
                else ""
            )
            + (
                f", {stream['quarantined_machines']} quarantined machine(s)"
                if stream.get("quarantined_machines")
                else ""
            )
        )
    return "\n".join(lines)
