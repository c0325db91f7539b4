"""
The fleet SLO engine: objectives as data, burn-rate alerting as a
state machine, rollups as the evidence.

PRs 3/7/9 emit telemetry; always-on scoring fleets are *operated*
against objectives — "are we inside SLO, and how fast are we burning
error budget" — not raw spans. This module renders that judgment:

- **objectives are declared, not coded**: a ``slos.toml`` (shipped like
  ``analysis/contracts.toml``, overridable per deployment via
  ``GORDO_TPU_SLO_CONFIG`` or a file beside the telemetry sinks) names
  each SLO's objective (``availability`` / ``latency`` over the request
  plane; ``stream_freshness`` / ``stream_integrity`` over the streaming
  plane's rollup row accounting), target and window;
- **evaluation runs over rollups** (telemetry/aggregate.py), never the
  raw span corpus: one incremental aggregation pass, then window merges
  — asking "last 6h burn rate" costs a few hundred small JSON reads,
  not a 256MiB re-parse;
- **alerting is the multi-window fast/slow burn-rate pattern** (the SRE
  workbook's): an alert trips only when the long window AND its short
  confirmation window both burn above threshold, so a stale incident
  cannot page forever and a blip cannot page at all. Alert lifecycle is
  an explicit persisted state machine — ``pending → firing → resolved``
  — atomically journaled to ``slo_state.json`` so a restarted process
  (or the lifecycle supervisor, which holds promotions while a page
  alert fires) reads the same truth;
- surfaces: ``gordo-tpu slo status|check`` (check exits non-zero while
  firing), the ``/gordo/v0/<project>/slo`` route, a section in
  :func:`fleet_status_document`, and bounded Prometheus gauges
  (``gordo_slo_*`` — label cardinality is the declared SLO count, never
  fleet or traffic size).

Stdlib-only, like the whole telemetry package.
"""

import json
import logging
import os
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from .aggregate import (
    RollupStore,
    histogram_percentile,
    store_for,
    summarize_rollup,
)
from .recorder import _iso, enabled

try:  # Python >= 3.11
    import tomllib
except ImportError:  # pragma: no cover - exercised on 3.10 images
    tomllib = None

logger = logging.getLogger(__name__)

#: the persisted alert state machine, beside the rollups
SLO_STATE_FILE = "slo_state.json"
#: a deployment's own objectives, beside the telemetry sinks
SLO_CONFIG_FILE = "slos.toml"
#: explicit config override (path to a slos.toml)
SLO_CONFIG_ENV = "GORDO_TPU_SLO_CONFIG"
#: /metrics-driven re-evaluation throttle for watched directories
#: (seconds; 0 = scrapes report the cached status only)
SCRAPE_REFRESH_ENV = "GORDO_TPU_SLO_SCRAPE_REFRESH"
DEFAULT_SCRAPE_REFRESH = 60.0

DEFAULT_SLOS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), SLO_CONFIG_FILE
)

#: alert states, in escalation order (the Prometheus gauge exports the
#: index; ``resolved`` maps back to 0 — it is an annotation, not a page)
ALERT_STATES = ("inactive", "pending", "firing", "resolved")

_DURATION_RE = re.compile(r"^\s*(\d+(?:\.\d+)?)\s*([smhdw])\s*$")
_DURATION_UNITS = {"s": 1.0, "m": 60.0, "h": 3600.0, "d": 86400.0, "w": 604800.0}


def parse_duration(value: Any) -> float:
    """``"30d"`` / ``"1h"`` / ``"90m"`` / a bare number of seconds →
    seconds. Raises ValueError on anything else."""
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        return float(value)
    match = _DURATION_RE.match(str(value))
    if not match:
        raise ValueError(f"unparseable duration: {value!r}")
    return float(match.group(1)) * _DURATION_UNITS[match.group(2)]


# -- config -------------------------------------------------------------------


@dataclass(frozen=True)
class SloSpec:
    """One declared objective."""

    name: str
    #: "availability" | "latency" | "stream_freshness" | "stream_integrity"
    objective: str
    target: float
    window: str  # the declared spelling ("30d")
    window_s: float
    threshold_ms: Optional[float] = None
    description: str = ""

    @property
    def budget(self) -> float:
        """The error budget: the bad fraction the target tolerates."""
        return max(1e-9, 1.0 - self.target)


@dataclass(frozen=True)
class BurnRule:
    """One multi-window burn-rate alert rule (fast or slow)."""

    name: str  # "fast" | "slow"
    severity: str  # "page" | "ticket"
    window: str  # declared spelling ("1h")
    window_s: float
    threshold: float
    confirmation_s: float  # the short confirmation window


@dataclass
class SloConfig:
    slos: List[SloSpec] = field(default_factory=list)
    rules: List[BurnRule] = field(default_factory=list)
    source: str = DEFAULT_SLOS_PATH


def _parse_toml_subset(text: str) -> Dict:
    """Minimal TOML reader for ``slos.toml`` on 3.10 images (no
    ``tomllib``; installs are off the table — the same shim pattern as
    ``analysis/contracts.py``). Supports ``[table]`` / ``[[array]]``
    headers and scalar ``key = value`` lines (strings, numbers, TOML
    booleans)."""
    doc: Dict = {}
    current: Dict = doc
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        array_header = re.fullmatch(r"\[\[([\w.\-]+)\]\]", line)
        table_header = re.fullmatch(r"\[([\w.\-]+)\]", line)
        if array_header:
            parts = array_header.group(1).split(".")
            node = doc
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            entries = node.setdefault(parts[-1], [])
            current = {}
            entries.append(current)
            continue
        if table_header:
            parts = table_header.group(1).split(".")
            node = doc
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            current = node.setdefault(parts[-1], {})
            continue
        match = re.match(r"([\w\-]+)\s*=\s*(.*)$", line)
        if not match:
            raise ValueError(f"slos.toml subset parser: bad line {line!r}")
        key, value = match.group(1), match.group(2).strip()
        if not value.startswith(("'", '"')):
            value = value.split("#", 1)[0].strip()
        if value == "true":
            current[key] = True
        elif value == "false":
            current[key] = False
        else:
            import ast as _ast

            try:
                current[key] = _ast.literal_eval(value)
            except (SyntaxError, ValueError) as exc:
                # literal_eval raises SyntaxError on typos like `0..99`;
                # the CLI/route error contract is ValueError
                raise ValueError(
                    f"slos.toml: bad value for {key!r}: {value!r} ({exc})"
                ) from exc
    return doc


def _read_toml(path: str) -> Dict:
    if tomllib is not None:
        with open(path, "rb") as handle:
            return tomllib.load(handle)
    with open(path, encoding="utf-8") as handle:
        return _parse_toml_subset(handle.read())


def resolve_config_path(directory: Optional[str] = None) -> str:
    """Config resolution: ``GORDO_TPU_SLO_CONFIG`` > a ``slos.toml``
    beside the telemetry sinks > the packaged defaults."""
    from ..utils.env import env_str

    override = env_str(SLO_CONFIG_ENV, None)
    if override:
        return override
    if directory:
        local = os.path.join(directory, SLO_CONFIG_FILE)
        if os.path.exists(local):
            return local
    return DEFAULT_SLOS_PATH


def load_slo_config(
    directory: Optional[str] = None, path: Optional[str] = None
) -> SloConfig:
    """Parse the resolved ``slos.toml`` into typed specs + burn rules.
    Malformed SLO entries raise ``ValueError`` — objectives are a
    contract, not advisory telemetry."""
    source = path or resolve_config_path(directory)
    doc = _read_toml(source)
    slos: List[SloSpec] = []
    for entry in doc.get("slo") or []:
        name = str(entry.get("name") or "").strip()
        objective = str(entry.get("objective") or "").strip()
        if not name or objective not in (
            "availability",
            "latency",
            "stream_freshness",
            "stream_integrity",
        ):
            raise ValueError(
                f"slos.toml: every [[slo]] needs a name and an objective "
                f"of availability|latency|stream_freshness|stream_integrity "
                f"(got {entry!r})"
            )
        target = float(entry.get("target", 0.0))
        if not 0.0 < target < 1.0:
            raise ValueError(
                f"slos.toml: {name}: target must be in (0, 1), got {target}"
            )
        threshold_ms = entry.get("threshold_ms")
        if objective in ("latency", "stream_freshness") and (
            threshold_ms is None
        ):
            raise ValueError(
                f"slos.toml: {name}: {objective} objectives need "
                f"threshold_ms"
            )
        window = str(entry.get("window", "30d"))
        slos.append(
            SloSpec(
                name=name,
                objective=objective,
                target=target,
                window=window,
                window_s=parse_duration(window),
                threshold_ms=(
                    float(threshold_ms) if threshold_ms is not None else None
                ),
                description=str(entry.get("description", "")),
            )
        )
    if len({slo.name for slo in slos}) != len(slos):
        raise ValueError("slos.toml: duplicate SLO names")
    burn = doc.get("burn") or {}
    divisor = max(1.0, float(burn.get("confirmation_divisor", 12)))
    rules: List[BurnRule] = []
    for rule_name, default_window, default_threshold, default_severity in (
        ("fast", "1h", 14.4, "page"),
        ("slow", "6h", 6.0, "ticket"),
    ):
        window = str(burn.get(f"{rule_name}_window", default_window))
        window_s = parse_duration(window)
        rules.append(
            BurnRule(
                name=rule_name,
                severity=str(
                    burn.get(f"{rule_name}_severity", default_severity)
                ),
                window=window,
                window_s=window_s,
                threshold=float(
                    burn.get(f"{rule_name}_threshold", default_threshold)
                ),
                confirmation_s=window_s / divisor,
            )
        )
    return SloConfig(slos=slos, rules=rules, source=source)


# -- the math -----------------------------------------------------------------


def histogram_fraction_over(
    histogram: Dict[str, Any], threshold_ms: float
) -> float:
    """Fraction of observations strictly above ``threshold_ms``,
    linearly interpolated inside the containing bucket."""
    total = histogram.get("count", 0)
    if not total:
        return 0.0
    edges = histogram.get("buckets_ms") or []
    counts = histogram.get("counts") or []
    over = 0.0
    lower = 0.0
    for i, count in enumerate(counts):
        upper = edges[i] if i < len(edges) else float("inf")
        if lower >= threshold_ms:
            over += count
        elif upper > threshold_ms and count:
            if upper == float("inf"):
                over += count
            else:
                inside = (upper - threshold_ms) / (upper - lower)
                over += count * max(0.0, min(1.0, inside))
        lower = upper if upper != float("inf") else lower
    return min(1.0, over / total)


def bad_fraction(spec: SloSpec, rollup: Dict[str, Any]) -> Tuple[float, int]:
    """(bad event fraction, total events) for ``spec`` over one merged
    rollup. Sampled traces keep ratios unbiased — counts are estimates,
    fractions are the contract (docs/observability.md).

    Stream objectives read the rollup's ``stream`` section instead of
    the request plane: *freshness* is the rows-weighted fraction of the
    ingest→scored lag histogram above ``threshold_ms``; *integrity* is
    the shed+failed row fraction of everything ingested. Zero stream
    traffic is (0.0, 0) — silence never burns budget."""
    if spec.objective in ("stream_freshness", "stream_integrity"):
        stream = rollup.get("stream") or {}
        if spec.objective == "stream_freshness":
            lag = stream.get("lag_ms") or {}
            total = int(lag.get("count", 0))
            if not total:
                return 0.0, 0
            return (
                histogram_fraction_over(lag, float(spec.threshold_ms)),
                total,
            )
        rows_in = int(stream.get("rows_in", 0))
        if not rows_in:
            return 0.0, 0
        bad = int(stream.get("rows_shed", 0)) + int(
            stream.get("rows_failed", 0)
        )
        return min(1.0, bad / rows_in), rows_in
    requests = rollup.get("requests") or {}
    total = int(requests.get("count", 0))
    if not total:
        return 0.0, 0
    if spec.objective == "availability":
        return int(requests.get("errors", 0)) / total, total
    latency = rollup.get("latency_ms") or {}
    return histogram_fraction_over(latency, float(spec.threshold_ms)), total


def burn_rate(spec: SloSpec, fraction: float) -> float:
    """How many error budgets per SLO window this bad-fraction pace
    spends: 1.0 = exactly on budget, 14.4 = the whole month's budget in
    ~2 days."""
    return round(fraction / spec.budget, 4)


# -- the alert state machine --------------------------------------------------


def advance_alert_state(previous: Optional[str], exceeded: bool) -> str:
    """One evaluation step of the pending → firing → resolved machine:

    - ``inactive``/``resolved`` + exceeded → ``pending`` (one more
      confirming evaluation away from a page);
    - ``pending`` + exceeded → ``firing``;
    - ``firing`` + exceeded → ``firing`` (pages don't flap);
    - ``pending`` + calm → ``inactive`` (the blip never paged);
    - ``firing`` + calm → ``resolved`` (the page is annotated closed);
    - ``resolved`` + calm → ``inactive``.
    """
    if exceeded:
        return "firing" if previous in ("pending", "firing") else "pending"
    if previous == "firing":
        return "resolved"
    return "inactive"


def _load_state(path: str) -> Dict[str, Any]:
    try:
        with open(path) as handle:
            doc = json.load(handle)
    except (OSError, ValueError):
        return {"version": 1, "alerts": {}}
    if not isinstance(doc, dict) or not isinstance(doc.get("alerts"), dict):
        return {"version": 1, "alerts": {}}
    return doc


def _write_state(path: str, doc: Dict[str, Any]) -> None:
    # stage + os.replace in this function (the telemetry atomic-write
    # contract): alert state is load-bearing — the lifecycle supervisor
    # gates promotions on it — so a torn write must be unobservable
    tmp = os.path.join(
        os.path.dirname(path) or ".",
        f".{os.path.basename(path)}.tmp-{os.getpid()}",
    )
    with open(tmp, "w") as handle:
        json.dump(doc, handle, sort_keys=True)
    os.replace(tmp, path)


def state_path(directory: str) -> str:
    return os.path.join(os.path.normpath(directory), SLO_STATE_FILE)


def load_alert_states(directory: str) -> Dict[str, Dict[str, Any]]:
    """The persisted alert records for ``directory`` (empty when the
    engine has never evaluated there)."""
    return dict(_load_state(state_path(directory)).get("alerts") or {})


#: a persisted 'firing' record older than this no longer holds
#: lifecycle promotions: once the evaluator stops running, nothing can
#: ever resolve the alert, and a dead evaluator must not freeze the
#: fleet's self-healing forever (two hours >> any sane scrape refresh)
STALE_ALERT_HOLD_S = 2 * 3600.0


def firing_alerts(
    directory: str,
    severity: Optional[str] = None,
    max_age_s: Optional[float] = None,
) -> List[Dict[str, Any]]:
    """Persisted alerts currently ``firing`` (optionally filtered by
    severity) — what the lifecycle supervisor consults before an
    auto-promotion, without running an evaluation of its own. With
    ``max_age_s``, a state document whose last evaluation is older
    than the bound is treated as silence, not as an eternal page: a
    stopped evaluator can never resolve anything, so its stale
    'firing' must not hold promotions forever (a warning is logged)."""
    state = _load_state(state_path(directory))
    alerts = state.get("alerts") or {}
    if max_age_s is not None and alerts:
        from .aggregate import parse_span_time

        updated = parse_span_time(state.get("updated_at"))
        if updated is not None and time.time() - updated > max_age_s:
            if any(a.get("state") == "firing" for a in alerts.values()):
                logger.warning(
                    "slo state in %s last evaluated %s — too stale to "
                    "hold promotions; run `gordo-tpu slo status` (or "
                    "keep the server scraping) to refresh it",
                    directory,
                    state.get("updated_at"),
                )
            return []
    found = []
    for alert_id, record in sorted(alerts.items()):
        if record.get("state") != "firing":
            continue
        if severity is not None and record.get("severity") != severity:
            continue
        found.append({"id": alert_id, **record})
    return found


# -- evaluation ---------------------------------------------------------------


def slo_directory(anchor: Optional[str] = None) -> Optional[str]:
    """Where the serving telemetry (and therefore the rollups and SLO
    state) live: ``GORDO_TPU_TELEMETRY_DIR`` when configured, else the
    caller's anchor (a build dir carries its own sinks)."""
    from ..utils.env import env_str

    from .recorder import TRACE_DIR_ENV

    return env_str(TRACE_DIR_ENV, None) or anchor


#: per-directory evaluation locks: the alert state machine is a
#: read-modify-write of slo_state.json, and two concurrent evaluations
#: (the scrape thread racing a /slo request) could otherwise step one
#: logical evaluation twice (pending -> firing in milliseconds) or lose
#: a firing write the lifecycle gate depends on
_eval_locks_guard = threading.Lock()
_eval_locks: Dict[str, threading.Lock] = {}


def _eval_lock(directory: str) -> threading.Lock:
    with _eval_locks_guard:
        lock = _eval_locks.get(directory)
        if lock is None:
            lock = _eval_locks[directory] = threading.Lock()
        return lock


def evaluate(
    directory: str,
    config: Optional[SloConfig] = None,
    now: Optional[float] = None,
    store: Optional[RollupStore] = None,
    aggregate_first: bool = True,
) -> Dict[str, Any]:
    """
    One SLO evaluation over ``directory``'s rollups: aggregate any new
    spans (incremental), compute per-SLO budgets and multi-window burn
    rates, advance the persisted alert state machine, and return the
    full status document (the shape ``gordo-tpu slo status --as-json``
    prints and the /slo route serves). Serialized per directory — see
    :data:`_eval_locks`.
    """
    directory = os.path.normpath(directory)
    config = config or load_slo_config(directory)
    # the SHARED per-directory store: its instance lock serializes a
    # scrape-thread evaluation against a concurrent /slo route one —
    # two fresh stores would double-fold the same new spans
    store = store or store_for(directory)
    with _eval_lock(directory):
        return _evaluate_locked(
            directory, config, now, store, aggregate_first
        )


def _evaluate_locked(
    directory: str,
    config: SloConfig,
    now: Optional[float],
    store: RollupStore,
    aggregate_first: bool,
) -> Dict[str, Any]:
    aggregation = store.aggregate() if aggregate_first else None
    now = time.time() if now is None else float(now)

    state_file = state_path(directory)
    state = _load_state(state_file)
    alerts_state: Dict[str, Any] = state.get("alerts") or {}

    slos_doc: List[Dict[str, Any]] = []
    alerts_doc: List[Dict[str, Any]] = []
    #: merged rollups are cached per distinct window length — the fast
    #: and slow rules of every SLO share the same four merges
    merged_cache: Dict[float, Dict[str, Any]] = {}

    def merged(seconds: float) -> Dict[str, Any]:
        if seconds not in merged_cache:
            merged_cache[seconds] = store.merged(
                since=now - seconds, until=now
            )
        return merged_cache[seconds]

    for spec in config.slos:
        window_rollup = merged(spec.window_s)
        fraction, total = bad_fraction(spec, window_rollup)
        consumed = min(1.0, fraction / spec.budget)
        burn_rates: Dict[str, float] = {}
        for rule in config.rules:
            long_fraction, _ = bad_fraction(spec, merged(rule.window_s))
            short_fraction, _ = bad_fraction(
                spec, merged(rule.confirmation_s)
            )
            long_burn = burn_rate(spec, long_fraction)
            short_burn = burn_rate(spec, short_fraction)
            burn_rates[rule.window] = long_burn
            exceeded = (
                long_burn > rule.threshold and short_burn > rule.threshold
            )
            alert_id = f"{spec.name}:{rule.name}"
            previous = alerts_state.get(alert_id) or {}
            previous_state = previous.get("state")
            next_state = advance_alert_state(previous_state, exceeded)
            record = {
                "slo": spec.name,
                "rule": rule.name,
                "severity": rule.severity,
                "state": next_state,
                "since": (
                    previous.get("since")
                    if next_state == previous_state
                    else _iso(now)
                ),
                "last_transition": (
                    previous.get("last_transition")
                    if next_state == previous_state
                    else _iso(now)
                ),
                "burn_rate": long_burn,
                "confirmation_burn_rate": short_burn,
                "threshold": rule.threshold,
                "window": rule.window,
                "confirmation_s": rule.confirmation_s,
            }
            alerts_state[alert_id] = record
            alerts_doc.append({"id": alert_id, **record})
        entry = {
            "name": spec.name,
            "objective": spec.objective,
            "description": spec.description,
            "target": spec.target,
            "window": spec.window,
            "threshold_ms": spec.threshold_ms,
            "requests": total,
            "bad_fraction": round(fraction, 6),
            "budget": {
                "total_ratio": round(spec.budget, 6),
                "consumed_ratio": round(consumed, 6),
                "remaining_ratio": round(1.0 - consumed, 6),
            },
            "burn_rates": burn_rates,
        }
        if spec.objective == "latency":
            entry["latency_p95_ms"] = histogram_percentile(
                window_rollup.get("latency_ms") or {}, 0.95
            )
        elif spec.objective == "stream_freshness":
            entry["lag_p95_ms"] = histogram_percentile(
                (window_rollup.get("stream") or {}).get("lag_ms") or {},
                0.95,
            )
        slos_doc.append(entry)

    # alerts for SLOs no longer declared are dropped, not zombie-fired
    declared = {f"{s.name}:{r.name}" for s in config.slos for r in config.rules}
    alerts_state = {
        key: value for key, value in alerts_state.items() if key in declared
    }
    state.update(
        {
            "version": 1,
            "alerts": alerts_state,
            "updated_at": _iso(now),
            "config_source": config.source,
        }
    )
    try:
        os.makedirs(directory, exist_ok=True)
        _write_state(state_file, state)
    except OSError as exc:
        logger.warning("slo state not persisted: %r", exc)

    firing = sum(1 for a in alerts_doc if a["state"] == "firing")
    pending = sum(1 for a in alerts_doc if a["state"] == "pending")
    doc = {
        "version": 1,
        "directory": directory,
        "generated_at": _iso(now),
        "config": {
            "source": config.source,
            "rules": [
                {
                    "name": rule.name,
                    "severity": rule.severity,
                    "window": rule.window,
                    "threshold": rule.threshold,
                    "confirmation_s": rule.confirmation_s,
                }
                for rule in config.rules
            ],
        },
        "slos": slos_doc,
        "alerts": alerts_doc,
        "firing": firing,
        "pending": pending,
        "ok": firing == 0,
        "recent": summarize_rollup(merged(3600.0)),
    }
    if aggregation is not None:
        doc["aggregation"] = aggregation
    note_status(directory, doc, now=now)
    return doc


#: the package-level spelling (``telemetry.evaluate_slos``); inside
#: this module the short name reads better
evaluate_slos = evaluate


def evaluate_cached(
    directory: str,
    config: Optional[SloConfig] = None,
    max_age_s: Optional[float] = None,
) -> Dict[str, Any]:
    """:func:`evaluate`, throttled: return the cached status when one
    younger than ``max_age_s`` exists (default: the scrape-refresh
    knob). The /slo route and the scrape collector both go through
    here, so an external poller cannot turn a read surface into
    write amplification — or drive the pending→firing confirmation
    step faster than the refresh cadence."""
    directory = os.path.normpath(directory)
    if max_age_s is None:
        max_age_s = scrape_refresh_seconds()
    if max_age_s > 0:
        with _registry_lock:
            entry = _statuses.get(directory)
        if entry is not None and time.time() - entry[1] < max_age_s:
            return entry[0]
    return evaluate(directory, config=config)


# -- the process-global status registry (Prometheus exposition) ---------------

_registry_lock = threading.Lock()
#: directory -> (status doc, evaluated-at epoch) — what the scrape-time
#: SloCollector exports; populated by every evaluate()
_statuses: Dict[str, Tuple[Dict[str, Any], float]] = {}
#: directories the serving process asked to keep fresh at scrape time
_watched: set = set()


def note_status(
    directory: str, doc: Dict[str, Any], now: Optional[float] = None
) -> None:
    with _registry_lock:
        _statuses[os.path.normpath(directory)] = (
            doc,
            time.time() if now is None else float(now),
        )


def watch(directory: Optional[str]) -> None:
    """Mark ``directory`` for scrape-time SLO refresh (the server calls
    this at boot for its anchor's telemetry dir)."""
    if directory and enabled():
        with _registry_lock:
            _watched.add(os.path.normpath(directory))


def reset_statuses() -> None:
    """Drop cached statuses and watches (tests only)."""
    with _registry_lock:
        _statuses.clear()
        _watched.clear()


def scrape_refresh_seconds() -> float:
    from ..utils.env import env_float

    value = env_float(SCRAPE_REFRESH_ENV, DEFAULT_SCRAPE_REFRESH)
    return max(0.0, value if value is not None else DEFAULT_SCRAPE_REFRESH)


def scrape_statuses() -> Dict[str, Dict[str, Any]]:
    """directory -> latest status doc for the Prometheus collector,
    re-evaluating watched directories whose cache is older than
    ``GORDO_TPU_SLO_SCRAPE_REFRESH`` (0 = cached only — scrapes never
    pay an aggregation)."""
    refresh = scrape_refresh_seconds()
    with _registry_lock:
        watched = set(_watched)
        cached = dict(_statuses)
    if refresh > 0:
        for directory in sorted(watched):
            try:
                evaluate_cached(directory, max_age_s=refresh)
            except Exception:  # noqa: BLE001 - scrapes must never fail
                # on a broken sink; the stale cache (if any) still reports
                logger.debug("scrape-time slo refresh failed", exc_info=True)
        with _registry_lock:
            cached = dict(_statuses)
    return {directory: doc for directory, (doc, _) in cached.items()}


def slo_section(directory: str) -> Optional[Dict[str, Any]]:
    """The compact SLO section for :func:`fleet_status_document`: alert
    states + headline budgets from the cached status when this process
    evaluated recently, else from the persisted state machine alone
    (cheap — one small JSON read, no aggregation)."""
    directory = os.path.normpath(directory)
    with _registry_lock:
        entry = _statuses.get(directory)
    if entry is not None:
        doc = entry[0]
        return {
            "firing": doc.get("firing", 0),
            "pending": doc.get("pending", 0),
            "ok": doc.get("ok", True),
            "alerts": doc.get("alerts"),
            "budgets": {
                slo["name"]: slo["budget"]["remaining_ratio"]
                for slo in doc.get("slos") or []
            },
            "evaluated_at": doc.get("generated_at"),
        }
    state = _load_state(state_path(directory))
    alerts = state.get("alerts") or {}
    if not alerts:
        return None
    firing = sum(1 for a in alerts.values() if a.get("state") == "firing")
    pending = sum(1 for a in alerts.values() if a.get("state") == "pending")
    return {
        "firing": firing,
        "pending": pending,
        "ok": firing == 0,
        "alerts": [
            {"id": alert_id, **record}
            for alert_id, record in sorted(alerts.items())
        ],
        "budgets": None,
        "evaluated_at": state.get("updated_at"),
    }


# -- rendering ----------------------------------------------------------------

_STATE_MARKS = {
    "inactive": "ok",
    "pending": "PENDING",
    "firing": "FIRING",
    "resolved": "resolved",
}


def render_slo_status(doc: Dict[str, Any]) -> str:
    """Human rendering of the status document (the ``slo status``
    table view)."""
    lines: List[str] = [
        f"SLO status: {doc.get('directory', '-')}  "
        f"(evaluated {doc.get('generated_at', '?')})"
    ]
    for slo in doc.get("slos") or []:
        budget = slo.get("budget") or {}
        burn = ", ".join(
            f"{window}={rate:g}x"
            for window, rate in (slo.get("burn_rates") or {}).items()
        )
        threshold = (
            f" (<= {slo['threshold_ms']:g}ms)"
            if slo.get("threshold_ms") is not None
            else ""
        )
        unit = (
            "row(s)"
            if str(slo.get("objective", "")).startswith("stream")
            else "request(s)"
        )
        lines.append(
            f"  {slo['name']}: {slo['objective']}{threshold} "
            f"target {slo['target']:.4%} over {slo['window']} — "
            f"budget remaining {budget.get('remaining_ratio', 0) * 100:.1f}%"
            f" ({slo.get('requests', 0)} {unit}, burn {burn or '-'})"
        )
    alerts = doc.get("alerts") or []
    active = [a for a in alerts if a.get("state") != "inactive"]
    lines.append(
        f"alerts: {doc.get('firing', 0)} firing, "
        f"{doc.get('pending', 0)} pending"
    )
    for alert in active:
        lines.append(
            f"  [{_STATE_MARKS.get(alert['state'], alert['state'])}] "
            f"{alert['id']} ({alert['severity']}): burn "
            f"{alert.get('burn_rate', 0):g}x over {alert['window']} "
            f"(threshold {alert.get('threshold', 0):g}x, since "
            f"{alert.get('since', '?')})"
        )
    verdict = "inside SLO" if doc.get("ok") else "BURNING — page is firing"
    lines.append(f"result: {verdict}")
    return "\n".join(lines)
