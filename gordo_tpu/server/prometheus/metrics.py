"""
Prometheus request metrics for the model server.

Reference parity: gordo/server/prometheus/metrics.py — request counter and
duration histogram labeled (method, path rule, status, gordo model name,
project, version), with multiprocess-registry support so gunicorn's worker
fleet aggregates into one scrape target.
"""

import logging
import os
import re
import weakref
from typing import Dict, Optional, Tuple

from prometheus_client import (
    REGISTRY,
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
)

import gordo_tpu

logger = logging.getLogger(__name__)

# Extract the model name from a request path under the API prefix:
# /gordo/v0/<project>/<name>/...
_MODEL_PATH_RE = re.compile(r"^/gordo/v0/(?P<project>[^/]+)/(?P<name>[^/]+)(?:/|$)")

# Routes that would only add scrape noise.
DEFAULT_IGNORE_PATHS = ("/healthcheck",)

PROJECT_LEVEL_ROUTES = (
    "models",
    "revisions",
    "expected-models",
    "build-status",
    "fleet-health",
    "slo",
)

#: request-stage latency buckets: stages span sub-millisecond metadata
#: lookups to second-scale inference+serialize on fat payloads — the
#: default request buckets start at 5ms and would flatten the fast half
_STAGE_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)


def _ensure_multiproc_dir() -> Optional[str]:
    """
    The configured ``PROMETHEUS_MULTIPROC_DIR`` (either env spelling),
    created if missing — prometheus_client crashes at first metric write
    when the mmap dir doesn't exist.
    """
    multiproc_dir = os.getenv("PROMETHEUS_MULTIPROC_DIR") or os.getenv(
        "prometheus_multiproc_dir"
    )
    if multiproc_dir:
        os.makedirs(multiproc_dir, exist_ok=True)
    return multiproc_dir


def multiprocess_registry() -> Optional[CollectorRegistry]:
    """
    A multiprocess collector registry when ``PROMETHEUS_MULTIPROC_DIR`` is
    configured (gunicorn worker fan-in), else None.
    """
    if _ensure_multiproc_dir():
        from prometheus_client import multiprocess

        registry = CollectorRegistry()
        multiprocess.MultiProcessCollector(registry)
        # Scrape-time collectors have no mmap backing, so the worker
        # fan-in alone would silently drop them: they must ride every
        # registry that answers scrapes.
        register_program_cache_collector(registry)
        register_fleet_console_collectors(registry)
        return registry
    return None


class GordoServerPrometheusMetrics:
    """The serving RED metric set, keyed by route/model/status:

    - **Rate** — ``gordo_server_requests_total`` (as before);
    - **Errors** — ``gordo_server_request_errors_total``, the explicit
      error counter (4xx = ``kind="client"``, 5xx = ``kind="server"``)
      so an error-rate panel is one PromQL ratio, no status-code regex;
    - **Duration** — the full-route latency histogram plus
      ``gordo_server_stage_duration_seconds{endpoint,stage}``: the same
      per-stage breakdown Server-Timing carries per response, as
      aggregable histograms — where the route's time goes, fleet-wide.
    """

    def __init__(
        self,
        project: Optional[str] = None,
        ignore_paths: Tuple[str, ...] = DEFAULT_IGNORE_PATHS,
        registry: Optional[CollectorRegistry] = None,
    ):
        _ensure_multiproc_dir()
        self.project = project
        self.ignore_paths = tuple(ignore_paths)
        self.registry = registry if registry is not None else REGISTRY

        label_names = ["method", "path", "status_code", "gordo_name", "project"]
        self.request_count = Counter(
            "gordo_server_requests_total",
            "Total number of requests to the gordo model server",
            labelnames=label_names,
            registry=self.registry,
        )
        self.request_duration = Histogram(
            "gordo_server_request_duration_seconds",
            "Request processing wall-time",
            labelnames=label_names,
            registry=self.registry,
        )
        self.error_count = Counter(
            "gordo_server_request_errors_total",
            "Requests answered with an error status (kind=client for "
            "4xx — including 429/504 batching backpressure — and "
            "kind=server for 5xx)",
            labelnames=label_names + ["kind"],
            registry=self.registry,
        )
        # stage labels are bounded: endpoint is the route map's endpoint
        # name, stage the handler-instrumented pipeline stage set
        # (model_resolve/data_decode/device_ingest/inference/
        # response_assemble/serialize + the micro-batcher's
        # queue_wait/batch_* intervals); data_decode is wire→host parse
        # only — the wire→device staging it used to hide is the
        # device_ingest stage
        self.stage_duration = Histogram(
            "gordo_server_stage_duration_seconds",
            "Per-request pipeline-stage wall-time (one observation per "
            "stage per request — the aggregable form of the "
            "Server-Timing response header)",
            labelnames=["project", "endpoint", "stage"],
            buckets=_STAGE_BUCKETS,
            registry=self.registry,
        )
        self.info = Gauge(
            "gordo_server_info",
            "Server build information",
            labelnames=["version", "project"],
            registry=self.registry,
            multiprocess_mode="max",
        )
        self.info.labels(
            version=gordo_tpu.__version__, project=project or ""
        ).set(1)
        # the fleet console's scrape-time aggregates (health states,
        # score histogram, device memory, compile-cache hit counters)
        # ride every scrape registry, batching on or off
        register_fleet_console_collectors(self.registry)
        # label-child caches: prometheus_client's .labels() rebuilds a
        # key tuple and takes the metric lock per call (~10us); on the
        # request hot path that is paid 2-7 times per request. Children
        # are stable objects — cache them per label tuple (bounded by
        # the same cardinality guards as the metrics themselves).
        self._request_children: dict = {}
        self._stage_children: dict = {}
        #: raw (method, path, status) -> computed labels dict; the two
        #: regex passes in _labels_uncached are ~6us per request and
        #: the distinct raw paths are bounded by models x routes
        self._labels_cache: dict = {}

    def _labels(self, request, response) -> Optional[dict]:
        key = (request.method, request.path, response.status_code)
        try:
            return self._labels_cache[key]
        except KeyError:
            labels = self._labels_uncached(request, response)
            if len(self._labels_cache) < 4096:
                self._labels_cache[key] = labels
            return labels

    def _labels_uncached(self, request, response) -> Optional[dict]:
        path = request.path
        if path in self.ignore_paths:
            return None
        gordo_name = ""
        project = self.project or ""
        match = _MODEL_PATH_RE.match(path)
        if match:
            project = project or match.group("project")
            name = match.group("name")
            if name not in PROJECT_LEVEL_ROUTES:
                gordo_name = name
                # Collapse the per-model path to its route shape so label
                # cardinality stays bounded by route count, not model count;
                # revision IDs are collapsed for the same reason.
                path = _MODEL_PATH_RE.sub("/gordo/v0/{project}/{name}/", path, count=1)
                path = re.sub(r"revision/\d+$", "revision/{revision}", path)
            else:
                path = _MODEL_PATH_RE.sub("/gordo/v0/{project}/" + name, path, count=1)
        elif path not in ("/healthcheck", "/server-version"):
            # Unmatched paths (scanners, typos) must not mint timeseries.
            path = "{unmatched}"
        return {
            "method": request.method,
            "path": path,
            "status_code": str(response.status_code),
            "gordo_name": gordo_name,
            "project": project,
        }

    def observe(self, request, response, duration_s: float):
        labels = self._labels(request, response)
        if labels is None:
            return
        key = (
            labels["method"],
            labels["path"],
            labels["status_code"],
            labels["gordo_name"],
            labels["project"],
        )
        children = self._request_children.get(key)
        if children is None:
            children = self._request_children[key] = (
                self.request_count.labels(**labels),
                self.request_duration.labels(**labels),
            )
        count_child, duration_child = children
        count_child.inc()
        duration_child.observe(duration_s)
        status = response.status_code
        if status >= 400:
            self.error_count.labels(
                **labels, kind="server" if status >= 500 else "client"
            ).inc()
        # per-stage durations ride the response object (_finalize stashes
        # them — the WSGI observer never sees the request context)
        stages = getattr(response, "gordo_stage_durations", None)
        if stages:
            endpoint = getattr(response, "gordo_endpoint", None) or "{unmatched}"
            for stage, seconds in stages.items():
                stage_key = (endpoint, stage)
                child = self._stage_children.get(stage_key)
                if child is None:
                    child = self._stage_children[stage_key] = (
                        self.stage_duration.labels(
                            project=labels["project"],
                            endpoint=endpoint,
                            stage=stage,
                        )
                    )
                child.observe(seconds)


def create_prometheus_metrics(
    project: Optional[str] = None, registry: Optional[CollectorRegistry] = None
) -> GordoServerPrometheusMetrics:
    if registry is None:
        registry = multiprocess_registry() or REGISTRY
    return GordoServerPrometheusMetrics(project=project, registry=registry)


#: (metric suffix, help) per fleet-build robustness counter — the
#: chip-fan-out analogs of the reference DAG's per-pod retry visibility
#: (a retried/failed pod shows in `argo get`; an in-process retry must
#: show in /metrics instead).
_BUILD_ROBUSTNESS_COUNTERS = (
    (
        "fleet_retries",
        "gordo_fleet_build_member_retries_total",
        "Diverged fleet members retrained with a reseeded RNG",
    ),
    (
        "bucket_bisects",
        "gordo_fleet_build_bucket_bisects_total",
        "Device-program bucket bisection (split-retry) events",
    ),
    (
        "data_fetch_retries",
        "gordo_fleet_build_data_fetch_retries_total",
        "Per-machine data fetch retry attempts",
    ),
    (
        "sequential_degraded",
        "gordo_fleet_build_sequential_degraded_total",
        "Machines degraded to the sequential builder after isolated "
        "device failures",
    ),
)

#: duration buckets for build phases — builds span sub-second host
#: phases to multi-minute device training, so the default request
#: buckets (capped at 10s) would flatten everything interesting
_PHASE_BUCKETS = (
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
    120.0, 300.0, 600.0, 1800.0, 3600.0,
)
#: first-call durations span quick XLA compiles to compile+first-run of
#: multi-minute training programs — the tail must stay resolvable
_COMPILE_BUCKETS = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
    30.0, 60.0, 120.0, 300.0, 600.0, 1800.0, 3600.0,
)
#: final training losses of normalized autoencoder fleets
_LOSS_BUCKETS = (
    1e-4, 1e-3, 1e-2, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 100.0,
)

#: one metric set per LIVE CollectorRegistry. A WeakKeyDictionary, not a
#: dict keyed by ``id(registry)``: a garbage-collected registry can hand
#: its id to a NEW registry, which would then silently receive the old
#: (unregistered-with-it) metric objects — increments that no scrape of
#: the new registry ever sees. Weak keys die with their registry, so a
#: fresh registry always builds (and owns) fresh metrics.
_build_metrics: "weakref.WeakKeyDictionary[CollectorRegistry, dict]" = (
    weakref.WeakKeyDictionary()
)


def fleet_build_metrics(registry: Optional[CollectorRegistry] = None) -> dict:
    """The full fleet-build metric set for ``registry`` (default: the
    global REGISTRY), created once per live registry: the robustness
    Counters, the phase/compile duration and member-final-loss
    Histograms, and the live machine-progress Gauges."""
    target = registry if registry is not None else REGISTRY
    if target not in _build_metrics:
        _ensure_multiproc_dir()
        metrics = {
            counter_key: Counter(
                name,
                help_text,
                labelnames=["project"],
                registry=target,
            )
            for counter_key, name, help_text in _BUILD_ROBUSTNESS_COUNTERS
        }
        metrics["phase_duration"] = Histogram(
            "gordo_fleet_build_phase_duration_seconds",
            "Wall-clock of fleet build phases (per occurrence; phases "
            "like cv_train recur once per bucket chunk)",
            labelnames=["project", "phase"],
            buckets=_PHASE_BUCKETS,
            registry=target,
        )
        metrics["compile_duration"] = Histogram(
            "gordo_fleet_compile_duration_seconds",
            "FIRST-CALL wall-clock of fleet device programs per program "
            "and bucket shape: XLA trace+compile plus the first "
            "execution (they are not separable without an AOT split). "
            "The cache-miss signal is the DELTA vs later calls of the "
            "same signature in gordo_fleet_build_phase_duration_seconds "
            "/ the device_program run spans, not this value alone",
            labelnames=["project", "program", "shape"],
            buckets=_COMPILE_BUCKETS,
            registry=target,
        )
        metrics["member_final_loss"] = Histogram(
            "gordo_fleet_member_final_loss",
            "Final training loss of fleet members at the end of their "
            "final fit",
            labelnames=["project"],
            buckets=_LOSS_BUCKETS,
            registry=target,
        )
        for gauge_key, name, help_text in (
            (
                "machines_total",
                "gordo_fleet_build_machines_total",
                "Machines in the currently running fleet build",
            ),
            (
                "machines_completed",
                "gordo_fleet_build_machines_completed",
                "Machines whose artifacts have landed in the current "
                "fleet build (updated live, not only at build end)",
            ),
            (
                "machines_failed",
                "gordo_fleet_build_machines_failed",
                "Machines failed so far in the current fleet build",
            ),
        ):
            metrics[gauge_key] = Gauge(
                name,
                help_text,
                labelnames=["project"],
                registry=target,
                multiprocess_mode="max",
            )
        # FleetPlan (gordo_tpu.planner) gauges: what the cost model
        # promised for this build, and what the final fit actually cost —
        # the pair an operator (or a recalibration job) diffs to see the
        # model's error. `strategy` is bounded (naive|packed).
        for gauge_key, name, help_text in (
            (
                "plan_predicted_seconds",
                "gordo_fleet_plan_predicted_seconds",
                "FleetPlan predicted build wall-clock (compile + run) for "
                "the planned final-fit buckets",
            ),
            (
                "plan_padding_waste",
                "gordo_fleet_plan_padding_waste_ratio",
                "FleetPlan predicted padded-FLOP waste ratio (padding "
                "FLOPs / total padded FLOPs) across the planned buckets",
            ),
            (
                "plan_compiles",
                "gordo_fleet_plan_compiles",
                "Distinct XLA programs the FleetPlan predicts the planned "
                "buckets will compile",
            ),
            (
                "plan_actual_compiles",
                "gordo_fleet_plan_actual_compiles",
                "First-call (compile) fit programs actually observed "
                "during the final-fit phase of the build",
            ),
            (
                "plan_actual_seconds",
                "gordo_fleet_plan_actual_seconds",
                "Wall-clock of fit device programs actually observed "
                "during the final-fit phase of the build",
            ),
        ):
            metrics[gauge_key] = Gauge(
                name,
                help_text,
                labelnames=["project", "strategy"],
                registry=target,
                multiprocess_mode="max",
            )
        _build_metrics[target] = metrics
    return _build_metrics[target]


def fleet_build_robustness_counters(
    registry: Optional[CollectorRegistry] = None,
) -> dict:
    """The build-robustness Counter subset for ``registry`` (kept for
    callers that predate :func:`fleet_build_metrics`)."""
    metrics = fleet_build_metrics(registry)
    return {key: metrics[key] for key, _, _ in _BUILD_ROBUSTNESS_COUNTERS}


def record_fleet_build_robustness(project: Optional[str], counters: dict):
    """Export a finished build's robustness counters (FleetBuilder calls
    this best-effort at the end of ``build``)."""
    built = fleet_build_robustness_counters()
    for key, counter in built.items():
        value = int(counters.get(key, 0) or 0)
        if value:
            counter.labels(project=project or "").inc(value)


def record_fleet_build_phase(
    project: Optional[str], phase: str, seconds: float
):
    """One build-phase occurrence's wall-clock (live, per span)."""
    fleet_build_metrics()["phase_duration"].labels(
        project=project or "", phase=phase
    ).observe(seconds)


def record_fleet_compile(
    project: Optional[str], program: str, shape: str, seconds: float
):
    """One device program's first-call (compile) wall-clock. ``shape``
    is the bucket's stacked-array shape string — bounded by the fleet's
    distinct (architecture, padded-size) buckets, so label cardinality
    stays at bucket count, not machine count."""
    fleet_build_metrics()["compile_duration"].labels(
        project=project or "", program=program, shape=shape
    ).observe(seconds)


def record_member_final_loss(project: Optional[str], loss: float):
    """One fleet member's final training loss, at final-fit completion."""
    fleet_build_metrics()["member_final_loss"].labels(
        project=project or ""
    ).observe(loss)


# -- serving micro-batcher metrics ------------------------------------------

#: batch sizes are bounded by GORDO_TPU_BATCH_MAX_SIZE (default 32);
#: powers of two mirror the member shape ladder
_BATCH_SIZE_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)
#: ratios in [0, 1] (program occupancy / padding waste)
_RATIO_BUCKETS = (0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 0.95, 1.0)


class ProgramCacheCollector:
    """Scrape-time reader of the serving program cache
    (``fleet_store.program_cache_stats``): ``cache="programs"`` counts
    cached (spec, backend) jit entries, ``cache="signatures"`` the XLA
    executables compiled inside them — the number the serve shape
    ladder exists to bound."""

    def collect(self):
        from prometheus_client.core import GaugeMetricFamily

        from ..fleet_store import program_cache_stats

        stats = program_cache_stats()
        family = GaugeMetricFamily(
            "gordo_server_program_cache_size",
            "Compiled serving-program cache size (programs = cached jit "
            "entries per (spec, backend); signatures = XLA executables "
            "compiled inside them)",
            labels=["cache"],
        )
        family.add_metric(["programs"], stats["programs"])
        family.add_metric(["signatures"], stats["signatures"])
        # the precision axis (PR 14): programs per serving precision —
        # bounded by the declared precision ladder (f32/bf16/int8)
        for precision, count in sorted(
            (stats.get("by_precision") or {}).items()
        ):
            family.add_metric([f"programs_{precision}"], count)
        yield family


class StoreResidencyCollector:
    """Scrape-time reader of the serving store's resident-revision byte
    estimates (``FleetModelStore.revision_stats``). The ``revision``
    label is BOUNDED by ``N_CACHED_REVISIONS`` (default 2) — revision
    basenames, never member names, so cardinality stays at revision
    count (the PR 8 prometheus-cardinality contract); the ``kind`` axis
    is a three-value constant."""

    def collect(self):
        from prometheus_client.core import GaugeMetricFamily

        from ..fleet_store import STORE

        family = GaugeMetricFamily(
            "gordo_store_revision_bytes",
            "Estimated resident bytes per cached serving revision "
            "(kind=model per-member params, kind=stacked fused f32 "
            "buckets, kind=cast reduced-precision copies)",
            labels=["revision", "kind"],
        )
        for revision, stats in sorted(STORE.revision_stats().items()):
            family.add_metric([revision, "model"], stats["model_bytes"])
            family.add_metric([revision, "stacked"], stats["stacked_bytes"])
            family.add_metric([revision, "cast"], stats["cast_bytes"])
        yield family


#: registries already carrying a ProgramCacheCollector — re-registering
#: would raise on the duplicated metric name
_program_cache_registries: "weakref.WeakSet" = weakref.WeakSet()


def register_program_cache_collector(registry: CollectorRegistry) -> None:
    """Attach the scrape-time program-cache gauge to ``registry``, once.

    Unlike Counter/Histogram, a custom collector is not mmap-backed, so
    it must be registered on every registry that answers scrapes — the
    in-process one AND the fresh multiprocess fan-in registry (where the
    reported values are the answering worker's own cache)."""
    if registry in _program_cache_registries:
        return
    _program_cache_registries.add(registry)
    registry.register(ProgramCacheCollector())
    registry.register(StoreResidencyCollector())


class FleetHealthCollector:
    """Scrape-time BOUNDED aggregates of the per-member health ledger
    (``telemetry/fleet_health.py``): machines-by-state counts and the
    fixed-bucket health-score histogram. Per-machine detail deliberately
    never reaches a label — that is the ledger's job (the PR 8
    prometheus-cardinality contract); the label sets here are constants:
    four states, five score buckets."""

    def collect(self):
        from prometheus_client.core import (
            GaugeHistogramMetricFamily,
            GaugeMetricFamily,
        )

        from ...telemetry.fleet_health import SCORE_BUCKETS, ledger_summaries

        states = GaugeMetricFamily(
            "gordo_fleet_health_machines",
            "Fleet members by health state (quarantined > degraded > "
            "drifting > healthy; per-machine detail lives in "
            "fleet_health.json, not in labels)",
            labels=["state"],
        )
        scores = GaugeHistogramMetricFamily(
            "gordo_fleet_health_score",
            "Distribution of per-member health scores in [0, 1] "
            "(1.0 = healthy; see telemetry.fleet_health.health_score)",
            labels=[],
        )
        totals = {"healthy": 0, "degraded": 0, "drifting": 0, "quarantined": 0}
        bins = [0] * len(SCORE_BUCKETS)
        machines = 0
        score_sum = 0.0
        for summary in ledger_summaries().values():
            if not summary:
                continue
            machines += summary.get("machines", 0)
            for state in totals:
                totals[state] += int(summary.get(state, 0))
            histogram = summary.get("score_histogram") or {}
            counts = histogram.get("counts") or []
            for i, count in enumerate(counts[: len(bins)]):
                bins[i] += int(count)
            score_sum += float(histogram.get("score_sum") or 0.0)
        for state, count in totals.items():
            states.add_metric([state], count)
        cumulative = 0
        buckets = []
        for edge, count in zip(SCORE_BUCKETS, bins):
            cumulative += count
            buckets.append((str(edge), cumulative))
        buckets.append(("+Inf", machines))
        # gsum is the sum of SCORES (mean fleet health = sum / count in
        # one PromQL division), never the machine count
        scores.add_metric([], buckets=buckets, gsum_value=score_sum)
        yield states
        yield scores


class DeviceUtilizationCollector:
    """Scrape-time device telemetry (``telemetry/device.py``): measured
    HBM occupancy per backend (summed over local devices) and the
    process-wide compile-vs-cache-hit counters — the measured
    counterpart of the planner's predicted HBM numbers. All label sets
    are constants (three memory kinds, two sides, two results)."""

    def collect(self):
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
        )

        from ...telemetry import device as device_telemetry

        memory_family = GaugeMetricFamily(
            "gordo_device_memory_bytes",
            "Device memory summed over local devices "
            "(Device.memory_stats; absent when the backend reports none)",
            labels=["kind"],
        )
        memory = device_telemetry.memory_snapshot()
        if memory and memory.get("available"):
            for kind in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
                if kind in memory:
                    memory_family.add_metric([kind], memory[kind])
            yield memory_family
        programs = CounterMetricFamily(
            "gordo_compile_cache_events",
            "jit-program executions by compile-cache outcome: "
            "result=compile is a cache miss that paid XLA, result=hit a "
            "steady-state run (side=build for fleet training programs, "
            "side=serve for the fused serving programs)",
            labels=["side", "result"],
        )
        for side, counters in sorted(
            device_telemetry.program_cache_counters().items()
        ):
            programs.add_metric([side, "compile"], counters.get("compiles", 0))
            programs.add_metric([side, "hit"], counters.get("cache_hits", 0))
        yield programs


#: numeric encoding of the alert state machine for the gauge below —
#: `resolved` maps to 0 (it is a closing annotation, not a page)
_SLO_ALERT_STATE_VALUES = {
    "inactive": 0,
    "resolved": 0,
    "pending": 1,
    "firing": 2,
}


class SloCollector:
    """Scrape-time SLO exposition (``telemetry/slo.py``): error-budget
    remaining, multi-window burn rates, and the alert state machine.
    Label cardinality is BOUNDED by the declared ``slos.toml`` — slo
    names and the two burn windows — never by traffic or fleet size
    (the PR 8 prometheus-cardinality contract). Watched directories
    re-evaluate at most once per ``GORDO_TPU_SLO_SCRAPE_REFRESH``."""

    def collect(self):
        from prometheus_client.core import GaugeMetricFamily

        from ...telemetry import slo as slo_engine

        budget = GaugeMetricFamily(
            "gordo_slo_error_budget_remaining_ratio",
            "Fraction of the SLO window's error budget still unspent "
            "(1.0 = clean, 0.0 = the objective is blown)",
            labels=["slo"],
        )
        burn = GaugeMetricFamily(
            "gordo_slo_burn_rate",
            "Error-budget burn rate over the alert windows (1.0 = "
            "spending exactly one budget per SLO window)",
            labels=["slo", "window"],
        )
        state = GaugeMetricFamily(
            "gordo_slo_alert_state",
            "Worst burn-rate alert state per SLO "
            "(0 = inactive/resolved, 1 = pending, 2 = firing)",
            labels=["slo"],
        )
        for doc in slo_engine.scrape_statuses().values():
            for slo in doc.get("slos") or []:
                name = str(slo.get("name"))
                budget.add_metric(
                    [name],
                    float((slo.get("budget") or {}).get("remaining_ratio", 1.0)),
                )
                for window, rate in (slo.get("burn_rates") or {}).items():
                    burn.add_metric([name, str(window)], float(rate))
            worst: Dict[str, int] = {}
            for alert in doc.get("alerts") or []:
                name = str(alert.get("slo"))
                value = _SLO_ALERT_STATE_VALUES.get(
                    str(alert.get("state")), 0
                )
                worst[name] = max(worst.get(name, 0), value)
            for name, value in worst.items():
                state.add_metric([name], value)
        yield budget
        yield burn
        yield state


class StreamPlaneCollector:
    """Scrape-time exposition of the streaming scoring plane
    (``gordo_tpu.stream``): session/subscriber/pending gauges, the
    row-accounting totals, and the flush-duration + ingest→scored
    score-lag fixed-bucket histograms from the process-global stream
    telemetry accumulator.

    Cardinality is BOUNDED by construction (the PR 8/9 contract): the
    only label sets are small constants — session states, row accounting
    scopes, event-drop scopes. Per-machine and per-stream detail NEVER
    reaches a label, however large the fleet grows; it lives on the
    ``/stream/status`` route and in the span trace instead."""

    def collect(self):
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeHistogramMetricFamily,
            GaugeMetricFamily,
        )

        from ... import stream as stream_plane

        sessions = GaugeMetricFamily(
            "gordo_stream_sessions",
            "Stream sessions by state (tombstoned = closed but retained "
            "for late cursors until the TTL)",
            labels=["state"],
        )
        subscribers = GaugeMetricFamily(
            "gordo_stream_subscribers",
            "Open SSE subscriptions across all stream sessions",
            labels=[],
        )
        pending = GaugeMetricFamily(
            "gordo_stream_pending_rows",
            "Rows buffered in the ingest rings awaiting the watermark, "
            "summed over sessions and machines",
            labels=[],
        )
        quarantined = GaugeMetricFamily(
            "gordo_stream_quarantined_machines",
            "Stream machines currently held by an open circuit breaker "
            "(their rows buffer instead of scoring)",
            labels=[],
        )
        rows = CounterMetricFamily(
            "gordo_stream_rows",
            "Streaming-plane row accounting by outcome (in/scored/"
            "failed/shed); in == scored + failed + pending + shed is "
            "the plane's zero-gap invariant",
            labels=["outcome"],
        )
        events_dropped = CounterMetricFamily(
            "gordo_stream_events_dropped",
            "Emitted events dropped by scope (outbox = slow-consumer "
            "ring eviction, emit = the emit fault site)",
            labels=["scope"],
        )
        flushes = CounterMetricFamily(
            "gordo_stream_flushes",
            "Watermark scoring flushes run by this process",
            labels=[],
        )
        flush_hist = GaugeHistogramMetricFamily(
            "gordo_stream_flush_duration_ms",
            "Wall milliseconds per watermark flush (cut + fused scoring "
            "+ event fan-out), fixed buckets",
            labels=[],
        )
        lag_hist = GaugeHistogramMetricFamily(
            "gordo_stream_score_lag_ms",
            "Ingest→scored lag in milliseconds, row-weighted (each "
            "flush contributes its scored rows at the span's oldest-row "
            "lag) — the freshness SLO's native distribution",
            labels=[],
        )

        plane = stream_plane.get_plane()
        active = tombstoned = subs = pending_rows = quarantine_count = 0
        dropped = {"outbox": 0, "emit": 0}
        if plane is not None:
            stats = plane.stats()
            for session in (stats.get("sessions") or {}).values():
                if session.get("closed"):
                    tombstoned += 1
                else:
                    active += 1
                subs += int(session.get("subscribers") or 0)
                dropped["outbox"] += int(
                    session.get("events_dropped_outbox") or 0
                )
                dropped["emit"] += int(
                    session.get("events_dropped_emit") or 0
                )
                for machine in (session.get("machines") or {}).values():
                    pending_rows += int(machine.get("rows_pending") or 0)
                    if machine.get("quarantined"):
                        quarantine_count += 1
        sessions.add_metric(["active"], active)
        sessions.add_metric(["tombstoned"], tombstoned)
        subscribers.add_metric([], subs)
        pending.add_metric([], pending_rows)
        quarantined.add_metric([], quarantine_count)
        for scope, count in dropped.items():
            events_dropped.add_metric([scope], count)

        telemetry = stream_plane.stream_telemetry().snapshot()
        rows.add_metric(["in"], telemetry["rows_in"])
        rows.add_metric(["scored"], telemetry["rows_scored"])
        rows.add_metric(["failed"], telemetry["rows_failed"])
        rows.add_metric(["shed"], telemetry["rows_shed"])
        flushes.add_metric([], telemetry["flushes"])
        for family, histogram in (
            (flush_hist, telemetry["flush_ms"]),
            (lag_hist, telemetry["lag_ms"]),
        ):
            cumulative = 0
            buckets = []
            counts = histogram.get("counts") or []
            for edge, count in zip(
                histogram.get("buckets_ms") or [], counts
            ):
                cumulative += int(count)
                buckets.append((str(edge), cumulative))
            buckets.append(("+Inf", int(histogram.get("count") or 0)))
            family.add_metric(
                [],
                buckets=buckets,
                gsum_value=float(histogram.get("sum_ms") or 0.0),
            )

        yield sessions
        yield subscribers
        yield pending
        yield quarantined
        yield rows
        yield events_dropped
        yield flushes
        yield flush_hist
        yield lag_hist


#: registries already carrying the fleet-console collectors (same
#: duplicate-registration guard as the program-cache WeakSet)
_fleet_console_registries: "weakref.WeakSet" = weakref.WeakSet()


def register_fleet_console_collectors(registry: CollectorRegistry) -> None:
    """Attach the fleet-health, device-utilization, SLO and stream-plane
    scrape collectors to ``registry``, once — on every registry that
    answers scrapes, like the program-cache collector (scrape-time
    collectors have no mmap backing to ride the multiprocess fan-in)."""
    if registry in _fleet_console_registries:
        return
    _fleet_console_registries.add(registry)
    registry.register(FleetHealthCollector())
    registry.register(DeviceUtilizationCollector())
    registry.register(SloCollector())
    registry.register(StreamPlaneCollector())


class ServeMetrics:
    """The micro-batching engine's metric set: queue depth, batch size /
    coalesce-ratio / padding-waste histograms, and the shed counter.
    Attached to a :class:`gordo_tpu.serve.ServeEngine` by ``build_app``;
    every method is safe to call from dispatcher threads."""

    def __init__(
        self,
        project: Optional[str] = None,
        registry: Optional[CollectorRegistry] = None,
    ):
        _ensure_multiproc_dir()
        self.project = project or ""
        self.registry = registry if registry is not None else REGISTRY
        labels = ["project"]
        self.queue_depth = Gauge(
            "gordo_server_batch_queue_depth",
            "Requests currently waiting in the micro-batch queue",
            labelnames=labels,
            registry=self.registry,
            multiprocess_mode="max",
        )
        self.batch_size = Histogram(
            "gordo_server_batch_size",
            "Requests coalesced into each fused device program",
            labelnames=labels,
            buckets=_BATCH_SIZE_BUCKETS,
            registry=self.registry,
        )
        self.coalesce_ratio = Histogram(
            "gordo_server_batch_coalesce_ratio",
            "Program occupancy: coalesced requests / padded member slots "
            "of the fused program (1.0 = a perfectly full batch)",
            labelnames=labels,
            buckets=_RATIO_BUCKETS,
            registry=self.registry,
        )
        self.padding_waste = Histogram(
            "gordo_server_batch_padding_waste",
            "Fraction of the fused program's padded (member x row) cells "
            "holding no request data",
            labelnames=labels,
            buckets=_RATIO_BUCKETS,
            registry=self.registry,
        )
        self.shed = Counter(
            "gordo_server_batch_shed_total",
            "Requests shed by serving admission control, by reason "
            "(queue_full -> 429, deadline -> 504, cancelled = waiter "
            "gave up before its batch ran, runner_error = the batcher's "
            "backstop resolved a crashed batch)",
            labelnames=labels + ["reason"],
            registry=self.registry,
        )
        # the serving circuit breakers (gordo_tpu.serve.breaker): the
        # `state` label is the breaker vocabulary (open / half_open /
        # closed) — bounded by construction
        self.breaker_transitions = Counter(
            "gordo_server_breaker_transitions_total",
            "Per-member serving circuit-breaker state transitions, by "
            "the state ENTERED (open = tripped into quarantine, "
            "half_open = probing, closed = recovered)",
            labelnames=labels + ["state"],
            registry=self.registry,
        )
        self.breaker_open = Gauge(
            "gordo_server_breaker_open_members",
            "Members currently quarantined by an open serving circuit "
            "breaker (answering 503 + Retry-After instead of riding "
            "batches)",
            labelnames=labels,
            registry=self.registry,
            multiprocess_mode="max",
        )
        register_program_cache_collector(self.registry)
        register_fleet_console_collectors(self.registry)

    def observe_batch(self, size: int, occupancy: float, padding_waste: float):
        self.batch_size.labels(project=self.project).observe(size)
        self.coalesce_ratio.labels(project=self.project).observe(occupancy)
        self.padding_waste.labels(project=self.project).observe(padding_waste)

    def observe_shed(self, reason: str, n: int = 1):
        self.shed.labels(project=self.project, reason=reason).inc(n)

    def observe_breaker(self, state: str):
        self.breaker_transitions.labels(
            project=self.project, state=state
        ).inc()

    def set_breaker_open(self, count: int):
        self.breaker_open.labels(project=self.project).set(count)

    def set_queue_depth(self, depth: int):
        self.queue_depth.labels(project=self.project).set(depth)

    def set_program_cache(self):
        # the gauge is a scrape-time collector; nothing to push
        pass


#: one ServeMetrics per LIVE registry (same WeakKey rationale as
#: ``_build_metrics`` above: a dead registry's id must never alias a new
#: registry into receiving unregistered metric objects)
_serve_metrics: "weakref.WeakKeyDictionary[CollectorRegistry, ServeMetrics]" = (
    weakref.WeakKeyDictionary()
)


def serve_metrics(
    project: Optional[str] = None,
    registry: Optional[CollectorRegistry] = None,
) -> ServeMetrics:
    """The serve metric set for ``registry`` (default: the global
    REGISTRY), created once per live registry."""
    target = registry if registry is not None else REGISTRY
    if target not in _serve_metrics:
        _serve_metrics[target] = ServeMetrics(project=project, registry=target)
    return _serve_metrics[target]


def set_fleet_plan_prediction(
    project: Optional[str],
    strategy: str,
    predicted_seconds: float,
    padding_waste: float,
    compiles: int,
):
    """Export a FleetPlan's headline predictions (at bucket-plan time)."""
    metrics = fleet_build_metrics()
    labels = {"project": project or "", "strategy": strategy}
    metrics["plan_predicted_seconds"].labels(**labels).set(predicted_seconds)
    metrics["plan_padding_waste"].labels(**labels).set(padding_waste)
    metrics["plan_compiles"].labels(**labels).set(compiles)


def set_fleet_plan_actuals(
    project: Optional[str], strategy: str, seconds: float, compiles: int
):
    """Export what the planned (final-fit) programs actually cost, so
    predicted-vs-actual is one PromQL subtraction."""
    metrics = fleet_build_metrics()
    labels = {"project": project or "", "strategy": strategy}
    metrics["plan_actual_seconds"].labels(**labels).set(seconds)
    metrics["plan_actual_compiles"].labels(**labels).set(compiles)


def set_fleet_build_progress(
    project: Optional[str], total: int, completed: int, failed: int
):
    """The live machine-progress gauges (the in-process analog of
    counting Succeeded/Failed pods in ``argo get``)."""
    metrics = fleet_build_metrics()
    labels = {"project": project or ""}
    metrics["machines_total"].labels(**labels).set(total)
    metrics["machines_completed"].labels(**labels).set(completed)
    metrics["machines_failed"].labels(**labels).set(failed)


# -- fleet lifecycle metrics --------------------------------------------------

#: one lifecycle metric set per LIVE registry (same WeakKey rationale as
#: ``_build_metrics``: id() reuse after GC must never resurrect stale
#: collector handles)
_lifecycle_metrics: "weakref.WeakKeyDictionary[CollectorRegistry, dict]" = (
    weakref.WeakKeyDictionary()
)

_LIFECYCLE_EVENT_COUNTERS = (
    (
        "rebuilds",
        "gordo_fleet_lifecycle_rebuilds_total",
        "Members rebuilt by the drift-triggered lifecycle loop",
    ),
    (
        "promotions",
        "gordo_fleet_lifecycle_promotions_total",
        "Canary revisions promoted into serving by the lifecycle loop",
    ),
    (
        "rollbacks",
        "gordo_fleet_lifecycle_rollbacks_total",
        "Canary revisions rolled back and quarantined (gate failures, "
        "failed rebuilds, operator rollbacks)",
    ),
)

#: hot swaps are sub-second by design; the tail buckets catch cold loads
_SWAP_BUCKETS = (0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0)


def fleet_lifecycle_metrics(
    registry: Optional[CollectorRegistry] = None,
) -> dict:
    """The ``gordo_fleet_lifecycle_*`` metric set for ``registry``
    (default: the global REGISTRY), created once per live registry:
    event Counters, the drift/canary status Gauges, and the hot-swap
    duration Histogram."""
    target = registry if registry is not None else REGISTRY
    if target not in _lifecycle_metrics:
        _ensure_multiproc_dir()
        metrics = {
            counter_key: Counter(
                name,
                help_text,
                labelnames=["project"],
                registry=target,
            )
            for counter_key, name, help_text in _LIFECYCLE_EVENT_COUNTERS
        }
        metrics["drifted"] = Gauge(
            "gordo_fleet_lifecycle_drifted_machines",
            "Machines whose latest drift evaluation tripped",
            labelnames=["project"],
            registry=target,
            multiprocess_mode="max",
        )
        metrics["stale"] = Gauge(
            "gordo_fleet_lifecycle_stale_machines",
            "Machines in the current stale set (being rebuilt/canaried)",
            labelnames=["project"],
            registry=target,
            multiprocess_mode="max",
        )
        metrics["canary_fraction"] = Gauge(
            "gordo_fleet_lifecycle_canary_fraction",
            "Traffic fraction currently routed to the canary revision "
            "(0 when no canary is serving)",
            labelnames=["project"],
            registry=target,
            multiprocess_mode="max",
        )
        metrics["swap_seconds"] = Histogram(
            "gordo_fleet_lifecycle_swap_seconds",
            "Wall-clock of promoting a canary into serving (the hot "
            "swap itself, warm included; requests are never paused)",
            labelnames=["project"],
            buckets=_SWAP_BUCKETS,
            registry=target,
        )
        _lifecycle_metrics[target] = metrics
    return _lifecycle_metrics[target]


def record_fleet_lifecycle_event(
    project: Optional[str], event: str, n: int = 1
):
    """Count one lifecycle event (``rebuilds``/``promotions``/
    ``rollbacks``); unknown event names are ignored (forward
    compatibility over crashes). The lookup is restricted to the
    counter keys — the metric dict also holds Gauges/Histograms, which
    must be neither inc'd nor crashed into."""
    if event not in {key for key, _, _ in _LIFECYCLE_EVENT_COUNTERS}:
        return
    if n:
        fleet_lifecycle_metrics()[event].labels(project=project or "").inc(n)


def set_fleet_lifecycle_status(
    project: Optional[str],
    drifted: int,
    stale: int,
    canary_fraction: float,
):
    """The lifecycle loop's live status gauges (per cycle)."""
    metrics = fleet_lifecycle_metrics()
    labels = {"project": project or ""}
    metrics["drifted"].labels(**labels).set(drifted)
    metrics["stale"].labels(**labels).set(stale)
    metrics["canary_fraction"].labels(**labels).set(canary_fraction)


def observe_lifecycle_swap(project: Optional[str], seconds: float):
    """One promotion hot-swap's wall-clock."""
    fleet_lifecycle_metrics()["swap_seconds"].labels(
        project=project or ""
    ).observe(seconds)
