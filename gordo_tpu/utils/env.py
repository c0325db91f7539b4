"""
Environment-knob parsing and the knob REGISTRY: one warn-and-fall-back
implementation for every ``GORDO_TPU_*`` knob instead of a per-call-site
copy, plus the single declared catalog of every knob the codebase reads.

Every ``GORDO_TPU_*`` environment read in the package must go through
one of the typed accessors here (``env_int``/``env_float``/``env_bool``/
``env_str``/``env_raw``), and every knob name must be declared in
:data:`KNOBS` — both invariants are enforced statically by the
``env-registry`` rule of ``gordo-tpu lint`` (see
``docs/static-analysis.md``), and the reference table in
``docs/configuration.md`` is generated from this registry
(``python docs/generate_env_docs.py``).

Malformed values never raise: they log ONE warning per distinct
(name, value) pair and fall back to the call-site default.

>>> import os
>>> os.environ["GORDO_TPU_DOCTEST_KNOB"] = "not-a-number"
>>> env_int("GORDO_TPU_DOCTEST_KNOB", 7)
7
>>> os.environ["GORDO_TPU_DOCTEST_KNOB"] = "maybe"
>>> env_bool("GORDO_TPU_DOCTEST_KNOB", False)
False
>>> del os.environ["GORDO_TPU_DOCTEST_KNOB"]
"""

import logging
import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

logger = logging.getLogger(__name__)

#: truthy / falsy spellings accepted by :func:`env_bool`
_TRUE_STRINGS = frozenset(("1", "true", "on", "yes"))
_FALSE_STRINGS = frozenset(("0", "false", "off", "no"))

#: (name, raw) pairs already warned about — malformed knobs warn once,
#: not once per read (hot paths re-read knobs per request/batch)
_warned: set = set()


def _warn_once(name: str, raw: str, default) -> None:
    key = (name, raw)
    if key not in _warned:
        _warned.add(key)
        logger.warning("Invalid %s=%r; using %r", name, raw, default)


def env_int(name: str, default: int) -> int:
    """``int(os.environ[name])`` with warn-once fallback to ``default``."""
    raw = os.environ.get(name)
    if raw:
        try:
            return int(raw)
        except ValueError:
            _warn_once(name, raw, default)
    return default


def env_float(name: str, default: Optional[float]) -> Optional[float]:
    """``float(os.environ[name])`` with warn-once fallback to ``default``."""
    raw = os.environ.get(name)
    if raw:
        try:
            return float(raw)
        except ValueError:
            _warn_once(name, raw, default)
    return default


def env_bool(name: str, default: bool) -> bool:
    """Boolean knob: ``1/true/on/yes`` → True, ``0/false/off/no`` →
    False, unset or empty (a blanked-out manifest var) → ``default``;
    anything else warns once and falls back."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    value = raw.strip().lower()
    if not value:
        return default
    if value in _TRUE_STRINGS:
        return True
    if value in _FALSE_STRINGS:
        return False
    _warn_once(name, raw, default)
    return default


def env_str(name: str, default: Optional[str]) -> Optional[str]:
    """String knob: the raw value, with unset/empty falling back to
    ``default`` (paths, strategy names, comma-lists)."""
    raw = os.environ.get(name)
    return raw if raw else default


def env_raw(name: str) -> Optional[str]:
    """The unparsed value (or None) — for call sites that cache a parsed
    knob keyed on the raw string and only re-parse when it changes."""
    return os.environ.get(name)


@dataclass(frozen=True)
class Knob:
    """One declared ``GORDO_TPU_*`` environment knob.

    ``type`` is the accessor family (``int``/``float``/``bool``/``str``),
    ``default`` the call-site fallback, ``doc`` the one-line reference
    description (the docs table row), and ``section`` the grouping header
    in ``docs/configuration.md``.
    """

    name: str
    type: str
    default: object
    doc: str
    section: str = "General"


def _knobs(*knobs: Knob) -> Dict[str, Knob]:
    table: Dict[str, Knob] = {}
    for knob in knobs:
        if knob.name in table:
            raise ValueError(f"duplicate knob declaration: {knob.name}")
        table[knob.name] = knob
    return table


#: The registry: every ``GORDO_TPU_*`` knob the package reads, in docs
#: order. Adding a read without declaring it here fails `gordo-tpu lint`
#: (env-registry rule) and the docs drift test.
KNOBS: Dict[str, Knob] = _knobs(
    # -- Training / device performance ------------------------------------
    Knob(
        "GORDO_TPU_CV_CHUNK_BYTES", "int", 1 << 30,
        "Fleet CV super-bucket memory budget in bytes.",
        "Performance",
    ),
    Knob(
        "GORDO_TPU_DISABLE_PALLAS", "bool", False,
        "Force the plain-XLA fleet forward program even where the Pallas "
        "kernel is available.",
        "Performance",
    ),
    Knob(
        "GORDO_TPU_RING_PREDICT_ROWS", "int", 65_536,
        "Row threshold past which windowed models shard the prediction "
        "time axis over the device mesh (`parallel/sequence.py`).",
        "Performance",
    ),
    # -- Bucket planner ----------------------------------------------------
    Knob(
        "GORDO_TPU_PLAN_STRATEGY", "str", "naive",
        "Bucket-construction strategy: `naive` (historical exact-key "
        "grouping, default) or `packed` (cost-model bin packing).",
        "Planner",
    ),
    Knob(
        "GORDO_TPU_PLAN_PAD_RATIO", "float", 1.25,
        "Geometric growth ratio for the packed strategy's dense sample "
        "axis.",
        "Planner",
    ),
    Knob(
        "GORDO_TPU_SERIES_PAD_RATIO", "float", 1.25,
        "Geometric growth ratio for the windowed (LSTM) series axis — "
        "applies to BOTH strategies; replaces the old pow2 time-axis "
        "padding.",
        "Planner",
    ),
    Knob(
        "GORDO_TPU_PLAN_COMPILE_BUDGET", "int", 0,
        "Hard cap on planned program count for `packed` (0 = stop rung "
        "merging at the cost model's compile-vs-padding break-even).",
        "Planner",
    ),
    Knob(
        "GORDO_TPU_PLAN_HBM_CAP_BYTES", "int", 4 << 30,
        "Per-bucket predicted resident-bytes cap for `packed` — buckets "
        "split *before* they would OOM.",
        "Planner",
    ),
    # -- Build robustness --------------------------------------------------
    Knob(
        "GORDO_TPU_DATA_RETRIES", "int", 2,
        "Extra data-fetch attempts per machine; deterministic config "
        "errors never retry.",
        "Robustness",
    ),
    Knob(
        "GORDO_TPU_DATA_BACKOFF", "float", 0.5,
        "Base backoff seconds between fetch attempts, doubling per "
        "attempt.",
        "Robustness",
    ),
    Knob(
        "GORDO_TPU_DATA_DEADLINE", "float", None,
        "Optional per-machine fetch deadline in seconds — retries stop "
        "once the next backoff would cross it.",
        "Robustness",
    ),
    Knob(
        "GORDO_TPU_FAULTS", "str", None,
        "Deterministic fault injection for drills/tests, e.g. "
        "`device_program:poison-*:times=inf` (sites: `data_fetch`, "
        "`device_program`, `dump_artifact`, `drift_eval`, `canary_build`, "
        "`promote_swap`, `rollback`, `process_kill_after_n_machines`, "
        "and the serving sites `serve_device_program`, "
        "`serve_member_poison`, `serve_scatter` keyed "
        "`<spec>:<precision>:<member>`).",
        "Robustness",
    ),
    # -- Telemetry ---------------------------------------------------------
    Knob(
        "GORDO_TPU_TELEMETRY", "bool", True,
        "Telemetry master switch: spans, traces, build-status heartbeat.",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_TELEMETRY_DIR", "str", None,
        "Span-sink directory (`build_trace.jsonl` / `serve_trace.jsonl`); "
        "builds default to the build output dir, serving has no default.",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_TELEMETRY_HEARTBEAT", "float", 0.5,
        "`build_status.json` heartbeat throttle seconds (0 = write "
        "exactly per completion; used by the fault drills).",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_TELEMETRY_MAX_BYTES", "int", 256 * 1024 * 1024,
        "Trace-sink rotation threshold per generation.",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_TELEMETRY_KEEP", "int", 3,
        "Rotated trace generations kept per sink (older are deleted).",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_TRACE_SAMPLE_RATE", "float", 0.05,
        "Head-sampling rate for exported request traces (ids/logs/RED "
        "metrics see all traffic; an upstream sampled flag or "
        "`?profile=1` always exports).",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_PROFILE_SAMPLE_RATE", "float", 0.0,
        "Fraction of requests host-profiled by the sampling profiler "
        "(`?profile=1` forces one request).",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_PROFILE_INTERVAL_MS", "float", 5.0,
        "Sampling profiler frame-capture interval.",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_PROFILE_DIR", "str", None,
        "Directory for `jax.profiler` device traces "
        "(`utils/profiling.py`; `?profile=device` on the server).",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_FLEET_HEALTH", "bool", True,
        "Per-member fleet health ledger master switch "
        "(`fleet_health.json` snapshots + the `fleet-status` surface; "
        "also requires `GORDO_TPU_TELEMETRY`).",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_HEALTH_HEARTBEAT", "float", 2.0,
        "Seconds between throttled `fleet_health.json` snapshot writes "
        "(state transitions — drift verdicts, quarantines — always "
        "write).",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_HEALTH_WINDOW", "int", 100_000,
        "Rows after which a machine's rolling serving-residual window "
        "decays (halves), so the ledger's residual mean tracks the "
        "present.",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_HEALTH_SHARDS", "int", 0,
        "Fleet-health snapshot shard count (`fleet_health.d/`): 0 "
        "(default) sizes adaptively — monolithic `fleet_health.json` "
        "for small fleets, then ~512 machines per shard up to 64 "
        "shards — so a dirty-shard flush rewrites one bounded file, "
        "not the whole fleet. Any positive value pins the count.",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_FLEET_STATUS_MAX_MACHINES", "int", 500,
        "Per-machine records inlined in the fleet-status document only "
        "while the fleet is at most this large (past it: summary + "
        "top-K offenders); also the hard cap on one `?machines=` page.",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_FLEET_STATUS_TOP_K", "int", 10,
        "Offender rows (unhealthiest machines) carried by the bounded "
        "fleet-status health section.",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_DEVICE_TELEMETRY", "bool", True,
        "Device-utilization sampling (`Device.memory_stats()` around "
        "fleet programs and at Prometheus scrape time); the "
        "compile-cache hit counters stay on with telemetry itself.",
        "Telemetry",
    ),
    Knob(
        "GORDO_TPU_WORKER_SINKS", "bool", "auto",
        "Per-process telemetry sinks: `serve_trace.jsonl` / "
        "`fleet_health.json` get a `-<pid>` suffix so N gunicorn "
        "workers stop overwriting one shared path (readers merge every "
        "variant). Default: on exactly when `PROMETHEUS_MULTIPROC_DIR` "
        "is configured — the existing multi-worker deployment signal.",
        "Telemetry",
    ),
    # -- SLO engine --------------------------------------------------------
    Knob(
        "GORDO_TPU_SLO_CONFIG", "str", None,
        "Path to a `slos.toml` declaring objectives and burn-rate "
        "alert rules (default: `<telemetry dir>/slos.toml`, then the "
        "packaged defaults).",
        "SLO",
    ),
    Knob(
        "GORDO_TPU_SLO_WINDOW_SECONDS", "int", 60,
        "Rollup window size for the cross-worker telemetry reducer "
        "(`rollups/<window>.json`); boundaries align to it, so rollups "
        "from different workers/hosts merge bucket-for-bucket.",
        "SLO",
    ),
    Knob(
        "GORDO_TPU_ROLLUP_MANIFEST", "bool", True,
        "Maintain `rollups/manifest.json` (window -> file map + "
        "per-sink span windows) so merged-window reads and "
        "`--since`/`--last` queries open only the rollup files they "
        "need instead of walking the directory.",
        "SLO",
    ),
    Knob(
        "GORDO_TPU_SLO_ROLLUP_KEEP", "int", 50_000,
        "Rollup windows retained on disk (oldest pruned past this); "
        "the default covers a 30d SLO window at 60s granularity.",
        "SLO",
    ),
    Knob(
        "GORDO_TPU_SLO_SINK_GC_AGE", "float", 86400.0,
        "Seconds a dead worker's fully-consumed trace-sink chain must "
        "sit unwritten before the rollup reducer deletes it; 0 "
        "disables sink GC (use that for aggregators running in "
        "another pid namespace/host, where the liveness probe is "
        "blind).",
        "SLO",
    ),
    Knob(
        "GORDO_TPU_SLO_SCRAPE_REFRESH", "float", 60.0,
        "Minimum seconds between scrape-driven SLO re-evaluations of a "
        "watched telemetry dir (`gordo_slo_*` gauges); 0 = scrapes "
        "report the cached status only.",
        "SLO",
    ),
    # -- Serving / micro-batching -----------------------------------------
    Knob(
        "GORDO_TPU_BATCHING", "bool", False,
        "Cross-request micro-batching master switch (`gordo_tpu.serve`).",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BATCH_MAX_SIZE", "int", 32,
        "Member-axis batch capacity per fused program.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BATCH_MAX_DELAY_MS", "float", 5.0,
        "Max time a request waits in the batch queue before a flush.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BATCH_QUEUE_DEPTH", "int", 512,
        "Admission-control queue depth; overflow sheds with 429 + "
        "Retry-After.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BATCH_DEADLINE_MS", "float", 2000.0,
        "Per-request queue deadline; expiry sheds with 504.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BATCH_DISPATCHERS", "int", 1,
        "Dispatcher threads per batching engine.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BATCH_ROW_LADDER", "str", "32,128,512,2048,8192",
        "Row-axis padding ladder (comma list, ascending); requests "
        "taller than the top rung fall back unbatched.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BATCH_INLINE_FLUSH", "bool", True,
        "Let the request thread that fills a batch flush it inline "
        "instead of waking a dispatcher.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_SERVE_WARMUP", "bool", True,
        "Precompile the batch-ladder programs in a background thread at "
        "server boot (only when batching is on).",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_SERVE_WARMUP_ROWS", "int", 512,
        "Tallest row rung warmed at boot.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_SERVE_PRECISION", "str", "f32",
        "Default serving precision for the fused batch programs: `f32` "
        "(default, byte-identical to pre-precision serving), `bf16`, or "
        "`int8` (experimental per-channel weight quantization; "
        "activations run bf16). A spec's own `precision:` field "
        "overrides per model; reduced precision only serves behind a "
        "passed precision-parity gate and degrades to f32 on failure.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_PRECISION_GATE", "bool", True,
        "Gate reduced-precision serving on f32 verdict parity "
        "(`gordo_tpu.serve.precision`); off serves the requested "
        "precision ungated (benches/tests).",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_WIRE_COLUMNAR", "bool", True,
        "Columnar response fast path on the prediction/anomaly/fleet "
        "routes: vectorized numpy assembly + dict-free wire encoders "
        "(byte-identical JSON). Off = the legacy pandas assembly.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_WIRE_ARROW", "bool", True,
        "Serve and accept Arrow-IPC request/response bodies when "
        "pyarrow is importable (`Accept`/`Content-Type: "
        "application/vnd.apache.arrow.stream`). Off drills the "
        "JSON-only fallback.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_WIRE_STREAM", "bool", False,
        "Stream JSON response bodies as WSGI chunks (encode overlaps "
        "the socket write). Off by default: streamed serialize time "
        "lands outside the request's exported stage spans (see "
        "`docs/serving.md`).",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_INGEST_COMPILED", "bool", True,
        "Compiled preprocessing plans (`gordo_tpu.ingest`): per-member "
        "scaler affines are extracted into stacked device arrays cached "
        "on the revision fleet, and scale/transform runs inside the "
        "fused gather program. Off = every route materializes "
        "transformed inputs host-side (the legacy path).",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_INGEST_DLPACK", "bool", True,
        "Per-column dlpack device transfer for raw wire columns "
        "(`gordo_tpu.ingest.to_device`) — skips the intermediate host "
        "`column_stack`. Only engages on accelerator backends: on CPU "
        "both rungs stage through host memory, so host staging is the "
        "fast rung regardless of this knob. Columns dlpack cannot "
        "export (read-only, strided) and off take host staging, "
        "counted by reason in `ingest_stats()['fallback_reasons']`.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_SERVE_FINITE_CHECK", "bool", True,
        "Scan every fused batch's output for non-finite (NaN/inf) rows: "
        "a member producing them from FINITE input is poisoned and "
        "fails alone (feeding its circuit breaker) instead of silently "
        "corrupting anomaly verdicts.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BREAKER_THRESHOLD", "int", 3,
        "Consecutive isolated device failures that trip a member's "
        "serving circuit breaker into quarantine (503 + Retry-After).",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BREAKER_COOLDOWN_S", "float", 30.0,
        "Initial quarantine cooldown before a tripped member's breaker "
        "half-opens and admits one probe request.",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BREAKER_BACKOFF", "float", 2.0,
        "Cooldown multiplier applied on every re-trip (a failed "
        "half-open probe re-opens with a longer cooldown).",
        "Serving",
    ),
    Knob(
        "GORDO_TPU_BREAKER_MAX_COOLDOWN_S", "float", 600.0,
        "Cap on the exponential breaker cooldown.",
        "Serving",
    ),
    # -- Streaming ---------------------------------------------------------
    Knob(
        "GORDO_TPU_STREAM_ENABLED", "bool", True,
        "Master switch for the always-on streaming scoring plane "
        "(`/stream/...` routes). Disabled, stream routes answer 503.",
        "Streaming",
    ),
    Knob(
        "GORDO_TPU_STREAM_RING_ROWS", "int", 8192,
        "Per-machine row-ring capacity on a stream session. Ingest "
        "beyond it sheds oldest-first (counted, surfaced as a `shed` "
        "control frame) — bounded memory, never a stall.",
        "Streaming",
    ),
    Knob(
        "GORDO_TPU_STREAM_WINDOW_ROWS", "int", 64,
        "Watermark window height: a machine scores once it has this "
        "many buffered rows, through the same fused gather programs as "
        "the request path.",
        "Streaming",
    ),
    Knob(
        "GORDO_TPU_STREAM_OUTBOX_EVENTS", "int", 1024,
        "Per-session outbox ring capacity (scored anomalies + control "
        "frames). A consumer slower than the ring gets a `shed` "
        "scope-`outbox` frame with the evicted count on catch-up.",
        "Streaming",
    ),
    Knob(
        "GORDO_TPU_STREAM_SESSION_TTL_S", "float", 3600.0,
        "Idle seconds before a stream session (no ingest, no "
        "subscriber activity) is expired with a terminal `end` frame.",
        "Streaming",
    ),
    Knob(
        "GORDO_TPU_STREAM_HEARTBEAT_S", "float", 15.0,
        "SSE keep-alive comment interval on an idle event feed (keeps "
        "proxies from reaping the long-lived response).",
        "Streaming",
    ),
    Knob(
        "GORDO_TPU_STREAM_MAX_SESSIONS", "int", 64,
        "Live stream sessions the plane admits before answering 429 + "
        "Retry-After (admission control for the standing plane).",
        "Streaming",
    ),
    Knob(
        "GORDO_TPU_STREAM_SHED_RETRY_S", "float", 1.0,
        "Retry-After hint (seconds) in backpressure ingest acks and "
        "429 saturation responses.",
        "Streaming",
    ),
    # -- Lifecycle ---------------------------------------------------------
    Knob(
        "GORDO_TPU_DRIFT_SIGMA", "float", 2.0,
        "Per-feature drift threshold in baseline standard deviations.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_DRIFT_FEATURE_QUORUM", "float", 0.25,
        "Fraction of features that must drift before a machine counts as "
        "drifted.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_DRIFT_RESIDUAL_RATIO", "float", 2.0,
        "Serving-mse ratio over the calibrated baseline that marks "
        "residual drift.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_DRIFT_MIN_SAMPLES", "int", 64,
        "Rows a drift window must accumulate before it is evaluated.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_DRIFT_CALIBRATION", "int", 3,
        "Scoring batches used to calibrate the residual baseline.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_GATE_MAX_ERROR_RATE", "float", 0.0,
        "Canary gate: max tolerated canary scoring error rate.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_GATE_THRESHOLD_RATIO", "float", 4.0,
        "Canary gate: max rebuilt-vs-base anomaly-threshold ratio.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_GATE_RESIDUAL_RATIO", "float", 2.0,
        "Canary gate: max canary-vs-base residual ratio.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_GATE_PRECISION_AGREEMENT", "float", 0.98,
        "Precision-parity gate: minimum reduced-vs-f32 anomaly-verdict "
        "agreement fraction on the probe window (serve-time bucket "
        "gating AND the canary promotion gate).",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_GATE_PRECISION_RTOL", "float", 0.05,
        "Precision-parity gate: relative row tolerance for the "
        "reconstruction-closeness fallback (members without a fitted "
        "anomaly threshold).",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_GATE_PRECISION_PROBE_ROWS", "int", 128,
        "Precision-parity gate: probe window height scored per member.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_CANARY_FRACTION", "float", 0.25,
        "Fraction of requests routed to a published canary revision.",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_QUARANTINE_COOLDOWN", "float", 3600.0,
        "Seconds a rolled-back machine stays quarantined before it may "
        "canary again (wall-clock: quarantine spans process restarts).",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_GATE_SLO_BURN", "bool", True,
        "Hold lifecycle auto-promotions while a page-severity SLO "
        "burn-rate alert is firing (the canary keeps its traffic "
        "slice; `lifecycle promote --force` bypasses).",
        "Lifecycle",
    ),
    Knob(
        "GORDO_TPU_LIFECYCLE_BREAKER_REBUILD", "bool", True,
        "Nominate members whose serving circuit breaker tripped (the "
        "health ledger's `breaker` section) as rebuild candidates "
        "alongside drifted ones.",
        "Lifecycle",
    ),
    # -- Learned performance model -----------------------------------------
    Knob(
        "GORDO_TPU_PERFMODEL", "bool", False,
        "Master switch for the learned performance model: cost tables "
        "carrying a fitted `learned` section answer in-domain "
        "predictions (device ms / compile ms / HBM bytes) from the "
        "trace-trained log-linear regressors instead of the analytic "
        "formula. Off: the section is inert — plans and ladder choices "
        "are byte-identical to the analytic model's.",
        "Performance model",
    ),
    Knob(
        "GORDO_TPU_PERFMODEL_TABLE", "str", None,
        "Path to the `cost_table.json` the SERVING plane's estimators "
        "(batch-span predictions, stream flush predictions, the "
        "model-informed consumers below) load; unreadable or "
        "mis-versioned tables warn once and degrade to the analytic "
        "defaults. Unset: the analytic defaults.",
        "Performance model",
    ),
    Knob(
        "GORDO_TPU_PERFMODEL_WARMUP", "bool", False,
        "Order serve warmup by predicted cost, hottest first (specs by "
        "predicted step time at the top warm shape, then per-spec "
        "shapes descending) so the most expensive compiles happen "
        "earliest in the warmup budget.",
        "Performance model",
    ),
    Knob(
        "GORDO_TPU_PERFMODEL_BATCH_CAP_BYTES", "int", 0,
        "Per-spec predicted-HBM batch cap in bytes: row rungs whose "
        "predicted fused-batch footprint (at the full member ladder) "
        "exceeds the budget are never batched into — requests taller "
        "than the allowed rungs serve unbatched. 0 = off.",
        "Performance model",
    ),
    Knob(
        "GORDO_TPU_PERFMODEL_BREAKER", "bool", False,
        "Predicted-HBM-aware OOM demotion: a RESOURCE_EXHAUSTED batch "
        "demotes to the largest ladder rung whose predicted footprint "
        "is safely below the failed shape's, instead of the fixed "
        "halve-members / drop-one-row-rung heuristic.",
        "Performance model",
    ),
    Knob(
        "GORDO_TPU_PERFMODEL_BREAKER_SAFETY", "float", 0.8,
        "Safety factor for predicted-HBM-aware demotion: the demoted "
        "rung's predicted bytes must be <= this fraction of the failed "
        "shape's predicted bytes.",
        "Performance model",
    ),
    Knob(
        "GORDO_TPU_PERFMODEL_PRECISION", "bool", False,
        "Model-informed precision rung choice: when neither the spec "
        "nor `GORDO_TPU_SERVE_PRECISION` pins a serving precision, pick "
        "the rung with the lowest predicted step time for the bucket's "
        "shape (the parity gate still decides whether reduced may "
        "actually serve).",
        "Performance model",
    ),
    Knob(
        "GORDO_TPU_PERFMODEL_RECAL", "bool", False,
        "Online recalibration: each lifecycle cycle refits the learned "
        "sections from the telemetry corpus and promotes the new table "
        "only if its holdout error beats the incumbent's "
        "(`gordo_tpu.perfmodel.service.maybe_recalibrate`).",
        "Performance model",
    ),
    Knob(
        "GORDO_TPU_PERFMODEL_MIN_SAMPLES", "int", 32,
        "Minimum training rows per (target, program) before a learned "
        "model is fitted for it; thinner populations stay analytic.",
        "Performance model",
    ),
    # -- Reporters ---------------------------------------------------------
    Knob(
        "GORDO_TPU_MLFLOW_DIR", "str", None,
        "Local MLflow tracking root (default: `<tmpdir>/gordo-mlruns`).",
        "Reporters",
    ),
    # -- Static analysis ---------------------------------------------------
    Knob(
        "GORDO_TPU_LOCK_TRACE", "str", None,
        "Opt-in lock-order tracing (`gordo_tpu.analysis.lockgraph`): a "
        "`.jsonl` path (or `1` for `./lock_trace.jsonl`) wraps every "
        "lock created after install in an instrumented wrapper that "
        "records per-thread acquisition-ordering edges into a "
        "pid-suffixed sink; `gordo-tpu lockgraph` analyzes the sinks "
        "and fails on ordering cycles (potential deadlocks). Off by "
        "default — zero overhead unless set.",
        "Static analysis",
    ),
    # -- Testing -----------------------------------------------------------
    Knob(
        "GORDO_TPU_DOCTEST_KNOB", "int", 7,
        "Reserved for the `utils.env` doctests and the lint fixture "
        "suite; never read by production code.",
        "Testing",
    ),
)


def knob_sections() -> Tuple[str, ...]:
    """Section names in declaration order (the docs-table grouping)."""
    seen: Dict[str, None] = {}
    for knob in KNOBS.values():
        seen.setdefault(knob.section)
    return tuple(seen)
