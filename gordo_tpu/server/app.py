"""
The model server: a werkzeug-native WSGI application.

Reference parity: gordo/server/server.py — same env-driven config
(``MODEL_COLLECTION_DIR``, ``EXPECTED_MODELS``, ``ENABLE_PROMETHEUS``,
``PROJECT``), Envoy/Ambassador prefix-rewrite middleware, per-request
revision resolution from ``?revision=``/header with 410 on a missing
revision, revision stamped into every JSON body and response header,
``Server-Timing`` header, ``/healthcheck`` and ``/server-version`` routes,
plus the base + anomaly route sets.

Engine difference: Flask isn't a dependency here — routing is a werkzeug
``Map`` and per-request state is an explicit :class:`RequestContext` passed
to handlers instead of the ``flask.g`` ambient global. The JSON encoder is
simplejson with ``ignore_nan`` so NaN heads of smoothed anomaly columns
serialize as null.
"""

import contextlib
import logging
import os
import time
import timeit
import typing
from functools import wraps
from typing import Any, Dict, Optional

from ..utils import json_compat as simplejson
import yaml
from werkzeug.exceptions import HTTPException
from werkzeug.routing import Map, Rule
from werkzeug.wrappers import Request, Response

import gordo_tpu

from ..telemetry import SpanRecorder, tracing
from ..telemetry import serving as serve_trace
from ..utils.env import env_bool
from ..telemetry.profiler import SamplingProfiler, should_profile
from . import utils as server_utils
from .utils import ServerError
from .views import anomaly, base
from .views import stream as stream_views

logger = logging.getLogger(__name__)


def enable_prometheus() -> bool:
    return os.getenv("ENABLE_PROMETHEUS", "false") != "false"


def default_config() -> Dict[str, Any]:
    """Server config resolved from the environment (reference server.py:36-43)."""
    return {
        "MODEL_COLLECTION_DIR_ENV_VAR": "MODEL_COLLECTION_DIR",
        "EXPECTED_MODELS": yaml.safe_load(os.getenv("EXPECTED_MODELS", "[]")),
        "ENABLE_PROMETHEUS": enable_prometheus(),
        "PROJECT": os.getenv("PROJECT"),
    }


class RequestContext:
    """
    Per-request state: the request, resolved revision/collection dir, and
    whatever the handlers load (model, metadata, X, y). The explicit
    equivalent of the reference's ``flask.g``.

    Every request owns a W3C trace identity: ``trace_id`` continues an
    incoming ``traceparent`` header (so a gateway's trace flows through
    the model server) or starts fresh; ``span_id`` names the request's
    root span. The per-request ``timing`` recorder adopts that identity,
    so the stage spans it collects nest under the request span — and at
    finalization the whole set exports into the process-shared
    ``serve_trace.jsonl`` (telemetry/serving.py).
    """

    __slots__ = (
        "request",
        "config",
        "start_time",
        "start_wall",
        "timing",
        "trace_id",
        "span_id",
        "remote_parent_id",
        "sampled",
        "current_stage",
        "profiler",
        "endpoint",
        "gordo_name",
        "collection_dir",
        "current_revision",
        "revision",
        "model",
        "metadata",
        "info",
        "resolution",
        "deferred_stage",
        "X",
        "y",
        "ingest",
    )

    def __init__(self, request: Request, config: Dict[str, Any]):
        self.request = request
        self.config = config
        self.start_time = timeit.default_timer()
        self.start_wall = time.time()
        incoming = tracing.parse_traceparent(
            request.headers.get(tracing.TRACEPARENT_HEADER)
        )
        if incoming is not None:
            self.trace_id = incoming.trace_id
            self.remote_parent_id = incoming.span_id
            # export sampling: the upstream decision is honored; locally
            # originated traces decide in _dispatch_bound (None =
            # undecided)
            self.sampled: Optional[bool] = incoming.sampled
            self.span_id = tracing.new_span_id()
        else:
            fresh = tracing.new_trace_context()
            self.trace_id = fresh.trace_id
            self.span_id = fresh.span_id
            self.remote_parent_id = None
            self.sampled = None
        # Per-request span recorder (telemetry/recorder.py, in-memory
        # only): handlers wrap their stages in ``ctx.stage(...)`` and
        # _finalize turns the recorded durations into Server-Timing
        # entries, so every response carries its own stage breakdown.
        self.timing = SpanRecorder(
            service="gordo-tpu-server", trace_id=self.trace_id
        )
        self.timing.default_parent_id = self.span_id
        self.current_stage: Optional[str] = None
        self.profiler: Optional[SamplingProfiler] = None
        self.endpoint: Optional[str] = None
        self.gordo_name: Optional[str] = None
        self.collection_dir: Optional[str] = None
        self.current_revision: Optional[str] = None
        self.revision: Optional[str] = None
        self.model = None
        self.metadata: Optional[dict] = None
        self.info: Optional[dict] = None
        self.resolution = None  # fleet ModelResolution (resolve_model)
        # (name, start_time) of a stage that ends WITH the request —
        # the wire fast path's serialize: after the encode there is
        # only response construction (~30µs), but under thread load the
        # GIL preemption a long encode earns lands exactly after the
        # stage's closing clock read, so a conventional span would leak
        # the parked tail into unattributed walltime (measured ~20ms
        # p50 at 16 threads — the whole attribution-coverage gap).
        # _finalize closes the interval at the request's own end clock.
        self.deferred_stage: Optional[tuple] = None
        self.X = None
        self.y = None
        # Raw wire columns (ingest.RawColumns) stashed by the Arrow
        # decode when they align with the model's tag order — the
        # device-resident ingest path scores them without the host
        # column_stack staging copy.
        self.ingest = None

    @contextlib.contextmanager
    def stage(self, name: str):
        """Span over one request stage (``model_resolve``, ``data_decode``,
        ``inference``, ``response_assemble``, ``serialize``); surfaces in
        Server-Timing, the exported request trace, and — while a sampling
        profiler is attached — as the stage axis of its self-time
        aggregation (``current_stage`` is read from the sampling thread)."""
        previous = self.current_stage
        self.current_stage = name
        try:
            with self.timing.span(name) as handle:
                yield handle
        finally:
            self.current_stage = previous

    # -- response builders --------------------------------------------------

    def json_response(self, payload: dict, status: int = 200) -> Response:
        # Revision is stamped here, at serialization time, rather than by
        # re-parsing the body in an after-request hook: prediction payloads
        # can be multi-MB and a loads/dumps round-trip would triple the
        # serialization cost of the hot path.
        if self.revision is not None and isinstance(payload, dict):
            payload = {**payload, "revision": self.revision}
        with self.stage("serialize"):
            body = simplejson.dumps(payload, default=str, ignore_nan=True)
        return Response(body, status=status, mimetype="application/json")

    def raw_response(
        self, body, mimetype: str, status: int = 200
    ) -> Response:
        """A pre-serialized response: the wire fast path encodes inside
        the handler's own ``serialize`` stage (JSON bytes, Arrow IPC, or
        a streamed chunk iterator) and hands the finished body here —
        re-serializing through :meth:`json_response` would walk the
        payload again."""
        return Response(body, status=status, mimetype=mimetype)

    def file_response(
        self, data: bytes, download_name: Optional[str] = None
    ) -> Response:
        response = Response(data, mimetype="application/octet-stream")
        if download_name:
            response.headers["Content-Disposition"] = (
                f"attachment; filename={download_name}"
            )
        return response


def adapt_proxy_deployment(wsgi_app: typing.Callable) -> typing.Callable:
    """
    WSGI middleware fixing behind-proxy routing on k8s/Envoy: the proxy
    forwards the full prefixed path (``/gordo/v0/<project>/<name>/metadata``)
    in ``HTTP_X_ENVOY_ORIGINAL_PATH`` while ``PATH_INFO`` holds the local
    route; reconstruct ``SCRIPT_NAME``/``PATH_INFO`` accordingly
    (reference server.py:46-118).
    """

    @wraps(wsgi_app)
    def wrapper(environ, start_response):
        script_name = environ.get("HTTP_X_ENVOY_ORIGINAL_PATH", "")
        if script_name:
            path_info = environ.get("PATH_INFO", "")
            if path_info.rstrip("/"):
                script_name = script_name.replace(path_info, "")
            environ["SCRIPT_NAME"] = script_name
            if path_info.startswith(script_name):
                environ["PATH_INFO"] = path_info[len(script_name):]

        scheme = environ.get("HTTP_X_FORWARDED_PROTO", "")
        if scheme:
            environ["wsgi.url_scheme"] = scheme
        return wsgi_app(environ, start_response)

    return wrapper


PREFIX = "/gordo/v0"

URL_MAP = Map(
    [
        Rule("/healthcheck", endpoint="healthcheck", methods=["GET"]),
        Rule("/server-version", endpoint="server-version", methods=["GET"]),
        Rule(
            f"{PREFIX}/<gordo_project>/<gordo_name>/prediction",
            endpoint="prediction",
            methods=["POST"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/<gordo_name>/anomaly/prediction",
            endpoint="anomaly-prediction",
            methods=["POST"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/<gordo_name>/metadata",
            endpoint="metadata",
            methods=["GET"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/<gordo_name>/healthcheck",
            endpoint="model-healthcheck",
            methods=["GET"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/<gordo_name>/download-model",
            endpoint="download-model",
            methods=["GET"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/<gordo_name>/revision/<revision>",
            endpoint="delete-revision",
            methods=["DELETE"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/prediction/fleet",
            endpoint="fleet-prediction",
            methods=["POST"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/stream/<stream_id>/ingest",
            endpoint="stream-ingest",
            methods=["POST"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/stream/<stream_id>/events",
            endpoint="stream-events",
            methods=["GET"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/stream/status",
            endpoint="stream-status",
            methods=["GET"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/stream/<stream_id>",
            endpoint="stream-close",
            methods=["DELETE"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/build-status",
            endpoint="build-status",
            methods=["GET"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/fleet-health",
            endpoint="fleet-health",
            methods=["GET"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/slo",
            endpoint="slo",
            methods=["GET"],
        ),
        Rule(f"{PREFIX}/<gordo_project>/models", endpoint="models", methods=["GET"]),
        Rule(
            f"{PREFIX}/<gordo_project>/revisions",
            endpoint="revisions",
            methods=["GET"],
        ),
        Rule(
            f"{PREFIX}/<gordo_project>/expected-models",
            endpoint="expected-models",
            methods=["GET"],
        ),
    ],
    strict_slashes=False,
)

HANDLERS = {
    "prediction": base.post_prediction,
    "anomaly-prediction": anomaly.post_anomaly_prediction,
    "fleet-prediction": base.post_fleet_prediction,
    "metadata": base.get_metadata,
    "model-healthcheck": base.get_metadata,
    "download-model": base.get_download_model,
    "delete-revision": base.delete_model_revision,
    "models": base.get_model_list,
    "revisions": base.get_revision_list,
    "expected-models": base.get_expected_models,
    "build-status": base.get_build_status,
    "fleet-health": base.get_fleet_health,
    "slo": base.get_slo_status,
    "stream-ingest": stream_views.post_stream_ingest,
    "stream-events": stream_views.get_stream_events,
    "stream-status": stream_views.get_stream_status,
    "stream-close": stream_views.delete_stream,
}


class GordoServerApp:
    """The WSGI application serving a model-collection directory."""

    def __init__(self, config: Optional[Dict[str, Any]] = None):
        self.config = default_config()
        if config is not None:
            self.config.update(config)
        self.prometheus_metrics = None
        # Graceful-shutdown flag: once draining, /healthcheck answers 503
        # (load balancers stop sending) while every already-accepted
        # request — including everything queued in the micro-batcher —
        # still gets a real response (drain_and_stop).
        import threading

        self._draining = threading.Event()

    def begin_drain(self) -> None:
        self._draining.set()

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    # -- request lifecycle --------------------------------------------------

    def _resolve_revision(self, ctx: RequestContext) -> Optional[Response]:
        """
        Point the context at the served (or requested) revision directory;
        410 for bad/missing revisions (reference server.py:169-195).

        Requests that do NOT pin a revision route through the fleet
        store's lifecycle routing (``STORE.route``): a hot-swapped
        (promoted) revision or the canary traffic slice resolves HERE,
        once per request, so every artifact the request touches comes
        from one revision — explicitly pinned revisions bypass routing.
        """
        ctx.collection_dir = os.environ[self.config["MODEL_COLLECTION_DIR_ENV_VAR"]]
        ctx.current_revision = os.path.basename(ctx.collection_dir)

        request = ctx.request
        revision = request.args.get("revision") or request.headers.get("revision")
        if revision:
            # Validate before adopting: a malformed revision must never be
            # echoed into response headers (newlines would crash werkzeug).
            if not server_utils.validate_revision(revision):
                return ctx.json_response(
                    {"error": "Revision should only contains numbers."}, status=410
                )
            ctx.revision = revision
            ctx.collection_dir = os.path.join(ctx.collection_dir, "..", revision)
            try:
                os.listdir(ctx.collection_dir)
            except FileNotFoundError:
                return ctx.json_response(
                    {"error": f"Revision '{revision}' not found."}, status=410
                )
        else:
            from .fleet_store import STORE

            routed = STORE.route(ctx.collection_dir)
            if routed != ctx.collection_dir:
                ctx.collection_dir = routed
                # the response honestly stamps the revision that SERVED it
                ctx.current_revision = os.path.basename(
                    os.path.normpath(routed)
                )
            ctx.revision = ctx.current_revision
        return None

    #: endpoints whose request traces would only add noise and volume
    #: (load balancers hit /healthcheck every few seconds)
    UNTRACED_ENDPOINTS = (None, "healthcheck", "server-version")

    def _finalize(self, ctx: RequestContext, response: Response) -> Response:
        """Stamp the revision + ``traceparent`` headers, add
        Server-Timing — one entry per recorded request stage
        (milliseconds, per the Server-Timing spec) plus the
        reference-parity ``request_walltime_s`` total (seconds, kept
        last under its original name/unit for existing dashboards) —
        then export the finished request into the shared serving trace
        and hand the stage durations to the Prometheus observer."""
        if ctx.revision is not None:
            response.headers["revision"] = ctx.revision
        response.headers[tracing.TRACEPARENT_HEADER] = tracing.format_traceparent(
            ctx.trace_id, ctx.span_id, sampled=bool(ctx.sampled)
        )

        runtime_s = timeit.default_timer() - ctx.start_time
        if ctx.deferred_stage is not None:
            name, stage_start = ctx.deferred_stage
            ctx.deferred_stage = None
            ctx.timing.record(
                name, max(0.0, timeit.default_timer() - stage_start)
            )
        logger.debug("Total runtime for request: %ss", runtime_s)
        durations = ctx.timing.durations()
        entries = [
            f"{name};dur={round(seconds * 1000.0, 2)}"
            for name, seconds in durations.items()
        ]
        entries.append(f"request_walltime_s;dur={runtime_s}")
        response.headers["Server-Timing"] = ", ".join(entries)

        # RED attribution for wsgi_app's Prometheus observer: the stage
        # breakdown and route identity ride the response object (the
        # observer sees only (request, response, duration)).
        response.gordo_stage_durations = durations
        response.gordo_endpoint = ctx.endpoint
        response.gordo_model_name = ctx.gordo_name

        profile_report = None
        if ctx.profiler is not None:
            profile_report = ctx.profiler.stop()
            ctx.profiler = None
        self._record_health(ctx, response)
        if ctx.sampled and ctx.endpoint not in self.UNTRACED_ENDPOINTS:
            serve_trace.export_request_trace(
                ctx.timing,
                span_id=ctx.span_id,
                parent_id=ctx.remote_parent_id,
                start=ctx.start_wall,
                duration_s=runtime_s,
                attributes={
                    "http.method": ctx.request.method,
                    "http.route": ctx.endpoint,
                    "http.status_code": response.status_code,
                    "gordo_name": ctx.gordo_name or "",
                    "revision": ctx.revision or "",
                },
                error=(
                    f"HTTP {response.status_code}"
                    if response.status_code >= 500
                    else None
                ),
                profile=profile_report,
            )
        return response

    #: endpoints whose outcomes feed the per-member health ledger —
    #: scoring traffic only (metadata/listing requests say nothing about
    #: a machine's serving health)
    HEALTH_ENDPOINTS = ("prediction", "anomaly-prediction")

    def _record_health(self, ctx: RequestContext, response: Response) -> None:
        """Per-machine request/error counts into the fleet health ledger
        (telemetry/fleet_health.py), keyed to the ANCHOR collection dir
        (the env var, not the routed revision) so counts survive
        lifecycle hot-swaps. 5xx marks the machine; 4xx is the client's
        problem. Best-effort and throttled — the ledger must never cost
        the request path more than a dict update.

        Gated on a RESOLVED model: ``gordo_name`` is client-supplied URL
        text, and recording it unconditionally would let a scanner mint
        one ledger record (and one 'healthy' machine in the Prometheus
        counts) per random path — the same request-derived-identity
        cardinality class the ``{unmatched}`` label collapse guards
        against. A name that never loaded a model is not a machine."""
        if (
            ctx.endpoint not in self.HEALTH_ENDPOINTS
            or not ctx.gordo_name
            or ctx.model is None
        ):
            return
        try:
            from ..telemetry import ledger_for

            anchor = os.environ.get(self.config["MODEL_COLLECTION_DIR_ENV_VAR"])
            if not anchor:
                return
            # 503 is backpressure (a breaker-quarantined member shedding
            # its own traffic), not NEW failure evidence — the trip that
            # caused it was already recorded by the breaker feed; letting
            # every rejected retry mark an error would ratchet the
            # machine's health down for the whole quarantine
            ledger_for(anchor, project=self.config.get("PROJECT") or "").record_request(
                ctx.gordo_name,
                error=response.status_code >= 500
                and response.status_code != 503,
            )
        except Exception:  # noqa: BLE001 - health telemetry is advisory
            logger.debug("health ledger request not recorded", exc_info=True)

    def dispatch(self, request: Request) -> Response:
        ctx = RequestContext(request, self.config)
        token = tracing.bind(ctx.trace_id)
        try:
            return self._dispatch_bound(ctx, request)
        finally:
            tracing.unbind(token)

    def _dispatch_bound(self, ctx: RequestContext, request: Request) -> Response:
        profile_arg = request.args.get("profile")
        try:
            endpoint_adapter = URL_MAP.bind_to_environ(request.environ)
            endpoint, view_args = endpoint_adapter.match()
            ctx.endpoint = endpoint
            ctx.gordo_name = view_args.get("gordo_name")

            if endpoint == "healthcheck":
                if self.draining:
                    response = Response("draining", status=503)
                else:
                    response = Response("", status=200)
                return self._finalize(ctx, response)
            if endpoint == "server-version":
                response = ctx.json_response({"version": gordo_tpu.__version__})
                return self._finalize(ctx, response)

            # trace-export sampling: with the serving sink on, honor an
            # upstream traceparent decision, else head-sample locally
            # (GORDO_TPU_TRACE_SAMPLE_RATE) — every request still gets a
            # trace id; sampling gates only span export
            if serve_trace.serve_recorder().enabled:
                if ctx.sampled is None:
                    ctx.sampled = serve_trace.sample_trace()
                # host-pipeline sampling profiler: per-request
                # (?profile=1) or a random slice
                # (GORDO_TPU_PROFILE_SAMPLE_RATE); a profiled request is
                # always exported — the report's destination is a
                # `profile` span in serve_trace.jsonl
                if should_profile(profile_arg):
                    ctx.sampled = True
                    ctx.profiler = SamplingProfiler().start(
                        stage_getter=lambda: ctx.current_stage
                    )
            else:
                ctx.sampled = False
            # the engine reads this to decide whether batch spans should
            # link back to this request's (exported) spans
            ctx.timing.sampled = ctx.sampled

            error_response = self._resolve_revision(ctx)
            if error_response is not None:
                return self._finalize(ctx, error_response)

            if profile_arg == "device":
                # the heavyweight opt-in layer: a TensorBoard-loadable
                # XLA device trace for this one request (no-op unless
                # GORDO_TPU_PROFILE_DIR is set)
                from ..utils.profiling import maybe_trace

                with maybe_trace(f"request-{ctx.trace_id[:16]}"):
                    response = HANDLERS[endpoint](ctx, **view_args)
            else:
                response = HANDLERS[endpoint](ctx, **view_args)
        except ServerError as exc:
            response = ctx.json_response(exc.payload, status=exc.status)
        except HTTPException as exc:
            response = ctx.json_response(
                {"error": exc.description}, status=exc.code or 500
            )
        except Exception:
            logger.exception("Unhandled server error")
            response = ctx.json_response({"error": "Internal Server Error"}, status=500)
        return self._finalize(ctx, response)

    def wsgi_app(self, environ, start_response):
        request = Request(environ)
        start = timeit.default_timer()
        response = self.dispatch(request)
        if self.prometheus_metrics is not None:
            self.prometheus_metrics.observe(
                request, response, timeit.default_timer() - start
            )
        return response(environ, start_response)

    def __call__(self, environ, start_response):
        return self._wsgi_entry(environ, start_response)

    # build_app replaces this per-instance with the proxy-adapted entry.
    _wsgi_entry = wsgi_app


def build_app(
    config: Optional[Dict[str, Any]] = None,
    prometheus_registry=None,
) -> GordoServerApp:
    """
    Build the server application with proxy adaptation applied and, when
    enabled, prometheus request metrics and the cross-request
    micro-batching engine (``GORDO_TPU_BATCHING`` — see
    ``gordo_tpu.serve``), including its startup warmup pass.
    """
    from ..parallel.mesh import announce_device

    # before any model is loaded or program compiled: the server shares
    # the builder's persistent compile cache, and says where it runs
    announce_device("model server")
    app = GordoServerApp(config)
    app._wsgi_entry = adapt_proxy_deployment(app.wsgi_app)
    # every in-request log record carries its trace_id from here on
    tracing.install_trace_log_stamping()

    if app.config["ENABLE_PROMETHEUS"]:
        from .prometheus.metrics import create_prometheus_metrics

        app.prometheus_metrics = create_prometheus_metrics(
            project=app.config.get("PROJECT"), registry=prometheus_registry
        )
    elif prometheus_registry is not None:
        logger.warning("Ignoring non empty prometheus_registry argument")

    # Lifecycle continuity: a promotion the supervisor recorded before
    # this process booted (state.json beside the revisions) is
    # re-installed as a hot-swap redirect, so a restarted server keeps
    # serving the promoted revision even when its env var still points
    # at the original one. BEFORE engine warmup, which warms whatever
    # the store routes to.
    collection_dir = os.environ.get(app.config["MODEL_COLLECTION_DIR_ENV_VAR"])
    if collection_dir and os.path.isdir(collection_dir):
        try:
            from ..lifecycle import restore_serving_state

            restore_serving_state(collection_dir)
        except Exception:  # noqa: BLE001 - serving state restore is
            # advisory; a torn state file must not take the server down
            logger.exception("lifecycle serving-state restore failed")

    # SLO exposition: mark the serving telemetry dir watched so /metrics
    # scrapes keep gordo_slo_* fresh (throttled re-evaluation; see
    # GORDO_TPU_SLO_SCRAPE_REFRESH). No-op with telemetry off.
    try:
        from ..telemetry import slo as slo_engine

        slo_engine.watch(slo_engine.slo_directory(collection_dir))
    except Exception:  # noqa: BLE001 - SLO exposition is advisory
        logger.debug("slo watch registration failed", exc_info=True)

    # Micro-batching engine: process-global (gthread workers share it,
    # like STORE); created here so the server lifecycle owns warmup and
    # the atexit drain. Default-off — without the env switch this is a
    # no-op and serving behaves exactly as before.
    from .. import serve

    engine = serve.ensure_engine()
    if engine is not None:
        if app.prometheus_metrics is not None and engine.metrics is None:
            from .prometheus.metrics import serve_metrics

            engine.metrics = serve_metrics(
                project=app.config.get("PROJECT"),
                registry=app.prometheus_metrics.registry,
            )
        # the ANCHOR dir the breaker feed should ledger against — wired
        # through the app's configurable env-var name, the same
        # indirection every other health feed resolves through (the
        # engine's own fallback reads the default MODEL_COLLECTION_DIR)
        if collection_dir:
            engine.ledger_anchor = collection_dir
        _start_serve_warmup(app, engine)
    return app


def drain_and_stop(app: GordoServerApp, server=None, engine=None) -> None:
    """Graceful shutdown: flip the app to draining (healthcheck 503 so
    load balancers stop routing here), drain the micro-batching engine —
    every queued and in-flight batch resolves its futures, new batched
    work falls back to the still-running unbatched path — then stop the
    HTTP server's accept loop. Queued requests never die unanswered with
    the process."""
    from .. import serve

    app.begin_drain()
    # standing streams FIRST: every live SSE subscriber gets its
    # terminal `drain` frame and flushes its outbox tail while the
    # batcher below is still resolving in-flight futures — a long-lived
    # stream socket closes cleanly instead of dying mid-frame
    try:
        from ..stream import get_plane

        plane = get_plane()
        if plane is not None:
            plane.drain()
    except Exception:  # noqa: BLE001 - stream drain is best-effort; the
        # engine drain and server stop below must still run
        logger.exception("stream plane drain failed")
    engine = engine if engine is not None else serve.get_engine()
    if engine is not None:
        logger.info("draining micro-batcher before shutdown")
        engine.shutdown(drain=True)
    # the serving trace is write-buffered; the drained batches' spans
    # and the final requests' traces must reach disk before exit
    serve_trace.serve_recorder().flush()
    if server is not None:
        server.shutdown()
    # close (not just flush) the shared trace recorder: close() joins
    # its async writer thread, so SIGTERM leaves no gordo-owned thread
    # alive — every remaining thread at this point is daemon by the
    # thread-lifecycle lint contract (the regression test in
    # tests/server/test_shutdown_threads.py pins both properties)
    serve_trace.reset_serve_recorder()


def install_graceful_shutdown(app: GordoServerApp, server=None):
    """SIGTERM/SIGINT → :func:`drain_and_stop` on a background thread
    (signal handlers must return fast). No-op outside the main thread
    (embedded/test servers manage their own lifecycle)."""
    import signal
    import threading

    def handler(_signum, _frame):
        threading.Thread(
            target=drain_and_stop,
            args=(app, server),
            name="gordo-drain",
            daemon=True,
        ).start()

    try:
        signal.signal(signal.SIGTERM, handler)
        signal.signal(signal.SIGINT, handler)
    except ValueError:  # not the main thread
        return None
    return handler


def serve_warmup_enabled() -> bool:
    """Startup precompile of the served buckets' ladder programs: on by
    default whenever batching is on (``GORDO_TPU_SERVE_WARMUP=0`` skips)."""
    return env_bool("GORDO_TPU_SERVE_WARMUP", True)


def _start_serve_warmup(app: GordoServerApp, engine) -> Optional[object]:
    """Kick off the engine's warmup for the served collection dir in the
    background, so the first request after boot hits compiled programs
    without the boot itself blocking on XLA."""
    import threading

    if not serve_warmup_enabled():
        return None
    collection_dir = os.environ.get(app.config["MODEL_COLLECTION_DIR_ENV_VAR"])
    if not collection_dir or not os.path.isdir(collection_dir):
        return None

    def warm():
        try:
            from .fleet_store import STORE

            # warm what requests will actually resolve: the lifecycle
            # routing may point this dir at a promoted revision
            engine.warmup_collection(STORE.route(collection_dir))
        except Exception:  # noqa: BLE001 - warmup is an optimization; a bad
            # artifact must not take the server down (requests would just
            # pay first-call compiles, as without warmup)
            logger.exception("serve warmup failed for %s", collection_dir)

    thread = threading.Thread(target=warm, name="gordo-serve-warmup", daemon=True)
    thread.start()
    return thread


# -- process runner ---------------------------------------------------------


def build_gunicorn_cmd(
    host: str,
    port: int,
    workers: int,
    log_level: str,
    config_module: Optional[str] = None,
    worker_connections: Optional[int] = None,
    threads: Optional[int] = None,
    worker_class: str = "gthread",
    server_app: str = "gordo_tpu.server.app:build_app()",
) -> list:
    """The gunicorn argv the reference would exec (server.py:240-304)."""
    cmd = [
        "gunicorn",
        "--bind",
        f"{host}:{port}",
        "--log-level",
        log_level,
        "--error-logfile",
        "-",
        "--access-logfile",
        "-",
        "--worker-class",
        worker_class,
        "--worker-tmp-dir",
        "/dev/shm",
        "--workers",
        str(workers),
    ]
    if config_module is not None:
        cmd.extend(("--config", "python:" + config_module))
    if worker_class == "gthread":
        if threads is not None:
            cmd.extend(("--threads", str(threads)))
    else:
        if worker_connections is not None:
            cmd.extend(("--worker-connections", str(worker_connections)))
    cmd.append(server_app)
    return cmd


def run_cmd(cmd):
    """Run a shell command, surfacing stderr on stdout."""
    import subprocess

    subprocess.check_call(cmd, stderr=subprocess.STDOUT)


def run_server(
    host: str,
    port: int,
    workers: int,
    log_level: str,
    config_module: Optional[str] = None,
    worker_connections: Optional[int] = None,
    threads: Optional[int] = None,
    worker_class: str = "gthread",
    server_app: str = "gordo_tpu.server.app:build_app()",
):
    """
    Serve via gunicorn when installed (production parity with the
    reference); otherwise fall back to werkzeug's threaded server — models
    live on an accelerator, so thread workers sharing the one in-process
    JAX runtime is the natural single-host deployment anyway.
    """
    import shutil as _shutil

    if _shutil.which("gunicorn"):
        run_cmd(
            build_gunicorn_cmd(
                host=host,
                port=port,
                workers=workers,
                log_level=log_level,
                config_module=config_module,
                worker_connections=worker_connections,
                threads=threads,
                worker_class=worker_class,
                server_app=server_app,
            )
        )
        return

    logger.warning("gunicorn not found; serving with werkzeug (threaded)")
    from werkzeug.serving import make_server

    logging.getLogger().setLevel(log_level.upper())
    # make_server (not run_simple): the graceful-shutdown path needs the
    # server handle so SIGTERM can drain the micro-batcher queues and
    # in-flight batches BEFORE the accept loop stops — queued request
    # futures must resolve, not die with the process.
    app = build_app()
    server = make_server(host, port, app, threaded=True)
    install_graceful_shutdown(app, server)
    logger.info("serving on %s:%d (werkzeug threaded)", host, port)
    server.serve_forever()
