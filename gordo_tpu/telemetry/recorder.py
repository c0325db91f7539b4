"""
The build-telemetry span recorder.

The reference system's observability was Kubernetes': one pod per model
build means ``argo get`` shows per-machine phase, duration and retries
for free. The chip-fan-out build collapses thousands of machines into
one process, so the same visibility has to be *data* the process emits:
this module records named spans (wall-clock intervals with attributes)
and point events into an in-memory list and an optional JSONL sink,
shaped like OpenTelemetry span dicts so a real OTLP exporter can be
bolted on later without touching the instrumentation sites.

Stdlib-only by design — the recorder is imported by the training hot
path (models/training.py, parallel/fleet.py) and must never drag server
or metrics dependencies into it. Prometheus export happens via
listeners the *builder* registers (parallel/fleet_build.py), keeping
the dependency arrow pointing outward.

Two activation models coexist:

- a process-global recorder (:func:`activate` / :func:`get_recorder`)
  used by the fleet build, so deep call sites (the trainer's device
  programs) record without threading a recorder argument through every
  layer. The default is :data:`NULL_RECORDER`, whose spans cost a few
  hundred nanoseconds and record nothing.
- explicit per-object recorders (the model server builds one per
  request for its ``Server-Timing`` stages).

Compile-vs-run attribution: :func:`program_span` wraps jit entry
points. The first call per ``(program, key)`` — key includes the spec,
fit config and array shapes, i.e. the XLA compilation signature — is
attributed ``compile=True`` (jax traces+compiles synchronously inside
that first call); later calls with the same signature are steady-state
``compile=False`` runs. This is the cache-hit/miss signal future
compile-cache work needs.
"""

import collections
import contextlib
import datetime
import json
import os
import random
import threading
import time
from typing import Any, Callable, Dict, Hashable, List, Optional

TELEMETRY_ENV = "GORDO_TPU_TELEMETRY"
TRACE_DIR_ENV = "GORDO_TPU_TELEMETRY_DIR"
#: size-based trace-sink rotation: when a JSONL sink crosses this many
#: bytes it is rotated (``trace.jsonl`` -> ``trace.jsonl.1`` -> ...), so
#: a months-lived serving or lifecycle process can never fill the disk.
#: 0 disables rotation.
MAX_BYTES_ENV = "GORDO_TPU_TELEMETRY_MAX_BYTES"
#: rotated generations kept per sink (older ones are deleted)
KEEP_ENV = "GORDO_TPU_TELEMETRY_KEEP"
DEFAULT_MAX_BYTES = 256 * 1024 * 1024
DEFAULT_KEEP = 3

#: what a ``build_part`` span may carry beside its seconds and ``count``,
#: summed into its entry of ``build_status.json``: the CPU seconds of the
#: thread (or, recorded as a sum, the threads) that ran it, the bytes it
#: moved, of a ``stack``'s bytes those filled into buffers the staging
#: pool already held (parallel/host_blocks.py), inside ``collect`` the
#: seconds of the device-to-host fetch alone, of the machines a
#: ``machine_fetch`` counts those fetched in a worker process
#: (:func:`part_sums` counts them off the span's ``worker``), and of a
#: large artifact's parameters (models/in_flight.py) the bytes a final
#: fit's ``collect`` started on their way and did not wait for, of a
#: ``write``'s bytes those its pickler took from such a transfer, and
#: the seconds it waited for them
PART_SUMS = (
    "cpu_seconds",
    "bytes",
    "bytes_reused",
    "d2h_seconds",
    "in_process",
    "bytes_deferred",
    "bytes_fetched_beside_write",
    "fetch_wait_seconds",
)
#: and a ``build_phase`` span beside its seconds: its own thread's CPU
#: seconds and the whole process's between its two ends
PHASE_SUMS = ("cpu_seconds", "process_cpu_seconds")


def add_sums(entry: Dict[str, Any], keys, given: Dict[str, Any]) -> None:
    """Add to ``entry`` each of ``keys`` that ``given`` has (and is not
    None): an entry gets such a key only where a span gave it."""
    for key in keys:
        if given.get(key) is not None:
            entry[key] = entry.get(key, 0) + given[key]

#: per-process sink split: when on, process-owned telemetry sinks
#: (``serve_trace.jsonl``, ``fleet_health.json``) get a ``-<pid>``
#: suffix so N gunicorn workers stop clobbering one shared path — the
#: aggregator (telemetry/aggregate.py) and every reader merge all
#: variants. Defaults to ON exactly when a multi-worker deployment is
#: already configured (``PROMETHEUS_MULTIPROC_DIR``, the same signal
#: prometheus_client keys worker fan-in on); single-process servers and
#: tests keep the unsuffixed spelling.
WORKER_SINKS_ENV = "GORDO_TPU_WORKER_SINKS"


def worker_sinks_enabled() -> bool:
    from ..utils.env import env_bool

    multi_worker = bool(
        os.environ.get("PROMETHEUS_MULTIPROC_DIR")
        or os.environ.get("prometheus_multiproc_dir")
    )
    return env_bool(WORKER_SINKS_ENV, multi_worker)


def worker_sink_path(path: str) -> str:
    """``serve_trace.jsonl`` -> ``serve_trace-<pid>.jsonl`` when worker
    sinks are on (the suffix sits before the extension so rotated
    generations keep their ``.N`` tail grammar)."""
    if not worker_sinks_enabled():
        return path
    stem, ext = os.path.splitext(path)
    return f"{stem}-{os.getpid()}{ext}"


def enabled() -> bool:
    """Telemetry master switch: on unless ``GORDO_TPU_TELEMETRY`` is a
    falsy string (``0``/``false``/``off``/``no``)."""
    from ..utils.env import env_bool

    return env_bool(TELEMETRY_ENV, True)


def _iso(ts: float) -> str:
    return datetime.datetime.fromtimestamp(
        ts, datetime.timezone.utc
    ).isoformat()


def _env_size(name: str, default: int) -> int:
    # utils.env is the one shared GORDO_TPU_* numeric-knob parser (it
    # warns on invalid values); stdlib-only, so the telemetry package's
    # no-heavy-deps contract holds
    from ..utils.env import env_int

    return max(0, env_int(name, default))


#: id generator for trace/span ids — a PRNG seeded once from the OS,
#: NOT uuid4: ids only need uniqueness, and uuid4's per-call urandom
#: syscall costs ~20x more, which matters at one span id per request
#: stage on the serving hot path (GIL makes getrandbits effectively
#: atomic; ids are not security tokens)
_id_source = random.Random(int.from_bytes(os.urandom(16), "big"))


def rand_hex(chars: int = 32) -> str:
    """``chars`` lowercase hex characters of PRNG randomness (32 = a
    W3C trace id, 16 = a span id)."""
    return f"{_id_source.getrandbits(chars * 4):0{chars}x}"


class SpanHandle:
    """The object a ``with recorder.span(...)`` block receives; lets the
    body attach attributes discovered mid-span (e.g. result counts) and
    OTel-shaped links to spans in OTHER traces (the serving engine links
    each fused batch span to the request spans it coalesced)."""

    __slots__ = ("attributes", "links", "trace_id", "span_id")

    #: False on the null recorder's handle: a caller that would compute
    #: something only to ``set`` it (a walk over a tree for its bytes, a
    #: second clock) asks first
    recording = True

    def __init__(
        self,
        attributes: Dict[str, Any],
        trace_id: str = "",
        span_id: str = "",
    ):
        self.attributes = attributes
        self.links: List[dict] = []
        #: this span's own identity (empty on the null recorder) — lets
        #: a producer hand its context to a LATER span in another trace
        #: that wants to link back (the stream ingest→flush links)
        self.trace_id = trace_id
        self.span_id = span_id

    def set(self, **attributes) -> "SpanHandle":
        self.attributes.update(attributes)
        return self

    def link(self, trace_id: str, span_id: str, **attributes) -> "SpanHandle":
        """Attach a link to a span in another trace (OTel link shape:
        a span context plus link attributes)."""
        self.links.append(
            {
                "context": {"trace_id": trace_id, "span_id": span_id},
                **({"attributes": attributes} if attributes else {}),
            }
        )
        return self


class NullHandle(SpanHandle):
    """What a span of the null recorder yields: takes what it is given
    and keeps it for nobody."""

    __slots__ = ()
    recording = False


class NullRecorder:
    """The do-nothing recorder: spans yield a throwaway handle and
    record nothing. Shared process-wide default."""

    enabled = False
    trace_id = ""
    default_parent_id = None
    phase = ""
    annotate = None

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        parent_id: Optional[str] = None,
        cpu_clock: bool = False,
        **attributes,
    ):
        yield NullHandle({})

    def event(self, name: str, **attributes) -> None:
        pass

    def record(
        self,
        name: str,
        seconds: float,
        start: Optional[float] = None,
        cpu_seconds: Optional[float] = None,
        **attributes,
    ) -> None:
        pass

    def emit(self, span: dict) -> None:
        pass

    def flush(self) -> None:
        pass

    def add_listener(self, listener: Callable[[dict], None]) -> None:
        pass

    def finished(self, name: Optional[str] = None) -> List[dict]:
        return []

    def durations(self) -> Dict[str, float]:
        return {}

    def close(self) -> None:
        pass


NULL_RECORDER = NullRecorder()


class SpanRecorder:
    """
    Span/event recorder: in-memory tree + optional JSONL sink.

    Thread-safe — the dump/data thread pools record spans concurrently;
    parent/child nesting is tracked per thread. A span opened on a pool
    thread has no enclosing span there; the opener names the span it
    works for with ``parent_id=`` (the fleet builder hands its pool
    threads the running ``build_phase`` span).

    Every finished span is appended to ``sink_path`` as one JSON line
    the instant it closes, so a killed build leaves a complete trace of
    everything that actually happened.
    """

    enabled = True

    def __init__(
        self,
        sink_path: Optional[str] = None,
        service: str = "gordo-tpu",
        retain_spans: Optional[bool] = None,
        trace_id: Optional[str] = None,
        max_bytes: Optional[int] = None,
        keep: Optional[int] = None,
        async_sink: bool = False,
    ):
        #: explicit ``trace_id`` joins an existing trace (the server's
        #: per-request recorders adopt the request's W3C trace id so its
        #: stage spans land in the caller's trace); default is a fresh one
        self.trace_id = trace_id or rand_hex(32)
        #: parent for spans opened on a thread with no enclosing span —
        #: the per-request recorder points this at the request's root
        #: span id, so stage spans (and the batcher's externally-timed
        #: ``record()`` intervals) nest under the request span
        self.default_parent_id: Optional[str] = None
        #: the build phase in progress (``FleetBuilder._phase`` keeps it),
        #: stamped on ``build_part`` spans by :func:`part_span`
        self.phase = ""
        #: ``annotate(name, attributes)`` -> a context manager entered
        #: around the span's body, or None. The fleet builder points it
        #: at ``jax.profiler.TraceAnnotation`` so phases, parts and
        #: device programs also lie in a profiler trace's host plane, on
        #: the profiler's clock; the recorder itself stays stdlib-only.
        self.annotate: Optional[Callable[[str, Dict[str, Any]], Any]] = None
        self.service = service
        self.sink_path = sink_path
        #: async sink: spans queue to a background writer thread that
        #: batch-writes them — the mode the process-shared SERVING
        #: recorder runs in, where the recording threads are request
        #: threads and the ~50us of json+write+flush per span would be
        #: paid at request rate. Builds keep the synchronous default
        #: (every span durable the instant it closes, crash-complete).
        self.async_sink = bool(async_sink) and sink_path is not None
        if sink_path is not None:
            # rotation knobs and writer plumbing only matter with a
            # sink; the per-REQUEST in-memory recorders skip all of it
            # (two env reads + deque/event allocation per request add up)
            self.max_bytes = (
                max_bytes
                if max_bytes is not None
                else _env_size(MAX_BYTES_ENV, DEFAULT_MAX_BYTES)
            )
            self.keep = (
                keep if keep is not None else _env_size(KEEP_ENV, DEFAULT_KEEP)
            )
            self._queue: "collections.deque" = collections.deque(maxlen=20000)
            self._wakeup = threading.Event()
            self._write_lock = threading.Lock()
        else:
            self.max_bytes = max_bytes or 0
            self.keep = keep or 0
        self._writer: Optional[threading.Thread] = None
        self._closed = False
        self._sink = None
        self._lock = threading.Lock()
        # In-memory retention serves short-lived recorders (the server's
        # per-request Server-Timing, in-process tests). A sink-backed
        # BUILD recorder must not retain: a many-hour fleet build emits
        # an unbounded span stream that nothing in the build path reads
        # back — the JSONL sink and the listeners are its consumers.
        self.retain_spans = (
            retain_spans if retain_spans is not None else sink_path is None
        )
        self._spans: List[dict] = []
        self._listeners: List[Callable[[dict], None]] = []
        self._local = threading.local()

    # -- recording ----------------------------------------------------------

    def _stack(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        parent_id: Optional[str] = None,
        cpu_clock: bool = False,
        **attributes,
    ):
        """Record the enclosed block as one span; exceptions mark the
        span ``ERROR`` (with the exception repr) and propagate. The
        parent is ``parent_id`` where given, else the span enclosing
        this one on the calling thread. The start stamp is wall time;
        the duration is taken from ``time.perf_counter()``, which no
        clock step can stretch. With ``cpu_clock`` (the build path's
        spans ask for it: :func:`part_span`, :func:`program_span`, the
        fleet builder's phases and parts; a serving span pays no second
        clock) the span also carries ``cpu_seconds``: what
        ``time.thread_time()`` moved by on the calling thread, that is
        the CPU this thread used (C code that released the GIL
        included), not what other threads used meanwhile."""
        span_id = rand_hex(16)
        handle = SpanHandle(dict(attributes), self.trace_id, span_id)
        stack = self._stack()
        if parent_id is None:
            parent_id = stack[-1] if stack else self.default_parent_id
        annotation = (
            self.annotate(name, handle.attributes)
            if self.annotate is not None
            else None
        )
        stack.append(span_id)
        cpu_started = time.thread_time() if cpu_clock else None
        start = time.time()
        started = time.perf_counter()
        error: Optional[BaseException] = None
        try:
            if annotation is None:
                yield handle
            else:
                with annotation:
                    yield handle
        except BaseException as exc:
            error = exc
            raise
        finally:
            end = start + (time.perf_counter() - started)
            if cpu_started is not None:
                handle.attributes["cpu_seconds"] = round(
                    time.thread_time() - cpu_started, 6
                )
            stack.pop()
            self._record(
                self._span_dict(
                    name,
                    span_id,
                    parent_id,
                    start,
                    end,
                    handle.attributes,
                    error,
                    links=handle.links or None,
                )
            )

    def event(self, name: str, **attributes) -> None:
        """A point-in-time (zero-duration) record."""
        now = time.time()
        stack = self._stack()
        self._record(
            self._span_dict(
                name,
                rand_hex(16),
                stack[-1] if stack else self.default_parent_id,
                now,
                now,
                dict(attributes),
                None,
                kind="event",
            )
        )

    def record(
        self,
        name: str,
        seconds: float,
        start: Optional[float] = None,
        cpu_seconds: Optional[float] = None,
        **attributes,
    ) -> None:
        """An externally-timed interval as a finished span: ending now,
        or beginning at the wall-clock stamp ``start``; with
        ``cpu_seconds`` where the thread that did the work also read its
        CPU clock (``time.thread_time()``) around it.

        For durations measured on ANOTHER thread's clock — e.g. a
        request handler folding the micro-batcher's shared stack/device
        stage times into its own Server-Timing — where a ``with span``
        block on this recorder would double-count the wait; and for work
        a pool thread timed and the thread that waits for it writes down
        (a sink write from a contended thread costs the pool far more
        than the write itself: its flush gives the GIL away)."""
        seconds = max(0.0, seconds)
        end = time.time() if start is None else start + seconds
        stack = self._stack()
        if cpu_seconds is not None:
            attributes["cpu_seconds"] = round(max(0.0, cpu_seconds), 6)
        self._record(
            self._span_dict(
                name,
                rand_hex(16),
                stack[-1] if stack else self.default_parent_id,
                end - seconds,
                end,
                dict(attributes),
                None,
            )
        )

    def emit(self, span: dict) -> None:
        """Record a pre-built span dict as-is (sink + listeners + retain).

        The request-trace export path uses this: per-request recorders
        are in-memory (cheap, no file handle per request); at response
        finalization their finished spans — already carrying the
        request's own trace id — are emitted into the process-shared
        serving sink in one pass."""
        self._record(span)

    def emit_deferred(self, build: Callable[[], List[dict]]) -> None:
        """Queue a zero-arg callable whose returned span dicts are
        materialized ON THE WRITER THREAD (async sinks only; falls back
        to immediate emission otherwise).

        The request-export hot path uses this so a request thread pays
        one deque append while dict assembly + json + IO happen off the
        request's GIL time — the difference between the serving trace
        costing ~100us and ~10us per request."""
        if self.async_sink and self.sink_path is not None:
            # gt-lint: disable=lock-guard -- deque.append/popleft are
            # GIL-atomic; the bounded deque IS the lock-free handoff to
            # the writer thread (locking here would serialize requests)
            self._queue.append(build)
            if self._writer is None:
                self._ensure_writer()
            elif len(self._queue) >= 2048:
                self._wakeup.set()
            return
        for span in build():
            self._record(span)

    def _span_dict(
        self,
        name,
        span_id,
        parent_id,
        start,
        end,
        attributes,
        error,
        kind="internal",
        links=None,
    ) -> dict:
        return {
            "name": name,
            "context": {"trace_id": self.trace_id, "span_id": span_id},
            "parent_id": parent_id,
            "kind": kind,
            "start_time": _iso(start),
            "end_time": _iso(end),
            "duration_ms": round((end - start) * 1000.0, 3),
            "status": {
                "status_code": "ERROR" if error is not None else "OK",
                **({"description": repr(error)} if error is not None else {}),
            },
            "attributes": attributes,
            **({"links": links} if links else {}),
            "resource": {"service.name": self.service},
        }

    def _record(self, span: dict) -> None:
        if self.async_sink and self.sink_path is not None:
            # the serving hot path: request threads pay one deque append
            # (~0.1us); the writer thread does the json encode + IO.
            # A bounded deque sheds oldest-first if the disk ever stalls
            # — advisory telemetry must never become backpressure.
            # gt-lint: disable=lock-guard -- deque.append/popleft are
            # GIL-atomic; the bounded deque IS the lock-free handoff to
            # the writer thread (locking here would serialize requests)
            self._queue.append(span)
            if self._writer is None:
                self._ensure_writer()
            elif len(self._queue) >= 2048:
                # deep backlog: wake the writer early rather than risk
                # the bounded deque shedding (the only signaling the
                # recording threads ever do — see _writer_loop)
                self._wakeup.set()
            if not self.retain_spans and not self._listeners:
                return
        with self._lock:
            if self.retain_spans:
                self._spans.append(span)
            if self.sink_path is not None and not self.async_sink:
                try:
                    self._ensure_sink_linked()
                    if self._sink is None:
                        self._sink = open(self.sink_path, "a")
                    self._sink.write(json.dumps(span, default=str) + "\n")
                    self._sink.flush()
                    if self.max_bytes and self._sink.tell() >= self.max_bytes:
                        self._rotate_locked()
                except OSError:
                    # telemetry is advisory: a full/readonly volume must
                    # never fail the build it is describing
                    self.sink_path = None
                    self._sink = None
            listeners = list(self._listeners)
        for listener in listeners:
            try:
                listener(span)
            except Exception:  # noqa: BLE001 - listeners are advisory too
                pass

    def _ensure_sink_linked(self) -> None:
        """Drop a sink handle whose file no longer sits at the sink
        path (an aggregator in another pid namespace garbage-collected
        a sink it wrongly judged dead, or another process rotated a
        shared path): appending through the orphaned fd would make
        every later span invisible to all readers forever. Detection is
        a path-stat vs fd-stat inode comparison, NOT ``st_nlink == 0``
        — overlayfs (containers) keeps reporting nlink 1 for an
        unlinked-but-open file. One stat pair per write/batch; the
        caller reopens by path right after, so the next span starts a
        fresh, discoverable file."""
        if self._sink is None:
            return
        try:
            handle_stat = os.fstat(self._sink.fileno())
            try:
                path_stat = os.stat(self.sink_path)
            except OSError:
                orphaned = True  # the path is simply gone
            else:
                orphaned = (
                    path_stat.st_ino != handle_stat.st_ino
                    or path_stat.st_dev != handle_stat.st_dev
                )
            if orphaned:
                self._sink.close()
                self._sink = None
        except OSError:
            self._sink = None

    # -- async sink (serving) -----------------------------------------------

    def _ensure_writer(self) -> None:
        with self._lock:
            if self._writer is None and not self._closed:
                self._writer = threading.Thread(
                    target=self._writer_loop,
                    name="gordo-trace-writer",
                    daemon=True,
                )
                self._writer.start()

    def _writer_loop(self) -> None:
        # Self-polling instead of per-span signaling: an Event.set()
        # from the recording thread is a futex syscall that wakes the
        # writer mid-request — measured ~4% of scoring throughput at a
        # 10% export rate. While spans flow the poll is 50ms (bounds
        # trace latency); an idle writer backs off exponentially to 1s
        # so a quiet server doesn't pay 20 scheduler wakes/second for
        # nothing (under cgroup CPU quota even idle wakes bill the
        # throttle budget). close()/flush() still signal for prompt
        # shutdown.
        timeout = 0.05
        while True:
            self._wakeup.wait(timeout=timeout)
            self._wakeup.clear()
            if self._queue:
                timeout = 0.05
                self._drain()
            else:
                timeout = min(1.0, timeout * 2)
            if self._closed and not self._queue:
                return

    def _drain(self) -> None:
        """Write everything queued, as one batched write+flush. Queue
        items are span dicts or deferred builders (zero-arg callables
        returning span lists — see :meth:`emit_deferred`)."""
        with self._write_lock:
            batch: List[dict] = []
            while True:
                try:
                    item = self._queue.popleft()
                except IndexError:
                    break
                if callable(item):
                    try:
                        batch.extend(item())
                    except Exception:  # noqa: BLE001 - a broken deferred
                        # builder loses ITS spans, never the writer
                        pass
                else:
                    batch.append(item)
            if not batch or self.sink_path is None:
                return
            try:
                self._ensure_sink_linked()
                if self._sink is None:
                    self._sink = open(self.sink_path, "a")
                self._sink.write(
                    "".join(
                        json.dumps(span, default=str) + "\n" for span in batch
                    )
                )
                self._sink.flush()
                if self.max_bytes and self._sink.tell() >= self.max_bytes:
                    self._rotate_locked()
            except OSError:
                self.sink_path = None
                self._sink = None

    def flush(self) -> None:
        """Block until everything recorded so far is on disk (async
        sinks; a synchronous sink is always flushed per span). Tests
        and the route bench call this before reading the trace back."""
        if self.async_sink:
            self._drain()

    def _rotate_locked(self) -> None:
        """Rotate the sink: ``p`` -> ``p.1`` -> ... -> ``p.<keep>``
        (older generations deleted), then reopen a fresh ``p``. Called
        with the lock held, right after a write crossed ``max_bytes`` —
        so a months-lived serving/lifecycle process caps its telemetry
        footprint at ~``(keep + 1) * max_bytes`` per sink instead of
        growing without bound."""
        self._sink.close()
        self._sink = None
        if self.keep < 1:
            os.remove(self.sink_path)
            return
        for generation in range(self.keep, 0, -1):
            src = (
                self.sink_path
                if generation == 1
                else f"{self.sink_path}.{generation - 1}"
            )
            if os.path.exists(src):
                os.replace(src, f"{self.sink_path}.{generation}")

    # -- introspection ------------------------------------------------------

    def add_listener(self, listener: Callable[[dict], None]) -> None:
        """Call ``listener(span_dict)`` for every span/event as it
        finishes (the builder uses this for live Prometheus export)."""
        with self._lock:
            self._listeners.append(listener)

    def finished(self, name: Optional[str] = None) -> List[dict]:
        """Finished spans (optionally filtered by name), oldest first.
        Empty when ``retain_spans`` is off (the default for sink-backed
        recorders — read the JSONL sink instead)."""
        with self._lock:
            spans = list(self._spans)
        if name is not None:
            spans = [s for s in spans if s["name"] == name]
        return spans

    def durations(self) -> Dict[str, float]:
        """Total seconds per span name, in first-seen order."""
        totals: Dict[str, float] = {}
        for span in self.finished():
            if span["kind"] == "event":
                continue
            totals[span["name"]] = (
                totals.get(span["name"], 0.0) + span["duration_ms"] / 1000.0
            )
        return totals

    def close(self) -> None:
        if self.async_sink:
            self._closed = True
            self._wakeup.set()
            writer = self._writer
            if writer is not None:
                writer.join(timeout=2.0)
                with self._lock:  # _ensure_writer races shutdown
                    self._writer = None
            self._drain()  # anything the writer left behind
            with self._write_lock:
                if self._sink is not None:
                    try:
                        self._sink.close()
                    except OSError:
                        pass
                    self._sink = None
            return
        with self._lock:
            if self._sink is not None:
                try:
                    self._sink.close()
                except OSError:
                    pass
                self._sink = None


# -- the process-global recorder --------------------------------------------

_active: Any = NULL_RECORDER
_active_lock = threading.Lock()


def get_recorder():
    """The currently active recorder (:data:`NULL_RECORDER` when no
    build is being traced)."""
    return _active


@contextlib.contextmanager
def activate(recorder):
    """Install ``recorder`` as the process-global recorder for the
    enclosed block (the fleet build wraps itself in this)."""
    global _active
    with _active_lock:
        previous, _active = _active, recorder
    try:
        yield recorder
    finally:
        with _active_lock:
            _active = previous


# -- compile-vs-run attribution ---------------------------------------------

_seen_lock = threading.Lock()
_seen_programs: set = set()


def seen_program(key: Hashable) -> bool:
    """Register a program signature; True when it was already seen this
    process (→ the jit cache will hit and the call is a steady-state
    run, not a compile)."""
    with _seen_lock:
        if key in _seen_programs:
            return True
        _seen_programs.add(key)
        return False


def reset_seen_programs() -> None:
    """Forget all program signatures (tests only — real processes keep
    the set for the jit caches' lifetime, which is the process)."""
    with _seen_lock:
        _seen_programs.clear()


def _suffixed(attributes: Dict[str, Any], suffix: str) -> Dict[str, float]:
    """``<part><suffix>`` numeric attributes as part -> value."""
    return {
        key[: -len(suffix)]: float(value)
        for key, value in attributes.items()
        if key.endswith(suffix) and isinstance(value, (int, float))
    }


def part_sums(attributes: Dict[str, Any]) -> Dict[str, Any]:
    """What a ``build_part`` span adds to its entry beside its seconds
    and ``count``: each of ``PART_SUMS`` it carries, and, where it says
    its ``worker`` (a ``machine_fetch``: ``process`` or ``thread``), one
    ``in_process`` or none."""
    sums = {key: attributes[key] for key in PART_SUMS if attributes.get(key) is not None}
    if "worker" in attributes:
        sums["in_process"] = int(attributes["worker"] == "process")
    return sums


def nested_part_seconds(attributes: Dict[str, Any]) -> Dict[str, float]:
    """The parts a ``build_part`` span carries as attributes instead of
    child spans: ``<part>_s`` -> seconds. ``machine_fetch`` carries the
    dataset's own parts so, because a span each, written from sixteen
    pool threads, cost more than it told (PERF.md, PR 24)."""
    return _suffixed(attributes, "_s")


def nested_part_cpu_seconds(attributes: Dict[str, Any]) -> Dict[str, float]:
    """The CPU seconds of those nested parts, the pair of each
    ``<part>_s``: ``<part>_cpu_seconds`` -> seconds (a spelling
    :func:`nested_part_seconds` does not take for a part of its own)."""
    return _suffixed(attributes, "_cpu_seconds")


def part_span(part: str, **attributes):
    """A ``build_part`` span on the process-global recorder: one named
    piece of work inside the build phase in progress (``phase`` is
    stamped from the recorder). The trainer under the fleet builder
    records through this, so it imports nothing of the builder;
    ``FleetBuilder._part`` is the same span with the phase's span as
    explicit parent, for pool threads."""
    recorder = get_recorder()
    return recorder.span(
        "build_part", cpu_clock=True, phase=recorder.phase, part=part, **attributes
    )


def program_span(program: str, key: Hashable, **attributes):
    """
    Span around one jit-program invocation, attributed ``compile=True``
    on the first call per signature and ``compile=False`` after.

    ``key`` must capture the full compilation signature — spec, fit
    config, and array shapes — exactly as the jit cache would.
    """
    compile_flag = not seen_program((program, key))
    # feed the process-wide compile-vs-cache-hit accounting (device.py):
    # unlike the span below this is unconditional — the fleet console's
    # hit-rate numbers must not depend on a recorder being active
    from .device import note_program_execution

    note_program_execution(compile_flag, kind="build")
    return get_recorder().span(
        "device_program",
        cpu_clock=True,
        program=program,
        compile=compile_flag,
        **attributes,
    )
