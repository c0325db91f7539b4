"""
A low-overhead sampling profiler for the serving host pipeline.

The open finding of a CPU run (PR 4) is that the full HTTP route runs
~50x slower than scoring alone — the host pipeline (JSON decode, pandas
alignment, response serialization) dominates, but nothing could say
*which functions* eat the time on a live server. Deterministic tracing
(``sys.setprofile``) is off the table: it taxes every Python call on
every request, profiled or not. This profiler samples instead: a
background thread wakes every few milliseconds, grabs the profiled
request thread's current frame via ``sys._current_frames()``, and
charges one sample of **self time** to the (request stage, top frame)
pair. The request thread itself executes zero extra instructions; the
cost is one sampling thread per *profiled* request, and profiling is
off by default.

Two switches, both per-request:

- ``?profile=1`` on any model route profiles that request;
- ``GORDO_TPU_PROFILE_SAMPLE_RATE=0.01`` profiles ~1% of requests at
  random — the always-on production setting that keeps a live
  self-time breakdown flowing into ``serve_trace.jsonl`` (the
  ``profile`` span; ``gordo-tpu trace`` aggregates them).

The aggregated report is intentionally tiny — top-N frames by self
time, keyed ``(stage, function)`` — because its destination is a span
attribute in a JSONL trace, not a pprof blob. For raw XLA device
traces there is the separate opt-in ``jax.profiler`` layer
(``utils/profiling.maybe_trace``; ``?profile=device`` hooks it when
``GORDO_TPU_PROFILE_DIR`` is set).
"""

import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

SAMPLE_RATE_ENV = "GORDO_TPU_PROFILE_SAMPLE_RATE"
INTERVAL_ENV = "GORDO_TPU_PROFILE_INTERVAL_MS"

DEFAULT_INTERVAL_MS = 5.0
#: hard wall on one profile's runtime: a hung request must not leak an
#: immortal sampling thread
MAX_PROFILE_SECONDS = 120.0
#: frames kept in the report (by self time) — it travels as a span
#: attribute, so it must stay small
MAX_REPORT_FRAMES = 25


def sample_rate() -> float:
    """The configured random-sampling fraction in [0, 1] (default 0 =
    only explicitly requested profiles run)."""
    from ..utils.env import env_float

    return min(1.0, max(0.0, env_float(SAMPLE_RATE_ENV, 0.0)))


def sample_interval_s() -> float:
    from ..utils.env import env_float

    return max(
        0.0005, env_float(INTERVAL_ENV, DEFAULT_INTERVAL_MS) / 1000.0
    )


def should_profile(explicit: Optional[str]) -> bool:
    """Whether to profile this request: an explicit ``?profile=``
    value wins (any truthy spelling); otherwise a coin flip at
    ``GORDO_TPU_PROFILE_SAMPLE_RATE``."""
    if explicit is not None:
        return explicit.strip().lower() not in ("", "0", "false", "off", "no")
    rate = sample_rate()
    if rate <= 0.0:
        return False
    import random

    return random.random() < rate


def _frame_label(frame) -> str:
    """``<file>:<function>`` with the path trimmed to its last two
    segments — stable across hosts, short enough for a span attribute."""
    code = frame.f_code
    parts = code.co_filename.replace("\\", "/").rsplit("/", 2)
    filename = "/".join(parts[-2:]) if len(parts) > 1 else parts[-1]
    return f"{filename}:{code.co_name}"


class SamplingProfiler:
    """
    Samples ONE thread's stack until stopped, aggregating self time by
    ``(stage, function)``.

    ``stage_getter`` is a zero-argument callable answering the profiled
    request's current pipeline stage (the request context updates it as
    ``ctx.stage(...)`` blocks enter and exit); samples landing outside
    any stage are charged to ``"-"``. Aggregation happens inside the
    sampling thread, so ``stop()`` is just an event + join.
    """

    def __init__(
        self,
        interval_s: Optional[float] = None,
        max_seconds: float = MAX_PROFILE_SECONDS,
    ):
        self.interval_s = interval_s if interval_s else sample_interval_s()
        self.max_seconds = max_seconds
        self._counts: Dict[Tuple[str, str], int] = {}
        self._samples = 0
        self._missed = 0
        self._started_at = 0.0
        self._stopped_at = 0.0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ----------------------------------------------------------

    def start(
        self,
        thread_id: Optional[int] = None,
        stage_getter: Optional[Callable[[], Optional[str]]] = None,
    ) -> "SamplingProfiler":
        """Begin sampling ``thread_id`` (default: the calling thread)."""
        target_id = thread_id if thread_id is not None else threading.get_ident()
        self._started_at = time.monotonic()
        self._thread = threading.Thread(
            target=self._sample_loop,
            args=(target_id, stage_getter or (lambda: None)),
            name="gordo-profile-sampler",
            daemon=True,
        )
        self._thread.start()
        return self

    def stop(self) -> Dict[str, Any]:
        """Stop sampling and return the aggregated report."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        self._stopped_at = time.monotonic()
        return self.report()

    # -- sampling (profiler thread) -----------------------------------------

    def _sample_loop(self, target_id: int, stage_getter) -> None:
        deadline = self._started_at + self.max_seconds
        interval = self.interval_s
        while not self._stop.wait(interval):
            if time.monotonic() > deadline:
                return
            frame = sys._current_frames().get(target_id)
            if frame is None:
                # the request thread finished (or hasn't a frame yet)
                self._missed += 1
                continue
            try:
                stage = stage_getter() or "-"
            except Exception:  # noqa: BLE001 - the getter reads request
                # state that may be mid-mutation; a bad read is one
                # mislabeled sample, never a dead profiler
                stage = "-"
            key = (str(stage), _frame_label(frame))
            self._counts[key] = self._counts.get(key, 0) + 1
            self._samples += 1
            del frame  # never keep a live frame reference across sleeps

    # -- report -------------------------------------------------------------

    def report(self, max_frames: int = MAX_REPORT_FRAMES) -> Dict[str, Any]:
        """The aggregated self-time profile: top ``max_frames`` by
        sample count, each charged ``samples * interval`` milliseconds
        of self time. Wire-shaped (plain dicts/lists) — this travels as
        a ``profile`` span's attributes."""
        stopped = self._stopped_at or time.monotonic()
        per_sample_ms = self.interval_s * 1000.0
        ranked = sorted(
            self._counts.items(), key=lambda kv: kv[1], reverse=True
        )
        frames: List[Dict[str, Any]] = [
            {
                "stage": stage,
                "function": function,
                "samples": count,
                "self_ms": round(count * per_sample_ms, 3),
            }
            for (stage, function), count in ranked[:max_frames]
        ]
        return {
            "samples": self._samples,
            "missed": self._missed,
            "interval_ms": round(per_sample_ms, 3),
            "duration_ms": round(
                max(0.0, stopped - self._started_at) * 1000.0, 3
            ),
            "truncated_frames": max(0, len(ranked) - max_frames),
            "frames": frames,
        }
