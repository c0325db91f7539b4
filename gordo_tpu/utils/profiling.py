"""
Opt-in device profiling (SURVEY.md §5 "Tracing / profiling": the reference
records only coarse wall-clock durations — query_duration_sec and
model_training_duration_sec in build metadata, the Server-Timing response
header. Those fields all exist here too; this module adds the TPU-native
layer the reference had no analog for: XLA device traces).

Set ``GORDO_TPU_PROFILE_DIR`` and every labeled region writes a
TensorBoard-loadable trace (``jax.profiler``) under
``$GORDO_TPU_PROFILE_DIR/<label>/``; unset, the context manager is free.

This is the heavyweight, opt-in layer: raw XLA device traces for deep
kernel work. The always-on, aggregated layer — phase spans, compile/run
attribution, the live build-status surface — is ``gordo_tpu.telemetry``
(docs/observability.md). The two meet in :func:`annotate`: the fleet
builder enters it for every ``build_phase``, ``build_part`` and
``device_program`` span, so whichever profiler session is running (a
``maybe_trace`` region, the chip benchmark's own) holds the build's host
work on the profiler's clock, beside the device's lines.
"""
# gt-lint: file-disable=jax-stdlib-only -- this module IS the jax.profiler
# wrapper; the import stays lazy so the utils package imports clean on
# hosts without jax

import contextlib
import logging
import os
import sys

from .env import env_str

logger = logging.getLogger(__name__)

PROFILE_DIR_ENV = "GORDO_TPU_PROFILE_DIR"


@contextlib.contextmanager
def maybe_trace(label: str):
    """Trace the enclosed region to ``$GORDO_TPU_PROFILE_DIR/<label>``
    when profiling is enabled; no-op otherwise."""
    trace_dir = env_str(PROFILE_DIR_ENV, None)
    if not trace_dir:
        yield
        return
    import jax

    path = os.path.join(trace_dir, label)
    logger.info("Profiling %s -> %s", label, path)
    with jax.profiler.trace(path):
        yield


def annotate(label: str):
    """A ``jax.profiler.TraceAnnotation``: a named region in the host
    plane of whichever profiler session is running, on that profiler's
    clock. The one way into ``TraceAnnotation`` for the build path. Not
    gated on ``GORDO_TPU_PROFILE_DIR``: the session may be someone
    else's, and without one the annotation records nothing. A process
    that has not imported jax has no session to be in, and is not made
    to import it for this: a fetch worker (``dataset/fetch_pool.py``)
    times a dataset's parts through here and must stay off jax."""
    if "jax" not in sys.modules:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(label)
