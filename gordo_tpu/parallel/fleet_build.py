"""
FleetBuilder: the whole-project build — every machine in one YAML trained
as mesh-sharded model batches, producing per-machine artifacts identical
in contract to ModelBuilder's.

Replaces the reference's per-machine Argo pod DAG
(argo-workflow.yml.template:1519-1598) with chip fan-out. Per machine it
reproduces ModelBuilder semantics (gordo/builder/build_model.py):

- data fetch (concurrent across machines, host-side)
- host-side pipeline transformers (scalers) fitted per machine
- CV folds → per-tag + aggregate metric scores and DiffBased threshold
  math, with fold boundaries expressed as weight masks so every fold of
  every machine in a bucket trains in one device program
- final fit → params injected back into per-machine estimator objects
- metadata tree + artifact save (model.pkl / metadata.json / info.json)

Model definitions the fleet path supports: a JaxBaseEstimator, optionally
inside an sklearn Pipeline (host transformers before it), optionally
wrapped by DiffBasedAnomalyDetector. Anything else transparently falls
back to the sequential ModelBuilder so `fleet_build` always builds the
full config.
"""

import concurrent.futures
import contextlib
import datetime
import logging
import os
import time
import traceback
import types
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pandas as pd
from sklearn.base import clone as sklearn_clone
from sklearn.model_selection import KFold, TimeSeriesSplit
from sklearn.pipeline import Pipeline
from sklearn.preprocessing import MinMaxScaler, RobustScaler, StandardScaler

import gordo_tpu
from .. import serializer, telemetry
from ..builder.build_model import ModelBuilder
from ..dataset import GordoBaseDataset, fetch_pool
from ..machine import Machine
from ..telemetry.device import compile_path_counters, watch_compile_path
from ..telemetry.progress import BUILD_TRACE_FILE
from ..utils.profiling import annotate, maybe_trace
from ..machine.metadata import (
    BuildMetadata,
    CrossValidationMetaData,
    DatasetBuildMetadata,
    ModelBuildMetadata,
    RobustnessMetadata,
    TrainingSummaryMetadata,
)
from ..models.anomaly.diff import (
    THRESHOLD_RUN,
    DiffBasedAnomalyDetector,
    DiffBasedKFCVAnomalyDetector,
    threshold_run,
)
from ..models.estimators import JaxBaseEstimator, JaxWindowedBaseEstimator
from ..models.in_flight import landed
from ..models.training import FitConfig, fit_config_from_kwargs, split_fit_kwargs
from ..ops.windows import model_offset as calc_model_offset
from ..ops.windows import window_targets
from ..planner.packing import trains_alone, windowed_scoring_batch
from ..utils.env import env_float, env_int, env_str
from ..utils.faults import fault_point
from ..utils.retry import retry_call
from . import host_blocks
from .fleet import (
    FleetMember,
    FleetTrainer,
    FoldScoring,
    WindowedFleetMember,
    fetch_members,
    is_device_error,
    land_flights,
)
from .journal import BuildJournal, clean_staging_dirs

logger = logging.getLogger(__name__)


@dataclass
class _Plan:
    """Everything needed to train + reassemble one machine."""

    machine: Machine
    dataset: GordoBaseDataset
    model_obj: Any  # the unfitted object graph from the definition
    detector: Optional[DiffBasedAnomalyDetector]
    pipeline: Optional[Pipeline]
    estimator: JaxBaseEstimator
    X: pd.DataFrame = None
    y: pd.DataFrame = None
    X_arr: np.ndarray = None  # transformed (post host-transformers) inputs
    y_arr: np.ndarray = None
    # Dense models: estimator-space samples [N, F]. Windowed (LSTM) models:
    # None — the raw series (X_arr) stays resident and windows are gathered
    # on device (models/training.py build_raw_windowed_fit_fn), avoiding
    # the lookback× host/HBM blowup of materialized windows.
    windows: np.ndarray = None
    targets: np.ndarray = None
    n_windows: int = 0  # virtual sample count (== len(X_arr) for dense)
    shuffle_perm: Optional[np.ndarray] = None  # detector-level row shuffle
    offset: int = 0
    # True when the CV folds are cut over the windows' target rows
    # (FleetBuilder._cv_splits): a history too short for row folds
    target_folds: bool = False
    spec: Any = None
    fit_config: FitConfig = None
    seed: int = 42
    query_duration: float = 0.0
    cv_scores: Dict[str, Any] = field(default_factory=dict)
    cv_splits: Dict[str, Any] = field(default_factory=dict)
    cv_duration: float = 0.0
    train_duration: float = 0.0
    # Robustness counters surfaced in BuildMetadata.robustness:
    data_retries: int = 0  # data-fetch attempts beyond the first
    fleet_retries: int = 0  # diverged-member reseed retries (CV + final)
    bucket_bisects: int = 0  # split-retry events this machine rode through
    # Final-fit History summary (final/best loss, epochs, early stop),
    # baked into BuildMetadata.model.training at assembly.
    training_summary: Optional[TrainingSummaryMetadata] = None
    _scoring_setup_cache: Any = None  # (metrics, fitted scoring scaler)
    # (the scoring scaler's parameters for a predict program, or None
    # where the host scores the machine,): FleetBuilder._device_scoring
    _device_scoring_cache: Any = None


class FleetBuildError(RuntimeError):
    pass


#: what ``build_status.json["fit_counters"]`` keeps of a fit program's
#: ``device_program`` span beside the counters the span names in its
#: ``fit_counters`` attribute (parallel/fleet._fit_counter_attrs)
FIT_PROGRAM_KEYS = (
    "program", "phase", "members", "params", "epochs", "stacked_samples",
    "tokens_per_step",
)


#: the metrics a predict program scores where the predictions are
#: (parallel/fleet.fold_scores): the callables of a default evaluation
DEVICE_METRICS = tuple(ModelBuilder.metrics_from_list())


def _per_tag_affine(scaler) -> bool:
    """Whether ``scaler``'s transform is four numbers a tag
    (:func:`_scaler_parameters`): none at all, or one of the three
    per-tag affine scalers itself. A subclass, a pipeline, another
    transformer or a ``MinMaxScaler`` that clips may do what four
    numbers cannot say."""
    return (
        scaler is None
        or type(scaler) in (StandardScaler, RobustScaler)
        or (type(scaler) is MinMaxScaler and not scaler.clip)
    )


def _scaler_parameters(scaler, tags: int) -> np.ndarray:
    """A fitted :func:`_per_tag_affine` scaler's transform as
    ``FoldScoring`` takes it: float32 ``[4, tags]`` rows ``(shift, mul,
    div, add)`` of ``((x - shift) * mul) / div + add``, the steps sklearn
    takes in the order it takes them; no scaler is the identity."""
    parameters = np.empty((4, tags), np.float32)
    parameters[:] = [[0.0], [1.0], [1.0], [0.0]]
    if isinstance(scaler, MinMaxScaler):
        parameters[1], parameters[3] = scaler.scale_, scaler.min_
    elif isinstance(scaler, StandardScaler):
        if scaler.with_mean:
            parameters[0] = scaler.mean_
        if scaler.with_std:
            parameters[2] = scaler.scale_
    elif isinstance(scaler, RobustScaler):
        if scaler.with_centering:
            parameters[0] = scaler.center_
        if scaler.with_scaling:
            parameters[2] = scaler.scale_
    return parameters


def _fold_scaler_parameters(scaler, y_train: np.ndarray) -> np.ndarray:
    """:func:`_scaler_parameters` of a clone of ``scaler`` fitted to a
    fold's training rows. A ``MinMaxScaler`` (the reference's detectors
    have no other) is fitted here, by the arithmetic of its
    ``partial_fit`` without the input validation that is most of a
    ``clone().fit`` on a fold's few thousand rows."""
    tags = y_train.shape[1]
    if not isinstance(scaler, MinMaxScaler):
        return _scaler_parameters(sklearn_clone(scaler).fit(y_train), tags)
    low, high = (y_train.dtype.type(bound) for bound in scaler.feature_range)
    data_min = np.nanmin(y_train, axis=0)
    data_range = np.nanmax(y_train, axis=0) - data_min
    data_range[data_range < 10 * np.finfo(data_range.dtype).eps] = 1.0
    parameters = _scaler_parameters(None, tags)
    parameters[1] = (high - low) / data_range
    parameters[3] = low - data_min * parameters[1]
    return parameters


def _take_rows(array: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """``array[rows]``, as a view where ``rows`` is one ascending run (a
    ``TimeSeriesSplit``'s folds are): a copy of a fold's training rows
    costs more than the minimum and maximum taken of it."""
    first = int(rows[0]) if len(rows) else 0
    if np.array_equal(rows, np.arange(first, first + len(rows))):
        return array[first : first + len(rows)]
    return array[rows]


def _cv_chunk_bytes() -> int:
    """Per-program staging budget for CV fold members (raw member data;
    the device program's true footprint is a few × this for gradients and
    optimizer moments). Override with GORDO_TPU_CV_CHUNK_BYTES."""
    return env_int("GORDO_TPU_CV_CHUNK_BYTES", 1 << 30)


def _member_nbytes(member) -> int:
    """Raw staged bytes of one fold member (X + non-aliased y, or series)."""
    if isinstance(member, WindowedFleetMember):
        return member.series.nbytes + member.targets.nbytes
    n = member.X.nbytes
    if member.y is not member.X:
        n += member.y.nbytes
    return n


def _chunk_by_bytes(members, items, budget: int):
    """Split (members, items) into order-preserving chunks whose summed
    member bytes stay under ``budget`` (every chunk holds ≥1 member)."""
    chunks = []
    start, used = 0, 0
    for i, member in enumerate(members):
        # a member whose state alone out-sizes a program (the planner's
        # trains_alone) takes the whole budget: it trains, and its fold
        # scores, alone
        size = budget if trains_alone(member.spec) else _member_nbytes(member)
        if i > start and used + size > budget:
            chunks.append((members[start:i], items[start:i]))
            start, used = i, 0
        used += size
    if start < len(members):
        chunks.append((members[start:], items[start:]))
    return chunks


def _fold_member_name(machine_name: str, fold_idx: int) -> str:
    """Unique member name for one machine's fold model. '::' cannot occur
    in machine names (k8s-name validated), so no collision is possible."""
    return f"{machine_name}::fold{fold_idx}"


def _no_clock() -> float:
    """The CPU clock of a build that records nothing
    (``FleetBuilder._cpu_clock``)."""
    return 0.0


def _try_call(fn, *args):
    """Run ``fn``; return the exception instead of raising (thread-pool
    safe capture for failFast:false semantics). Interpreter-shutdown
    signals are explicitly NOT captured: ``failFast:false`` means one
    machine's failure spares the rest, not that a Ctrl-C or SystemExit
    (e.g. an injected process kill) gets silently journaled as a
    per-machine build error and the build marches on."""
    try:
        fn(*args)
        return None
    except (KeyboardInterrupt, SystemExit):
        raise
    except BaseException as exc:  # noqa: BLE001 - recorded per machine
        return exc


class FleetBuilder:
    def __init__(
        self,
        machines: Sequence[Machine],
        trainer: Optional[FleetTrainer] = None,
        data_workers: int = 16,
        fail_fast: bool = False,
        data_retries: Optional[int] = None,
        data_backoff: Optional[float] = None,
        data_deadline: Optional[float] = None,
        plan_strategy: Optional[str] = None,
        fleet_plan: Optional[Any] = None,
        cost_table: Optional[Any] = None,
        health_ledger: Optional[Any] = None,
    ):
        self.machines = list(machines)
        if trainer is None:
            trainer = FleetTrainer()
        # Bucket planning (gordo_tpu.planner): strategy / pre-computed
        # FleetPlan / calibrated cost table ride on the trainer — it is
        # the component that materializes buckets. Explicit arguments win
        # over whatever the (possibly caller-provided) trainer carries.
        if plan_strategy is not None:
            trainer.plan_strategy = plan_strategy
        if fleet_plan is not None:
            trainer.fleet_plan = fleet_plan
        if cost_table is not None:
            trainer.cost_table = cost_table
        self.trainer = trainer
        # A plan handed in (directly or already on the trainer) is
        # REPLAYED; otherwise each build computes a fresh one — a trainer
        # reused across builds must not leak the previous fleet's plan
        # (or the strategy a replayed plan switched it to).
        self._external_plan = getattr(trainer, "fleet_plan", None)
        self._external_strategy = getattr(trainer, "plan_strategy", None)
        self.data_workers = data_workers
        # The reference DAG runs with failFast:false
        # (argo-workflow.yml.template: one machine's builder pod failing
        # does not stop the fleet); mirror that — failed machines are
        # recorded in ``build_errors`` and the rest of the fleet builds.
        self.fail_fast = fail_fast
        self.build_errors: Dict[str, BaseException] = {}
        # Wall-clock per build phase (seconds), for the bench's host/device
        # breakdown: plan, data_fetch, stage, cv_train (device programs),
        # cv_score (host threshold/metric math), cv_finalize, final_fit,
        # assemble, dump.
        self.phase_seconds: Dict[str, float] = defaultdict(float)
        # Data-plane retry knobs (reference analog: the builder pod's
        # retryStrategy with backoff); env-overridable for operators.
        self.data_retries = (
            env_int("GORDO_TPU_DATA_RETRIES", 2)
            if data_retries is None
            else data_retries
        )
        self.data_backoff = (
            env_float("GORDO_TPU_DATA_BACKOFF", 0.5)
            if data_backoff is None
            else data_backoff
        )
        self.data_deadline = (
            env_float("GORDO_TPU_DATA_DEADLINE", None)
            if data_deadline is None
            else data_deadline
        )
        # Fleet-wide robustness counters (surfaced in BuildMetadata per
        # machine and as Prometheus counters at build end).
        self.robustness: Dict[str, int] = defaultdict(int)
        # Machines degraded out of the fleet path to the sequential
        # ModelBuilder after an isolated device failure: name -> cause.
        self.degraded: Dict[str, BaseException] = {}
        # Machine names skipped by --resume (journaled complete).
        self.resumed: List[str] = []
        self._journal: Optional[BuildJournal] = None
        self._config_hashes: Dict[str, str] = {}
        # Telemetry: the per-build span recorder + live progress surface
        # (installed by build(); NULL/None outside one, so every
        # instrumentation site stays unconditional).
        self.recorder: Any = telemetry.NULL_RECORDER
        self.progress: Optional[telemetry.BuildProgress] = None
        self._project = ""
        # Predicted-vs-actual bookkeeping for the FleetPlan: the span
        # listener attributes final-fit device programs here so the
        # cost model's error is observable (event + gauges at build end).
        self._current_phase = ""
        # span id of the running build_phase span: the explicit parent
        # of the parts its pool threads record (_part)
        self._phase_span_id: Optional[str] = None
        self._plan_actuals: Dict[str, float] = defaultdict(float)
        # Per-member fleet health ledger (telemetry/fleet_health.py):
        # build provenance — final losses, failures, degradations —
        # lands per machine, so the fleet console can answer "which of
        # my machines are degraded" without parsing the span trace.
        # An explicit `health_ledger` overrides the default
        # ledger-per-output-dir: lifecycle incremental rebuilds train
        # into a .lifecycle/build-<rev> STAGING dir, but their
        # provenance belongs in the anchor collection's ledger — the
        # one the fleet-status surfaces actually read.
        self._health_ledger_override = health_ledger
        self._ledger: Any = telemetry.NULL_LEDGER
        self._ledger_flushed = False
        self._output_revision: Optional[str] = None
        # Measured device-utilization actuals: member-axis occupancy of
        # the executed final-fit programs and the max observed HBM peak
        # (Device.memory_stats), joined against the FleetPlan's
        # predictions in _export_plan_accuracy.
        self._member_actuals: Dict[str, int] = defaultdict(int)
        self._device_peak_bytes = 0
        self._last_device_sample = 0.0
        self._device: Optional[Dict[str, Any]] = None

    #: phases that end with a device-utilization sample (``cv_*`` phases
    #: recur once per bucket chunk and are throttled by time instead)
    _DEVICE_SAMPLED_PHASES = frozenset(
        {"stage", "cv_train", "final_fit", "assemble", "dump"}
    )

    @contextlib.contextmanager
    def _phase(self, name: str):
        started = time.perf_counter()
        previous = (self._current_phase, self._phase_span_id)
        try:
            with self.recorder.span(
                "build_phase",
                cpu_clock=True,
                phase=name,
                machines=len(self.machines),
            ) as handle:
                # every thread's CPU between the phase's two ends, beside
                # the span's own thread's: over the phase's seconds, the
                # cores the phase kept busy
                process_cpu_started = (
                    self._process_cpu_clock() if handle.recording else None
                )
                # the phase pays for its own status write and device
                # sample: nothing of a build lies between two phases
                if self.progress is not None:
                    self.progress.phase(name)
                self._enter_phase(name, handle.span_id or None)
                try:
                    yield
                finally:
                    self._sample_device(name)
                    if process_cpu_started is not None:
                        handle.set(
                            process_cpu_seconds=round(
                                self._process_cpu_clock() - process_cpu_started, 6
                            )
                        )
        finally:
            self._enter_phase(*previous)
            self.phase_seconds[name] += time.perf_counter() - started

    def _enter_phase(self, name: str, span_id: Optional[str]) -> None:
        self._current_phase, self._phase_span_id = name, span_id
        if self.recorder.enabled:
            # telemetry.part_span (the trainer) reads it
            self.recorder.phase = name

    def _part(self, name: str, **attributes):
        """One named piece of work inside the running phase, as a
        ``build_part`` span under the phase's span — also from a pool
        thread, which has no enclosing span of its own. The span
        listener folds its seconds into
        ``build_status.json["phases"][phase]["parts"][name]``."""
        return self.recorder.span(
            "build_part",
            parent_id=self._phase_span_id,
            cpu_clock=True,
            phase=self._current_phase,
            part=name,
            **attributes,
        )

    @staticmethod
    def _process_cpu_clock() -> float:
        """The CPU seconds of the process and of the fetch workers that
        compute for it (``time.process_time()`` does not see a child
        until it is reaped, and these live on): a phase whose work left
        for the workers would otherwise read fewer cores busy for it."""
        return time.process_time() + fetch_pool.cpu_seconds()

    def _cpu_clock(self) -> Callable[[], float]:
        """``time.thread_time`` for work that is timed in place and
        handed to :meth:`_record_part`; where nothing is recorded, a
        clock that stands still: a build without telemetry reads no
        second clock."""
        return time.thread_time if self.recorder.enabled else _no_clock

    def _record_part(
        self,
        name: str,
        seconds: float,
        count: int,
        cpu_seconds: Optional[float] = None,
        **attributes: Any,
    ) -> None:
        """``count`` pieces of one kind of work as ONE ``build_part``
        span of their summed seconds (and, where the threads that did
        them read ``time.thread_time()`` too, of their summed CPU
        seconds): per machine-fold or per artifact they would cost more
        lines than they are worth (docs/observability.md, the span
        budget)."""
        self.recorder.record(
            "build_part",
            seconds,
            cpu_seconds=cpu_seconds,
            phase=self._current_phase,
            part=name,
            count=count,
            **attributes,
        )

    def _record_phase(self, name: str, seconds: float) -> None:
        """A phase timed by the caller, ending now: ``config_load`` ran
        before this build had a recorder."""
        self.phase_seconds[name] += seconds
        if self.progress is not None:
            self.progress.phase(name)
        self.recorder.record(
            "build_phase", seconds, phase=name, machines=len(self.machines)
        )

    @staticmethod
    def _annotation(name: str, attributes: Dict[str, Any]):
        """The recorder's ``annotate`` hook: phases, parts and device
        programs as ``jax.profiler.TraceAnnotation``s, so a profiler
        session (``GORDO_TPU_PROFILE_DIR``'s, the chip benchmark's) holds
        them in its host plane, on its own clock."""
        if name == "build_phase":
            return annotate(f"build_phase:{attributes.get('phase')}")
        if name == "build_part":
            return annotate(
                f"build_part:{attributes.get('phase')}/{attributes.get('part')}"
            )
        if name == "device_program":
            return annotate(f"device_program:{attributes.get('program')}")
        return None

    def _sample_device(self, phase: str) -> None:
        """Sample what the process holds (the devices' memory, the
        host's peak resident set) at the end of device-heavy phases,
        time-throttled so a thousand-chunk CV loop costs a handful of
        samples, not a thousand. Tracks the build's max observed HBM
        peak for the plan-accuracy join, and hands the newest sample to
        ``build_status.json["resources"]``."""
        if phase not in self._DEVICE_SAMPLED_PHASES:
            return
        now = time.time()
        if now - self._last_device_sample < 1.0 and phase != "final_fit":
            return
        self._last_device_sample = now
        try:
            sample = telemetry.sample_resources()
        except Exception as exc:  # noqa: BLE001 - device telemetry is advisory
            logger.debug("resources not sampled: %r", exc)
            return
        snapshot = sample.pop("memory")
        if snapshot and snapshot.get("available"):
            self._device_peak_bytes = max(
                self._device_peak_bytes,
                int(snapshot.get("max_peak_bytes_in_use") or 0),
            )
        if self.progress is not None:
            self.progress.resources = {
                "hbm_peak_bytes": self._device_peak_bytes or None,
                **sample,
            }

    def _fail(self, name: str, exc: BaseException):
        if self._journal is not None:
            self._journal.record(name, "failed", error=repr(exc))
        if self.fail_fast:
            raise exc
        logger.error("Fleet build of machine %s failed: %r", name, exc)
        first_failure = name not in self.build_errors
        self.build_errors[name] = exc
        if first_failure:
            self.recorder.event("machine_failed", machine=name, error=repr(exc))
            if self.progress is not None:
                self.progress.machine_failed(name)
                self._update_progress_gauges()

    def _skipped(self, name: str) -> bool:
        """A machine out of the fleet path: failed, or degraded to the
        sequential builder (it finishes there, not here)."""
        return name in self.build_errors or name in self.degraded

    def _degrade(self, plan: "_Plan", exc: BaseException):
        """Pull one machine out of the fleet path after its device
        program failed in isolation; it rebuilds on the sequential
        ModelBuilder path (the same escape hatch unsupported definitions
        take), so a poisonous member costs one sequential build instead
        of the fleet."""
        name = plan.machine.name
        logger.warning(
            "Fleet degrade: %s falls back to the sequential builder after "
            "an isolated device failure: %r",
            name,
            exc,
        )
        self.robustness["sequential_degraded"] += 1
        self.degraded[name] = exc
        self.recorder.event(
            "machine_degraded", machine=name, error=repr(exc)
        )
        if self.progress is not None:
            self.progress.degraded = len(self.degraded)
            self.progress.write()

    # ------------------------------------------------------------------ API

    def build(
        self,
        output_dir: Optional[str] = None,
        model_register_dir: Optional[str] = None,
        replace_cache: bool = False,
        resume: bool = False,
        started: Optional[float] = None,
        report: bool = False,
    ) -> List[Tuple[Any, Machine]]:
        """
        Train the whole fleet; optionally dump per-machine artifacts to
        ``output_dir/<machine-name>/``. With a ``model_register_dir``, the
        content-addressed build cache applies per machine exactly as in
        ``ModelBuilder.build`` — cache hits skip training entirely and
        fresh builds are registered for the next run.

        With an ``output_dir`` the build keeps a journal
        (``build_state.json``, written with atomic replaces) of every
        machine's status; ``resume=True`` replays it after a crash —
        machines journaled ``built`` under an unchanged config hash with
        a complete artifact on disk are skipped entirely (recorded in
        ``self.resumed``), and only the remainder is replanned. Resumed
        machines are not re-loaded, so they do not appear in the return
        value; their artifacts are already in place.

        Telemetry (on unless ``GORDO_TPU_TELEMETRY`` is falsy): the
        build records a span per phase and device program into
        ``self.recorder`` (JSONL-sunk to ``<output_dir>/build_trace.jsonl``
        or ``$GORDO_TPU_TELEMETRY_DIR``), heartbeats a live
        ``build_status.json`` beside the journal, and exports phase/
        compile durations, member final losses and machine-progress
        gauges to Prometheus as they happen.

        ``started`` and ``report`` bring the command's own work under
        the build's phases: the time since ``started`` (a
        ``time.perf_counter()`` stamp taken where the caller began
        loading its config) is recorded as phase ``config_load``, and
        with ``report`` every built machine's reporters
        (``Machine.report``) run as the last phase, ``report``, before
        the status turns ``complete``.
        """
        self.build_errors = {}
        self.phase_seconds = defaultdict(float)
        self.robustness = defaultdict(int)
        self.degraded = {}
        self.resumed = []
        self._journal = None
        self._plan_actuals = defaultdict(float)
        self._member_actuals = defaultdict(int)
        # the final fits that did not wait for their parameters: each
        # plan with the transfers its estimator's leaves belong to
        self._in_flight: List[Tuple[_Plan, Any]] = []
        self._device_peak_bytes = 0
        self._current_phase, self._phase_span_id = "", None
        self._ledger_flushed = False
        self._project = self.machines[0].project_name if self.machines else ""
        # where this build runs, asked once: the status document and
        # every artifact's metadata carry the same answer
        self._device = telemetry.device_identity()
        self._output_revision = (
            os.path.basename(os.path.normpath(output_dir))
            if output_dir is not None
            else None
        )
        if self._health_ledger_override is not None:
            self._ledger = self._health_ledger_override
        elif output_dir is not None:
            self._ledger = telemetry.ledger_for(
                output_dir, project=self._project
            )
        else:
            self._ledger = telemetry.NULL_LEDGER

        recorder: Any = telemetry.NULL_RECORDER
        self.progress = None
        if telemetry.enabled():
            trace_path = None
            if output_dir is not None:
                trace_dir = env_str(telemetry.TRACE_DIR_ENV, None) or output_dir
                try:
                    os.makedirs(trace_dir, exist_ok=True)
                    trace_path = os.path.join(trace_dir, BUILD_TRACE_FILE)
                except OSError as exc:
                    logger.debug("No span trace sink: %r", exc)
            recorder = telemetry.SpanRecorder(
                sink_path=trace_path, service="gordo-tpu-fleet-build"
            )
            recorder.annotate = self._annotation
            recorder.add_listener(self._export_span)
            self.progress = telemetry.BuildProgress(
                output_dir,
                project=self._project,
                total=len(self.machines),
                phase_seconds=self.phase_seconds,
                robustness=self.robustness,
                device=self._device,
            )
            self._update_progress_gauges()
        self.recorder = recorder
        watch_compile_path()
        compile_before = compile_path_counters()
        try:
            # GORDO_TPU_PROFILE_DIR: one device trace of the whole
            # build, its phases, parts and programs annotated in it
            with maybe_trace("fleet-build"), telemetry.activate(recorder):
                with recorder.span(
                    "fleet_build",
                    project=self._project,
                    machines=len(self.machines),
                ) as root:
                    if started is not None:
                        self._record_phase(
                            "config_load", time.perf_counter() - started
                        )
                    try:
                        results = self._run_build(
                            output_dir, model_register_dir, replace_cache, resume
                        )
                    finally:
                        # The build-computed plan (and any strategy a
                        # replayed plan switched the trainer to) must not
                        # outlive the build on a shared trainer: a later
                        # FleetBuilder reusing this trainer would
                        # otherwise replay THIS fleet's plan as if the
                        # caller had passed it.
                        self.trainer.fleet_plan = self._external_plan
                        self.trainer.plan_strategy = self._external_strategy
                    if report:
                        with self._phase("report"):
                            for _, machine in results:
                                machine.report()
                    # what this build spent on the way to its
                    # executables: tracing, lowering, compiling, loading
                    compile_after = compile_path_counters()
                    compile_path = {
                        key: round(value - compile_before[key], 6)
                        for key, value in compile_after.items()
                    }
                    root.set(**compile_path)
                    if self.progress is not None:
                        self.progress.compile = compile_path
        except Exception:
            # a build-level failure (per-machine failures do NOT raise);
            # SystemExit/KeyboardInterrupt skip this on purpose — a
            # killed build leaves the status "running", like a real kill
            if self.progress is not None:
                self.progress.finish("failed")
                self._update_progress_gauges()
            raise
        finally:
            recorder.close()
            # the staging pool keeps what this build used, and no more
            host_blocks.trim()
            if not self._ledger_flushed:
                # a build that ended early never reached its finish phase
                self._ledger.flush()
        if self.progress is not None:
            self.progress.finish("complete")
            self._update_progress_gauges()
        return results

    def _run_build(
        self,
        output_dir: Optional[str],
        model_register_dir: Optional[str],
        replace_cache: bool,
        resume: bool,
    ) -> List[Tuple[Any, Machine]]:
        machines = self.machines
        trainer_bisects_start = getattr(self.trainer, "bucket_bisects", 0)
        trainer_counts_start = dict(getattr(self.trainer, "bisect_counts", {}))
        config_hashes: Dict[str, str] = {}
        cached_results: List[Tuple[Any, Machine]] = []
        # journal, config hashes, --resume and build-cache probing: host
        # work before the first machine is planned
        with self._phase("prepare"):
            if output_dir is not None:
                config_hashes = {
                    m.name: ModelBuilder.calculate_cache_key(m) for m in machines
                }
                self._config_hashes = config_hashes
                # Orphaned `.<name>.tmp-*` staging dirs from a killed run are
                # dead weight either way; sweep them before anything else.
                clean_staging_dirs(output_dir)
                self._journal = (
                    BuildJournal.load(output_dir) if resume else BuildJournal(output_dir)
                )
                if resume:
                    remaining = []
                    for machine in machines:
                        if self._journal.resumable(
                            machine.name, config_hashes[machine.name]
                        ):
                            self.resumed.append(machine.name)
                        else:
                            remaining.append(machine)
                    machines = remaining
                    logger.info(
                        "Resume: %d machine(s) already built and verified, "
                        "%d to build",
                        len(self.resumed),
                        len(machines),
                    )
                    if self.progress is not None:
                        self.progress.resumed = len(self.resumed)
                        self.progress.write(force=True)

            if model_register_dir:
                # register() dumps atomically under builds/ too — sweep any
                # staging orphans a killed build left in the shared registry.
                clean_staging_dirs(os.path.join(str(model_register_dir), "builds"))
                to_probe, machines = machines, []
                for machine in to_probe:
                    cached = ModelBuilder(machine).load_cached(
                        model_register_dir, replace_cache=replace_cache
                    )
                    if cached is not None:
                        cached_results.append(cached)
                    else:
                        machines.append(machine)
                logger.info(
                    "Fleet cache: %d hits, %d to build",
                    len(cached_results),
                    len(machines),
                )
                if self.progress is not None:
                    self.progress.cached = len(cached_results)
                    self.progress.write(force=True)

        with self._phase("plan"):
            plans, fallbacks = self._plan_all(machines)
            if self.progress is not None:
                self.progress.fallbacks = len(fallbacks)
            if self._journal is not None:
                for machine in machines:
                    self._journal.record(
                        machine.name,
                        "planned",
                        config_hash=config_hashes.get(machine.name),
                        flush=False,
                    )
                self._journal.flush()
        plans = self._load_all_data(plans)
        self._prepare_fleet_plan(plans, output_dir)

        def alive(ps):
            return [p for p in ps if not self._skipped(p.machine.name)]

        # CV folds then final fit, bucketed across all plans at once
        cv_plans = [
            p
            for p in alive(plans)
            if p.machine.evaluation.get("cv_mode", "full_build").lower()
            in ("full_build", "cross_val_only")
        ]
        if cv_plans:
            self._run_cross_validation(cv_plans)
            if self._journal is not None:
                with self._phase("cv_finalize"):
                    for plan in alive(cv_plans):
                        self._journal.record(
                            plan.machine.name, "cv_done", flush=False
                        )
                    self._journal.flush()
        final_plans = [
            p
            for p in alive(plans)
            if p.machine.evaluation.get("cv_mode", "full_build").lower()
            != "cross_val_only"
        ]
        self._run_final_fit(final_plans)

        # Attribute trainer-INTERNAL bisections (resolved inside
        # FleetTrainer without surfacing here) to their machines before
        # assembly bakes the per-machine robustness metadata: member
        # names are `machine` or `machine::foldN`.
        trainer_counts = getattr(self.trainer, "bisect_counts", {})
        if trainer_counts:
            per_machine: Dict[str, int] = defaultdict(int)
            for member_name, count in trainer_counts.items():
                delta = count - trainer_counts_start.get(member_name, 0)
                if delta > 0:
                    per_machine[member_name.split("::", 1)[0]] += delta
            for plan in plans:
                plan.bucket_bisects += per_machine.get(plan.machine.name, 0)

        results = []
        with self._phase("assemble"):
            for plan in alive(plans):
                try:
                    results.append(self._assemble(plan))
                except Exception as exc:
                    self._fail(plan.machine.name, exc)
        if fallbacks or self.degraded:
            with self._phase("sequential"):
                self._build_sequentially(machines, fallbacks, results)

        if model_register_dir:
            with self._phase("register"):
                for model, machine in results:
                    try:
                        ModelBuilder(machine).register(
                            model, machine, model_register_dir
                        )
                    except Exception as exc:
                        self._fail(machine.name, exc)

        results = cached_results + results
        if output_dir is not None:
            with self._phase("dump"):
                results = self._dump_all(results, output_dir)
                # compact the per-machine event overlay into the base
                # journal so a finished build leaves one clean state file
                self._journal.flush()
        # Fold in bisections the trainer resolved internally (they never
        # surfaced as exceptions here, but they are still split-retry
        # events an operator wants on a dashboard).
        self.robustness["bucket_bisects"] += max(
            0, getattr(self.trainer, "bucket_bisects", 0) - trainer_bisects_start
        )
        with self._phase("finish"):
            self._land_parameters()
            self._record_prometheus(machines)
            self._export_plan_accuracy()
            self._ledger.flush()
            self._ledger_flushed = True
        return [
            (model, machine)
            for model, machine in results
            if machine.name not in self.build_errors
        ]

    def _build_sequentially(self, machines, fallbacks, results) -> None:
        """The machines the fleet path does not train, one by one on the
        sequential ModelBuilder, appended to ``results``."""
        self._land_flights()  # its programs find the chip as it was
        for machine in fallbacks:
            logger.info("Fleet fallback to ModelBuilder for %s", machine.name)
            try:
                results.append(ModelBuilder(machine).build())
            except Exception as exc:
                self._fail(machine.name, exc)
        # Machines degraded out of the fleet after isolated device
        # failures rebuild sequentially, exactly like unsupported
        # definitions; a machine that fails here too is a real failure
        # (recorded with the sequential cause, the device cause logged).
        degraded_machines = {m.name: m for m in machines}
        for name, cause in self.degraded.items():
            machine = degraded_machines.get(name)
            if machine is None:
                continue
            logger.info(
                "Sequential rebuild of degraded machine %s (device cause: %r)",
                name,
                cause,
            )
            try:
                results.append(ModelBuilder(machine).build())
            except Exception as exc:
                self._fail(name, exc)

    def _export_span(self, span: dict) -> None:
        """Live Prometheus export of finished telemetry spans — phase
        durations, first-call (compile) program durations, and member
        final losses land in /metrics as they happen, not at build end.
        Best-effort like every metrics path: the build must not care
        whether a Prometheus stack is configured."""
        name = span["name"]
        attrs = span.get("attributes") or {}
        seconds = float(span.get("duration_ms") or 0.0) / 1000.0
        if (
            name == "device_program"
            and self._current_phase == "final_fit"
            and str(attrs.get("program", "")).endswith("_fit")
        ):
            # The plan covers exactly the final-fit fit programs; their
            # observed cost is the plan's predicted-vs-actual 'actual'.
            self._plan_actuals["seconds"] += seconds
            if attrs.get("compile"):
                self._plan_actuals["compiles"] += 1
            # Measured member-axis occupancy: `members` is the live
            # bucket size, `stacked_members` the padded rung the program
            # actually executed — the measured counterpart of the plan's
            # predicted padding waste.
            live = attrs.get("members")
            padded = attrs.get("stacked_members")
            if live is not None and padded:
                self._member_actuals["live"] += int(live)
                self._member_actuals["padded"] += int(padded)
        if (
            name == "device_program"
            and attrs.get("fit_counters")
            and self.progress is not None
        ):
            keys = FIT_PROGRAM_KEYS + tuple(attrs["fit_counters"])
            self.progress.add_fit_counters(
                {key: attrs[key] for key in keys if key in attrs}
            )
        if name == "build_part" and self.progress is not None:
            phase = str(attrs.get("phase", ""))
            self.progress.add_part(
                phase,
                str(attrs.get("part", "")),
                seconds,
                int(attrs.get("count", 1)),
                **telemetry.part_sums(attrs),
            )
            nested_cpu = telemetry.nested_part_cpu_seconds(attrs)
            for part, nested in telemetry.nested_part_seconds(attrs).items():
                self.progress.add_part(
                    phase, part, nested, cpu_seconds=nested_cpu.get(part)
                )
        if name == "build_phase" and self.progress is not None:
            self.progress.add_phase_cpu(
                str(attrs.get("phase", "")),
                cpu_seconds=attrs.get("cpu_seconds"),
                process_cpu_seconds=attrs.get("process_cpu_seconds"),
            )
        self._feed_health_ledger(name, attrs)
        try:
            from ..server.prometheus import metrics as prom

            if name == "build_phase":
                prom.record_fleet_build_phase(
                    self._project, str(attrs.get("phase", "")), seconds
                )
            elif name == "device_program" and attrs.get("compile"):
                prom.record_fleet_compile(
                    self._project,
                    str(attrs.get("program", "")),
                    str(attrs.get("shape", "")),
                    seconds,
                )
            elif name == "member_trained":
                loss = attrs.get("final_loss")
                if loss is not None and np.isfinite(loss):
                    prom.record_member_final_loss(self._project, float(loss))
        except Exception as exc:  # noqa: BLE001 - metrics are advisory
            logger.debug("Telemetry span not exported: %r", exc)

    def _feed_health_ledger(self, name: str, attrs: Dict[str, Any]) -> None:
        """Per-member build provenance into the fleet health ledger
        (telemetry/fleet_health.py) as the build's own events happen.
        Per-member VALUES live in the ledger; Prometheus only ever sees
        the bounded loss histogram and the aggregate health counts (the
        PR 8 cardinality contract)."""
        machine = attrs.get("machine")
        if not machine:
            return
        try:
            if name == "member_trained":
                loss = attrs.get("final_loss")
                self._ledger.record_build(
                    str(machine),
                    final_loss=(
                        float(loss)
                        if loss is not None and np.isfinite(loss)
                        else None
                    ),
                    retries=attrs.get("retries"),
                )
            elif name == "machine_built":
                # an artifact landing supersedes a PREVIOUS build's
                # failure evidence (a recovered machine must not read
                # 'degraded' forever) — but a machine that degraded to
                # the sequential builder in THIS build keeps the flag
                # its artifact genuinely carries (None = leave as-is)
                self._ledger.record_build(
                    str(machine),
                    revision=self._output_revision,
                    failed=False,
                    degraded=False if str(machine) not in self.degraded else None,
                )
            elif name == "machine_failed":
                self._ledger.record_build(
                    str(machine), failed=True, error=attrs.get("error")
                )
            elif name == "machine_degraded":
                self._ledger.record_build(
                    str(machine), degraded=True, error=attrs.get("error")
                )
        except Exception as exc:  # noqa: BLE001 - the ledger is advisory
            logger.debug("Health ledger not fed: %r", exc)

    def _update_progress_gauges(self) -> None:
        """Push the live machine-progress counters to the Prometheus
        gauges (best-effort; called from the dump pool too — Gauge.set
        is thread-safe)."""
        if self.progress is None:
            return
        try:
            from ..server.prometheus.metrics import set_fleet_build_progress

            set_fleet_build_progress(
                self._project,
                self.progress.total,
                self.progress.completed,
                self.progress.failed,
            )
        except Exception as exc:  # noqa: BLE001 - metrics are advisory
            logger.debug("Progress gauges not exported: %r", exc)

    def _record_prometheus(self, machines: Sequence[Machine]):
        """Best-effort robustness counter export; the build must not care
        whether a Prometheus stack is configured."""
        if not any(self.robustness.values()):
            return
        try:
            from ..server.prometheus.metrics import record_fleet_build_robustness

            project = machines[0].project_name if machines else ""
            record_fleet_build_robustness(project, dict(self.robustness))
        except Exception as exc:  # noqa: BLE001 - metrics are advisory
            logger.debug("Robustness counters not exported: %r", exc)

    def _dump_all(self, results, output_dir: str):
        """Per-machine artifact dump, thread-pooled: pickling releases the
        GIL for the array copies and the file writes overlap, so the dump
        phase scales with cores instead of machine count. Per-machine
        error capture keeps failFast:false semantics.

        Each artifact is written atomically (staging dir + rename), so a
        crash at any instant leaves either a complete artifact or none —
        never a half-written ``model.pkl`` a later resume or the serving
        store could load. Completion is journaled per machine before the
        kill-injection site, so a death right after machine N leaves N
        resumable machines."""

        # (metadata seconds, artifact seconds, the same two of the
        # thread's CPU clock, what went into model.pkl) a machine, from
        # the pool's threads (list.append is atomic); two spans a build,
        # not a machine
        timings: List[Tuple[float, float, float, float, serializer.Written]] = []
        clock, cpu_clock = time.perf_counter, self._cpu_clock()
        # what the picklers find of the final fits' leaves that are on
        # their way, and wait for, is the flights' count from here on
        before = {
            flight: (flight.bytes_landed, flight.wait_seconds)
            for _, flight in self._in_flight
        }

        def dump_one(item):
            model, machine = item
            path = os.path.join(output_dir, machine.name)
            began, cpu_began = clock(), cpu_clock()
            metadata = machine.to_dict()
            serialized, cpu_serialized = clock(), cpu_clock()
            written = serializer.dump_atomic(
                model, path, metadata=metadata, cpu_clock=cpu_clock
            )
            timings.append(
                (
                    serialized - began,
                    clock() - serialized,
                    cpu_serialized - cpu_began,
                    cpu_clock() - cpu_serialized,
                    written,
                )
            )
            if self._journal is not None:
                # Record the hash too: cache-hit machines skip the planning
                # pass (where it is normally journaled), and resume needs it.
                self._journal.record(
                    machine.name,
                    "built",
                    config_hash=self._config_hashes.get(machine.name),
                )
            # Progress lands BEFORE the kill-injection site, mirroring
            # the journal: a death right after machine N leaves a status
            # document (and gauges) that already show N completed —
            # exactly, with GORDO_TPU_TELEMETRY_HEARTBEAT=0 (the fault
            # drills); within one heartbeat interval otherwise.
            self.recorder.event("machine_built", machine=machine.name)
            if self.progress is not None:
                self.progress.machine_completed(machine.name)
                self._update_progress_gauges()
            fault_point("process_kill_after_n_machines", machine.name)

        to_dump = [
            (model, machine)
            for model, machine in results
            # A machine can fail *after* assembly (e.g. at register);
            # never dump artifacts for machines already in build_errors.
            if machine.name not in self.build_errors
        ]
        pool = concurrent.futures.ThreadPoolExecutor(min(8, max(1, len(to_dump))))
        try:
            outcomes = list(pool.map(lambda it: _try_call(dump_one, it), to_dump))
        except (KeyboardInterrupt, SystemExit):
            # Interpreter shutdown mid-dump: stop scheduling new dumps.
            # In-flight atomic writes either land whole (and are
            # journaled) or vanish with their staging dirs; queued
            # machines stay journaled un-built, exactly what a later
            # ``--resume`` expects.
            pool.shutdown(wait=True, cancel_futures=True)
            raise
        finally:
            pool.shutdown(wait=True)
        # serialize: the machine and its build metadata to a plain dict;
        # write: the pickle, hashed as it is written, + JSON into the
        # staging dir, renamed
        # (the helper threads that hash beside the write count their CPU
        # into ``write``); collect: of ``write``'s bytes the leaves a
        # pickler took from a transfer the final fit had started, and of
        # its seconds those it waited for one (near none: the link
        # outruns the md5)
        found = [
            (flight.bytes_landed - landed, flight.wait_seconds - waited)
            for flight, (landed, waited) in before.items()
            if flight.bytes_landed > landed
        ]
        fetched = sum(nbytes for nbytes, _ in found)
        waited = sum(seconds for _, seconds in found)
        if found:
            self._record_part(
                "collect", waited, len(found), bytes=fetched, d2h_seconds=waited
            )
        self._record_part(
            "serialize",
            sum(t[0] for t in timings),
            len(timings),
            cpu_seconds=sum(t[2] for t in timings),
        )
        self._record_part(
            "write",
            sum(t[1] for t in timings),
            len(timings),
            cpu_seconds=sum(t[3] + t[4].hash_cpu_seconds for t in timings),
            bytes=sum(t[4].bytes for t in timings),
            bytes_hashed_beside_write=sum(
                t[4].bytes_hashed_beside_write for t in timings
            ),
            bytes_fetched_beside_write=fetched,
            fetch_wait_seconds=waited,
        )
        saved = []
        for (model, machine), exc in zip(to_dump, outcomes):
            if exc is not None:
                # also where a leaf on its way did not land: what the
                # final fit's ``collect`` would have raised, met here
                self._fail(machine.name, exc)
                continue
            saved.append((model, machine))
        return saved

    def _land_flights(self) -> None:
        """The final fits' parameters that are still on their way,
        waited for: a flight holds its fit's block on the device, and
        what runs there next (a later final fit, the sequential
        builder) runs without it, as on the eager schedule. The copies
        are there within a second of their ``collect``."""
        land_flights(flight for _, flight in self._in_flight)

    def _land_parameters(self) -> None:
        """Every leaf of a final fit that is still on its way, taken:
        what ``build()`` returns holds plain ``numpy`` parameters,
        whether a dump took the leaves first (then nothing is waited
        for here) or none was asked for. A leaf that does not land then
        fails its machine alone."""
        for plan, _ in self._in_flight:
            if self._skipped(plan.machine.name):
                continue  # its dump met the error first
            try:
                plan.estimator.params_ = landed(plan.estimator.params_)
            except Exception as exc:
                self._fail(plan.machine.name, exc)
        self._in_flight = []  # and with them the transfers' arrays

    # ------------------------------------------------------------- planning

    def _plan_all(
        self, machines: Optional[Sequence[Machine]] = None
    ) -> Tuple[List[_Plan], List[Machine]]:
        plans, fallbacks = [], []
        for machine in self.machines if machines is None else machines:
            plan = self._plan_machine(machine)
            if plan is None:
                fallbacks.append(machine)
            else:
                plans.append(plan)
        return plans, fallbacks

    @staticmethod
    def _plan_machine(machine: Machine) -> Optional[_Plan]:
        model_obj = serializer.from_definition(machine.model)
        obj = model_obj
        detector = None
        if isinstance(obj, DiffBasedAnomalyDetector):
            detector = obj
            obj = obj.base_estimator
        pipeline = None
        if isinstance(obj, Pipeline):
            pipeline = obj
            obj = obj.steps[-1][1]
        if not isinstance(obj, JaxBaseEstimator):
            return None
        if isinstance(obj, JaxWindowedBaseEstimator) and isinstance(
            detector, DiffBasedKFCVAnomalyDetector
        ):
            # scattered KFold test indices don't map cleanly onto window
            # semantics; keep exact reference behavior via the fallback
            return None
        dataset = (
            machine.dataset
            if isinstance(machine.dataset, GordoBaseDataset)
            else GordoBaseDataset.from_dict(machine.dataset)
        )
        return _Plan(
            machine=machine,
            dataset=dataset,
            model_obj=model_obj,
            detector=detector,
            pipeline=pipeline,
            estimator=obj,
        )

    # ------------------------------------------------------- bucket planning

    def _final_fit_plans(self, plans: List[_Plan]) -> List[_Plan]:
        """The plans whose machines will take the final fit (the member
        set a FleetPlan covers; ``cross_val_only`` machines never final-
        fit, and CV fold members pack live by design — fold models are
        shape-twins of their machine, differing only in weight masks)."""
        return [
            p
            for p in plans
            if not self._skipped(p.machine.name)
            and p.machine.evaluation.get("cv_mode", "full_build").lower()
            != "cross_val_only"
        ]

    def _plan_strategy_name(self) -> str:
        from ..planner import default_strategy

        return self.trainer.plan_strategy or default_strategy()

    @staticmethod
    def _plan_member_proxy(plan: _Plan):
        """A shape-only stand-in for the member ``plan`` will train: the
        packer reads name/spec/sample-count/aliasing, and building REAL
        members here would materialize every machine's shuffled window
        copies during the bucket_plan phase — resident through all of CV
        instead of appearing one final-fit bucket at a time."""
        if plan.windows is None:
            return types.SimpleNamespace(
                name=plan.machine.name,
                spec=plan.spec,
                series=range(len(plan.X_arr)),
                n_windows=len(plan.targets),
            )
        x_token = object()
        return types.SimpleNamespace(
            name=plan.machine.name,
            spec=plan.spec,
            n=len(plan.windows),
            X=x_token,
            y=x_token if plan.windows is plan.targets else object(),
        )

    def _compute_fleet_plan(self, final_plans: List[_Plan], strategy: str):
        """Pack the final-fit members into buckets and assemble the
        deterministic :class:`~gordo_tpu.planner.FleetPlan` artifact."""
        from .. import planner

        by_config: Dict[FitConfig, List[Any]] = {}
        for plan in final_plans:
            by_config.setdefault(plan.fit_config, []).append(
                self._plan_member_proxy(plan)
            )
        cost_model = self.trainer.cost_model()
        buckets_by_config = [
            (
                config,
                planner.plan_train_buckets(
                    members, config, strategy=strategy, cost_model=cost_model
                ),
            )
            for config, members in by_config.items()
        ]
        fingerprint = planner.config_fingerprint(
            [
                self._config_hashes.get(p.machine.name)
                or ModelBuilder.calculate_cache_key(p.machine)
                for p in final_plans
            ]
        )
        return planner.build_plan_doc(
            buckets_by_config,
            strategy,
            cost_model.mesh_shape,
            cost_model.table,
            fingerprint,
        )

    def _prepare_fleet_plan(self, plans: List[_Plan], output_dir: Optional[str]):
        """Fix the final-fit bucket composition BEFORE training: replay
        an externally provided plan (``build-fleet --plan-from``) or
        compute a fresh one, hand it to the trainer, persist it beside
        the artifacts, journal its hash, and export its predictions."""
        from .. import planner

        final_plans = self._final_fit_plans(plans)
        strategy = self._plan_strategy_name()
        if not final_plans:
            return
        with self._phase("bucket_plan"):
            plan = self._external_plan
            if plan is not None:
                expected = planner.config_fingerprint(
                    [
                        self._config_hashes.get(p.machine.name)
                        or ModelBuilder.calculate_cache_key(p.machine)
                        for p in final_plans
                    ]
                )
                recorded = str(plan.doc.get("config_fingerprint", ""))
                if recorded and recorded != expected:
                    # Stale plans stay usable: members it does not know
                    # (or whose data outgrew their pad target) repack
                    # live; warn so the operator re-plans eventually.
                    logger.warning(
                        "FleetPlan %s was computed for a different config "
                        "set (fingerprint %s != %s); unknown members will "
                        "be packed live",
                        plan.plan_hash,
                        recorded,
                        expected,
                    )
                strategy = plan.strategy or strategy
            else:
                plan = self._compute_fleet_plan(final_plans, strategy)
            self.trainer.fleet_plan = plan
            # The strategy must ride with the plan: members the plan
            # does not cover — every CV fold member, late additions —
            # pack live with trainer.plan_strategy, and a packed plan
            # replayed onto a default trainer would otherwise run its
            # whole CV phase naive while journal and gauges say packed.
            self.trainer.plan_strategy = strategy
            totals = plan.totals
            self.recorder.event(
                "fleet_plan",
                plan_hash=plan.plan_hash,
                strategy=strategy,
                replayed=self._external_plan is not None,
                buckets=totals.get("buckets", 0),
                members=totals.get("members", 0),
                compiles=totals.get("compiles", 0),
                predicted_wall_s=totals.get("predicted_wall_s", 0.0),
                padding_waste=totals.get("padding_waste", 0.0),
            )
            if output_dir is not None:
                try:
                    plan.save(os.path.join(output_dir, planner.PLAN_FILE))
                except OSError as exc:
                    logger.warning("FleetPlan not persisted: %r", exc)
            if self._journal is not None:
                # The replay-vs-replan signal --resume acts on: a resumed
                # build whose plan hash changed is REPLANNING the
                # remaining members (config or strategy drift), not
                # replaying the journaled build's shapes.
                previous = self._journal.plan()
                if previous and previous.get("plan_hash") != plan.plan_hash:
                    logger.info(
                        "FleetPlan %s differs from the journaled %s: "
                        "remaining members are replanned%s",
                        plan.plan_hash,
                        previous.get("plan_hash"),
                        ""
                        if self._external_plan is None
                        else " (a different --plan-from was supplied)",
                    )
                self._journal.set_plan(plan.plan_hash, strategy)
            try:
                from ..server.prometheus.metrics import set_fleet_plan_prediction

                set_fleet_plan_prediction(
                    self._project,
                    strategy,
                    float(totals.get("predicted_wall_s", 0.0)),
                    float(totals.get("padding_waste", 0.0)),
                    int(totals.get("compiles", 0)),
                )
            except Exception as exc:  # noqa: BLE001 - metrics are advisory
                logger.debug("Plan prediction gauges not exported: %r", exc)

    def plan_only(self):
        """Plan without training: machine planning + data fetch/stage +
        bucket packing, returning the :class:`~gordo_tpu.planner.FleetPlan`
        the `gordo-tpu plan` CLI renders and ``build-fleet --plan-from``
        replays. Machines that would fall back to the sequential builder
        (unsupported definitions) are not part of a fleet plan."""
        plans, fallbacks = self._plan_all()
        if fallbacks:
            logger.info(
                "%d machine(s) use the sequential builder and are not "
                "fleet-planned: %s",
                len(fallbacks),
                ", ".join(m.name for m in fallbacks[:5]),
            )
        plans = self._load_all_data(plans)
        return self._compute_fleet_plan(
            self._final_fit_plans(plans), self._plan_strategy_name()
        )

    def _export_plan_accuracy(self):
        """Predicted-vs-actual at build end: what the FleetPlan promised
        against the final-fit fit-programs the span listener observed."""
        plan = getattr(self.trainer, "fleet_plan", None)
        if plan is None:
            return
        totals = plan.totals
        actual_seconds = round(float(self._plan_actuals.get("seconds", 0.0)), 3)
        actual_compiles = int(self._plan_actuals.get("compiles", 0))
        # MEASURED utilization actuals beside the predicted numbers:
        # member-axis occupancy of the executed final-fit programs and
        # the max HBM peak Device.memory_stats() reported during the
        # build (None on backends without the stats) — the feedback the
        # ROADMAP's learned-performance-model work trains on.
        padded = int(self._member_actuals.get("padded", 0))
        measured_waste = (
            round(1.0 - self._member_actuals["live"] / padded, 6)
            if padded
            else None
        )
        measured_hbm = self._device_peak_bytes or None
        # the precision feature rides the accuracy record: which compute
        # precisions the planned programs ran at (the cost model's new
        # axis — predicted-vs-actual is only comparable per precision)
        try:
            from ..planner.costmodel import compute_precision

            plan_precisions = sorted(
                {compute_precision(bucket.spec) for bucket in plan.buckets}
            )
        except Exception:  # noqa: BLE001 - a replayed plan may carry
            # serialized bucket entries; the feature is advisory
            plan_precisions = None
        accuracy = dict(
            plan_hash=plan.plan_hash,
            strategy=plan.strategy,
            precisions=plan_precisions,
            predicted_compiles=totals.get("compiles", 0),
            actual_compiles=actual_compiles,
            predicted_wall_s=totals.get("predicted_wall_s", 0.0),
            actual_fit_s=actual_seconds,
            predicted_padding_waste=totals.get("padding_waste", 0.0),
            measured_member_waste=measured_waste,
            predicted_hbm_peak_bytes=totals.get("hbm_peak_bytes", 0),
            measured_hbm_peak_bytes=measured_hbm,
        )
        self.recorder.event("fleet_plan_accuracy", **accuracy)
        self._ledger.record_plan_accuracy(accuracy)
        try:
            from ..server.prometheus.metrics import set_fleet_plan_actuals

            set_fleet_plan_actuals(
                self._project, plan.strategy, actual_seconds, actual_compiles
            )
        except Exception as exc:  # noqa: BLE001 - metrics are advisory
            logger.debug("Plan actuals not exported: %r", exc)

    # ---------------------------------------------------------------- data

    def _load_all_data(self, plans: List[_Plan]) -> List[_Plan]:
        """Fetch + stage every plan; failed machines drop out of the fleet
        (failFast:false) and are recorded in ``build_errors``.

        Fetches retry with exponential backoff (``GORDO_TPU_DATA_RETRIES``
        extra attempts, ``GORDO_TPU_DATA_BACKOFF`` base seconds, optional
        per-machine ``GORDO_TPU_DATA_DEADLINE``) — the in-process analog
        of the reference builder pod's retryStrategy. Deterministic
        config errors (insufficient data, bad tags) are not retried."""
        from ..dataset.exceptions import ConfigException, InsufficientDataError

        cpu_clock = self._cpu_clock()
        # one algorithm under two executors, chosen from what the job
        # shows: a job of many machines hands each fetch's computing to a
        # worker process (dataset/fetch_pool.py has why, and the line);
        # a job of one machine, and a dataset that cannot cross, compute
        # on the thread that waits
        pooled = fetch_pool.wanted(len(plans))

        def load(plan: _Plan):
            start = time.time()
            # one span a machine: their summed seconds over the phase's
            # wall seconds are the fetches that were computing at a time.
            # This thread times it (and annotates it, on the profiler's
            # clock); the main thread, which only waits, writes the span:
            # written from here, among sixteen threads, a span cost the
            # phase about a millisecond. The dataset's own parts ride on
            # it as <part>_s attributes, their CPU seconds as
            # <part>_cpu_seconds. Fetched in a worker, the span's seconds
            # and cpu_seconds are the worker's for the call (this thread's
            # would read its wait for a free worker); fetched here, this
            # thread's, whose CPU clock is read only where the span will
            # be written.
            attrs = {
                "phase": "data_fetch",
                "part": "machine_fetch",
                "machine": plan.machine.name,
            }
            if self.recorder.enabled:
                plan.dataset.fetch_cpu_timed = True  # TimeSeriesDataset._timed
            request = fetch_pool.crossing(plan.dataset) if pooled else None
            #: what the workers gave, over this machine's attempts
            crossed = {"seconds": 0.0, "cpu_seconds": 0.0, "bytes": 0}

            def fetch():
                nonlocal request
                fault_point("data_fetch", plan.machine.name)
                if request is not None:
                    try:
                        fetched = fetch_pool.fetch(request)
                    except fetch_pool.CannotCross as exc:
                        logger.info(
                            "%s is fetched on a thread: %s", plan.machine.name, exc
                        )
                        request = None
                    else:
                        crossed["seconds"] += fetched.seconds
                        crossed["cpu_seconds"] += fetched.cpu_seconds
                        crossed["bytes"] += fetched.nbytes
                        vars(plan.dataset).update(fetched.state)
                        return fetched.X, fetched.y
                return plan.dataset.get_data()

            def note_retry(attempt: int, exc: BaseException):
                # Per-plan counter only: each plan's retries run in ONE
                # pool thread, so this is race-free; the fleet total is
                # summed on the main thread below (incrementing the shared
                # dict from 16 fetch threads would drop updates).
                plan.data_retries += 1
                logger.warning(
                    "Data fetch retry %d for %s after %r",
                    attempt,
                    plan.machine.name,
                    exc,
                )

            began = time.perf_counter()
            cpu_began = cpu_clock()
            try:
                with self._annotation("build_part", attrs):
                    X, y = retry_call(
                        fetch,
                        attempts=1 + max(0, self.data_retries),
                        backoff=self.data_backoff,
                        deadline=self.data_deadline,
                        no_retry=(ConfigException, InsufficientDataError),
                        on_retry=note_retry,
                    )
                attrs["rows"] = len(X)
                for name, seconds in getattr(
                    plan.dataset, "fetch_seconds", {}
                ).items():
                    attrs[f"{name}_s"] = round(seconds, 6)
                for name, seconds in getattr(
                    plan.dataset, "fetch_cpu_seconds", {}
                ).items():
                    attrs[f"{name}_cpu_seconds"] = round(seconds, 6)
            finally:
                attrs["retries"] = plan.data_retries
                if request is not None:
                    seconds = crossed.pop("seconds")
                    attrs.update(worker="process", **crossed)
                else:
                    attrs.update(worker="thread", cpu_seconds=cpu_clock() - cpu_began)
                    seconds = time.perf_counter() - began
                fetched_spans[plan.machine.name] = (seconds, start, attrs)
            plan.query_duration = time.time() - start
            plan.X, plan.y = X, y

        fetched_spans: Dict[str, Tuple[float, float, Dict[str, Any]]] = {}

        def record_fetch(plan: _Plan, outcome):
            if plan.machine.name in fetched_spans:
                seconds, began_wall, attrs = fetched_spans.pop(plan.machine.name)
                self.recorder.record(
                    "build_part", seconds, start=began_wall, **attrs
                )
            return outcome

        with self._phase("data_fetch"):
            # the pool's start, where this job is the process's first over
            # the line: a part of the phase, so the phase's seconds say
            # what the job paid and the part's count which job paid it
            began, cpu_began = time.perf_counter(), fetch_pool.cpu_seconds()
            started = (
                fetch_pool.ensure(
                    fetch_pool.workers_for(self.data_workers, len(plans))
                )
                if pooled
                else 0
            )
            self._record_part(
                "pool_start",
                time.perf_counter() - began,
                started,
                cpu_seconds=fetch_pool.cpu_seconds() - cpu_began,
            )
            pool = concurrent.futures.ThreadPoolExecutor(self.data_workers)
            try:
                outcomes = [
                    record_fetch(plan, outcome)
                    for plan, outcome in zip(
                        plans, pool.map(lambda p: _try_call(load, p), plans)
                    )
                ]
            except (KeyboardInterrupt, SystemExit):
                # Same contract as _dump_all: a shutdown signal must not
                # wait on thousands of queued fetches (and their backoff
                # ladders) before the process dies.
                pool.shutdown(wait=True, cancel_futures=True)
                raise
            finally:
                pool.shutdown(wait=True)
        self.robustness["data_fetch_retries"] += sum(
            p.data_retries for p in plans
        )
        surviving = []
        with self._phase("stage"):
            for plan, exc in zip(plans, outcomes):
                if exc is not None:
                    self._fail(plan.machine.name, exc)
                    continue
                try:
                    self._stage_arrays(plan)
                except Exception as stage_exc:
                    self._fail(plan.machine.name, stage_exc)
                    continue
                surviving.append(plan)
        if self._journal is not None:
            for plan in surviving:
                self._journal.record(plan.machine.name, "data_loaded", flush=False)
            self._journal.flush()
        return surviving

    @staticmethod
    def _stage_arrays(plan: _Plan):
        """Fit host transformers, window if LSTM, resolve spec + fit config."""
        X_arr = np.asarray(plan.X.to_numpy(), np.float32)
        y_arr = np.asarray(plan.y.to_numpy(), np.float32)
        if plan.pipeline is not None and len(plan.pipeline.steps) > 1:
            transformed = plan.X
            for _, transformer in plan.pipeline.steps[:-1]:
                transformed = transformer.fit_transform(transformed, plan.y)
            X_arr = np.asarray(
                getattr(transformed, "to_numpy", lambda: transformed)(), np.float32
            )
        plan.X_arr, plan.y_arr = X_arr, y_arr

        est = plan.estimator
        est.kwargs.update(
            {"n_features": X_arr.shape[1], "n_features_out": y_arr.shape[1]}
        )
        fit_kwargs, factory_kwargs = split_fit_kwargs(est.sk_params)
        if isinstance(est, JaxWindowedBaseEstimator):
            lookback, lookahead = est.lookback_window, est.lookahead
            plan.offset = calc_model_offset(lookback, lookahead)
            plan.windows = None  # on-device windowing; series stays resident
            plan.targets = window_targets(y_arr, lookback, lookahead)
            plan.n_windows = len(plan.targets)
            fit_kwargs["shuffle"] = False
        else:
            plan.offset = 0
            # Pure-AE builds train y == X; aliasing lets the fleet stacker
            # stage (and transfer to device) the block once. The content
            # check is a host-side memcmp — orders of magnitude cheaper
            # than the duplicate copy and device transfer it avoids.
            if (
                X_arr is not y_arr
                and X_arr.shape == y_arr.shape
                and np.array_equal(X_arr, y_arr)
            ):
                y_arr = X_arr
            plan.windows, plan.targets = X_arr, y_arr
            plan.n_windows = len(X_arr)
        if plan.detector is not None and getattr(plan.detector, "shuffle", False):
            # Sequential DiffBased.fit row-shuffles before training
            # (diff.py: sklearn_shuffle(..., random_state=0)); mirror it as
            # a stored permutation applied to training members only —
            # scoring always runs on chronological windows.
            from sklearn.utils import shuffle as sklearn_shuffle

            plan.shuffle_perm = sklearn_shuffle(
                np.arange(plan.n_windows), random_state=0
            )
        plan.spec = est._build_spec(factory_kwargs)
        config, host_callbacks = fit_config_from_kwargs(fit_kwargs)
        if host_callbacks:
            raise FleetBuildError(
                f"{plan.machine.name}: custom host callbacks are not supported "
                "in fleet builds"
            )
        plan.fit_config = config
        plan.seed = int(fit_kwargs.get("seed", 42))

    # ------------------------------------------------------------------- CV

    def _run_cross_validation(self, plans: List[_Plan]):
        """
        Per-fold fleet training. Fold boundaries become train-weight masks
        over window indices; every (spec, config) bucket trains all its
        machines' folds together.
        """
        start = time.time()
        fold_state: Dict[str, Dict[str, Any]] = {p.machine.name: {} for p in plans}

        with self._phase("cv_split"):
            per_plan_folds, grouped = self._split_folds(plans)
        for config, (members, fold_items) in grouped.items():
            live_items = [
                (plan, fold_idx)
                for plan, fold_idx in fold_items
                if not self._skipped(plan.machine.name)
            ]
            live_members = [
                m
                for m, (plan, _) in zip(members, fold_items)
                if not self._skipped(plan.machine.name)
            ]
            # Chunk by staged bytes: n_machines × n_folds members in ONE
            # program is the fast path, but an unbounded super-bucket
            # could out-size HBM on big fleets. Chunks preserve the
            # fold-major order (threshold accumulators are last-fold-wins
            # per machine).
            for chunk_members, chunk_items in _chunk_by_bytes(
                live_members, live_items, _cv_chunk_bytes()
            ):
                self._train_and_score_folds(
                    chunk_members, chunk_items, config, per_plan_folds, fold_state
                )

        with self._phase("cv_finalize"):
            for plan in plans:
                if self._skipped(plan.machine.name):
                    continue
                try:
                    self._finalize_cv(plan, fold_state[plan.machine.name])
                except Exception as exc:
                    self._fail(plan.machine.name, exc)
                    continue
                plan.cv_duration = time.time() - start

    def _split_folds(self, plans: List[_Plan]):
        """Every plan's CV splits and its fold members, grouped by fit
        config: ``(per_plan_folds, {config: (members, [(plan, fold)])})``."""
        max_folds = 0
        per_plan_folds: Dict[str, List[Tuple[np.ndarray, np.ndarray]]] = {}
        for plan in plans:
            try:
                splits = self._cv_splits(plan)
                plan.cv_splits = self._split_metadata(plan, splits)
            except Exception as exc:
                self._fail(plan.machine.name, exc)
                continue
            per_plan_folds[plan.machine.name] = splits
            max_folds = max(max_folds, len(splits))

        # Every machine's EVERY fold goes into one member list per fit
        # config: fold models of the same (spec, shape) differ only in
        # their train-weight masks, so they join a single vmapped bucket
        # and the whole CV trains as ONE device program per architecture
        # group — one dispatch and one result fetch where a fold-major
        # loop paid max_folds of each (SURVEY §7: "fold = extra batch
        # axis"). Fold-major append order keeps per-machine fold order for
        # the threshold accumulators downstream.
        grouped: Dict[
            FitConfig, Tuple[List[Any], List[Tuple[_Plan, int]]]
        ] = {}
        for fold_idx in range(max_folds):
            for plan in plans:
                if self._skipped(plan.machine.name):
                    continue
                splits = per_plan_folds[plan.machine.name]
                if fold_idx >= len(splits):
                    continue
                train_idx, _ = splits[fold_idx]
                try:
                    weights = self._window_train_weights(plan, train_idx)
                    member = self._make_member(
                        plan,
                        weights,
                        seed=plan.seed + 1000 * (fold_idx + 1),
                        name=_fold_member_name(plan.machine.name, fold_idx),
                    )
                except Exception as exc:
                    self._fail(plan.machine.name, exc)
                    continue
                members, fold_items = grouped.setdefault(plan.fit_config, ([], []))
                members.append(member)
                fold_items.append((plan, fold_idx))
        return per_plan_folds, grouped

    @staticmethod
    def _make_member(
        plan: _Plan,
        train_weights: Optional[np.ndarray],
        seed: int,
        name: Optional[str] = None,
    ):
        """Training member with the detector-level shuffle applied.
        ``name`` overrides the member name (CV submits every fold of a
        machine into one bucket, so fold members need distinct names)."""
        perm = plan.shuffle_perm
        name = name or plan.machine.name
        if plan.windows is None:
            # Windowed (LSTM) path: ship the raw series; the shuffle becomes
            # the order map and weights move into virtual (shuffled) space.
            if perm is not None and train_weights is not None:
                train_weights = train_weights[perm]
            return WindowedFleetMember(
                name=name,
                spec=plan.spec,
                series=plan.X_arr,
                targets=plan.targets,
                order=perm,
                train_weights=train_weights,
                seed=seed,
            )
        if perm is None:
            X, y = plan.windows, plan.targets
        else:
            cached = getattr(plan, "_shuffled_windows_cache", None)
            if cached is None:
                X = plan.windows[perm]
                # Preserve y-is-X aliasing through the permutation gather.
                y = X if plan.targets is plan.windows else plan.targets[perm]
                plan._shuffled_windows_cache = (X, y)
            else:
                X, y = cached
            if train_weights is not None:
                train_weights = train_weights[perm]
        return FleetMember(
            name=name,
            spec=plan.spec,
            X=X,
            y=y,
            train_weights=train_weights,
            seed=seed,
        )

    @staticmethod
    def _cv_for(plan: _Plan):
        if isinstance(plan.detector, DiffBasedKFCVAnomalyDetector):
            return KFold(n_splits=5, shuffle=True, random_state=0)
        cv_def = plan.machine.evaluation.get("cv")
        if cv_def:
            return serializer.from_definition(cv_def)
        return TimeSeriesSplit(n_splits=3)

    def _cv_splits(self, plan: _Plan) -> List[Tuple[np.ndarray, np.ndarray]]:
        """The plan's CV folds as ``(train rows, test rows)``. A windowed
        model scores the windows that lie inside a fold's test rows, so a
        test span no longer than the model's offset holds none: where NO
        fold of the history is long enough (a lookback of days over a
        history of days), the folds are cut over the rows that are some
        window's target instead (rows ``offset..n-1``), and a test
        window reads its context from the rows before its fold, as the
        served model does. Histories with room for row folds keep them."""
        cv = self._cv_for(plan)
        splits = list(cv.split(plan.X_arr))
        if plan.offset and all(len(test) <= plan.offset for _, test in splits):
            rows = np.arange(plan.offset, len(plan.X_arr))
            splits = [(rows[train], rows[test]) for train, test in cv.split(rows)]
            plan.target_folds = True
            logger.warning(
                "%s: no CV fold of its %d rows is longer than the model's "
                "offset of %d rows, so no fold would hold a window to score; "
                "its folds are cut over the %d target rows instead "
                "(cross_validation.splits says folds-over: target-rows)",
                plan.machine.name, len(plan.X_arr), plan.offset, len(rows),
            )
        return splits

    def _window_train_weights(self, plan: _Plan, train_idx: np.ndarray) -> np.ndarray:
        """Row-index fold → window-index training mask."""
        n_windows = plan.n_windows
        weights = np.zeros(n_windows, np.float32)
        if plan.offset == 0:
            weights[train_idx[train_idx < n_windows]] = 1.0
        else:
            # windowed models need contiguous [0, b) folds (TimeSeriesSplit);
            # scattered folds have no clean window mapping
            if len(train_idx) != int(train_idx[-1]) - int(train_idx[0]) + 1:
                raise FleetBuildError(
                    f"{plan.machine.name}: non-contiguous CV folds are not "
                    "supported for windowed (LSTM) models in fleet builds"
                )
            boundary = int(train_idx[-1]) + 1
            weights[: max(boundary - plan.offset, 0)] = 1.0
        return weights

    def _test_window_rows(
        self, plan: _Plan, rows: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Fold-test row indices → (window indices to predict, target rows)
        honoring the window offset. Only these windows are staged and
        forwarded — a fold's test split is ~1/(n_folds+1) of the series,
        so predicting all windows would move ~4× the data both ways."""
        if plan.offset == 0:
            rows = rows[rows < plan.n_windows]
            return rows, rows
        if plan.target_folds:
            # the fold is over target rows: the window that predicts row r
            return rows - plan.offset, rows
        # contiguous test [b, c) → window indices [b, c - offset)
        b, c = int(rows[0]), int(rows[-1]) + 1
        window_idx = np.arange(b, max(c - plan.offset, b))
        window_idx = window_idx[window_idx < plan.n_windows]
        return window_idx, window_idx + plan.offset

    def _train_and_score_folds(
        self, members, fold_items, config, per_plan_folds, fold_state
    ):
        """
        Train one chunk of fold members and score it. A failing chunk is
        split in half and retried (down to single members), so a bad
        machine — or a chunk that out-sizes device memory despite the
        byte budget — degrades to per-member isolation instead of taking
        every machine of the fit config down.
        """
        # A machine that failed in an earlier chunk of this config must not
        # waste device time training its remaining folds here (its
        # accumulators are dead — _finalize_cv skips failed machines).
        live = [
            i
            for i, (plan, _) in enumerate(fold_items)
            if not self._skipped(plan.machine.name)
        ]
        if len(live) != len(fold_items):
            members = [members[i] for i in live]
            fold_items = [fold_items[i] for i in live]
        if not members:
            return
        try:
            with self._phase("cv_train"):
                # a fold model exists to predict its test rows: its
                # parameters stay on the device, where the group's
                # predict program takes them (_score_folds), and go with
                # this call's results
                fold_results = self.trainer.train(
                    members, config, params_on_device=True
                )
        except Exception as exc:
            # CV chunks split on ANY exception — unlike _train_final_group,
            # which gates on device errors. The asymmetry is deliberate:
            # CV's any-exception halving is the pinned bad-machine
            # isolation contract (a member-specific host error — bad
            # shapes, poisoned data — fails only its machine, at
            # O(N log N) retrain cost in the worst chunk-wide case),
            # while the final fit keeps its original fail-the-group
            # semantics for deterministic host errors.
            if len(members) == 1:
                plan = fold_items[0][0]
                if is_device_error(exc):
                    self._degrade(plan, exc)
                else:
                    self._fail(plan.machine.name, exc)
                return
            logger.warning(
                "CV chunk of %d fold-members failed (%s); splitting",
                len(members),
                exc,
            )
            fold_results = None
        if fold_results is None:
            # outside the handler: the failed call's traceback, and the
            # device arrays its frames hold, are gone before a half trains
            self.robustness["bucket_bisects"] += 1
            for plan, _ in fold_items:
                plan.bucket_bisects += 1
            mid = len(members) // 2
            self._train_and_score_folds(
                members[:mid], fold_items[:mid], config,
                per_plan_folds, fold_state,
            )
            self._train_and_score_folds(
                members[mid:], fold_items[mid:], config,
                per_plan_folds, fold_state,
            )
            return
        # The trainer's own bucket bisection reports members that failed
        # in ISOLATION as error-results instead of raising: degrade those
        # machines to the sequential path first, then score only fold
        # results of machines still on the fleet path (a degraded
        # machine's OTHER folds in this chunk are dead too).
        for (plan, _), result in zip(fold_items, fold_results):
            if result.error is None or self._skipped(plan.machine.name):
                continue
            if is_device_error(result.error):
                self._degrade(plan, result.error)
            else:
                self._fail(plan.machine.name, result.error)
        scorable_items, scorable_results = [], []
        for (plan, fold_idx), result in zip(fold_items, fold_results):
            if result.error is not None or self._skipped(plan.machine.name):
                continue
            plan.fleet_retries += result.retries
            self.robustness["fleet_retries"] += result.retries
            scorable_items.append((plan, fold_idx))
            scorable_results.append(result)
        if not scorable_items:
            return
        try:
            self._score_folds(
                scorable_items, scorable_results, per_plan_folds, fold_state
            )
        except Exception as exc:
            # the error is kept with its machines, and its traceback
            # keeps the frames it came through (this one too, as their
            # caller), whose locals hold the fold models' parameters on
            # the device. Those go with this chunk, not with the build:
            # the frames below are cleared, and this one lets go by name
            traceback.clear_frames(exc.__traceback__)
            del fold_results, scorable_results, result
            for plan, _ in scorable_items:
                self._fail(plan.machine.name, exc)

    def _score_folds(self, fold_items, fold_results, per_plan_folds, fold_state):
        """
        Score trained fold models: ``fold_items`` is ``[(plan, fold_idx)]``
        in fold-major order (every fold of every machine of one fit
        config). One batched forward per (spec, geometry, detector window)
        group — all folds of all machines of an architecture predict in
        one dispatch, and the same program scores them where its
        predictions are (``parallel/fleet.fold_scores``), for every
        machine whose evaluation it can express (:meth:`_device_scoring`);
        the predictions of the others come to the host and are scored
        there, a machine-fold at a time.
        Windowed (LSTM) plans predict through the on-device window-gather
        scan; dense plans through the stacked forward.
        """
        by_name = {r.name: r for r in fold_results}
        groups: Dict[Tuple, List[Tuple[_Plan, int]]] = {}
        for plan, fold_idx in fold_items:
            geometry = (
                ("windowed",) if plan.windows is None else plan.windows.shape[1:]
            )
            window = getattr(plan.detector, "window", None)
            groups.setdefault((plan.spec, geometry, window), []).append(
                (plan, fold_idx)
            )
        for (spec, geometry, window), group in groups.items():
            # the group's staging blocks (parallel/host_blocks.py), a
            # lease a ``stack`` part, are held until its predict program
            # has answered: its scores, or its predictions, are then on
            # the host, and what stays on the device is the program's own
            # output
            with host_blocks.lease() as scoring_blocks, host_blocks.lease() as blocks:
                with self._phase("cv_score"), self._part("stack") as span:
                    # per item: (train_rows, window_idx, target_rows)
                    fold_rows = []
                    for plan, fold_idx in group:
                        train_rows, test_rows = per_plan_folds[plan.machine.name][
                            fold_idx
                        ]
                        window_idx, target_rows = self._test_window_rows(
                            plan, test_rows
                        )
                        fold_rows.append((train_rows, window_idx, target_rows))
                    scoring = self._fold_scoring(
                        group, fold_rows, window, scoring_blocks
                    )
                    if scoring is not None and span.recording:
                        span.set(
                            bytes=scoring.nbytes,
                            bytes_reused=scoring_blocks.bytes_reused,
                        )
                with self._phase("cv_predict"):
                    with self._part("stack"):
                        # on the device: the bucket's own block where the
                        # group is the bucket, else a gather of its rows
                        stacked = self.trainer.device_params(
                            spec,
                            [
                                by_name[_fold_member_name(p.machine.name, k)]
                                for p, k in group
                            ],
                        )
                    if geometry == ("windowed",):
                        predicted = self._predict_windowed_group(
                            spec,
                            stacked,
                            [p for p, _ in group],
                            [wi for _, wi, _ in fold_rows],
                            blocks,
                            scoring,
                        )
                    else:
                        with self._part("stack") as span:
                            X = blocks.stacked(
                                (len(group), max(len(wi) for _, wi, _ in fold_rows))
                                + group[0][0].windows.shape[1:],
                                (
                                    p.windows[wi]
                                    for (p, _), (_, wi, _) in zip(group, fold_rows)
                                ),
                            )
                            span.set(
                                bytes=X.nbytes, bytes_reused=blocks.bytes_reused
                            )
                        predicted = self.trainer.predict_bucket(
                            spec, stacked, X, scoring=scoring
                        )
            with self._phase("cv_score"):
                self._adopt_fold_scores(group, fold_rows, scoring, predicted, fold_state)

    def _fold_scoring(self, group, fold_rows, window, blocks) -> Optional[FoldScoring]:
        """What the group's predict program needs to score its fold
        models (``FoldScoring``): targets, rows and scalers of every
        member :meth:`_device_scoring` takes; a member the host scores
        counts no rows there. ``None`` where the host scores them all.
        ``y_true`` is a block of the lease ``blocks``."""
        metric_scalers = [self._device_scoring(plan) for plan, _ in group]
        if all(scaler is None for scaler in metric_scalers):
            return None
        tags = group[0][0].y_arr.shape[1]
        n_max = max(len(target_rows) for _, _, target_rows in fold_rows)
        scoring = FoldScoring(
            y_true=blocks.stacked(
                (len(group), n_max, tags),
                (
                    _take_rows(plan.y_arr, target_rows)
                    if scaler is not None
                    else plan.y_arr[:0]  # the host scores it: no row
                    for (plan, _), (_, _, target_rows), scaler in zip(
                        group, fold_rows, metric_scalers
                    )
                ),
            ),
            rows=np.zeros(len(group), np.int32),
            metric_scaler=np.ones((len(group), 4, tags), np.float32),
            error_scaler=np.ones((len(group), 4, tags), np.float32),
            window=window,
        )
        for i, ((plan, _), (train_rows, _, target_rows)) in enumerate(
            zip(group, fold_rows)
        ):
            if metric_scalers[i] is None:
                continue
            scoring.rows[i] = len(target_rows)
            scoring.metric_scaler[i] = metric_scalers[i]
            # the fold model's scaler is fit on the fold-TRAIN targets, as
            # in _accumulate_thresholds
            scoring.error_scaler[i] = (
                _fold_scaler_parameters(
                    plan.detector.scaler, _take_rows(plan.y_arr, train_rows)
                )
                if plan.detector is not None
                else metric_scalers[i]
            )
        return scoring

    def _adopt_fold_scores(self, group, fold_rows, scoring, predicted, fold_state):
        """A group's fold scores into ``plan.cv_scores`` and the
        machines' ``fold_state``: what the predict program scored
        (``predicted`` is then its predictions on the device and its
        scores), and, through the host's own arithmetic, every member it
        did not: one :meth:`_device_scoring` leaves to the host, and one
        the program found ``unscorable``, so that the host's code says
        what is wrong with it. Parts: ``device_scores`` counts the
        machine-folds scored on the device, ``metric_scores`` and
        ``thresholds`` those scored here."""
        clock, cpu_clock = time.perf_counter, self._cpu_clock()
        began, cpu_began = clock(), cpu_clock()
        on_host = list(range(len(group)))
        predictions = predicted
        if scoring is not None:
            predictions, scores = predicted
            on_host = [
                i for i in on_host if not scoring.rows[i] or scores["unscorable"][i]
            ]
            metrics = {
                metric: (scores[metric.__name__], scores[metric.__name__].mean(axis=-1))
                for metric in DEVICE_METRICS
            }
            thresholds = ["aggregate_threshold", "feature_thresholds"]
            if scoring.window is not None:
                thresholds += ["smooth_aggregate_threshold", "smooth_feature_thresholds"]
            left_to_host = set(on_host)
            for i, (plan, fold_idx) in enumerate(group):
                if i in left_to_host:
                    continue
                for metric in self._scoring_setup(plan)[0]:
                    per_tag, aggregate = metrics[metric]
                    self._record_metric_scores(
                        plan, fold_idx, metric, per_tag[i], aggregate[i]
                    )
                if plan.detector is not None:
                    # float64 as the host's _rolling_min_max hands them on
                    # (of float32 minima and maxima: the same numbers)
                    self._record_thresholds(
                        plan, fold_idx, fold_state[plan.machine.name],
                        int(scoring.rows[i]),
                        *(scores[name][i].astype(np.float64) for name in thresholds),
                    )
            if on_host:
                with self._part("collect") as span:
                    predictions = fetch_members(predictions, on_host)
                    span.set(bytes=predictions.nbytes)
            else:
                # they stay where they are (across processes not even
                # addressable from here): nothing below reads one
                predictions = ()
        self._record_part(
            "device_scores",
            clock() - began,
            len(group) - len(on_host),
            cpu_seconds=cpu_clock() - cpu_began,
        )
        # per machine-fold work, timed here (both clocks) and recorded as
        # two spans a group: a span each would be 2 x members lines
        metric_seconds = threshold_seconds = 0.0
        metric_cpu = threshold_cpu = 0.0
        for i, y_pred in zip(on_host, predictions):
            plan, fold_idx = group[i]
            train_rows, window_idx, target_rows = fold_rows[i]
            y_true = plan.y_arr[target_rows]
            y_pred = y_pred[: len(window_idx)]
            began, cpu_began = clock(), cpu_clock()
            self._accumulate_metric_scores(plan, y_true, y_pred, fold_idx)
            scored, cpu_scored = clock(), cpu_clock()
            metric_seconds += scored - began
            metric_cpu += cpu_scored - cpu_began
            if plan.detector is not None:
                self._accumulate_thresholds(
                    plan, y_true, y_pred, fold_idx, fold_state[plan.machine.name],
                    y_train=plan.y_arr[train_rows],
                    test_rows=target_rows,
                )
                threshold_seconds += clock() - scored
                threshold_cpu += cpu_clock() - cpu_scored
        self._record_part(
            "metric_scores", metric_seconds, len(on_host), cpu_seconds=metric_cpu
        )
        self._record_part(
            "thresholds", threshold_seconds, len(on_host), cpu_seconds=threshold_cpu
        )

    def _predict_windowed_group(
        self,
        spec,
        stacked,
        group: List[_Plan],
        window_idx: List[np.ndarray],
        blocks: host_blocks.Lease,
        scoring: Optional[FoldScoring] = None,
    ):
        """Predictions for windowed plans, windows gathered on device (scan
        over ``planner.packing.windowed_scoring_batch`` windows a step), model-axis sharded over the
        trainer's mesh like the dense scoring path. ``window_idx`` gives
        each plan's window positions to predict (the fold-test windows);
        ``blocks`` the lease the series and the positions are stacked in;
        ``scoring`` as ``FleetTrainer.predict_bucket`` takes it."""
        with self._part("stack") as span:
            series = blocks.stacked(
                (len(group), max(len(p.X_arr) for p in group), group[0].X_arr.shape[1]),
                (p.X_arr for p in group),
            )
            order = blocks.stacked(
                (len(group), max(len(o) for o in window_idx)), window_idx, np.int32
            )
            span.set(
                bytes=series.nbytes + order.nbytes, bytes_reused=blocks.bytes_reused
            )
        return self.trainer.predict_windowed_bucket(
            spec, stacked, series, order,
            batch_size=windowed_scoring_batch(spec), scoring=scoring,
        )

    @staticmethod
    def _scoring_setup(plan: _Plan):
        """Resolved metrics + the fitted scoring scaler, cached per plan —
        re-deriving them per fold was a measured CV hot spot (63ms per
        machine-fold at 20 tags on CPU)."""
        cached = getattr(plan, "_scoring_setup_cache", None)
        if cached is not None:
            return cached
        evaluation = plan.machine.evaluation
        metrics_list = ModelBuilder.metrics_from_list(evaluation.get("metrics"))
        scaler_def = evaluation.get("scoring_scaler")
        scaler = None
        if scaler_def:
            scaler = (
                serializer.from_definition(scaler_def)
                if isinstance(scaler_def, (str, dict))
                else scaler_def
            )
            # The scoring scaler always fits the FULL target frame (not
            # the fold), so one fit serves every fold.
            scaler = sklearn_clone(scaler).fit(plan.y_arr)
        plan._scoring_setup_cache = (metrics_list, scaler)
        return plan._scoring_setup_cache

    @classmethod
    def _device_scoring(cls, plan: _Plan) -> Optional[np.ndarray]:
        """Whether the group's predict program can score this machine's
        folds, read off what the plan holds: every metric is one of the
        default four themselves (a user's callable cannot be traced), the
        scoring scaler and the detector's are per-tag affine
        (:func:`_per_tag_affine`), the detector is none or a
        ``DiffBasedAnomalyDetector`` itself (the KFCV detector stitches
        scattered test rows before it smooths). Then the scoring
        scaler's parameters, else ``None``: the host scores the machine."""
        cached = plan._device_scoring_cache
        if cached is None:
            metrics_list, scaler = cls._scoring_setup(plan)
            detector = plan.detector
            able = (
                all(any(metric is known for known in DEVICE_METRICS) for metric in metrics_list)
                and _per_tag_affine(scaler)
                and (
                    detector is None
                    or (
                        type(detector) is DiffBasedAnomalyDetector
                        and _per_tag_affine(detector.scaler)
                    )
                )
            )
            cached = plan._device_scoring_cache = (
                _scaler_parameters(scaler, plan.y_arr.shape[1]) if able else None,
            )
        return cached[0]

    @staticmethod
    def _record_metric_scores(plan, fold_idx, metric, per_tag, aggregate):
        """One metric of one fold into ``plan.cv_scores``: a value a tag
        and the aggregate over the tags."""
        name = metric.__name__.replace("_", "-")
        fold_key = f"fold-{fold_idx + 1}"
        for tag, value in zip(plan.y.columns, per_tag):
            key = f"{name}-{str(tag).replace(' ', '-')}"
            plan.cv_scores.setdefault(key, {})[fold_key] = float(value)
        plan.cv_scores.setdefault(name, {})[fold_key] = float(aggregate)

    def _accumulate_metric_scores(self, plan, y_true, y_pred, fold_idx):
        metrics_list, scaler = self._scoring_setup(plan)
        if scaler is not None:
            y_true_s, y_pred_s = scaler.transform(y_true), scaler.transform(y_pred)
        else:
            y_true_s, y_pred_s = y_true, y_pred
        tags = range(y_true.shape[1])
        for metric in metrics_list:
            per_tag = None
            vectorized = False
            try:
                # One vectorized call for all tags (sklearn regression
                # metrics support multioutput) instead of a Python loop of
                # per-column calls — ~20× fewer sklearn invocations.
                per_tag = np.asarray(
                    metric(y_true_s, y_pred_s, multioutput="raw_values")
                )
                vectorized = per_tag.shape == (len(tags),)
            except TypeError:
                pass
            if not vectorized:
                # Custom metrics may lack multioutput support — or swallow
                # the kwarg and return something else entirely; only trust
                # a correctly-shaped per-tag vector.
                per_tag = np.asarray(
                    [metric(y_true_s[:, i], y_pred_s[:, i]) for i in tags]
                )
            # sklearn regression metrics aggregate with multioutput=
            # "uniform_average" — the plain mean of the raw_values vector —
            # so when the vectorized call succeeded the aggregate is free.
            self._record_metric_scores(
                plan, fold_idx, metric, per_tag,
                np.mean(per_tag) if vectorized else metric(y_true_s, y_pred_s),
            )

    @staticmethod
    def _rolling_min_max(values: np.ndarray, window: int):
        """
        ``pd.rolling(window).min().max()`` in vectorized numpy — the
        reference's threshold statistic (diff.py: max over time of the
        min over each ``window``-long run), ~20× cheaper than building a
        pandas object per (machine, fold). Matches pandas NaN semantics:
        windows containing NaN (min_periods=window counts valid values)
        are skipped by the NaN-aware max; no complete window → NaN.
        Works on ``[n]`` (returns float) and ``[n, k]`` (returns ``[k]``).
        """
        values = np.asarray(values, np.float64)
        if len(values) < window:
            return (
                np.nan if values.ndim == 1 else np.full(values.shape[1], np.nan)
            )
        mins = np.lib.stride_tricks.sliding_window_view(
            values, window, axis=0
        ).min(axis=-1)
        if np.isnan(mins).any():
            import warnings

            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)  # all-NaN slice
                out = np.nanmax(mins, axis=0)
        else:
            out = mins.max(axis=0)
        return float(out) if values.ndim == 1 else out

    @classmethod
    def _accumulate_thresholds(
        cls, plan, y_true, y_pred, fold_idx, state, y_train=None, test_rows=None
    ):
        detector = plan.detector
        # The fold model's scaler is fit on the fold-TRAIN targets
        # (reference: DiffBased.fit → scaler.fit(y) on the train split,
        # then _scaled_mse_per_timestep transforms the test rows with it)
        scaler = sklearn_clone(detector.scaler).fit(
            y_train if y_train is not None else y_true
        )
        scaled_mse = np.mean(
            np.square(scaler.transform(y_pred) - scaler.transform(y_true)), axis=1
        )
        abs_err = np.abs(y_true - y_pred)
        if isinstance(detector, DiffBasedKFCVAnomalyDetector):
            # KFold test rows are scattered; keep them with their original
            # row positions so errors can be re-stitched chronologically
            # before window smoothing (the sequential path smooths in time
            # order — diff.py KFCV cross_validate).
            state.setdefault("kfcv_parts", []).append(
                (np.asarray(test_rows), scaled_mse, abs_err)
            )
        else:
            run = threshold_run(len(scaled_mse))
            cls._record_thresholds(
                plan, fold_idx, state, len(scaled_mse),
                cls._rolling_min_max(scaled_mse, run),
                cls._rolling_min_max(abs_err, run),
                *(
                    (
                        cls._rolling_min_max(scaled_mse, detector.window),
                        cls._rolling_min_max(abs_err, detector.window),
                    )
                    if detector.window is not None
                    else ()
                ),
            )

    @staticmethod
    def _record_thresholds(
        plan, fold_idx, state, rows, aggregate, features,
        smooth_aggregate=None, smooth_features=None,
    ):
        """One fold's thresholds (a float and a value a tag, of ``rows``
        scored rows; the ``smooth_*`` pair where the detector has a
        window) into the machine's ``fold_state``, as ``_finalize_cv``
        reads them."""
        fold = f"fold-{fold_idx}"
        run = state["threshold_run_rows"] = threshold_run(rows)
        if run < THRESHOLD_RUN:
            logger.warning(
                "%s: fold %d scored %d rows, fewer than the %d a threshold's "
                "run takes; its thresholds are the minimum over those rows",
                plan.machine.name, fold_idx, rows, THRESHOLD_RUN,
            )
        state["aggregate_threshold"] = float(aggregate)
        state.setdefault("feature_folds", {})[fold] = pd.Series(features, name=fold)
        state.setdefault("agg_folds", {})[fold] = float(aggregate)
        if smooth_features is not None:
            smooth_tags = pd.Series(smooth_features, name=fold)
            state["smooth_aggregate_threshold"] = float(smooth_aggregate)
            state["smooth_feature_thresholds"] = smooth_tags
            state.setdefault("smooth_feature_folds", {})[fold] = smooth_tags
            state.setdefault("smooth_agg_folds", {})[fold] = float(smooth_aggregate)

    def _finalize_cv(self, plan: _Plan, state: Dict[str, Any]):
        # fold-stat summary rows (fold-mean/std/min/max) like the reference
        for key, folds in plan.cv_scores.items():
            values = np.array(
                [v for k, v in folds.items() if k.startswith("fold-")]
            )
            folds.update(
                {
                    "fold-mean": float(values.mean()),
                    "fold-std": float(values.std()),
                    "fold-max": float(values.max()),
                    "fold-min": float(values.min()),
                }
            )
        detector = plan.detector
        if detector is None:
            return
        feature_names = [str(c) for c in plan.y.columns]
        if isinstance(detector, DiffBasedKFCVAnomalyDetector):
            # Stitch fold errors back into chronological (row) order before
            # rolling-window smoothing
            n = len(plan.y_arr)
            mse_full = np.full(n, np.nan)
            abs_full = np.full((n, len(feature_names)), np.nan)
            for rows, mse_part, abs_part in state["kfcv_parts"]:
                mse_full[rows] = mse_part
                abs_full[rows] = abs_part
            detector.aggregate_threshold_ = float(
                detector._calculate_threshold(pd.Series(mse_full))
            )
            thresholds = detector._calculate_threshold(
                pd.DataFrame(abs_full, columns=feature_names)
            )
            detector.feature_thresholds_ = thresholds
        elif "feature_folds" in state:
            folds_df = pd.DataFrame(state["feature_folds"]).T
            folds_df.columns = feature_names
            detector.feature_thresholds_per_fold_ = folds_df
            detector.aggregate_thresholds_per_fold_ = state["agg_folds"]
            detector.threshold_run_rows_ = state["threshold_run_rows"]
            last = folds_df.iloc[-1]
            last.name = folds_df.index[-1]
            detector.feature_thresholds_ = last
            detector.aggregate_threshold_ = state["aggregate_threshold"]
            detector.smooth_aggregate_threshold_ = state.get(
                "smooth_aggregate_threshold"
            )
            smooth = state.get("smooth_feature_thresholds")
            if smooth is not None:
                smooth = smooth.copy()
                smooth.index = feature_names
            detector.smooth_feature_thresholds_ = smooth
            if "smooth_feature_folds" in state:
                smooth_df = pd.DataFrame(state["smooth_feature_folds"]).T
                smooth_df.columns = feature_names
                detector.smooth_feature_thresholds_per_fold_ = smooth_df
                detector.smooth_aggregate_thresholds_per_fold_ = state[
                    "smooth_agg_folds"
                ]

    # ------------------------------------------------------------ final fit

    def _run_final_fit(self, plans: List[_Plan]):
        if not plans:
            return
        start = time.time()
        # group per distinct fit config to keep train() calls homogeneous
        by_config: Dict[FitConfig, List[_Plan]] = {}
        for plan in plans:
            by_config.setdefault(plan.fit_config, []).append(plan)
        for config, group in by_config.items():
            members, member_plans = [], []
            for plan in group:
                try:
                    members.append(self._make_member(plan, None, seed=plan.seed))
                    member_plans.append(plan)
                except Exception as exc:
                    self._fail(plan.machine.name, exc)
            if not members:
                continue
            self._train_final_group(members, member_plans, config, start)

    def _train_final_group(self, members, member_plans, config, start):
        """
        Final-fit one config group with the same degradation ladder as
        the CV chunks: a failing group splits in half and retries (down
        to single members), an isolated device failure degrades that one
        machine to the sequential builder, anything else fails just that
        machine — one poisonous machine or an over-packed group never
        takes the fleet's final fit down.
        """
        live = [
            i
            for i, plan in enumerate(member_plans)
            if not self._skipped(plan.machine.name)
        ]
        if len(live) != len(member_plans):
            members = [members[i] for i in live]
            member_plans = [member_plans[i] for i in live]
        if not members:
            return
        try:
            with self._phase("final_fit"):
                # these parameters are the artifact: they come to the
                # host, a large one's while its model.pkl is written
                # (the last fit's: an earlier one's are landed here,
                # off the chip before this one runs there)
                self._land_flights()
                results = self.trainer.train(
                    members, config, params_on_device=False
                )
        except Exception as exc:
            # Split-retry DEVICE errors only (the trainer's own rule): a
            # host-side exception is deterministic and would fail every
            # half identically — 2N-1 futile retrains of a 100-machine
            # group, each paying staging + compile. The trainer already
            # converts in-bucket device errors to error-results, so this
            # is the net for failures outside its per-bucket scope.
            if is_device_error(exc) and len(members) > 1:
                logger.warning(
                    "Final-fit group of %d members failed (%s); splitting",
                    len(members),
                    exc,
                )
                self.robustness["bucket_bisects"] += 1
                for plan in member_plans:
                    plan.bucket_bisects += 1
                mid = len(members) // 2
                self._train_final_group(
                    members[:mid], member_plans[:mid], config, start
                )
                self._train_final_group(
                    members[mid:], member_plans[mid:], config, start
                )
                return
            if is_device_error(exc):
                self._degrade(member_plans[0], exc)
                return
            for plan in member_plans:
                self._fail(plan.machine.name, exc)
            return
        with self._phase("assemble"):
            self._adopt_final_results(member_plans, results, start)

    def _adopt_final_results(self, member_plans, results, start) -> None:
        """Each machine's trained parameters, history and training
        summary onto its plan; its detector's scaler fitted."""
        for plan, result in zip(member_plans, results):
            if result.error is not None:
                if is_device_error(result.error):
                    self._degrade(plan, result.error)
                else:
                    self._fail(plan.machine.name, result.error)
                continue
            try:
                plan.fleet_retries += result.retries
                self.robustness["fleet_retries"] += result.retries
                plan.estimator.params_ = result.params
                if result.in_flight is not None:
                    self._in_flight.append((plan, result.in_flight))
                plan.estimator.spec_ = plan.spec
                plan.estimator._history = result.history
                plan.train_duration = time.time() - start
                plan.training_summary = TrainingSummaryMetadata.from_history(
                    result.history
                )
                self.recorder.event(
                    "member_trained",
                    machine=plan.machine.name,
                    final_loss=plan.training_summary.final_loss,
                    best_loss=plan.training_summary.best_loss,
                    epochs_run=plan.training_summary.epochs_run,
                    early_stop_epoch=plan.training_summary.early_stop_epoch,
                    retries=result.retries,
                )
                if plan.detector is not None:
                    plan.detector.scaler.fit(plan.y)
            except Exception as exc:
                self._fail(plan.machine.name, exc)

    # ------------------------------------------------------------- assembly

    def _assemble(self, plan: _Plan) -> Tuple[Any, Machine]:
        machine = plan.machine.copy()
        machine.metadata.build_metadata = BuildMetadata(
            model=ModelBuildMetadata(
                model_offset=plan.offset,
                model_creation_date=str(
                    datetime.datetime.now(datetime.timezone.utc).astimezone()
                ),
                model_builder_version=gordo_tpu.__version__,
                model_training_duration_sec=plan.train_duration,
                cross_validation=CrossValidationMetaData(
                    cv_duration_sec=plan.cv_duration,
                    scores=plan.cv_scores,
                    splits=plan.cv_splits,
                ),
                model_meta=ModelBuilder._extract_metadata_from_model(plan.model_obj),
                training=plan.training_summary or TrainingSummaryMetadata(),
                device=dict(self._device or {}),
            ),
            dataset=DatasetBuildMetadata(
                query_duration_sec=plan.query_duration,
                dataset_meta=plan.dataset.get_metadata(),
            ),
            robustness=RobustnessMetadata(
                fleet_retries=plan.fleet_retries,
                bucket_bisects=plan.bucket_bisects,
                data_fetch_retries=plan.data_retries,
            ),
            drift_baseline=ModelBuilder._drift_baseline(plan.X),
        )
        return plan.model_obj, machine

    @staticmethod
    def _split_metadata(plan: _Plan, splits) -> Dict[str, Any]:
        metadata = {}
        index = plan.X.index
        for i, (train, test) in enumerate(splits):
            for label, idx in (("train", train), ("test", test)):
                for endpoint, pos in (("start", idx[0]), ("end", idx[-1])):
                    value = index[pos]
                    metadata[f"fold-{i + 1}-{label}-{endpoint}"] = (
                        value.isoformat() if hasattr(value, "isoformat") else int(value)
                    )
        if plan.target_folds:
            # FleetBuilder._cv_splits: the other rule says nothing here
            metadata["folds-over"] = "target-rows"
        return metadata


def fleet_build(
    machines: Sequence[Machine],
    output_dir: Optional[str] = None,
    trainer: Optional[FleetTrainer] = None,
    resume: bool = False,
) -> List[Tuple[Any, Machine]]:
    """Convenience wrapper: build the whole fleet."""
    return FleetBuilder(machines, trainer=trainer).build(
        output_dir=output_dir, resume=resume
    )


def rebuild_stale(
    machines: Sequence[Machine],
    stale_names: Sequence[str],
    output_dir: str,
    base_plan: Optional[Any] = None,
    base_plan_path: Optional[str] = None,
    resume: bool = True,
    trainer: Optional[FleetTrainer] = None,
    health_ledger: Optional[Any] = None,
) -> FleetBuilder:
    """
    Partial-fleet rebuild: train ONLY ``stale_names`` (the drift-tripped
    subset the lifecycle loop hands in) into ``output_dir``, leaving
    every other member untouched — the incremental half of the
    self-healing loop (``gordo_tpu.lifecycle``).

    Reuses the full crash-safety stack: the rebuild keeps its own
    journal in ``output_dir`` and ``resume=True`` (the default — a
    lifecycle restart must converge on the same canary, not restart it)
    skips members already rebuilt. When the base build's FleetPlan is
    available (``base_plan`` in memory or ``base_plan_path`` on disk,
    typically ``<base revision>/fleet_plan.json``) it is REPLAYED:
    :meth:`~gordo_tpu.planner.FleetPlan.materialize_buckets` re-binds
    bucket rosters by name, so a stale member keeps its planned pad
    targets and trains under the exact program shape of its original
    build — members the plan does not cover (or whose data outgrew the
    pad target) repack live, and the untouched majority is simply never
    in the member list.

    Returns the builder (artifacts + journal are in ``output_dir``;
    callers read ``build_errors``/``resumed`` off it).
    """
    stale = set(stale_names)
    unknown = stale - {m.name for m in machines}
    if unknown:
        raise FleetBuildError(
            f"stale members not in the machine set: {sorted(unknown)}"
        )
    if base_plan is None and base_plan_path and os.path.isfile(base_plan_path):
        from ..planner import FleetPlan

        try:
            base_plan = FleetPlan.load(base_plan_path)
        except ValueError as exc:
            logger.warning(
                "Base FleetPlan %s unusable (%s); stale members pack live",
                base_plan_path,
                exc,
            )
    builder = FleetBuilder(
        [m for m in machines if m.name in stale],
        trainer=trainer,
        fleet_plan=base_plan,
        # provenance belongs in the CALLER's (anchor) ledger, not one
        # keyed to this staging dir nothing ever reads
        health_ledger=health_ledger,
    )
    builder.build(output_dir=output_dir, resume=resume)
    return builder
